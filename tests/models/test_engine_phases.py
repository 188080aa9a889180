"""The caption engine's step phases: one helper (`CaptionEngine._phase`) gives
every phase of `step()` a counter on the host's clock and a span on the
profiler's. No profiler trace is started in this process (PERF.md §6: a later
pyarrow thread dies with SIGSEGV); the spans are read off a recorder put in
`jax.profiler.TraceAnnotation`'s place."""

import threading

import numpy as np
import pytest

import jax

from cosmos_curate_tpu.models.tokenizer import ByteTokenizer
from cosmos_curate_tpu.models.vlm import (
    CaptionEngine,
    CaptionRequest,
    SamplingConfig,
    VLM_TINY_TEST,
)

ROOTS = ("step_s", "prep_s")  # elapsed; every other key but the derived two is self time
DERIVED = ("prefill_s", "decode_s")
STEP_LEAVES = (
    "lock_wait_s",
    "admit_s",
    "prefill_build_s",
    "prefill_dispatch_s",
    "prefill_wait_s",
    "prefill_sample_s",
    "decode_build_s",
    "decode_dispatch_s",
    "decode_wait_s",
    "decode_sample_s",
)
ALL_KEYS = {
    *ROOTS, *DERIVED, *STEP_LEAVES, "step_other_s", "prep_other_s", "vision_encode_s",
}


def _req(rid, text="describe", frames=False, max_new=8, prefix=""):
    tok = ByteTokenizer()
    return CaptionRequest(
        request_id=rid,
        prefix_ids=tok.encode(prefix) if prefix else [],
        prompt_ids=tok.encode(text),
        frames=(
            np.random.default_rng(0).integers(0, 255, (2, 32, 32, 3), np.uint8)
            if frames
            else None
        ),
        sampling=SamplingConfig(max_new_tokens=max_new),
    )


@pytest.fixture(scope="module")
def engine():
    """Sync prep: everything, prep included, runs on the stepping thread."""
    eng = CaptionEngine(VLM_TINY_TEST, max_batch=4, prefill_chunk=8)
    eng.setup()
    return eng


def _self_time_sum(phases: dict) -> float:
    return sum(v for k, v in phases.items() if k not in ROOTS + DERIVED)


def _drive(eng, kind: str) -> int:
    """Run one kind of work to the end, one step() at a time; the steps taken."""
    if kind == "whole_prompt":  # idle engine: one bucketed program for the prompt
        eng.add_request(_req("w0", text="a " * 20, max_new=3))
    elif kind == "chunked":  # a decode is in flight: the long prompt goes by chunks
        eng.add_request(_req("s0", text="hi", max_new=12))
        eng.step()
        eng.add_request(_req("c0", text="b " * 20, max_new=3))
    elif kind == "vision":
        eng.add_request(_req("v0", frames=True, max_new=3))
    elif kind == "shared_prefix":  # builds the prefix under admit, on this thread
        eng.add_request(_req("p0", prefix="you are a captioner. ", max_new=3))
    steps = 0
    while eng.has_work():
        eng.step()
        steps += 1
    eng.completed.clear()
    return steps


class TestCounters:
    def test_keys(self, engine):
        assert set(engine.phase_seconds) == ALL_KEYS

    @pytest.mark.parametrize("kind", ["whole_prompt", "chunked", "vision", "shared_prefix"])
    def test_leaves_partition_the_step(self, engine, kind):
        engine.reset_stats()
        steps = _drive(engine, kind) + (kind == "chunked")
        ph = engine.phase_seconds
        assert ph["step_s"] > 0
        assert abs(_self_time_sum(ph) - ph["step_s"]) <= 1e-6 * steps
        assert all(v >= 0 for v in ph.values()), ph
        # the work of this drive shows under its own names
        expect = {
            "whole_prompt": ("prefill_build_s", "prefill_wait_s", "decode_wait_s"),
            "chunked": ("prefill_dispatch_s", "prefill_sample_s", "decode_sample_s"),
            "vision": ("vision_encode_s", "prep_other_s", "admit_s"),
            "shared_prefix": ("prefill_dispatch_s", "prep_s", "decode_build_s"),
        }[kind]
        assert all(ph[k] > 0 for k in expect), {k: ph[k] for k in expect}
        if kind != "vision":
            assert ph["vision_encode_s"] == 0

    def test_old_keys_are_sums_of_the_new(self, engine):
        engine.reset_stats()
        _drive(engine, "chunked")
        _drive(engine, "vision")
        ph = engine.phase_seconds
        assert ph["decode_s"] == pytest.approx(
            ph["decode_dispatch_s"] + ph["decode_wait_s"], abs=1e-12
        )
        assert ph["prefill_s"] == pytest.approx(
            ph["prefill_dispatch_s"] + ph["prefill_wait_s"] + ph["prefill_sample_s"], abs=1e-12
        )
        # prep stays inclusive of the vision encode nested in it
        assert ph["prep_s"] >= ph["vision_encode_s"] + ph["prep_other_s"] > 0
        stats = engine.stats()
        assert stats["decode_s"] == ph["decode_s"] == engine.decode_time_s
        assert stats["prefill_s"] == ph["prefill_s"]
        assert stats["decode_attention_s"] == ph["decode_s"]

    def test_chunk_step_with_no_finished_row_does_not_wait(self, engine):
        engine.add_request(_req("s0", text="hi", max_new=12))
        engine.step()
        engine.add_request(_req("c0", text="b " * 20, max_new=3))
        engine.step()  # admits c0 as a pending chunked prefill; first of 5 chunks
        assert engine.pending
        engine.reset_stats()
        engine.step()  # one more chunk, no row finishes: only the decode syncs
        assert engine.pending
        ph = engine.phase_seconds
        assert ph["prefill_dispatch_s"] > 0 and ph["prefill_wait_s"] == 0
        assert ph["decode_wait_s"] > 0
        while engine.has_work():
            engine.step()
        engine.completed.clear()

    def test_reset_zeroes_every_key(self, engine):
        _drive(engine, "vision")
        assert engine.phase_seconds["step_s"] > 0
        engine.reset_stats()
        assert engine.phase_seconds == dict.fromkeys(ALL_KEYS, 0.0)
        assert engine.stats()["decode_s"] == 0 and engine.stats()["prefill_s"] == 0

    def test_phase_outside_a_root_and_after_an_error(self, engine):
        engine.reset_stats()
        with pytest.raises(ValueError), engine._phase("admit"):
            with engine._phase("prefill_wait"):
                raise ValueError("boom")
        ph = engine.phase_seconds
        assert ph["admit_s"] >= 0 and ph["prefill_wait_s"] > 0 and ph["step_s"] == 0
        assert engine._phase_open.stack == []  # nothing left open on this thread


class _Recorder:
    """Stands in for jax.profiler.TraceAnnotation: (event, name, thread)."""

    log: list = []

    def __init__(self, name, **_kw):
        self.name = name

    def __enter__(self):
        _Recorder.log.append(("open", self.name, threading.current_thread().name))
        return self

    def __exit__(self, *_exc):
        _Recorder.log.append(("close", self.name, threading.current_thread().name))
        return False


@pytest.fixture()
def spans(monkeypatch):
    _Recorder.log = []
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _Recorder)
    return _Recorder.log


def _nesting(log, thread=None):
    """[(name, depth)] in opening order, checking that spans close in order."""
    out, open_ = [], []
    for event, name, th in log:
        if thread is not None and th != thread:
            continue
        if event == "open":
            out.append((name, len(open_)))
            open_.append(name)
        else:
            assert open_.pop() == name
    assert not open_
    return out


class TestSpans:
    def test_one_step_emits_the_leaves_in_order_inside_engine_step(self, engine, spans):
        engine.add_request(_req("s0", text="hi", max_new=12))
        engine.step()
        engine.add_request(_req("c0", text="b " * 20, max_new=3))
        engine.step()
        del spans[:]
        engine.step()  # a chunk and a decode, nothing admitted
        assert _nesting(spans) == [
            ("engine.step", 0),
            ("engine.lock_wait", 1),
            ("engine.admit", 1),
            ("engine.prefill_build", 1),
            ("engine.prefill_dispatch", 1),
            ("engine.prefill_sample", 1),
            ("engine.decode_build", 1),
            ("engine.decode_dispatch", 1),
            ("engine.decode_wait", 1),
            ("engine.decode_sample", 1),
        ]
        while engine.has_work():
            engine.step()
        engine.completed.clear()

    def test_admission_nests_prep_and_the_whole_prompt_prefill(self, engine, spans):
        engine.add_request(_req("v0", frames=True, max_new=2))
        engine.step()
        names = _nesting(spans)
        assert names[:3] == [("engine.step", 0), ("engine.lock_wait", 1), ("engine.admit", 1)]
        assert ("engine.prep", 2) in names and ("engine.vision_encode", 3) in names
        under_admit = [n for n, depth in names if depth == 2]
        assert under_admit == [
            "engine.prep",
            "engine.prefill_build",
            "engine.prefill_dispatch",
            "engine.prefill_wait",
            "engine.prefill_sample",
        ]
        while engine.has_work():
            engine.step()
        engine.completed.clear()

    def test_prep_thread_emits_prep_around_vision_encode(self, spans):
        eng = CaptionEngine(VLM_TINY_TEST, max_batch=2, async_prep=True)
        eng.setup()
        try:
            eng.add_request(_req("v0", frames=True, max_new=2))
            results = eng.run_until_complete()
            assert [r.request_id for r in results] == ["v0"]
            assert _nesting(spans, thread="caption-prep") == [
                ("engine.prep", 0),
                ("engine.vision_encode", 1),
            ]
            main = {n for n, _ in _nesting(spans, thread=threading.current_thread().name)}
            assert "engine.step" in main and "engine.prep" not in main
            ph = eng.phase_seconds
            # prep ran beside step(), not inside it: the step's partition holds without it
            step_side = sum(ph[k] for k in STEP_LEAVES) + ph["step_other_s"]
            assert step_side == pytest.approx(ph["step_s"], abs=1e-5)
            assert ph["prep_s"] >= ph["vision_encode_s"] > 0
        finally:
            eng.shutdown()
