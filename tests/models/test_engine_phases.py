"""The caption engine's step phases: one helper (`CaptionEngine._phase`) gives
every phase of `step()` a counter on the host's clock and a span on the
profiler's, counts what its site hands it, and reads the device-queue clock
(`step_exposed_s`: seconds with nothing handed over and not shown done).
No profiler trace is started in this process (PERF.md §6: a later
pyarrow thread dies with SIGSEGV); the spans are read off a recorder put in
`jax.profiler.TraceAnnotation`'s place."""

import dataclasses
import sys
import threading
import time

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
    "prefix_tail_copy_s",  # (two pools: a wrapping row's copy of a shared prefix's window blocks)
    "prefill_build_s",
    "prefill_dispatch_s",
    "prefill_wait_s",
    "prefill_sample_s",
    "decode_build_s",
    "decode_dispatch_s",
    "decode_wait_s",
    "decode_sample_s",
)
SECONDS = {
    *ROOTS, *DERIVED, *STEP_LEAVES, "step_other_s", "prep_other_s", "vision_encode_s",
}
# seconds the device queue was provably empty: the step, and the dispatch phases' part
EXPOSED = {"step_exposed_s", "prefill_dispatch_exposed_s", "decode_dispatch_exposed_s"}
# what the sites count, each for a reader: entries, and integers handed to `_phase`
COUNTS = {
    "step_n", "prefill_dispatch_n", "decode_dispatch_n", "decode_sample_n",
    "prefill_dispatch_tokens", "prefill_dispatch_room", "prefill_sample_first",
    "decode_dispatch_rows", "decode_dispatch_live", "decode_dispatch_ahead",
    "decode_wait_fresh", "decode_wait_ready", "decode_sample_tokens",
    "admit_held", "admit_guests", "prep_n", "prep_requests", "prefix_tail_copy_n", "prefix_tail_copy_blocks",
}
# a request's life (`CaptionEngine._stamp`): the interval that closes at each boundary
# after the first, as (seconds, count), and the two counts beside them
LIFE = ("arrived", "taken", "ready", "admitted", "first_token", "finished")
INTERVALS = {
    "taken": ("request_queue_s", "request_taken_n"),
    "ready": ("request_prep_s", "request_ready_n"),
    "admitted": ("request_row_wait_s", "request_admitted_n"),
    "first_token": ("request_prefill_s", "request_first_n"),
    "finished": ("request_decode_s", "request_finished_n"),
}
SECONDS |= {s for s, _ in INTERVALS.values()}
COUNTS |= {n for _, n in INTERVALS.values()} | {"request_dropped_n", "request_decode_gaps"}
# ...and beyond what the account held at PR 59: two loose counters that became their
# phase's (`<phase>_<key>`; `engine._COUNTS` quotes them for `stats()`)
PHASES_SINCE_PR59 = {"vision_encode_n", "step_interleaved"}
COUNTS |= PHASES_SINCE_PR59
# ...and PR 61's two: the prompts a prefill program carried (rows a program = `_live` / `_n`),
# and the steps that held a lane's pending chunks back (`CaptionEngine._prefill_due`)
COUNTS |= {"prefill_dispatch_live", "step_held"}
ALL_KEYS = SECONDS | EXPOSED | COUNTS
KINDS = ["whole_prompt", "chunked", "vision", "shared_prefix"]


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
    return sum(
        v for k, v in phases.items()
        if k in SECONDS and k not in ROOTS + DERIVED and not k.startswith("request_")
    )


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

    @pytest.mark.parametrize("kind", KINDS)
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
        assert stats["decode_s"] == ph["decode_s"] == engine._decode_time
        assert stats["prefill_s"] == ph["prefill_s"]

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
        assert all(isinstance(engine.phase_seconds[k], int) for k in COUNTS)
        assert engine.stats()["decode_s"] == 0 and engine.stats()["prefill_s"] == 0

    def test_phase_outside_a_root_and_after_an_error(self, engine):
        engine.reset_stats()
        with pytest.raises(ValueError), engine._phase("admit"):
            with engine._phase("prefill_wait"):
                raise ValueError("boom")
        ph = engine.phase_seconds
        assert ph["admit_s"] >= 0 and ph["prefill_wait_s"] > 0 and ph["step_s"] == 0
        assert engine._phase_open.stack == []  # nothing left open on this thread


class TestProgramInFlight:
    """With one decode program of look-ahead a step dispatches a lane's next
    program and then reads the one before: the four decode phases keep their
    names and sites, and what the benchmark's readers divide stays what its
    name says."""

    DECODE = ("decode_build_s", "decode_dispatch_s", "decode_wait_s", "decode_sample_s")

    def _in_flight(self, engine, max_new=16):
        engine.add_request(_req("f0", text="hi", max_new=max_new))
        while not engine.slots:
            engine.step()
        (lane,) = [l for l in engine.lanes if l.inflight is not None]
        return lane

    def _finish(self, engine):
        while engine.has_work():
            engine.step()
        engine.completed.clear()

    @pytest.mark.parametrize("steps", [1, 5])
    def test_the_decode_phases_partition_steps_that_look_ahead(self, engine, steps):
        lane = self._in_flight(engine)
        engine.reset_stats()
        for _ in range(steps):
            engine.step()
        ph, stats = engine.phase_seconds, engine.stats()
        assert lane.inflight is not None
        assert stats["decode_programs_ahead"] == stats["paged_kernel_steps"] == steps
        assert abs(_self_time_sum(ph) - ph["step_s"]) <= 1e-6 * steps
        assert all(ph[k] > 0 for k in self.DECODE), {k: ph[k] for k in self.DECODE}
        assert ph["prefill_s"] == 0 and ph["admit_s"] >= 0
        self._finish(engine)

    def test_a_step_dispatches_the_next_program_before_it_reads_the_last(self, engine, spans):
        self._in_flight(engine)
        del spans[:]
        engine.step()
        assert [n for n, depth in _nesting(spans) if depth == 1][2:] == [
            "engine.decode_build", "engine.decode_dispatch", "engine.decode_wait", "engine.decode_sample",
        ]
        self._finish(engine)

    def test_a_lane_that_cannot_look_ahead_reads_first(self, engine, spans):
        """A row sampled on the host: the step reads the program in flight
        (wait, sample), then builds and dispatches the next from the host's
        tokens. Same four phases, each once a program."""
        engine.add_request(
            CaptionRequest(
                request_id="t0", prompt_ids=ByteTokenizer().encode("hi"),
                sampling=SamplingConfig(max_new_tokens=6, temperature=0.7, seed=1),
            )
        )
        while not engine.slots:
            engine.step()
        del spans[:]
        engine.reset_stats()
        engine.step()
        assert [n for n, depth in _nesting(spans) if depth == 1][2:] == [
            "engine.decode_wait", "engine.decode_sample", "engine.decode_build", "engine.decode_dispatch",
        ]
        assert engine.stats()["decode_programs_ahead"] == 0
        self._finish(engine)

    def test_what_the_readers_divide(self, engine, spans):
        """``engine.decode_ms_per_token`` divides dispatch + wait by the decode
        tokens, ``engine.decode_host_ms_per_program`` build + dispatch + sample
        by the decode programs: over a drained run every program has been
        built, dispatched, waited for and sampled exactly once, every decode
        token is some program's, and ``decode_s`` is still dispatch + wait."""
        engine.reset_stats()
        del spans[:]
        engine.add_request(_req("r0", text="hi", max_new=9))
        engine.add_request(_req("r1", text="b " * 6, max_new=5))
        self._finish(engine)
        ph, stats = engine.phase_seconds, engine.stats()
        programs = stats["paged_kernel_steps"]
        names = [n for n, _ in _nesting(spans)]
        for phase in ("build", "dispatch", "wait", "sample"):
            assert names.count(f"engine.decode_{phase}") == programs
        assert stats["decode_tokens"] == (9 - 1) + (5 - 1)
        assert 0 < stats["decode_programs_ahead"] < programs <= stats["decode_tokens"]
        assert stats["decode_s"] == pytest.approx(ph["decode_dispatch_s"] + ph["decode_wait_s"], abs=1e-12)
        assert abs(_self_time_sum(ph) - ph["step_s"]) <= 1e-6 * len([n for n in names if n == "engine.step"])


class _OldLines:
    """The counters as the engine kept them before `_phase` took counts: one
    hand-placed `+=` each, at the site it had, replayed by spies around the
    same calls. (`spy_chunk` counts what a chunk program is ABOUT to take: the
    engine states no cap, so `_prefill_due` holds nothing and a lane with rows
    pending runs one chunk program every step, as before PR 61; with a cap a
    step may run none, and `_prefill_chunk_step` is not called in it.)"""

    def __init__(self, eng, monkeypatch):
        self.eng = eng
        self.decode_tokens = self.decode_rows = self.paged_kernel_steps = 0
        self.decode_programs_ahead = self.prefill_tokens = 0
        self.decode_calls = self.prefill_calls = 0
        collect, dispatch = eng._decode_collect, eng._decode_dispatch
        group, chunk = eng._prefill_group, eng._prefill_chunk_step
        decode, run_prefill, prefix = eng._decode, eng._run_prefill, eng._prefix_prefill

        def spy_collect(lane, flight):
            self.decode_tokens += len(flight.emitted(lane))
            self.decode_rows += lane.n_slots
            self.paged_kernel_steps += 1
            return collect(lane, flight)

        def spy_dispatch(lane, prev, tokens):
            self.decode_programs_ahead += prev is not None
            return dispatch(lane, prev, tokens)

        def spy_group(lane, bucket, items):
            out = group(lane, bucket, items)
            self.prefill_tokens += int(sum(it[3] for it in items))
            return out

        def spy_chunk(lane):
            items = list(lane.pending.values())[: eng.max_prefill_rows]
            new = sum(min(eng.prefill_chunk, p.t_valid - p.progress) for p in items)
            out = chunk(lane)
            self.prefill_tokens += new
            return out

        def spy_decode(*a):
            self.decode_calls += 1
            return decode(*a)

        def spy_run_prefill(*a):
            self.prefill_calls += 1
            return run_prefill(*a)

        def spy_prefix(params, emb, pos, tp):
            self.prefill_calls += 1
            self.prefill_tokens += int(tp)
            return prefix(params, emb, pos, tp)

        for name, spy in (
            ("_decode_collect", spy_collect), ("_decode_dispatch", spy_dispatch),
            ("_prefill_group", spy_group), ("_prefill_chunk_step", spy_chunk),
            ("_decode", spy_decode), ("_run_prefill", spy_run_prefill), ("_prefix_prefill", spy_prefix),
        ):
            monkeypatch.setattr(eng, name, spy)


class TestCounts:
    """`_phase` books what its site hands it, an entry at a time: the counts of
    a drive are the programs that were called, and the counters the engine
    always reported are read from them unchanged."""

    @pytest.mark.parametrize("kind", KINDS)
    def test_entries_are_the_steps_and_the_programs(self, engine, monkeypatch, kind):
        engine.clear_prefix_cache()
        engine.reset_stats()
        old = _OldLines(engine, monkeypatch)
        steps = _drive(engine, kind) + (kind == "chunked")
        ph, stats = engine.phase_seconds, engine.stats()
        assert ph["step_n"] == steps
        assert ph["decode_dispatch_n"] == old.decode_calls > 0
        assert ph["prefill_dispatch_n"] == old.prefill_calls > 0
        assert ph["decode_sample_n"] == old.decode_calls  # every program dispatched was read, once
        assert 0 < ph["decode_dispatch_live"] <= ph["decode_dispatch_rows"]
        assert ph["decode_dispatch_rows"] == 4 * old.decode_calls  # one lane of max_batch=4
        assert 0 < ph["prefill_dispatch_tokens"] <= ph["prefill_dispatch_room"]
        assert ph["decode_sample_tokens"] == stats["decode_tokens"] > 0
        assert ph["prefill_dispatch_tokens"] == stats["prefill_tokens"]
        assert ph["prefill_sample_first"] == (2 if kind == "chunked" else 1)  # a first token a request
        assert 0 <= ph["decode_wait_ready"] <= ph["decode_wait_fresh"] <= ph["decode_sample_n"]
        mine = engine._phase_thread()
        assert not engine.has_work() and mine.handed == mine.proven > 0 and engine._queue_busy == 0

    @pytest.mark.parametrize("kind", KINDS)
    def test_the_old_counters_read_what_their_own_lines_gave(self, engine, monkeypatch, kind):
        engine.clear_prefix_cache()
        engine.reset_stats()
        old = _OldLines(engine, monkeypatch)
        _drive(engine, kind)
        stats = engine.stats()
        assert stats["decode_tokens"] == old.decode_tokens == engine.phase_seconds["decode_sample_tokens"]
        assert stats["prefill_tokens"] == old.prefill_tokens == engine.prefill_tokens
        assert stats["paged_kernel_steps"] == old.paged_kernel_steps == engine.paged_kernel_steps
        assert stats["decode_programs_ahead"] == old.decode_programs_ahead
        assert engine.decode_slot_utilization == old.decode_tokens / old.decode_rows

    def test_an_entry_that_raised_counts_seconds_and_nothing_else(self, engine):
        engine.reset_stats()
        handed = engine._phase_thread().handed
        with pytest.raises(ValueError):
            with engine._phase("decode_dispatch", rows=4, live=1, ahead=1, program=handed + 50):
                raise ValueError("boom")
        ph = engine.phase_seconds
        assert ph["decode_dispatch_s"] > 0
        assert ph["decode_dispatch_n"] == ph["decode_dispatch_rows"] == ph["decode_dispatch_ahead"] == 0
        assert engine._phase_thread().handed == handed and engine._queue_busy == 0  # nothing was handed over

    def test_a_keyword_no_count_names_is_metadata_only(self, engine, spans):
        engine.reset_stats()
        with engine._phase("decode_sample", tokens=3, lane=64):
            pass
        with engine._phase("vision_encode", frames=2, program=7):
            pass
        with engine._phase("prefill_dispatch", rows=2, live=1, tokens=5, room=8):
            pass
        ph = engine.phase_seconds
        assert (ph["decode_sample_n"], ph["decode_sample_tokens"]) == (1, 3)
        assert (ph["prefill_dispatch_n"], ph["prefill_dispatch_tokens"], ph["prefill_dispatch_room"]) == (1, 5, 8)
        assert ph["prefill_dispatch_live"] == 1  # since PR 61 a count: the rows a prefill program carried
        assert set(ph) == ALL_KEYS  # no `lane`, `frames` or `rows` of a prefill: a reader each, or none kept
        assert _Recorder.meta[:2] == [
            ("engine.decode_sample", {"tokens": 3, "lane": 64}),
            ("engine.vision_encode", {"frames": 2, "program": 7}),
        ]

    def test_a_site_may_count_inside_its_phase(self, engine):
        """`decode_sample` learns its tokens inside the phase: `_phase` hands
        the open phase back, and what the site sets on it is booked at exit."""
        engine.reset_stats()
        with engine._phase("decode_sample") as phase:
            phase.counts["tokens"] = 4
        ph = engine.phase_seconds
        assert (ph["decode_sample_n"], ph["decode_sample_tokens"]) == (1, 4)

    def test_the_spans_of_one_program_share_its_number(self, engine, spans):
        engine.add_request(_req("m0", text="hi", max_new=6))
        while engine.has_work():
            engine.step()
        engine.completed.clear()
        sent = [kw for n, kw in _Recorder.meta if n == "engine.decode_dispatch"]
        read = [kw["program"] for n, kw in _Recorder.meta if n == "engine.decode_wait"]
        assert [kw["program"] for kw in sent] == read == sorted(read)
        assert all(kw["lane"] == engine.lanes[0].length and kw["rows"] == 4 for kw in sent)
        (prefill,) = [kw for n, kw in _Recorder.meta if n == "engine.prefill_dispatch"]
        (wait,) = [kw for n, kw in _Recorder.meta if n == "engine.prefill_wait"]
        assert prefill["program"] == wait["program"] == sent[0]["program"] - 1
        assert prefill["room"] % prefill["rows"] == 0 and prefill["room"] >= prefill["tokens"] > 0
        assert prefill["rows"] >= prefill["live"] == 1  # a prefill's rows and prompts: on the span alone

    def test_ready_counts_the_fresh_reads_that_found_the_result_there(self, engine, monkeypatch):
        collect = engine._decode_collect

        def late(lane, flight):  # the host arrives after the program is done
            jax.block_until_ready(flight.greedy)
            return collect(lane, flight)

        monkeypatch.setattr(engine, "_decode_collect", late)
        engine.reset_stats()
        _drive(engine, "whole_prompt")
        ph = engine.phase_seconds
        assert ph["decode_wait_ready"] == ph["decode_wait_fresh"] == ph["decode_sample_n"] > 0
        # a program that an earlier sync has passed already is neither fresh nor a late
        # arrival: the chunked drive reads a finished chunk, then the decode program before it
        engine.reset_stats()
        _drive(engine, "chunked")
        ph = engine.phase_seconds
        assert ph["decode_wait_ready"] == ph["decode_wait_fresh"] == ph["decode_sample_n"] - 1 > 0

    def test_a_read_that_waits_is_fresh_and_not_ready(self, engine, monkeypatch):
        collect = engine._decode_collect

        class NotReady:  # the token vector of a program the device still runs
            def __init__(self, array):
                self.array = array

            def is_ready(self):
                return False

            def __array__(self, *a, **kw):
                return np.asarray(self.array)

        def early(lane, flight):
            flight.greedy = NotReady(flight.greedy)
            return collect(lane, flight)

        monkeypatch.setattr(engine, "_decode_collect", early)
        engine.reset_stats()
        _drive(engine, "whole_prompt")
        ph = engine.phase_seconds
        assert ph["decode_wait_ready"] == 0 and ph["decode_wait_fresh"] == ph["decode_sample_n"] > 0


class TestDeviceQueueClock:
    """No thread with a program handed over and not shown done means the device
    holds nothing of this engine: the seconds `step` ran that way are its
    `step_exposed_s`, the dispatch phases' part of them their own."""

    @pytest.mark.parametrize("kind", KINDS)
    def test_exposed_seconds_are_part_of_the_phase(self, engine, kind):
        engine.reset_stats()
        _drive(engine, kind)
        ph = engine.phase_seconds
        for key in EXPOSED:
            assert 0.0 <= ph[key] <= ph[key.replace("_exposed_s", "_s")], key
        # the dispatch phases' exposed seconds lie inside the step's, and a wait never does:
        # its program is in the queue until the wait ends
        own = ph["prefill_dispatch_exposed_s"] + ph["decode_dispatch_exposed_s"]
        assert own <= ph["step_exposed_s"] <= ph["step_s"] - ph["prefill_wait_s"] + 1e-9
        assert own > 0  # the queue was empty when the drive's first program was dispatched

    def test_a_lane_that_reads_first_exposes_its_build_and_dispatch(self, engine):
        engine.add_request(
            CaptionRequest(
                request_id="t0", prompt_ids=ByteTokenizer().encode("hi"),
                sampling=SamplingConfig(max_new_tokens=8, temperature=0.7, seed=1),
            )
        )
        while not engine.slots:
            engine.step()
        engine.reset_stats()
        for _ in range(4):
            engine.step()
        ph = engine.phase_seconds
        assert ph["decode_dispatch_ahead"] == 0 and ph["decode_dispatch_n"] == 4
        assert ph["decode_dispatch_exposed_s"] == ph["decode_dispatch_s"] > 0
        # from a read to the close of the next dispatch nothing is queued; between that
        # and the next step's read the device holds the program
        host = ph["decode_sample_s"] + ph["decode_build_s"] + ph["decode_dispatch_s"]
        assert host <= ph["step_exposed_s"] + 1e-9
        assert ph["step_exposed_s"] <= ph["step_s"] - ph["decode_wait_s"] - ph["lock_wait_s"] - ph["admit_s"] + 1e-9
        while engine.has_work():
            engine.step()
        engine.completed.clear()

    def test_a_lane_with_a_program_ahead_exposes_nothing(self, engine):
        engine.add_request(_req("g0", text="hi", max_new=16))
        while not engine.slots:
            engine.step()
        engine.reset_stats()
        for _ in range(5):
            engine.step()
        ph = engine.phase_seconds
        assert ph["decode_dispatch_ahead"] == ph["decode_dispatch_n"] == 5
        assert all(ph[k] == 0.0 for k in EXPOSED), ph
        while engine.has_work():
            engine.step()
        engine.completed.clear()
        assert engine._queue_busy == 0

    def test_reset_stats_leaves_the_queue_as_it_is(self, engine):
        engine.add_request(_req("q0", text="hi", max_new=8))
        while not engine.slots:
            engine.step()
        mine = engine._phase_thread()
        handed, proven = mine.handed, mine.proven
        assert handed > proven and engine._queue_busy == 1  # the decode program in flight
        engine.reset_stats()
        assert (mine.handed, mine.proven, engine._queue_busy) == (handed, proven, 1)
        assert engine._empty_since is None
        while engine.has_work():
            engine.step()
        engine.completed.clear()
        assert mine.handed == mine.proven > handed
        assert engine._queue_busy == 0 and engine._empty_since is not None

    def test_a_sync_proves_every_program_before_it(self, engine):
        """A prefill chunk nobody reads is proven by the next read of anything
        the thread handed over after it."""
        engine.add_request(_req("s0", text="hi", max_new=12))
        engine.step()
        engine.add_request(_req("c0", text="b " * 20, max_new=3))
        engine.step()
        engine.reset_stats()
        engine.step()  # a chunk, unread, then a decode program; the decode before them is read
        ph, mine = engine.phase_seconds, engine._phase_thread()
        assert ph["prefill_dispatch_n"] == 1 and ph["prefill_sample_first"] == 0
        chunk = mine.proven + 1
        assert mine.handed == chunk + 1 == engine.lanes[0].inflight.program
        engine.step()  # reads that decode program: the chunk before it is done too
        assert mine.proven > chunk
        while engine.has_work():
            engine.step()
        engine.completed.clear()
        assert engine._queue_busy == 0

    def test_the_prep_thread_feeds_the_same_queue(self):
        eng = CaptionEngine(VLM_TINY_TEST, max_batch=2, async_prep=True)
        eng.setup()
        try:
            eng.add_request(_req("v0", frames=True, max_new=3))
            assert [r.request_id for r in eng.run_until_complete()] == ["v0"]
            ph, mine = eng.phase_seconds, eng._phase_thread()
            assert ph["vision_encode_s"] > 0 and eng._queue_busy == 0
            # the tower took a number on its own thread: this thread's are the rest
            assert next(eng._programs) - 1 == ph["decode_dispatch_n"] + ph["prefill_dispatch_n"] + 1
            assert mine.handed == mine.proven > 0
            assert 0 <= ph["step_exposed_s"] <= ph["step_s"]
        finally:
            eng.shutdown()

    def test_a_thread_that_takes_its_number_early_still_holds_the_queue(self, engine):
        """The tower's number is drawn before its frames are put: meanwhile the
        step thread hands over and proves higher numbers. The queue is a pair a
        thread, so the tower's program still stops the empty clock."""
        engine.reset_stats()
        tower = next(engine._programs)
        later = next(engine._programs)
        with engine._phase("decode_dispatch", rows=4, live=1, ahead=0, program=later):
            pass
        with engine._phase("decode_wait", fresh=1, ready=0, program=later):
            pass
        assert engine._queue_busy == 0 and engine._empty_since is not None
        done = threading.Event()

        def prep():
            with engine._phase("vision_encode", frames=2, program=tower) as phase:
                phase.moved(handed=tower)
                assert engine._queue_busy == 1 and engine._empty_since is None
                with engine._phase("step"):  # anything that runs meanwhile is covered
                    pass
                phase.moved(proven=tower)
            done.set()

        t = threading.Thread(target=prep)
        t.start()
        t.join(timeout=30)
        assert done.is_set() and engine._queue_busy == 0 and engine._empty_since is not None
        assert engine.phase_seconds["step_exposed_s"] == 0.0

    def test_two_threads_lose_no_entry_and_leave_the_queue_empty(self, engine):
        engine.reset_stats()
        rounds, workers = 300, 12
        interval = sys.getswitchinterval()
        sys.setswitchinterval(1e-5)
        try:
            def work():
                for _ in range(rounds):
                    program = next(engine._programs)
                    with engine._phase("prefill_dispatch", rows=2, live=1, tokens=3, room=8, program=program):
                        pass
                    with engine._phase("prefill_wait", program=program):
                        pass

            threads = [threading.Thread(target=work) for _ in range(workers)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=60)
            assert not any(t.is_alive() for t in threads)
        finally:
            sys.setswitchinterval(interval)
        ph = engine.phase_seconds
        total = rounds * workers
        assert ph["prefill_dispatch_n"] == total
        assert ph["prefill_dispatch_tokens"] == 3 * total and ph["prefill_dispatch_room"] == 8 * total
        assert engine._queue_busy == 0 and engine._empty_since is not None
        assert 0.0 <= ph["prefill_dispatch_exposed_s"] <= ph["prefill_dispatch_s"]


class _Recorder:
    """Stands in for jax.profiler.TraceAnnotation: (event, name, thread)."""

    log: list = []

    meta: list = []  # (name, the keywords it was given)

    def __init__(self, name, **kw):
        self.name = name
        _Recorder.meta.append((name, kw))

    def __enter__(self):
        _Recorder.log.append(("open", self.name, threading.current_thread().name))
        return self

    def __exit__(self, *_exc):
        _Recorder.log.append(("close", self.name, threading.current_thread().name))
        return False


@pytest.fixture()
def spans(monkeypatch):
    _Recorder.log, _Recorder.meta = [], []
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


# -- a round of the prep thread ------------------------------------------------


def _text(rid, n=12, prefix="", max_new=4, owner=None, **kw):
    """A text request of ``n`` prompt ids, its own by ``rid``."""
    seed = sum(map(ord, rid))
    return CaptionRequest(
        request_id=rid,
        prefix_ids=ByteTokenizer().encode(prefix) if prefix else [],
        prompt_ids=[3 + (seed + 7 * i) % 200 for i in range(n)],
        sampling=SamplingConfig(max_new_tokens=max_new),
        owner=owner,
        **kw,
    )


def _output_ids(eng) -> dict:
    """Every finished request's output ids, by request id, as they finish."""
    ids, maybe_finish = {}, eng._maybe_finish

    def finishing(lane, slot_idx, slot):
        ids[slot.request.request_id] = list(slot.generated)
        return maybe_finish(lane, slot_idx, slot)

    eng._maybe_finish = finishing
    return ids


def _rounds(eng) -> list:
    """The request ids of every round the engine prepares from here on."""
    rounds, prepare_round = [], eng._prepare_round

    def spy(reqs):
        rounds.append([r.request_id for r in reqs])
        return prepare_round(reqs)

    eng._prepare_round = spy
    return rounds


def _queued(reqs, cfg=VLM_TINY_TEST, spy=True, **kw):
    """An engine with a prep thread that finds ``reqs`` waiting when ``setup()``
    starts it: its first round takes what it may of ALL of them."""
    eng = CaptionEngine(cfg, async_prep=True, **{"max_batch": 8, **kw})
    for r in reqs:
        eng.add_request(r, owner=r.owner or "me")
    rounds = _rounds(eng) if spy else None
    eng.setup()
    return eng, rounds


PREFIX = "system: you rewrite captions, tersely. user: "


class TestPrepRounds:
    """The prep thread takes every waiting text request it may a round: one
    hold of the lock to take them, one embedding call and one read for their
    text, one hold to hand them on. What a request is prepared to is what a
    round of its own gives."""

    @pytest.mark.parametrize("prefix", ["", PREFIX], ids=["no_prefix", "cached_prefix"])
    @pytest.mark.parametrize("flavor", ["plain_rope", "m_rope"])
    def test_queued_text_is_one_round_and_decodes_as_one_at_a_time(self, flavor, prefix):
        from cosmos_curate_tpu.models.vlm.model import VLM_QWEN2VL_TINY_TEST

        cfg = {"plain_rope": VLM_TINY_TEST, "m_rope": VLM_QWEN2VL_TINY_TEST}[flavor]
        assert (cfg.mrope_section is not None) == (flavor == "m_rope")
        reqs = lambda: [_text(f"r{i}", n=5 + 3 * i, prefix=prefix, max_new=6) for i in range(5)]
        one_at_a_time = CaptionEngine(cfg, max_batch=8)  # sync prep: a request a round, its own call
        one_at_a_time.setup()
        want = _output_ids(one_at_a_time)
        for r in reqs():
            one_at_a_time.add_request(r, owner="me")
        one_at_a_time.run_until_complete("me")
        assert one_at_a_time.phase_seconds["prep_n"] == one_at_a_time.phase_seconds["prep_requests"] == 5

        eng, rounds = _queued(reqs(), cfg)
        try:
            got = _output_ids(eng)
            done = eng.run_until_complete("me")
            ph = eng.phase_seconds
            assert rounds == [[f"r{i}" for i in range(5)]]
            assert (ph["prep_n"], ph["prep_requests"]) == (1, 5)
            assert sorted(r.request_id for r in done) == sorted(want)
            assert got == want and all(len(v) == 6 for v in got.values())
            if prefix:
                assert (eng.prefix_cache_hits, eng.prefix_cache_misses) == (4, 1)
        finally:
            eng.shutdown()

    @pytest.mark.parametrize("prefix", ["", PREFIX], ids=["no_prefix", "cached_prefix"])
    @pytest.mark.parametrize("flavor", ["plain_rope", "m_rope"])
    def test_a_round_prepares_bit_for_bit_what_a_request_alone_is_prepared_to(self, flavor, prefix):
        from cosmos_curate_tpu.models.vlm.model import VLM_QWEN2VL_TINY_TEST

        cfg = {"plain_rope": VLM_TINY_TEST, "m_rope": VLM_QWEN2VL_TINY_TEST}[flavor]
        eng = CaptionEngine(cfg, max_batch=4)
        eng.setup()
        assert eng._embed_tokens._cache_size() == 0  # inline prep: no round, no bucket warmed
        # lengths on both sides of a bucket's edge, and one over the lane's budget (tail kept)
        over = eng._max_len + 9
        reqs = [_text(f"q{i}", n=n, prefix=prefix) for i, n in enumerate((1, 40, 70, 9, over))]
        alone = [eng._prepare(r) for r in reqs]
        together = eng._prepare_round(reqs[:4]) + eng._prepare_round(reqs[4:])
        for a, b in zip(alone, together, strict=True):
            assert a.request is b.request and (a.t_suffix, a.next_rope, a.base, a.prefix_key) == (
                b.t_suffix, b.next_rope, b.base, b.prefix_key
            )
            assert a.embeds.dtype == b.embeds.dtype == np.float32 and a.ds is None and b.ds is None
            assert np.array_equal(a.embeds, b.embeds) and np.array_equal(a.rope, b.rope)
        # each request's rows are a copy of their own: none keeps the round's buffer alive
        assert all(rows.flags.owndata for rows in eng._embed_round(reqs[:4]))
        assert (alone[0].base > 0) == bool(prefix) and alone[4].t_suffix == eng._max_len - 4 - 1

    def test_a_request_with_frames_is_a_round_of_its_own(self):
        reqs = [_text("t0"), _text("t1"), _req("v0", frames=True, max_new=3), _text("t2"),
                _req("v1", frames=True, max_new=3), _req("v2", frames=True, max_new=3)]
        eng, rounds = _queued(reqs)
        try:
            done = eng.run_until_complete("me")
            assert rounds == [["t0", "t1"], ["v0"], ["t2"], ["v1"], ["v2"]]
            ph = eng.phase_seconds
            assert (ph["prep_n"], ph["prep_requests"]) == (5, 6) and len(done) == 6
            assert eng.vision_encodes == 3
        finally:
            eng.shutdown()

    def test_the_longest_lane_is_a_rounds_token_budget(self):
        # 64 positions: three prompts of 20 ids fit a round, a fourth does not
        reqs = [_text(f"b{i}", n=20) for i in range(7)] + [_text("b7", n=59)]
        eng, rounds = _queued(reqs, kv_lanes=((64, 8),))
        try:
            assert eng._max_len == 64 and len(eng.run_until_complete("me")) == 8
            assert rounds == [["b0", "b1", "b2"], ["b3", "b4", "b5"], ["b6"], ["b7"]]
        finally:
            eng.shutdown()

    def test_a_long_prompt_is_a_round_of_its_own(self):
        """Past 4,096 ids together a round takes no second request, whatever the lane."""
        cfg = dataclasses.replace(VLM_TINY_TEST, max_seq=16384)
        eng = CaptionEngine(cfg, max_batch=8, kv_lanes=((16384, 8),), async_prep=True)  # never set up
        lengths = {"a": 448, "b": 3520, "c": 1984, "d": 11200, "e": 448, "f": 5056, "g": 128}
        eng.waiting.extend(_text(rid, n=n, owner="me") for rid, n in lengths.items())
        rounds = []
        with eng._work_cv:
            while eng.waiting:
                rounds.append([r.request_id for r in eng._take_round()])
        assert rounds == [["a", "b"], ["c"], ["d"], ["e"], ["f"], ["g"]]

    def test_room_in_the_ready_queue_bounds_a_round(self):
        eng, rounds = _queued([_text(f"c{i}") for i in range(7)], max_batch=2)
        try:
            assert eng._prep_ahead_limit() == 4
            deadline = time.monotonic() + 30
            while len(eng._ready) < 4 and time.monotonic() < deadline:
                time.sleep(0.01)
            time.sleep(0.05)  # the thread waits: nothing more is taken while nothing is admitted
            with eng._work_cv:
                assert [p.request.request_id for p in eng._ready] == ["c0", "c1", "c2", "c3"]
                assert rounds == [["c0", "c1", "c2", "c3"]] and len(eng.waiting) == 3
            assert len(eng.run_until_complete("me")) == 7
            assert sum(rounds, []) == [f"c{i}" for i in range(7)]  # FIFO across rounds
        finally:
            eng.shutdown()

    def test_owners_rotate_inside_a_round(self):
        reqs = [_text(f"a{i}", owner="A") for i in range(3)] + [_text(f"b{i}", owner="B") for i in range(2)]
        eng, rounds = _queued(reqs)
        try:
            deadline = time.monotonic() + 30
            while len(eng._ready) < 5 and time.monotonic() < deadline:
                time.sleep(0.01)
            with eng._work_cv:
                assert rounds == [["a0", "b0", "a1", "b1", "a2"]]
                assert [p.request.request_id for p in eng._ready] == rounds[0]
            assert len(eng.run_until_complete("A")) == 3 and len(eng.run_until_complete("B")) == 2
        finally:
            eng.shutdown()

    def test_every_request_of_a_round_in_flight_is_counted(self):
        eng, _ = _queued([], spy=False)
        embed, entered, release = eng._embed_tokens, threading.Event(), threading.Event()

        def held_up(params, ids):
            entered.set()
            assert release.wait(30)
            return embed(params, ids)

        eng._embed_tokens = held_up
        try:
            with eng._work_cv:  # the thread sees all five at once
                for i in range(5):
                    eng.add_request(_text(f"f{i}", owner="A" if i < 3 else "B"))
            assert entered.wait(30)
            with eng._work_cv:  # mid-round: out of `waiting`, not yet ready
                assert not eng.waiting and not eng._ready and not eng.slots
                assert [r.request_id for r in eng._prep_requests()] == ["f0", "f3", "f1", "f4", "f2"]
                # the drivers' closed loop sizes itself by this sum (perfbench: `in_engine`)
                assert len(eng.waiting) + len(eng._prep_requests()) + len(eng.slots) + len(eng.pending) == 5
                assert eng.has_work() and eng.has_work("A") and eng.has_work("B") and not eng.has_work("C")
                owners = eng.owner_stats()
                assert (owners["A"]["waiting"], owners["B"]["waiting"]) == (3, 2)
                assert eng._owner_cap({}) == 4  # two owners share the eight rows
            release.set()
            assert len(eng.run_until_complete("A")) == 3 and len(eng.run_until_complete("B")) == 2
            assert not eng.has_work() and eng._prep_inflight == []
        finally:
            release.set()
            eng.shutdown()

    def test_a_request_whose_preparation_raises_is_dropped_alone(self):
        reqs = [_text("g0"), _text("empty", n=0), _text("g1"), _text("boom"), _text("g2")]
        eng, rounds = _queued([], spy=True)
        prepare = eng._prepare

        def failing(req, **kw):
            if req.request_id == "boom":
                raise RuntimeError("no preparation for this one")
            return prepare(req, **kw)

        eng._prepare = failing
        try:
            with eng._work_cv:
                for r in reqs:
                    eng.add_request(r, owner="me")
            done = eng.run_until_complete("me")
            assert sorted(r.request_id for r in done) == ["g0", "g1", "g2"]
            assert rounds == [["g0", "empty", "g1", "boom", "g2"]] and not eng.has_work()
            ph = eng.phase_seconds
            assert (ph["prep_n"], ph["prep_requests"]) == (1, 5)  # what the round carried
        finally:
            eng.shutdown()

    def test_a_failed_batched_call_leaves_each_request_its_own(self):
        reqs = lambda: [_text(f"h{i}", n=6 + i) for i in range(4)]
        eng, rounds = _queued(reqs())
        try:
            want = _output_ids(eng)
            eng.run_until_complete("me")
            want = dict(want)
            assert rounds == [["h0", "h1", "h2", "h3"]]

            def broken(reqs):
                raise RuntimeError("the batched call failed")

            eng._embed_round = broken
            got = _output_ids(eng)
            with eng._work_cv:
                for r in reqs():
                    eng.add_request(r, owner="me")
            assert len(eng.run_until_complete("me")) == 4
            assert rounds[1:] == [["h0", "h1", "h2", "h3"]]
            assert {k: got[k] for k in want} == want
        finally:
            eng.shutdown()

    def test_no_embedding_shape_is_compiled_after_setup(self):
        cfg = dataclasses.replace(VLM_TINY_TEST, max_seq=512)
        eng, rounds = _queued([], cfg, kv_lanes=((64, 4), (512, 4)))
        try:
            buckets = eng._embed_buckets()
            assert buckets == [128, 256, 512] and eng._embed_tokens._cache_size() == 3
            for burst in ((1, 2, 3), (100, 27), (300,), (129, 130, 131), (500,), (64, 64), (128,)):
                with eng._work_cv:
                    for n in burst:
                        eng.add_request(_text(f"s{n}", n=n, max_new=2), owner="me")
                assert len(eng.run_until_complete("me")) == len(burst)
            assert len(rounds) >= 7 and eng._embed_tokens._cache_size() == 3
        finally:
            eng.shutdown()

    @pytest.mark.parametrize(
        "longest, want",
        [
            (64, [128]),
            (1024, [128, 256, 512, 1024]),
            (4096, [128, 256, 512, 1024, 2048, 4096]),
            (5000, [128, 256, 512, 1024, 2048, 4096, 8192]),
            (16384, [128, 256, 512, 1024, 2048, 4096, 8192, 12288, 16384]),
            (32768, [128, 256, 512, 1024, 2048, 4096, 8192, 12288, 16384, 20480, 24576, 28672, 32768]),
        ],
    )
    def test_the_buckets_cover_every_round(self, longest, want):
        cfg = dataclasses.replace(VLM_TINY_TEST, max_seq=longest)
        eng = CaptionEngine(cfg, max_batch=2, kv_lanes=((longest, 2),))
        assert eng._embed_buckets() == want
        for n in (n for n in (1, 127, 128, 129, longest // 2 + 1, longest - 1, longest) if n <= longest):
            bucket = eng._embed_bucket(n)
            assert bucket in want and n <= bucket
            assert bucket < max(2 * n, 129) and bucket - n < max(n, 128, 4096)

    def test_a_sync_engine_counts_a_round_a_request(self, engine):
        engine.reset_stats()
        for i in range(3):
            engine.add_request(_text(f"y{i}"))
        engine.run_until_complete()
        ph = engine.phase_seconds
        assert (ph["prep_n"], ph["prep_requests"]) == (3, 3)

    def test_no_request_is_lost_or_counted_twice_while_rounds_race_submitters(self):
        """Submitters, the prep thread and the stepping thread under a short
        switch interval: at every look, under the engine's lock, what was
        submitted is waiting, in a round, ready, in a row, or done."""
        eng, rounds = _queued([], max_batch=4)
        submitted = [0]  # moves under the engine's lock, with the queue
        old = sys.getswitchinterval()
        sys.setswitchinterval(1e-5)

        def submit(name):
            for i in range(12):
                with eng._work_cv:
                    eng.add_request(_text(f"{name}{i}", n=3 + i, max_new=2, owner=name))
                    submitted[0] += 1
                time.sleep(0.001 * (i % 3))

        threads = [threading.Thread(target=submit, args=(n,)) for n in "abcdef"]
        try:
            for t in threads:
                t.start()
            deadline = time.monotonic() + 120
            while (any(t.is_alive() for t in threads) or eng.has_work()) and time.monotonic() < deadline:
                with eng._work_cv:
                    inside = len(eng.waiting) + len(eng._prep_requests()) + len(eng.slots) + len(eng.pending)
                    assert inside + len(eng.completed) == submitted[0]
                    if eng._ready or any(l.slots or l.pending or l.inflight for l in eng.lanes):
                        eng.step()
                time.sleep(0)
            for t in threads:
                t.join(timeout=10)
                assert not t.is_alive()
            assert not eng.has_work() and len(eng.completed) == submitted[0] == 72
            assert sorted(sum(rounds, [])) == sorted(f"{n}{i}" for n in "abcdef" for i in range(12))
            ph = eng.phase_seconds
            assert ph["prep_requests"] == 72 and 1 <= ph["prep_n"] <= 72
        finally:
            sys.setswitchinterval(old)
            eng.shutdown()


# -- a request's life ------------------------------------------------------------


def _account_is_the_lives(ph: dict, lives: list, since: float = 0.0) -> None:
    """Every interval's sum and count in ``ph`` are those of the ``lives`` (the
    ``timing`` records) that closed it at or after ``since``."""
    for i, closes in enumerate(LIFE[1:]):
        seconds, n = INTERVALS[closes]
        closed = [t for t in lives if t.get(closes, -1.0) >= since]
        assert ph[n] == len(closed), (n, ph[n], len(closed))
        assert ph[seconds] == pytest.approx(sum(t[closes] - t[LIFE[i]] for t in closed), abs=1e-9), seconds


def _lives_are_in_order(lives: list, eng) -> None:
    for t in lives:
        stamps = [t[k] for k in LIFE]
        assert stamps == sorted(stamps), t
        steps = [t[k + "_step"] for k in LIFE[3:]]
        assert steps == sorted(steps) and steps[0] >= 1, t
        assert t["lane"] in [l.length for l in eng.lanes]
        assert set(t) == {*LIFE, "lane", *(k + "_step" for k in LIFE[3:])}


def _conserved(ph: dict, drained: bool) -> None:
    assert ph["request_taken_n"] >= ph["request_ready_n"] + ph["request_dropped_n"]
    assert ph["request_admitted_n"] <= ph["request_ready_n"]
    assert ph["request_finished_n"] <= ph["request_first_n"] <= ph["request_admitted_n"]
    if drained:
        assert ph["request_taken_n"] == ph["request_ready_n"] + ph["request_dropped_n"]
        assert ph["request_ready_n"] == ph["request_admitted_n"] == ph["request_first_n"]
        assert ph["request_first_n"] == ph["request_finished_n"]


def _prepared(eng, n: int) -> None:
    """Wait, without stepping, until the prep thread has handed on ``n`` requests
    (inline prep happens under ``step()``: nothing to wait for)."""
    deadline = time.monotonic() + 60
    while eng.async_prep and len(eng._ready) < n and time.monotonic() < deadline:
        time.sleep(0.005)
    assert not eng.async_prep or len(eng._ready) == n


@pytest.fixture(scope="module")
def prep_thread_engine():
    eng = CaptionEngine(VLM_TINY_TEST, max_batch=4, prefill_chunk=8, async_prep=True)
    eng.setup()
    yield eng
    eng.shutdown()


class TestRequestLife:
    """Six stamps a request on the host's clock, each made once where the work
    happens; the interval a stamp closes goes into the account `phase_seconds`
    hands out, and the record itself onto the request's result (`timing`)."""

    @pytest.mark.parametrize("prefill", ["whole_prompt", "chunked"])
    @pytest.mark.parametrize("prep", ["inline", "prep_thread"])
    def test_the_account_is_the_sum_of_the_results_stamps(self, engine, prep_thread_engine, prep, prefill):
        eng = {"inline": engine, "prep_thread": prep_thread_engine}[prep]
        eng.reset_stats()
        if prefill == "chunked":  # a decode is in flight: the long prompts go by chunks
            eng.add_request(_text("s0", n=4, max_new=16))
            _prepared(eng, 1)
            while not eng.slots:
                eng.step()
        for i in range(3):
            eng.add_request(_text(f"l{i}", n=20 + i, max_new=3 + i))
        _prepared(eng, 3)  # the three meet ONE admission: a group, or chunks of one program
        done = eng.run_until_complete()
        assert len(done) == 3 + (prefill == "chunked") and not eng.has_work()
        ph, lives = eng.phase_seconds, [r.timing for r in done]
        _lives_are_in_order(lives, eng)
        _account_is_the_lives(ph, lives)
        _conserved(ph, drained=True)
        assert ph["request_decode_gaps"] == sum(r.num_output_tokens - 1 for r in done)
        assert ph["request_dropped_n"] == 0
        # the path the name says: a prompt's chunks ride several steps, a group one
        long = [r.timing for r in done if r.request_id != "s0"]
        took = {t["first_token_step"] - t["admitted_step"] for t in long}
        assert took == ({2} if prefill == "chunked" else {0}), took

    @pytest.mark.parametrize("prep", ["inline", "prep_thread"])
    def test_a_head_pushed_back_waits_for_its_row_and_is_booked_once(self, prep):
        # both need the long lane's one row; the short lane's free row keeps their owner
        # under its cap, so the second is taken and prepared while the first decodes
        eng = CaptionEngine(
            VLM_TINY_TEST, kv_lanes=((32, 1), (128, 1)), async_prep=prep == "prep_thread",
            admission_linger_s=0.0,
        )
        eng.add_request(_text("first", n=30, max_new=8), owner="me")
        eng.add_request(_text("second", n=30, max_new=2), owner="me")
        eng.setup()
        try:
            pushed_back, admit = [0], eng._admit

            def counting(counts):
                before = len(eng._ready)
                admit(counts)
                pushed_back[0] += bool(eng._ready) and len(eng._ready) >= before and bool(eng.slots)

            eng._admit = counting
            done = {r.request_id: r.timing for r in eng.run_until_complete("me")}
            assert pushed_back[0] >= 3  # the one row was taken: the head went back step after step
            first, second = done["first"], done["second"]
            # its wait for a row spans the other's whole decode, and ends in the step that freed it
            assert second["ready"] < first["first_token"] <= first["finished"] <= second["admitted"]
            assert second["admitted_step"] >= first["finished_step"] > first["admitted_step"]
            ph = eng.phase_seconds
            _account_is_the_lives(ph, list(done.values()))
            _conserved(ph, drained=True)
            assert ph["request_row_wait_s"] >= first["finished"] - first["first_token"]
        finally:
            eng.shutdown()

    def test_a_follow_up_is_a_life_of_its_own(self, engine):
        engine.reset_stats()
        first = _text("two-pass", max_new=3)
        second = _text("two-pass", n=9, max_new=2)
        first.on_complete = lambda text: second
        engine.add_request(first)
        (result,) = engine.run_until_complete()
        assert result.timing is second._life and result.num_prompt_tokens == 9
        lives = [first._life, second._life]
        _lives_are_in_order(lives, engine)
        # the refinement arrives where the first pass ends, and not before
        assert first._life["finished"] <= second._life["arrived"]
        ph = engine.phase_seconds
        _account_is_the_lives(ph, lives)  # the superseded pass finished once, and is counted once
        _conserved(ph, drained=True)
        assert ph["request_finished_n"] == 2 and ph["request_decode_gaps"] == (3 - 1) + (2 - 1)

    @pytest.mark.parametrize("prep", ["inline", "prep_thread"])
    def test_a_dropped_request_ends_where_it_would_have_become_ready(self, engine, prep_thread_engine, prep, monkeypatch):
        eng = {"inline": engine, "prep_thread": prep_thread_engine}[prep]
        eng.reset_stats()
        prepare = eng._prepare

        def failing(req, **kw):
            if req.request_id == "boom":
                raise RuntimeError("no preparation for this one")
            return prepare(req, **kw)

        monkeypatch.setattr(eng, "_prepare", failing)
        reqs = [_text("g0"), _text("boom"), _text("g1")]
        with eng._work_cv:
            for r in reqs:
                eng.add_request(r)
        done = eng.run_until_complete()
        assert sorted(r.request_id for r in done) == ["g0", "g1"] and not eng.has_work()
        ph = eng.phase_seconds
        assert (ph["request_taken_n"], ph["request_ready_n"], ph["request_dropped_n"]) == (3, 2, 1)
        _conserved(ph, drained=True)
        dropped = reqs[1]._life
        assert set(dropped) == {"arrived", "taken", "dropped"} and dropped["taken"] <= dropped["dropped"]
        _account_is_the_lives(ph, [r._life for r in reqs])  # its queue wait counts, its round does not

    def test_reset_stats_zeroes_the_account_and_no_interval_is_booked_twice(self, engine):
        engine.reset_stats()
        reqs = [_text(f"z{i}", max_new=6) for i in range(3)]
        for r in reqs:
            engine.add_request(r)
        while not engine.slots:
            engine.step()
        _conserved(engine.phase_seconds, drained=False)
        assert engine.phase_seconds["request_first_n"] == 3 and engine.phase_seconds["request_finished_n"] == 0
        engine.reset_stats()
        since = time.monotonic()
        assert all(engine.phase_seconds[k] == 0 for k in ALL_KEYS if k.startswith("request_"))
        done = engine.run_until_complete()
        ph, lives = engine.phase_seconds, [r.timing for r in done]
        _lives_are_in_order(lives, engine)  # the ordinals run on across the reset
        _account_is_the_lives(ph, lives, since)  # only what closed after it: the last interval
        assert ph["request_finished_n"] == 3 and ph["request_first_n"] == ph["request_taken_n"] == 0
        assert ph["request_decode_gaps"] == 3 * (6 - 1)

    def test_a_request_that_never_arrived_through_add_request_books_nothing(self, engine):
        engine.reset_stats()
        engine.waiting.append(_text("side-door", max_new=2, owner=threading.get_ident()))
        (result,) = engine.run_until_complete()
        assert result.timing == {}
        assert all(engine.phase_seconds[k] == 0 for k in ALL_KEYS if k.startswith("request_"))

    def test_the_step_span_carries_the_ordinal_a_result_names(self, engine, spans):
        engine.reset_stats()
        engine.add_request(_text("o0", max_new=4))
        (result,) = engine.run_until_complete()
        ordinals = [kw["ordinal"] for name, kw in _Recorder.meta if name == "engine.step"]
        assert ordinals == list(range(ordinals[0], ordinals[0] + len(ordinals)))  # a step, a number
        assert len(ordinals) == engine.phase_seconds["step_n"]
        t = result.timing
        assert {t["admitted_step"], t["first_token_step"], t["finished_step"]} <= set(ordinals)
        assert t["finished_step"] == ordinals[-1]  # the step that read its last token ended the drive
        assert not any("ordinal" in k for k in engine.phase_seconds)  # span metadata: no key sums it

    def test_a_request_and_its_result_pickle_with_their_record(self, engine):
        import pickle

        engine.reset_stats()
        req = _text("p0", max_new=2)
        engine.add_request(req)
        (result,) = engine.run_until_complete()
        assert pickle.loads(pickle.dumps(result)).timing == result.timing
        assert pickle.loads(pickle.dumps(req)) == req  # the record is no part of what a request is
        assert "_life" not in repr(req) and dataclasses.replace(req, request_id="p1")._life is None


# -- one account: every counter is a name of one table ---------------------------
# What `stats()` handed out at PR 59 (the parent of the PR that made the loose counters
# a table), written out, as `ALL_KEYS` less `PHASES_SINCE_PR59` is what `phase_seconds`
# did: a key that leaves either fails here.
STATS_AT_PR59 = {
    "paged_attention", "mesh_geometry", "param_bytes_per_chip", "kv_pool_bytes_per_chip",
    "full_pool_bytes_per_chip", "window_pool_bytes_per_chip", "kv_heads_per_pool_row", "kv_block_size",
    "kv_block_size_requested", "paged_kernel_steps", "decode_programs_ahead", "decode_rows_discarded",
    "admit_held", "admit_guests", "paged_decode_pages_walked", "paged_decode_pages_spanned",
    "paged_prefill_pages_walked", "paged_prefill_pages_spanned", "decode_tokens", "decode_s",
    "prefill_tokens", "prefill_s", "kv_blocks_total", "kv_blocks_used", "kv_blocks_used_peak",
    "prefix_cache_hits", "prefix_tokens_saved", "prefix_window_blocks_held", "prefix_tail_blocks_copied",
    "recurrent_state_bytes_per_chip", "conv_tail_bytes_per_chip", "recurrent_rows_total",
    "recurrent_rows_used_peak", "prefix_state_snapshots", "ssm_decode_calls", "delta_decode_calls",
    "delta_prefill_chunks", "latent_pool_bytes_per_chip", "mla_decode_calls", "expert_assignments_held",
    "expert_assignments_held_live", "index_pool_bytes_per_chip", "sparse_decode_calls",
    "sparse_decode_positions_live", "sparse_decode_positions_chosen", "sparse_decode_positions_read",
    "sparse_decode_rows_walked", "sparse_decode_rows_gathered",
}
# ...and what they hold beyond it: the counters that had a property and no key
STATS_SINCE = {
    "prefix_cache_misses", "prefix_cache_evictions", "vision_encodes", "vision_reuses",
    "prefix_block_refs", "kv_cow_copies", "interleaved_steps",
}
# what `stats()` sums beside the table's names (the rest is state and sizes, which
# `reset_stats()` leaves; the two peaks restart from what is held)
OTHER_SUMS = {"paged_kernel_steps", "decode_s", "prefill_s", "expert_assignments_held", "expert_assignments_held_live"}


def _flavor_kinds() -> dict:
    from cosmos_curate_tpu.models.vlm import model

    return {
        "dense": model.VLM_TINY_TEST,
        "hybrid": model.VLM_GRANITE_HYBRID_TINY_TEST,
        "latent": model.VLM_DEEPSEEK_V2_TINY_TEST,
        "indexed": model.VLM_KEYE_TINY_TEST,
        "windowed": model.VLM_TRINITY_TINY_TEST,
    }


@pytest.mark.parametrize("kind", ["dense", "hybrid", "latent", "indexed", "windowed"])
def test_every_counter_is_a_name_of_one_table_and_a_key_handed_out(kind):
    from cosmos_curate_tpu.models.vlm import engine as engine_module

    eng = CaptionEngine(_flavor_kinds()[kind], kv_lanes=((64, 2), (128, 2)), block_size=8, prefill_chunk=16)
    eng.setup()
    rng = np.random.default_rng(0)
    prefix = [int(t) for t in rng.integers(3, 200, 12)]
    for i, (n, new) in enumerate([(5, 6), (9, 4), (40, 5), (70, 3), (7, 8)]):
        eng.add_request(
            CaptionRequest(
                request_id=f"r{i}", prefix_ids=list(prefix), prompt_ids=[int(t) for t in rng.integers(3, 200, n)],
                sampling=SamplingConfig(max_new_tokens=new),
            ),
            owner=f"o{i % 2}",
        )
    results = eng.run_until_complete(owner="o0") + eng.run_until_complete(owner="o1")
    stats, phases = eng.stats(), eng.phase_seconds
    assert set(stats) == STATS_AT_PR59 | STATS_SINCE
    assert set(phases) == ALL_KEYS  # (PR 59's and PHASES_SINCE_PR59)
    # the table: every name is handed out; a quoted count IS its phase's; the store holds the rest
    table = engine_module._COUNTS
    assert set(table) <= set(stats) and set(eng._counts) == {k for k, quoted in table.items() if quoted is None}
    for name, quoted in table.items():
        if quoted is not None:
            assert quoted[1] in engine_module._PHASE_COUNTS[quoted[0]]
            assert stats[name] == phases["_".join(quoted)]
    # the seeded drive moved them: five requests behind one prefix, two owners in one batch
    generated = sum(r.num_output_tokens for r in results)
    assert stats["decode_tokens"] == generated - len(results) == sum(eng.owner_decode_tokens.values())
    assert (stats["prefix_cache_misses"], stats["prefix_cache_hits"]) == (1, 4)
    assert stats["prefix_block_refs"] == 5 == stats["kv_cow_copies"]  # a 12-token prefix in blocks of 8
    assert stats["interleaved_steps"] == eng.interleaved_decode_steps > 0
    assert stats["paged_decode_pages_walked"] > 0 and stats["kv_blocks_used_peak"] > stats["kv_blocks_used"]
    by_kind = {
        "dense": (), "hybrid": ("ssm_decode_calls", "prefix_state_snapshots"), "latent": ("mla_decode_calls",),
        "indexed": ("sparse_decode_calls", "sparse_decode_positions_read"), "windowed": (),
    }[kind]
    assert all(stats[k] > 0 for k in by_kind)
    eng.reset_stats()
    stats, phases = eng.stats(), eng.phase_seconds
    assert {k: stats[k] for k in set(table) | OTHER_SUMS if stats[k]} == {}
    assert {k: v for k, v in phases.items() if v} == {} and eng.owner_decode_tokens == {}
    assert stats["kv_blocks_used_peak"] == stats["kv_blocks_used"] == eng.kv_blocks_used  # what the prefix holds
    eng.shutdown()
