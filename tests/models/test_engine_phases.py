"""The caption engine's step phases: one helper (`CaptionEngine._phase`) gives
every phase of `step()` a counter on the host's clock and a span on the
profiler's, counts what its site hands it, and reads the device-queue clock
(`step_exposed_s`: seconds with nothing handed over and not shown done).
No profiler trace is started in this process (PERF.md §6: a later
pyarrow thread dies with SIGSEGV); the spans are read off a recorder put in
`jax.profiler.TraceAnnotation`'s place."""

import sys
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
    "admit_held", "admit_guests",
}
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
    return sum(v for k, v in phases.items() if k in SECONDS and k not in ROOTS + DERIVED)


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
        assert stats["decode_s"] == ph["decode_s"] == engine.decode_time_s
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
    same calls."""

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
        assert stats["decode_tokens"] == old.decode_tokens == engine.decode_tokens
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
        assert set(ph) == ALL_KEYS  # no `lane`, `frames`, `rows` or `live` of a prefill: a reader each, or none kept
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
