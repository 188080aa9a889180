"""When a lane dispatches its prefill program (``CaptionEngine._prefill_due``,
PR 61): a flavor that caps a prefill program's rows holds a lane's pending
chunks back, while a lane decodes, until the program is full or holding stops
paying; a flavor that states no cap holds nothing. Counted in steps and rows on
the CPU at test size: one engine a flavor, its cap set case by case (the cap
changes no program, only which step a chunk runs in)."""

import flax.linen as nn
import numpy as np
import pytest

from cosmos_curate_tpu.models.vlm import CaptionEngine, CaptionRequest, SamplingConfig
from cosmos_curate_tpu.models.vlm.engine import _init_params
from cosmos_curate_tpu.models.vlm.model import (
    VLM, VLM_DEEPSEEK_V2_TINY_TEST, VLM_LFM2_MOE_TINY_TEST, VLM_TINY_TEST,
)

CHUNK = 16
CAP = 2  # of the closed loop: 8 rows of 6-17 tokens each end within a lone held row's break-even
# dense; sparse experts with no capacity_factor (a program's rows are each other's
# bystanders); a recurrent store beside the pool (tails alone) over sparse experts
FLAVORS = {"dense": VLM_TINY_TEST, "moe": VLM_DEEPSEEK_V2_TINY_TEST, "hybrid": VLM_LFM2_MOE_TINY_TEST}
SERVED = ["dense", "moe"]


class Log:
    """What an engine ran, step by step: ``steps[i]`` lists step ``i``'s programs in
    order, ``("prefill", padded rows, T, live rows)`` and ``("decode", live rows)``;
    ``chunks`` every live prefill row's ``(request, write index, valid)``; ``tokens``
    a request's output ids; ``first`` its first-step logits; ``kv`` the K/V its
    prompt left in its own blocks, read as its first token was sampled."""

    def __init__(self, engine):
        self.engine = engine
        self.steps, self.chunks, self.tokens, self.first, self.kv = [], [], {}, {}, {}
        step, run_prefill, dispatch = engine.step, engine._run_prefill, engine._decode_dispatch
        finish, start = engine._maybe_finish, engine._start_slot

        def on_step():
            self.steps.append([])
            return step()

        def on_prefill(lane, slots_arr, tables, embeds, write_index, t_valid, *rest):
            live = sorted(set(int(i) for i in slots_arr))
            self.steps[-1].append(("prefill", *embeds.shape[:2], len(live)))
            for j, i in enumerate(slots_arr[: len(live)]):  # (padding repeats row 0 after the live rows)
                row = lane.pending.get(int(i))  # a group program's rows are in no dict yet
                self.chunks.append((row and row.request.request_id, int(write_index[j]), int(t_valid[j])))
            return run_prefill(lane, slots_arr, tables, embeds, write_index, t_valid, *rest)

        def on_dispatch(lane, prev, tokens):
            self.steps[-1].append(("decode", len(lane.slots)))
            return dispatch(lane, prev, tokens)

        def on_start(lane, slot_idx, req, t_valid, next_rope, logits_row):
            self.first[req.request_id] = np.array(logits_row, np.float32)
            blocks = lane.table[slot_idx][: -(-t_valid // engine.block_size)]
            self.kv[req.request_id] = (
                np.asarray(engine._pool_k[:, blocks], np.float32), np.asarray(engine._pool_v[:, blocks], np.float32), t_valid,
            )
            return start(lane, slot_idx, req, t_valid, next_rope, logits_row)

        def on_finish(lane, slot_idx, slot):
            self.tokens[slot.request.request_id] = list(slot.generated)
            return finish(lane, slot_idx, slot)

        engine.step, engine._run_prefill, engine._decode_dispatch = on_step, on_prefill, on_dispatch
        engine._maybe_finish, engine._start_slot = on_finish, on_start

    def fresh(self, cap):
        """The engine drained, its account and this log at zero, its cap ``cap``."""
        assert not self.engine.has_work()
        self.engine.completed.clear()
        self.engine.reset_stats()
        self.engine.max_prefill_rows = cap
        self.engine.__dict__.pop("_prefill_due", None)
        self.steps, self.chunks, self.tokens, self.first, self.kv = [], [], {}, {}, {}
        return self.engine

    def programs(self):
        """Every step's programs, by kind and rows: what a cap must not change
        where nothing is held."""
        return [[p[:3] if p[0] == "prefill" else p for p in step] for step in self.steps]


@pytest.fixture(scope="module")
def logs():
    built = {}
    for name, cfg in FLAVORS.items():
        engine = CaptionEngine(
            cfg, kv_lanes=((96, 8),), prefill_chunk=CHUNK, block_size=8, enable_prefix_cache=False,
            params=nn.unbox(_init_params(VLM(cfg), 3)),
        )
        engine.setup()
        built[name] = Log(engine)
    yield built
    for log in built.values():
        log.engine.shutdown()


def _ids(n, seed):
    return np.random.default_rng(seed).integers(10, 250, n).tolist()


def _add(engine, name, n, max_new, seed=None):
    engine.add_request(CaptionRequest(
        request_id=name, prompt_ids=_ids(n, seed if seed is not None else n + max_new),
        sampling=SamplingConfig(max_new_tokens=max_new),
    ))


def _drain(engine):
    while engine.has_work():
        engine.step()


def _until_decoding(engine, rows):
    while len(engine.slots) < rows or engine.pending:
        engine.step()


# -- (a) one request outstanding: today's engine, step for step ------------------


def _one_outstanding(engine, scenario):
    beside, n = scenario.split("-")
    if beside == "beside":  # a row decodes: the prompt goes by chunks (a short one as ONE chunk where a cap is stated)
        _add(engine, "hold", 12, max_new=30)
        _until_decoding(engine, 1)
    _add(engine, "one", {"short": 11, "chunk": 16, "long": 37}[n], max_new=4)
    _drain(engine)


@pytest.mark.parametrize("scenario", ["idle-short", "idle-long", "beside-short", "beside-chunk", "beside-long"])
@pytest.mark.parametrize("flavor", SERVED)
def test_one_request_outstanding_runs_the_uncapped_engines_programs(logs, flavor, scenario):
    """The ramp's requests, ``correct``'s and a drained stage's meet rules 2 and
    3 (no lane decodes; nothing can join): the same programs of the same rows in
    the same steps as the engine with no cap, the same tokens, nothing held."""
    log = logs[flavor]
    _one_outstanding(log.fresh(None), scenario)
    free, free_tokens = log.programs(), dict(log.tokens)
    _one_outstanding(log.fresh(4), scenario)
    assert log.programs() == free and log.tokens == free_tokens
    assert any(p[0] == "prefill" for step in free for p in step)
    assert log.engine.phase_seconds["step_held"] == 0


# -- (b) a closed loop of short prompts: fewer, fuller programs, the same tokens ---


def _closed_loop(engine, requests=52):
    """Every request queued at once behind 8 rows (sync prep: what no row takes
    stays ``waiting``), prompts of 9-16 tokens (one chunk's bucket), outputs of
    6-17 tokens so that the rows end apart."""
    for i in range(requests):
        _add(engine, f"r{i}", 9 + i % 8, max_new=6 + (5 * i) % 12, seed=100 + i)
    _drain(engine)


@pytest.mark.parametrize("flavor", SERVED)
def test_a_closed_loop_of_short_prompts_fills_its_prefill_programs(logs, flavor):
    log = logs[flavor]
    engine = log.fresh(CAP)
    engine._prefill_due = lambda lane: True  # the same cap and the same programs, nothing held
    _closed_loop(engine)
    ph = engine.phase_seconds
    free = (ph["prefill_dispatch_n"], ph["prefill_dispatch_live"], dict(log.tokens))
    assert ph["step_held"] == 0 and len(free[2]) == 52

    _closed_loop(log.fresh(CAP))
    ph = engine.phase_seconds
    programs, live = ph["prefill_dispatch_n"], ph["prefill_dispatch_live"]
    assert live == free[1] == 52  # a prompt is a chunk: the same rows, in fewer programs
    assert programs < free[0] and ph["step_held"] > 0
    assert live / programs >= 0.9 * CAP > free[1] / free[0]
    assert max(p[3] for step in log.steps for p in step if p[0] == "prefill") == CAP  # never past it
    assert log.tokens == free[2]  # token for token


# -- (c) a lone pending row: held only while a row ends within break-even --------


@pytest.mark.parametrize("ends_in,held", [(30, False), (3, True)], ids=["no-end-in-reach", "an-end-in-reach"])
@pytest.mark.parametrize("flavor", SERVED)
def test_a_lone_pending_row_is_held_only_while_holding_pays(logs, flavor, ends_in, held):
    """Seven rows decode, one is free, four prompts arrive: one takes the row,
    three wait (more than the cap of 2: the engine is saturated). The program
    is full with the next joiner, who comes behind the first row to end. 1 row
    x ``h`` steps against 7 live rows: an end 30 steps off does not pay and
    the row is dispatched in the step that admitted it; an end 3 steps off
    does, and the two rows advance in ONE program (after which two prompts are
    left in line, no more than a program takes, and nothing more is held)."""
    log = logs[flavor]
    engine = log.fresh(2)
    for i in range(7):
        _add(engine, f"d{i}", 10, max_new=40 if i else ends_in + 6, seed=i)
    _until_decoding(engine, 7)
    (lane,) = engine.lanes
    while len(lane.slots[0].generated) < 6:
        engine.step()
    assert len(lane.slots) == 7 and not lane.pending
    left = sorted(s.request.sampling.max_new_tokens - len(s.generated) for s in lane.slots.values())
    assert left[0] == ends_in and left[1] > 7
    log.steps.clear()
    for i in range(4):
        _add(engine, f"p{i}", 12, max_new=3, seed=50 + i)
    engine.step()
    assert engine.phase_seconds["step_held"] == int(held)
    assert [p[0] for p in log.steps[-1]] == (["decode"] if held else ["prefill", "decode"])
    assert len(lane.pending) == int(held) and len(engine.waiting) + len(engine._ready) == 3
    _drain(engine)
    first = next(p for step in log.steps for p in step if p[0] == "prefill")
    assert first[3] == (2 if held else 1)
    assert engine.phase_seconds["step_held"] == (ends_in if held else 0)
    assert set(log.tokens) >= {"p0", "p1", "p2", "p3"}


# -- a caller that fills the rows one by one is never held ------------------------


@pytest.mark.parametrize("flavor", SERVED)
def test_a_ramp_that_waits_for_no_row_pending_is_never_held(logs, flavor):
    """The benchmark's ``ClosedLoop.ramp``: a target that grows by one request
    at a time, finished requests replaced, and before each growth a wait for a
    step that leaves NO row pending. Its line is never longer than a program
    takes (here 8 rows, a target of up to 10, a cap of 2), so nothing is held:
    the programs of the engine whose rule is switched off, step for step, and
    as many steps as the engine with no cap."""
    log = logs[flavor]
    seen = {}
    for rule, cap in (("no cap", None), ("off", 2), ("on", 2)):
        engine = log.fresh(cap)
        if rule == "off":
            engine._prefill_due = lambda lane: True
        (lane,) = engine.lanes
        submitted = 0
        for target in range(1, 11):
            while True:
                while submitted - len(engine.completed) < target:  # feed: the finished are replaced
                    _add(engine, f"w{submitted}", 9 + 7 * (submitted % 5), max_new=5 + submitted % 7, seed=submitted)
                    submitted += 1
                engine.step()
                started = f"w{target - 1}" in log.first
                if started and not lane.pending:
                    break
        seen[rule] = (len(log.steps), log.programs(), engine.phase_seconds["step_held"])
        _drain(engine)
    assert seen["on"] == seen["off"] and seen["on"][2] == 0 and seen["on"][0] == seen["no cap"][0]


# -- (d) long prompts keep the program full: never held -------------------------


@pytest.mark.parametrize("flavor", SERVED)
def test_long_prompts_that_keep_the_program_full_are_never_held(logs, flavor):
    """Three prompts of 4-5 chunks beside a decoding row, cap 2: two rows are due
    a chunk every step until the last prompt is alone, and then nothing can
    join it: a chunk program every step, of 2 rows while two are pending."""
    log = logs[flavor]
    engine = log.fresh(2)
    _add(engine, "hold", 12, max_new=40)
    _until_decoding(engine, 1)
    log.steps.clear()
    for i, n in enumerate((70, 64, 75)):
        _add(engine, f"long{i}", n, max_new=2, seed=7 + i)
    (lane,) = engine.lanes
    while engine.waiting or engine._ready or lane.pending:
        due = len(lane.pending) or 3  # (the first step admits all three)
        engine.step()
        prefills = [p for p in log.steps[-1] if p[0] == "prefill"]
        assert len(prefills) == 1 and prefills[0][3] == min(2, due)
    assert engine.phase_seconds["step_held"] == 0
    _drain(engine)
    assert engine.phase_seconds["prefill_dispatch_tokens"] == 12 + 70 + 64 + 75


# -- (e) a first chunk shorter than C: the K/V the group program wrote -----------


@pytest.mark.parametrize("n", [11, 16], ids=["shorter-than-a-chunk", "a-whole-chunk"])
@pytest.mark.parametrize("flavor", SERVED)
def test_a_short_prompt_riding_the_chunk_program_writes_the_group_programs_kv(logs, flavor, n, monkeypatch):
    """With a cap a prompt whose bucket is the chunk enters ``pending`` as one
    chunk (padded at its end, not shifted back) and rides ``_prefill_chunk_step``;
    without one it is ``_prefill_group``'s. One compiled program, the same
    arguments: the same K/V in the request's blocks and the same logits, bit for bit."""
    log = logs[flavor]
    seen = {}
    for cap in (None, 4):
        engine = log.fresh(cap)
        calls = []
        for method in ("_prefill_group", "_prefill_chunk_step"):
            inner = getattr(engine, method)
            monkeypatch.setattr(engine, method, lambda *a, _m=method, _f=inner: (calls.append(_m), _f(*a))[1])
        _add(engine, "hold", 12, max_new=20)
        _until_decoding(engine, 1)
        del calls[:], log.chunks[:]
        _add(engine, "short", n, max_new=3, seed=9)
        _drain(engine)
        assert calls == (["_prefill_chunk_step"] if cap else ["_prefill_group"])
        assert [c[1:] for c in log.chunks] == [(0, n)]  # written from its base on, `n` positions valid
        seen[cap] = (log.kv["short"], log.first["short"], log.tokens["short"])
        monkeypatch.undo()
    (k0, v0, t0), first0, tokens0 = seen[None]
    (k1, v1, t1), first1, tokens1 = seen[4]
    assert t0 == t1 == n and k0.shape == k1.shape and np.abs(k0).max() > 0
    assert np.array_equal(k0, k1) and np.array_equal(v0, v1)
    assert np.array_equal(first0, first1) and tokens0 == tokens1


# -- (f) where each flavor's chunks are written ----------------------------------


@pytest.mark.parametrize(
    "flavor,n,want",
    [
        # a recurrence cannot take a token twice: the last chunk starts where the one before ended, padded at its end
        ("hybrid", 37, [(0, 16), (16, 16), (32, 5)]),
        ("hybrid", 11, [(0, 11)]),  # a short prompt's one chunk: the same padding
        # attention alone: the last chunk is shifted back to END at the prompt's end (it rewrites 11 positions)...
        ("dense", 37, [(0, 16), (16, 16), (21, 16)]),
        ("moe", 37, [(0, 16), (16, 16), (21, 16)]),
        ("dense", 11, [(0, 11)]),  # ...but a FIRST chunk shorter than C has nothing before it to shift onto
    ],
)
def test_where_a_pending_rows_chunks_are_written(logs, flavor, n, want):
    log = logs[flavor]
    engine = log.fresh(4)
    _add(engine, "hold", 12, max_new=20)
    _until_decoding(engine, 1)
    log.chunks.clear()
    _add(engine, "p", n, max_new=3, seed=21)
    _drain(engine)
    assert [c[1:] for c in log.chunks] == want and all(c[0] == "p" for c in log.chunks)
    assert engine.phase_seconds["prefill_dispatch_tokens"] == 12 + n
    assert len(log.tokens["p"]) <= 3 and log.tokens["p"]


# -- the rule's inputs, one at a time --------------------------------------------


def _pending_state(log, cap, pending, waiting, lefts):
    """An engine whose one lane has ``len(lefts)`` decoding rows with that many
    tokens to go, ``pending`` rows due a chunk and ``waiting`` prompts in line."""
    engine = log.fresh(cap)
    (lane,) = engine.lanes
    for i, left in enumerate(lefts):  # admitted together by an idle engine: a first token each, `left` to go
        _add(engine, f"d{i}", 10, max_new=left + 1, seed=i)
    _until_decoding(engine, len(lefts))
    for i in range(pending + waiting):
        _add(engine, f"p{i}", 40, max_new=2, seed=30 + i)
    with engine._work_cv:
        free = [i for i in range(lane.n_slots) if i not in lane.slots]
        lane.reserved.update(free[pending:])  # the lane has `pending` free rows, no more
        engine._admit({})  # by hand: no program runs
        lane.reserved.clear()
        due = engine._prefill_due(lane)
    return engine, lane, due


@pytest.mark.parametrize(
    "cap,pending,waiting,lefts,due,why",
    [
        (None, 1, 3, [2, 2, 2], True, "no cap stated: nothing is held"),
        (2, 2, 3, [2, 2, 2], True, "full"),
        (2, 1, 0, [2, 2, 2], True, "no prompt in line"),
        (2, 1, 2, [3, 9, 9], True, "a line no longer than a program: not saturated"),
        (4, 1, 5, [2, 2], True, "fewer rows decode than the program lacks"),
        (2, 1, 3, [3, 9, 9], False, "1 row x 3 steps against 3 live rows"),
        (2, 1, 3, [4, 9, 9], True, "1 row x 4 steps against 3 live rows"),
        (4, 2, 5, [1, 2, 2, 9], False, "2 rows x 2 steps (the SECOND end fills it) against 4 live rows"),
        (4, 2, 5, [1, 3, 3, 9], True, "2 rows x 3 steps against 4 live rows"),
    ],
    ids=lambda v: v.replace(" ", "-") if isinstance(v, str) else None,
)
def test_the_rule_reads_the_cap_the_rows_due_the_line_and_the_known_ends(logs, cap, pending, waiting, lefts, due, why):
    engine, lane, got = _pending_state(logs["dense"], cap, pending, waiting, lefts)
    assert len(lane.pending) == pending and len(lane.slots) == len(lefts)
    assert len(engine._ready) + len(engine.waiting) == waiting
    assert got == due, why
    _drain(engine)
