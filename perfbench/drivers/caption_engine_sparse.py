"""Drives a ``CaptionEngine`` that serves a decoder whose every layer has a learned
indexer (Keye-VL-2.0-30B-A3B's language model as one chip of an expert-parallel
deployment: index keys beside the K/V pool, a choice of 2,048 positions a query,
sparse experts held in part) as the same offline batch as
``drivers/caption_engine.py``: its closed loop (``SpreadLoop``; the ramp is this
cell's own: ``DigestLoop``), the lengths drawn without replacement a run of
eight at a time as the Trinity cell's driver draws them, in pairs of one sum
(``lengths_in_pairs``), the shape of its window kept line for line. What differs
is what this flavor needs:

- the configuration file is checked against the flavor by its own keys (HF
  ``KeyeVL2``'s: ``sa_config``, the router's counts, the share held, two lanes of
  which one reaches 32,768 positions);
- the warmers hand the programs the pair (K, index keys) and the decode program's
  rider; for ``check*`` requests the K rows and the index-key rows of two layers
  after the prompt, the tokens, the decode steps' logits and WHAT EVERY LAYER'S
  LAST QUERY CHOSE (the bitmap the timed programs hand out) are kept;
- while a slice is traced the live rows of every prefill program are recorded, and
  the device's time is read BY ``jax.named_scope``: a trace names an operation by
  its HLO instruction alone, so the compiled text of each warmed program says
  which instructions stand under ``attn.index_score`` / ``attn.select`` /
  ``attn.sparse`` (``scope_maps``), and ``scope_seconds`` sums the events of those
  names inside each program's runs. The decode step's gather and its ``top_k`` are
  XLA operations with no name of their own; the three Pallas kernels are also
  summed under their pinned names, as a cross-check on a line;
- ``correct`` compares with ``reference/keye_vl2.py``, computed in blocks of
  queries: first-step logits after prompts under the top-k (every position
  attended) and of over 26,000 tokens, at prompts whose routing is no near-tie, on
  the median; the K rows and the index-key rows out of the pools, the shared
  prefix's blocks included; decode steps after the long prompt against the
  reference's ONE full forward; and the choice itself: the engine's chosen sets
  against the reference's own, as their overlap (two sets of 2,048 out of 26,000
  that were picked from scores in bfloat16 and in float32 differ at the boundary
  and nowhere else).

``python -m perfbench.drivers.caption_engine_sparse --faults`` prints what
``check``'s limits read when the reference itself carries a named fault: the
second of the two readings each limit lies between (PERF.md).
"""

from __future__ import annotations

import bisect
import dataclasses
import os
import re
import time

import numpy as np

from perfbench import measure, trace_reduce
from perfbench.catalog import Cell, load_module
from perfbench.drivers.caption_engine import HOST_SPANS, _Private, reachable
from perfbench.drivers.caption_engine_hybrid import SpreadLoop, _judge, _rms_err, _serve
from perfbench.drivers.caption_engine_latent import EXPERT_KERNELS, _judge_median, judge_late_rows, late_row_errors
from perfbench.drivers.caption_engine_windowed import PROGRAM_LINE, PROGRAMS, _few_rows, program_seconds
from perfbench.measure import annotate, log

# the custom calls a device trace names (a decode step's choice and gather are XLA's)
KERNELS = {
    "sparse_index_score": r"^_?sparse_index_score",
    "sparse_select": r"^_?sparse_select",
    "sparse_prefill": r"^_?sparse_prefill",
}
SCOPES = re.compile(r"attn\.index_score|attn\.index\b|attn\.select|attn\.sparse|moe\.route|moe\.experts")
_INSTRUCTION_LINE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


# -- configuration ------------------------------------------------------------


def _program_config(cell: Cell, rehearse: bool):
    """(VLMConfig, kv_lanes, block_size or None for the engine's own, prefill_chunk,
    prefill_rows) as the program defines them."""
    from cosmos_curate_tpu.models.vlm import model as vlm_model

    conf = cell.config
    if rehearse:
        r = conf["rehearse"]
        cfg = getattr(vlm_model, r["preset"])
        return cfg, tuple(map(tuple, r["kv_lanes"])), int(r["block_size"]), int(r["prefill_chunk"]), r.get("prefill_rows")
    flavor = vlm_model.vlm_flavor(conf["flavor"])
    check_config_file(conf, flavor.cfg, flavor.kv_lanes, flavor.prefill_rows)
    return flavor.cfg, flavor.kv_lanes, None, int(conf["serving"]["prefill_chunk"]), flavor.prefill_rows


def program_sizes(cfg) -> dict:
    """The flavor's sizes under the configuration file's (HF KeyeVL2's) keys."""
    m, ix = cfg.moe, cfg.indexer
    return {
        "hidden_size": cfg.dim,
        "intermediate_size": int(round(cfg.dim * cfg.hidden_mult)),
        "num_hidden_layers": cfg.n_layers,
        "num_attention_heads": cfg.n_heads,
        "num_key_value_heads": cfg.n_kv_heads,
        "head_dim": cfg.head_dim,
        "vocab_size": cfg.vocab,
        "max_position_embeddings": cfg.max_seq,
        "rope_theta": cfg.rope_theta,
        "rms_norm_eps": cfg.rms_eps,
        "tie_word_embeddings": cfg.tied_embeddings,
        "attention_bias": cfg.qkv_bias,
        "moe_intermediate_size": m.hidden,
        "num_experts": m.held_experts[1],
        "num_local_experts": m.held_experts[1],
        "num_experts_per_tok": m.top_k,
        "norm_topk_prob": m.norm_topk_prob,
        "sa_config": {
            "indexer_head_dim": ix.head_dim, "indexer_num_heads": ix.n_heads, "indexer_num_kv_heads": 1,
            "kv_chunk_size": 512, "q_chunk_size": 512, "topk": ix.top_k,
        },
        "rope_scaling": {"mrope_section": list(cfg.mrope_section), "rope_type": "default", "type": "default"},
    }


def check_config_file(conf: dict, cfg, lanes, prefill_rows) -> None:
    """The file under ``configs/`` holds the sizes as run: refuse to measure a
    program whose flavor has moved away from it."""
    from cosmos_curate_tpu.models.vlm.engine import default_block_size

    m = cfg.moe
    bad = {k: (conf[k], v) for k, v in program_sizes(cfg).items() if conf[k] != v}
    counts = conf["published_counts"]
    if counts["router_outputs"] != m.n_experts or list(counts["held_experts"]) != list(m.held_experts):
        bad["published_counts"] = (counts, (m.n_experts, m.held_experts))
    mechanisms = (cfg.qk_norm, m.shared_hidden == 0, m.first_dense == 0, m.score_func == "softmax", m.dispatch == "sorted")
    if not all(mechanisms):
        bad["assumed"] = ("q/k norm, no shared expert, no dense layer, softmax router, sorted dispatch", mechanisms)
    if [list(l) for l in lanes] != conf["serving"]["kv_lanes"]:
        bad["kv_lanes"] = (conf["serving"]["kv_lanes"], lanes)
    if conf["serving"]["block_size"] != default_block_size(lanes):
        bad["block_size"] = (conf["serving"]["block_size"], default_block_size(lanes))
    if conf["serving"]["prefill_rows"] != prefill_rows:
        bad["prefill_rows"] = (conf["serving"]["prefill_rows"], prefill_rows)
    if bad:
        raise ValueError(f"configs/{conf['name']}.json (file, program) disagree: {bad}")


# -- parameters ---------------------------------------------------------------


def make_params(cfg, seed: int):
    """Seeded parameters, plain arrays, made on the device in one jitted call IN
    THE TYPES THE ENGINE SERVES FROM. A fresh LayerNorm's bias is zero and its
    scale one: the index key's are drawn, so that neither is a no-op."""
    import flax.linen as nn
    import jax
    import jax.numpy as jnp

    from cosmos_curate_tpu.models.vlm.model import VLM, init_cache

    model = VLM(cfg, param_dtype=VLM.dtype)

    def plain(key):
        size = cfg.vision.image_size
        tree = nn.unbox(model.init(
            key, jnp.zeros((1, 1, size, size, 3), jnp.uint8), jnp.zeros((1, 4), jnp.int32),
            *init_cache(cfg, 1, length=64), method=model.init_everything,
        ))
        for i in range(cfg.n_layers):
            norm = tree["params"][f"layer_{i}"]["index_k_norm"]
            k1, k2 = jax.random.split(jax.random.fold_in(key, 1000 + i))
            norm["scale"] = 1 + 0.1 * jax.random.normal(k1, norm["scale"].shape, jnp.float32)
            norm["bias"] = 0.1 * jax.random.normal(k2, norm["bias"].shape, jnp.float32)
        return tree

    # the hardware generator: threefry spends ten seconds on a billion draws
    return jax.jit(plain)(jax.random.key(seed, impl="rbg"))


# -- traffic ------------------------------------------------------------------


def lengths_in_pairs(traffic) -> None:
    """The mix's lengths drawn WITHOUT REPLACEMENT, as the Trinity cell's driver
    draws them (every run of ``len(grid)`` requests holds each length of the grid
    once, in an order drawn from (seed, run); each request's length is uniform
    over the grid and a pure function of (seed, index)), in a narrower family of
    orders: the run is its PAIRS of one sum (shortest with longest, second with
    second to last, ...: on an evenly spaced grid every pair has the same sum),
    the pairs in an order drawn from (seed, run) and the two of a pair in an order
    drawn with it. This loop is prefill-bound at a steady rate of prompt tokens,
    and a 40 s window holds 28 requests, three and a half runs: with the whole
    run permuted the half run at a window's edge is four short prompts or four
    long ones, and tokens out spread 0.088 over four seeds and 0.073 over seven
    (my chip runs, PR 40); with pairs any stretch of the queue from one pair's
    edge to another's holds the same prompt tokens. The generator is an existing
    file: its ``request`` is wrapped here, and a length the caller fixes stays
    fixed."""
    draw, grid = traffic.request, traffic.grid
    half = len(grid) // 2
    pairs = [(grid[i], grid[-1 - i]) for i in range(half)] + ([(grid[half],)] if len(grid) % 2 else [])

    def order(run: int) -> list[int]:
        rng = np.random.default_rng([traffic.seed, 3, run])
        out = []
        for at in rng.permutation(len(pairs)):
            out += [int(n) for n in rng.permutation(pairs[at])]
        return out

    def request(i: int, *, name=None, prompt_len=None, max_new_tokens=None):
        if prompt_len is None:
            run, k = divmod(int(i), len(grid))
            prompt_len = order(run)[k]
        return draw(i, name=name, prompt_len=prompt_len, max_new_tokens=max_new_tokens)

    traffic.request = request


# -- the closed loop ----------------------------------------------------------


class DigestLoop(SpreadLoop):
    """``SpreadLoop`` with a ramp for a loop the PREFILL bounds. ``ClosedLoop.ramp``
    fills the slots one at a time and waits, for each, until no lane has a
    prompt pending: here a mean request is 63 chunks of prefill against 256
    decode steps that up to 16 rows share, so once half a dozen rows decode one
    of them ends every second or two, its replacement's prompt is pending at
    once, and that wait never ends (my chip run, PR 40: 900 s at slot 13 of 16).
    The steady state of such a loop is most slots waiting for their turn to
    prefill. So: the first request and the warmers as ever, then the whole
    target at once and ONE TURNOVER of the slots (as many requests finished as
    there are slots), after which the rows' phases are as mixed as the lengths'
    order makes them."""

    def ramp(self, timeout_s: float) -> None:
        t0 = time.monotonic()
        deadline = t0 + timeout_s
        self._turn_until(lambda: bool(self.engine.slots), deadline, "the first request to decode")
        t1 = time.monotonic()
        for i, n in enumerate(self.traffic.grid):  # one at a time: one row a prefill
            spec = self.traffic.request(10**6 + 1 + i, name=f"warm{n}", prompt_len=n, max_new_tokens=1)
            self.engine.add_request(self._request(spec))
            self._turn_until(lambda: self.warm_done == i + 1, deadline, f"the warmer of length {n}")
        t2 = time.monotonic()
        self.target = self.full_target
        done0 = len(self.results)
        self._turn_until(
            lambda: len(self.results) - done0 >= self.reachable_slots, deadline, "one turnover of the slots"
        )
        log(
            f"ramp: first request {t1 - t0:.2f} s, {len(self.traffic.grid)} warmers {t2 - t1:.2f} s, "
            f"one turnover of {self.reachable_slots} slots {time.monotonic() - t2:.2f} s"
        )


# -- the engine's private face ------------------------------------------------


def unpack_choice(words, n: int) -> np.ndarray:
    """``pack_choice``'s words ``[..., W]`` uint32 back to ``[..., n]`` bool."""
    words = np.asarray(words).astype(np.uint32)
    bits = (words[..., None] >> np.arange(32, dtype=np.uint32)) & 1
    return bits.reshape(*words.shape[:-1], -1)[..., :n].astype(bool)


class _SparsePrivate(_Private):
    """``_Private`` for an engine whose K pool travels as the pair (K, index keys)
    and whose programs hand out what their rows chose. For ``check*`` requests:
    the K rows and index-key rows in the pools after the prompt
    (``rows[name][layer]``: (``[T, Hkv * D]``, ``[T, Di]``)), the first step's
    chosen sets (``first_choice[name]``: ``[layers, T]`` bool), the tokens, and
    of every decode step the logits and the chosen sets; while ``prefill_rows`` is
    a list, the live rows of every prefill program; while ``programs`` is a
    dict, the abstract arguments of every program warmed (``scope_maps``)."""

    ROWS_OF = "check-prefix-long-0"  # the request whose rows are read out of the pools

    def __init__(self, engine) -> None:
        super().__init__(engine)  # first-step logits of check* requests
        self.rows: dict[str, dict[int, tuple]] = {}
        self.first_choice: dict[str, np.ndarray] = {}
        self.tokens: dict[str, list[int]] = {}
        self.decode_logits: dict[str, list[np.ndarray]] = {}
        self.decode_choice: dict[str, list[np.ndarray]] = {}
        self.prefill_rows: list | None = None
        self.programs: dict | None = None
        self._last_prefill = None
        start_slot, finish, collect = engine._start_slot, engine._maybe_finish, engine._decode_collect
        run_prefill = engine._run_prefill
        cfg, bs = engine.cfg, engine.block_size
        self.row_layers = (0, cfg.n_layers - 1)

        def on_start(lane, slot_idx, req, t_valid, *rest):
            name = req.request_id
            if name.startswith("check") and self._last_prefill is not None:
                slots, choice = self._last_prefill
                if slot_idx in slots and choice is not None:
                    words = np.asarray(choice)[:, slots.index(slot_idx)]
                    self.first_choice[name] = unpack_choice(words, t_valid)
            if name == self.ROWS_OF:
                # read BEFORE the slot can finish and its blocks be claimed again
                blocks = lane.table[slot_idx][: -(-t_valid // bs)]
                kept = {}
                for layer in self.row_layers:
                    k = np.asarray(engine._pool_k[layer][blocks], np.float32)  # [n, Hkv, bs, D]
                    n, hk, _, d = k.shape
                    ki = np.asarray(engine._pool_i[layer][blocks][:, 0], np.float32)  # [n, bs, W]
                    kept[layer] = (
                        k.transpose(0, 2, 1, 3).reshape(n * bs, hk * d)[:t_valid],
                        ki.reshape(n * bs, -1)[:t_valid, : cfg.indexer.head_dim],
                    )
                self.rows[name] = kept
            return start_slot(lane, slot_idx, req, t_valid, *rest)

        def on_finish(lane, slot_idx, slot):
            name = slot.request.request_id
            if name.startswith("check"):  # asked after every token: the last call holds them all
                self.tokens[name] = list(slot.generated)
            return finish(lane, slot_idx, slot)

        def on_collect(lane, flight):
            wanted = {
                i: s.request.request_id for i, s in flight.rows.items()
                if s.request.request_id.startswith("check")
            }
            if wanted:
                logits = np.asarray(flight.logits, np.float32)
                choice = np.asarray(flight.choice)
                for i in flight.emitted(lane).keys() & wanted.keys():
                    self.decode_logits.setdefault(wanted[i], []).append(logits[i])
                    # the step's query stands at flight.positions[i]: it sees that many + 1
                    seen = int(flight.positions[i]) + 1
                    self.decode_choice.setdefault(wanted[i], []).append(unpack_choice(choice[:, i], seen))
            return collect(lane, flight)

        def on_prefill(lane, slots_arr, tables, embeds, write_index, t_valid, *rest):
            if self.prefill_rows is not None:
                live = {int(s): (int(w), int(v)) for s, w, v in zip(slots_arr, write_index, t_valid)}
                self.prefill_rows.append(sorted(live.values()))  # padding rows repeat row 0
            out = run_prefill(lane, slots_arr, tables, embeds, write_index, t_valid, *rest)
            self._last_prefill = ([int(s) for s in slots_arr], engine._choice_digest)
            return out

        engine._start_slot, engine._maybe_finish, engine._decode_collect = on_start, on_finish, on_collect
        engine._run_prefill = on_prefill

    def _note(self, kind: str, program, args) -> None:
        if self.programs is not None:
            import jax

            shapes = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), args)
            self.programs.setdefault(kind, []).append((program, shapes))

    def warm_prefill(self, lane, rows: int, t: int) -> None:
        """One call of the prefill program of this shape, every row writing its
        one valid position into the garbage block."""
        import jax.numpy as jnp

        e, cfg = self.e, self.e.cfg
        rope = (rows, t, 3) if cfg.mrope_section is not None else (rows, t)
        args = (
            e.params, *e._pools(), jnp.asarray(np.zeros((rows, lane.length // e.block_size), np.int32)),
            jnp.asarray(np.zeros((rows, t, cfg.dim), np.float32)),
            jnp.asarray(np.zeros(rows, np.int32)), jnp.asarray(np.ones(rows, np.int32)),
            jnp.asarray(np.zeros(rope, np.int32)), None,
        )
        self._note("prefill", e._prefill_batch, args)
        logits, *pools = e._prefill_batch(*args)
        e._keep_pools(*pools)
        np.asarray(logits)

    def warm_decode(self, lane) -> None:
        import jax.numpy as jnp

        e = self.e
        zeros = jnp.asarray(np.zeros(lane.n_slots, np.int32))
        args = (
            e.params, *e._pools(), jnp.asarray(np.zeros((lane.n_slots, lane.length // e.block_size), np.int32)),
            zeros, zeros, zeros, e._expert_held,
        )
        self._note("decode", e._decode, args)
        greedy, _logits, *pools, e._expert_held = e._decode(*args)
        e._keep_pools(*pools)
        np.asarray(greedy)


# -- device time by named scope -----------------------------------------------


def scope_maps(programs: dict) -> dict:
    """{kind: {HLO instruction name: scope}} from the compiled text of every
    program warmed (``programs``: {kind: [(jitted, abstract arguments)]}). A
    device trace names an operation's event by its HLO line WITHOUT the line's
    metadata; the compiled text has both, so it says which instructions a
    ``jax.named_scope`` covers. The variants of a kind (two lanes, one or two
    rows) are the same lines at other shapes; a name that two of them put under
    different scopes is dropped and counted (``conflicts``)."""
    maps = {}
    for kind, variants in programs.items():
        merged, conflicts = {}, set()
        for program, shapes in variants:
            text = program.lower(*shapes).compile().as_text()
            for line in text.splitlines():
                named = _INSTRUCTION_LINE.match(line)
                if not named:
                    continue
                op = _OP_NAME.search(line)
                scope = SCOPES.search(op.group(1)) if op else None
                scope = scope.group(0) if scope else None
                name = named.group(1)
                if name in merged and merged[name] != scope:
                    conflicts.add(name)
                merged.setdefault(name, scope)
        for name in conflicts:
            merged.pop(name)
        maps[kind] = {name: scope for name, scope in merged.items() if scope}
        log(
            f"scopes: {kind}: {len(variants)} compiled programs, {len(maps[kind])} instructions under a scope, "
            f"{len(conflicts)} dropped for standing under two"
        )
    return maps


def scope_seconds(planes, maps: dict) -> dict | None:
    """{(kind, scope): device seconds} of the first chip inside the traced slice:
    every operation's event is given to the program whose run holds it (the line
    with one event a run of a jitted function) and, by its instruction's name, to
    the scope that program's compiled text puts it under. None where the trace
    has no such line."""
    chips = sorted((int(m.group(1)), p) for p in planes if (m := trace_reduce.DEVICE_PLANE.match(p.name)))
    window = trace_reduce.slice_window(planes)
    if not chips or window is None:
        return None
    plane = chips[0][1]
    modules, ops = plane.line(PROGRAM_LINE), plane.line(trace_reduce.OP_LINE)
    if modules is None or ops is None:
        return None
    lo, hi = window
    kinds = {kind: re.compile(rx) for kind, rx in PROGRAMS.items()}
    runs = sorted(
        (start, start + duration, next((k for k, rx in kinds.items() if rx.search(name)), None))
        for name, start, duration in modules.events
    )
    starts = [r[0] for r in runs]
    out: dict = {}
    names: dict = {}
    for name, start, duration in ops.events:
        inside = min(start + duration, hi) - max(start, lo)
        if inside <= 0:
            continue
        at = bisect.bisect_right(starts, start) - 1
        if at < 0 or start >= runs[at][1] or runs[at][2] not in maps:
            continue
        kind = runs[at][2]
        op = names.get(name)
        if op is None:
            op = names[name] = trace_reduce.instruction(name)
        scope = maps[kind].get(op)
        if scope:
            out[(kind, scope)] = out.get((kind, scope), 0.0) + inside / 1e9
    return out


# -- correctness --------------------------------------------------------------
#
# Routing with seeded weights is chaotic under bfloat16 (PERF.md section 6, PR
# 33): logits are compared where the reference's own routing margin is wide, on
# the median over the prompts found. The choice is compared as an OVERLAP: the
# engine scores in bfloat16 what the reference scores in float32, so of 2,048
# positions picked out of 26,000 the few dozen at the boundary differ, and a
# wrong choice (left out, half as many, every key one position off) shares a
# small part of the set or has another size. The configuration file's `check`
# has each limit's reason and its two readings.


def overlap(got: np.ndarray, want: np.ndarray) -> float:
    """|got & want| / |got | want| of two sets of positions as bool rows."""
    n = min(got.shape[-1], want.shape[-1])
    got, want = got[..., :n], want[..., :n]
    return float((got & want).sum()) / max(float((got | want).sum()), 1.0)


def judge_choice(what: str, pairs, top_k: int, tol: float) -> bool:
    """``pairs``: (the engine's sets ``[layers, T]`` bool, the reference's) a
    compared query. Every engine set must hold ``min(T, top_k)`` positions, and
    the MEDIAN overlap over (query, layer) must be at least ``tol``."""
    sizes_ok, overlaps = True, []
    for got, want in pairs:
        t = want.shape[-1]
        sizes_ok &= bool((got[..., :t].sum(axis=-1) == min(t, top_k)).all())
        overlaps += [overlap(g, w) for g, w in zip(got, want)]
    mid = float(np.median(overlaps)) if overlaps else float("nan")
    good = bool(sizes_ok and np.isfinite(mid) and mid >= tol)
    log(
        f"correct: {what}: {len(overlaps)} chosen sets (query x layer), every one of min(context, {top_k}) "
        f"positions: {sizes_ok}; overlap with the reference's own set: least {min(overlaps, default=float('nan')):.4f}, "
        f"median {mid:.4f} (at least {tol}) {'ok' if good else 'FAILED'}"
    )
    return good


def candidates(ref, params, sizes, make, *, limit: int, prompts: int, least: float, what: str, rows_of=()) -> list:
    """[(spec, logits at the last position, routing margin, chosen sets [layers,
    T], rows or None)]: candidate 0 always (its rows are read), then the first
    of ``limit`` seeded requests whose last position's routing margin is at least
    ``least`` until ``prompts`` qualify; where fewer do, the widest of the others
    make up the number (said on a line). Never empty."""
    import jax.numpy as jnp

    seen = []
    for j in range(int(limit)):
        spec = make(j)
        ids = jnp.asarray(list(spec.prefix_ids) + list(spec.prompt_ids), jnp.int32)
        at = [ids.shape[0] - 1]
        logits, margin, sets, rows = ref.logits_at(params, ids, at, **sizes, rows_of=rows_of if j == 0 else ())
        seen.append((
            dataclasses.replace(spec, request_id=f"{spec.request_id}-{j}"), np.asarray(logits[0], np.float32),
            float(margin[0]), np.asarray(sets[:, 0]), rows if j == 0 else None,
        ))
        if sum(c[2] >= least for c in seen) >= int(prompts):
            break
    wide = [c for c in seen if c[2] >= least]
    if len(wide) < int(prompts):
        rest = sorted((c for c in seen if c[2] < least), key=lambda c: -c[2])[: int(prompts) - len(wide)]
        log(
            f"correct: {what}: {len(wide)} of {len(seen)} candidates have a routing margin of {least}; judged with the "
            f"widest of the others, margins {[round(c[2], 4) for c in rest]}"
        )
        wide += rest
    if seen[0] not in wide:
        wide.append(seen[0])  # served for its rows; its logits are judged with the others' (the median's business)
    return sorted(wide, key=lambda c: int(c[0].request_id.rsplit("-", 1)[1]))


def check_against_reference(engine, private, traffic, cfg, check) -> bool:
    """The engine's timed path (its own programs at the timed sizes: chunked
    prefill in the lanes' programs, then decode, through the K/V pool and the
    index-key array) against the plain float32 forward pass on the same
    parameter tree. Two lengths (a reference pass is compiled for a length): the
    mix's shortest request behind the check's instruction (under the top-k: every
    position attended, the prefix's blocks referenced), and its request nearest
    ``long_tokens`` behind the same instruction (over 26,000 positions: the
    choice at work in every layer, the first two blocks the prefix's own)."""
    import jax.numpy as jnp

    ref = load_module("reference", "keye_vl2")
    sizes = ref.model_kwargs(cfg)
    top_k = cfg.indexer.top_k
    grid, n_prefix = traffic.grid, len(traffic.prefix_ids)
    long_ = min(grid, key=lambda n: abs(n - int(check["long_tokens"])))
    lengths = {"prefix-short": grid[0], "prefix-long": long_}

    def make(group: str, j: int):
        n = lengths[group]
        spec = traffic.request(10**6 + 100 * n + j, prompt_len=n)
        return dataclasses.replace(spec, request_id=f"check-{group}")

    ok, found = True, {}
    hits0 = engine.prefix_cache_hits
    for group, n in lengths.items():
        kind = group.split("-")[-1]
        found[group] = candidates(
            ref, engine.params, sizes, lambda j: make(group, j), limit=check[f"candidates_{kind}"],
            prompts=check[f"prompts_{kind}"], least=check["routing_margin"], what=f"{group} prompt of {n_prefix}+{n} tokens",
            rows_of=private.row_layers if kind == "long" else (),
        )
        if group == "prefix-short":  # the build, so that every judged request is a hit
            build = found[group][0][0]
            ok &= _serve(engine, traffic, "check-prefix-build", build.prompt_ids, build.prefix_ids)
        served = [c for c in found[group] if _serve(engine, traffic, c[0].request_id, c[0].prompt_ids, c[0].prefix_ids)]
        ok &= len(served) == len(found[group])
        sets = [(private.first_choice[c[0].request_id], c[3]) for c in served if c[0].request_id in private.first_choice]
        ok &= len(sets) == len(served)
        ok &= judge_choice(f"{group}: what each layer's last query chose", sets, top_k, check["choice_overlap_tol"])
        # a set of 2,048 out of 26,000 always differs from the reference's at its
        # boundary, and one position in two thousand moves no logit; of a
        # rehearsal's 12 out of 77 one position moves a logit by a third, so where
        # some prompts' sets ARE the reference's own the logits are judged on those
        own = [c for c, (got, want) in zip(served, sets) if overlap(got, want) == 1.0] if len(sets) == len(served) else []
        judged = own if own and len(own) < len(served) else served
        ok &= _judge_median(
            f"{group}: {n_prefix}+{n}-token prompts (margins {[round(c[2], 3) for c in judged]}"
            + (f"; {len(served) - len(judged)} left out, their sets differ from the reference's" if judged is own else "")
            + "), first-step logits vs float32 reference",
            [(private.first_logits[c[0].request_id], c[1]) for c in judged], check["reference_rel_tol"],
        )
    if engine.prefix_cache_hits - hits0 < sum(len(v) for v in found.values()):
        log("correct: a prefix request did not start from the cached prefix's blocks: FAILED")
        ok = False
    # the first short prefix request again, after the long ones
    spec = found["prefix-short"][0][0]
    before = private.first_logits[spec.request_id]
    ok &= _serve(engine, traffic, "check-prefix-again", spec.prompt_ids, spec.prefix_ids)
    ok &= _judge(
        "the short prefix request again after the long ones: first-step logits unmoved",
        private.first_logits.get("check-prefix-again", np.full_like(before, np.nan)), before, check["prefix_unmoved_tol"],
    )

    # K rows and index-key rows out of the pools after the first long prompt: the
    # prefix's shared blocks first, then what 103 chunks wrote
    spec, _, _, _, want = found["prefix-long"][0]
    first, last = private.row_layers
    late = []
    if spec.request_id in private.rows and want:
        got = private.rows[spec.request_id]
        whole = n_prefix // engine.block_size * engine.block_size
        what = f"{n_prefix}+{len(spec.prompt_ids)}-token prompt, layer {first}"
        ok &= _judge(f"{what}'s K rows vs float32 reference", got[first][0], want[first][0], check["rows_rms_tol"], _rms_err)
        ok &= _judge(f"{what}'s index-key rows vs float32 reference", got[first][1], want[first][1], check["index_rows_rms_tol"], _rms_err)
        if whole:
            ok &= _judge(
                f"{what}'s index-key rows of the prefix's {whole} shared positions", got[first][1][:whole],
                want[first][1][:whole], check["index_rows_rms_tol"], _rms_err,
            )
        for rows_got, rows_want in zip(got[last], want[last][:2]):
            late.append(late_row_errors(rows_got, np.asarray(rows_want), np.asarray(want[last][2]), check["late_rows_margin"]))
    else:
        log(f"correct: the rows of {spec.request_id} were not read: FAILED")
        ok = False
    what = f"layer {last}: K rows and index-key rows vs float32 reference"
    if "least_positions" in check:  # the rehearsal: tens of positions, not thousands
        ok &= _few_rows(late, check)
    else:
        ok &= judge_late_rows(np.concatenate(late) if late else [], check, what)

    # decode after the long prompt, cut by the steps so that the reference's ONE
    # forward over prompt + generated ids is as long as the long prompts were
    steps = int(check["decode_steps"])
    prompt = list(spec.prompt_ids)[: len(spec.prompt_ids) - steps]
    name = "check-decode"
    if not _serve(engine, traffic, name, prompt, spec.prefix_ids, max_new=steps + 1):
        return False
    generated, seen = private.tokens.get(name, []), private.decode_logits.get(name, [])
    if len(generated) != len(seen) + 1 or not min(steps, 4) <= len(seen) <= steps:
        log(f"correct: {name} made {len(generated)} tokens in {len(seen)} steps: FAILED")
        return False
    if len(seen) < steps:  # greedy decoding met the end-of-sequence id: the steps made are compared
        log(f"correct: {name} ended on EOS after {len(seen)} of {steps} steps")
        steps = len(seen)
    ids = jnp.asarray(list(spec.prefix_ids) + prompt + generated[:steps], jnp.int32)
    t = n_prefix + len(prompt)
    want_logits, margins, sets, _ = ref.logits_at(engine.params, ids, list(range(t, t + steps)), **sizes)
    wide = [s for s in range(steps) if float(margins[s]) >= check["decode_routing_margin"]]
    if len(wide) < 4:  # the median over every step is robust too, with more flips in it
        wide = list(range(steps))
    ok &= _judge_median(
        f"logits after decode steps {[s + 1 for s in wide]} of {steps} (the others' routing is a near-tie) vs the "
        f"reference's ONE full forward over {t + steps} ids",
        [(seen[s], want_logits[s]) for s in wide], check["decode_rel_tol"],
    )
    chose = private.decode_choice.get(name, [])
    ok &= judge_choice(
        f"what each layer's query chose in {steps} decode steps",
        [(chose[s], np.asarray(sets[:, s])[:, : t + s + 1]) for s in range(min(steps, len(chose)))], top_k,
        check["choice_overlap_tol"],
    )
    return bool(ok)


# -- the run ------------------------------------------------------------------


def run(cell: Cell, *, seed: int, seconds: float, trace: bool, rehearse: bool, devices, clock) -> dict:
    import jax

    from cosmos_curate_tpu.models.registry import WEIGHTS_DIR_ENV
    from cosmos_curate_tpu.models.vlm import CaptionEngine
    from cosmos_curate_tpu.utils.jax_cache import enable_persistent_cache

    conf = cell.config
    # the program looks for staged weights and tokenizers under /tmp unless told
    # where: nothing is staged here, and nothing outside the checkout is read
    os.environ[WEIGHTS_DIR_ENV] = str(measure.CACHE_DIR / "weights" / "none")
    log(f"compile cache at {enable_persistent_cache()}")
    cfg, lanes, block_size, chunk, prefill_rows = _program_config(cell, rehearse)
    compiles = measure.CompileCounter()

    with clock.part("params"):
        params = make_params(cfg, seed)
        jax.block_until_ready(params)
    n_params = sum(x.size for x in jax.tree.leaves(params))
    log(f"{n_params / 1e9:.3f} B parameters made from seed {seed}, in the serving types")

    with clock.part("engine"):
        engine = CaptionEngine(
            cfg, kv_lanes=lanes, async_prep=bool(conf["serving"]["async_prep"]),
            paged_attention=conf["serving"]["paged_attention"], block_size=block_size,
            prefill_chunk=chunk, params=params, max_prefill_rows=prefill_rows,
        )
        engine.setup(seed)
        private = _SparsePrivate(engine)
        if trace:
            private.programs = {}
    traffic_mod = load_module("traffic", cell.traffic["generator"])
    tparams = cell.traffic_params(rehearse)
    if int(tparams["frames"]):
        raise ValueError(f"{cell.name}: the flavor is text only and the mix sends frames")
    traffic = traffic_mod.CaptionTraffic(tparams, seed, vocab=cfg.vocab, image_size=cfg.vision.image_size)
    lengths_in_pairs(traffic)
    use_lanes, lengths = reachable(engine, traffic, engine.prefill_chunk)
    loop = DigestLoop(engine, private, traffic, sum(l.n_slots for l in use_lanes), int(tparams["backlog"]))
    stats = engine.stats()
    positions = sum(l.length * l.n_slots for l in engine.lanes)
    log(
        f"lanes {[(l.length, l.n_slots) for l in engine.lanes]}; the mix reaches "
        f"{[(l.length, l.n_slots) for l in use_lanes]}, prefill lengths {lengths}, "
        f"prompt grid {traffic.grid[0]}..{traffic.grid[-1]} step {tparams['prompt_tokens']['step']}; "
        f"resident: parameters {stats['param_bytes_per_chip'] / 2**30:.2f} GiB, K/V pool "
        f"{stats['full_pool_bytes_per_chip'] / 2**30:.2f} GiB, index keys {stats['index_pool_bytes_per_chip'] / 2**30:.2f} GiB "
        f"({engine.kv_blocks_total} blocks of {engine.block_size} x {cfg.n_layers} layers, {positions} positions for the rows)"
    )

    with clock.part("warm_programs"):
        for lane in use_lanes:
            rows = 1
            while rows <= min(int(tparams["warm_rows"]), lane.n_slots, prefill_rows or lane.n_slots):
                for t in lengths:
                    t0 = time.monotonic()
                    private.warm_prefill(lane, rows, t)
                    log(f"warm: prefill lane {lane.length} rows {rows} T {t}: {time.monotonic() - t0:.2f} s")
                rows *= 2
            t0 = time.monotonic()
            private.warm_decode(lane)
            log(f"warm: decode lane {lane.length} rows {lane.n_slots}: {time.monotonic() - t0:.2f} s")
        maps = None
        if trace and not rehearse:  # a traced run's own: the end-to-end runs pay nothing for it
            t0 = time.monotonic()
            maps = scope_maps(private.programs)
            log(f"scopes: the compiled text of the warmed programs read in {time.monotonic() - t0:.2f} s")
        private.programs = None

    check = dict(conf["check"], **(conf["rehearse"].get("check", {}) if rehearse else {}))
    # the check's instruction is long enough to fill whole blocks of the pool
    # (the mix's 64 tokens are less than one block of 128: nothing would be shared)
    check_traffic = traffic_mod.CaptionTraffic(
        dict(tparams, prefix_tokens=check.get("prefix_tokens", tparams["prefix_tokens"])), seed,
        vocab=cfg.vocab, image_size=cfg.vision.image_size,
    )
    with clock.part("correct"):
        correct = check_against_reference(engine, private, check_traffic, cfg, check)
        engine.run_until_complete()  # the last hold request ends
        private.rows.clear()

    with clock.part("ramp"):
        loop.ramp(timeout_s=900.0)
    setup_s = clock.close()

    # ---- the measured window (drivers/caption_engine.py's, line for line) ----
    tracer = measure.Tracer(cell.name) if trace else None
    trace_from = 0.25 * seconds
    trace_for = float(tparams["trace_seconds"])
    stats0, phases0 = engine.stats(), engine.phase_seconds
    done0, lost_base = len(loop.results), loop.submitted - len(loop.results) - private.in_engine()
    slice_span = None
    prefill_rows_seen = None
    with compiles.window():
        t_start = time.monotonic()
        tokens0 = loop.tokens_emitted()
        marks: list[tuple[float, int]] = []  # (seconds into the window, tokens so far), every 5 s
        while (now := time.monotonic()) < t_start + seconds:
            if now - t_start >= 5.0 * (len(marks) + 1):
                marks.append((round(now - t_start, 3), loop.tokens_emitted() - tokens0))
            if tracer is not None:
                if tracer.started_at is None and now >= t_start + trace_from:
                    tracer.start()
                    slice_span = annotate(trace_reduce.SLICE_SPAN)
                    slice_span.__enter__()
                    loop.decode_lengths = []
                    private.prefill_rows = []
                elif tracer.active and now >= tracer.started_at + trace_for:
                    slice_span.__exit__(None, None, None)
                    tracer.stop()
                    decode_lengths, loop.decode_lengths = loop.decode_lengths, None
                    prefill_rows_seen, private.prefill_rows = private.prefill_rows, None
            loop.turn()
        tokens1 = loop.tokens_emitted()
        t_end = time.monotonic()
    if tracer is not None and tracer.active:
        raise RuntimeError("the window closed before the traced slice did: --seconds is too short")
    window_s = t_end - t_start
    stats1, phases1 = engine.stats(), engine.phase_seconds  # reads the device's count: after the window
    finished = len(loop.results) - done0
    lost = loop.submitted - len(loop.results) - private.in_engine() - lost_base
    tokens = tokens1 - tokens0
    counted = stats1["decode_tokens"] - stats0["decode_tokens"]
    log(
        f"window {window_s:.3f} s: {tokens} output tokens ({counted} of them decode steps' by "
        f"the engine's counter), {finished} requests finished, {lost} lost, "
        f"{loop.early_eos} ended early on EOS since start; "
        f"prompt tokens prefilled {stats1['prefill_tokens'] - stats0['prefill_tokens']}"
    )
    log(f"tokens by time into the window: {marks}")
    log(f"engine stats at window end (since the engine started): {stats1}")
    log(f"decode programs in window: {stats1['paged_kernel_steps'] - stats0['paged_kernel_steps']}")
    log(f"engine phase seconds in window: { {k: round(phases1[k] - phases0[k], 3) for k in phases1} }")

    delta = ("decode_tokens", "decode_s", "prefill_tokens", "prefill_s", "paged_kernel_steps")
    sparse_counts = ("sparse_decode_calls", "sparse_decode_positions_live", "sparse_decode_positions_chosen")
    record = {
        "correct": bool(correct),
        "attempted": finished + lost,
        "failed": lost,
        "setup_s": setup_s,
        "window_s": window_s,
        "end_to_end": {"output_tok_per_s": tokens / window_s, "setup_s": setup_s},
        "stats_delta": {k: stats1[k] - stats0[k] for k in delta},
        "phase_delta": {k: phases1[k] - phases0[k] for k in phases1},
        "compiles_in_window": compiles.count,
        "devices": devices,
        "rehearse": rehearse,
        "trace": None,
        "expert_trace": None,
        "program_s": None,
        "scope_s": None,
        # the index keys, what the decode steps saw and read, the experts held: each has a reader
        "sparse": {"index_pool_bytes_per_chip": stats1["index_pool_bytes_per_chip"]}
        | {k: stats1[k] - stats0[k] for k in (*sparse_counts, "expert_assignments_held")},
    }
    if tracer is not None:
        planes = trace_reduce.load_xplane(tracer.xplane())
        measure.keep_trace_for_reading(
            planes, cell.name + (".rehearsal" if rehearse else ""), HOST_SPANS
        )
        summary = trace_reduce.reduce(planes, kernels={}, host_spans=HOST_SPANS, chips=len(devices))
        tracer.discard()
        record["trace"] = summary
        record["slice"] = {
            "decode_lengths": decode_lengths,
            "prefill_rows": prefill_rows_seen,
            "sparse_shape": dict(
                n_layers=cfg.n_layers, top_k=cfg.indexer.top_k, index_heads=cfg.indexer.n_heads,
                index_dim=cfg.indexer.head_dim, n_kv_heads=cfg.n_kv_heads, head_dim=cfg.head_dim, dtype_bytes=2,
            ),
        }
        if summary is not None:
            seen_ops = set(summary.op_s)
            named = {k: rx for k, rx in KERNELS.items() if any(re.search(rx, op) for op in seen_ops)}
            by_name = trace_reduce.reduce(planes, kernels=named, chips=len(devices))
            experts = trace_reduce.reduce(planes, kernels=EXPERT_KERNELS, chips=len(devices))
            programs = program_seconds(planes)
            scopes = scope_seconds(planes, maps) if maps else None
            record["expert_trace"] = {"kernel_s": experts.kernel_s, "kernel_calls": experts.kernel_calls}
            record["program_s"] = programs
            record["scope_s"] = scopes
            # the cell's attention, all of it, for ``kernel.paged_attention_time_share``:
            # by scope (the decode step's choice and gather have no kernel's name),
            # else the three kernels by their names
            attention = ("attn.index_score", "attn.select", "attn.sparse")
            summary.kernel_s = (
                {f"{kind}:{scope}": s for (kind, scope), s in scopes.items() if scope in attention}
                if scopes else dict(by_name.kernel_s)
            )
            log(
                f"traced slice {summary.window_s:.3f} s, {summary.events} device events: busy "
                f"{summary.busy_s:.3f} s, by scope {scopes}, the Pallas kernels by name {by_name.kernel_s} calls "
                f"{by_name.kernel_calls}, grouped matmul {experts.kernel_s} calls {experts.kernel_calls}, programs by "
                f"kind {programs}, {len(decode_lengths)} decode and {len(prefill_rows_seen)} prefill programs "
                f"recorded, gaps {summary.gap_s}"
            )
    return record


# -- the second reading of check's limits --------------------------------------


FAULTS = (
    ("bfloat16 activations (what the engine computes in: must pass)", dict(activation_mantissa_bits=7)),
    ("a bfloat16 indexer (what the engine computes in: must pass)", dict(indexer_mantissa_bits=7)),
    ("the choice left out (attention over every position)", dict(topk=10**9)),
    ("a top-k of half the configuration's", dict(topk="half")),
    ("index keys written one position off", dict(index_shift=1)),
    ("the indexer in 8-bit floats (3 bits of mantissa)", dict(indexer_mantissa_bits=3)),
    ("the activations in 8-bit floats (3 bits of mantissa)", dict(activation_mantissa_bits=3)),
    ("a bfloat16 router", dict(router_mantissa_bits=7)),
)


def fault_readings(seed: int, tokens: int | None = None, cell_name: str = "keye-vl2-a3b-ep8.digest-2k-30k") -> None:
    """What ``check``'s statistics read when the reference itself carries a named
    fault, against the same reference without it, on seeded parameters at the
    configuration's full size: the second of the two readings each limit lies
    between. ``tokens``: the prompt's length (None: the check's long prompt)."""
    import jax
    import jax.numpy as jnp

    from cosmos_curate_tpu.models.vlm import model as vlm_model
    from perfbench.catalog import load_cell
    from perfbench.traffic.caption_requests import CaptionTraffic

    cell = load_cell(cell_name)
    cfg = vlm_model.vlm_flavor(cell.config["flavor"]).cfg
    check = cell.config["check"]
    ref = load_module("reference", "keye_vl2")
    params = make_params(cfg, seed)
    traffic = CaptionTraffic(cell.traffic_params(False), seed, vocab=cfg.vocab, image_size=cfg.vision.image_size)
    sizes = ref.model_kwargs(cfg)
    n = int(tokens or check["long_tokens"])
    layers = (0, cfg.n_layers - 1)
    found = candidates(
        ref, params, sizes, lambda j: traffic.request(10**6 + j, name="check-fault", prompt_len=n),
        limit=check["candidates_long"], prompts=check["prompts_long"], least=check["routing_margin"],
        what=f"prompts of {n} tokens", rows_of=layers,
    )
    want_rows = found[0][4]
    for what, low in FAULTS:
        low = {k: (cfg.indexer.top_k // 2 if v == "half" else v) for k, v in low.items()}
        log(f"the reference with {what}, against itself without:")
        pairs, sets = [], []
        rows = None
        for j, (spec, want, _, want_sets, _) in enumerate(found):
            ids = jnp.asarray(list(spec.prefix_ids) + list(spec.prompt_ids), jnp.int32)
            got, _, got_sets, got_rows = ref.logits_at(
                params, ids, [ids.shape[0] - 1], **sizes, **low, rows_of=layers if j == 0 else ()
            )
            pairs.append((got[0], want))
            sets.append((np.asarray(got_sets[:, 0]), want_sets))
            rows = got_rows if j == 0 else rows
        _judge_median("    first-step logits", pairs, check["reference_rel_tol"])
        judge_choice("    what each layer's last query chose", sets, cfg.indexer.top_k, check["choice_overlap_tol"])
        first, last = layers
        _judge(f"    layer {first}'s K rows", rows[first][0], want_rows[first][0], check["rows_rms_tol"], _rms_err)
        _judge(f"    layer {first}'s index-key rows", rows[first][1], want_rows[first][1], check["index_rows_rms_tol"], _rms_err)
        late = [
            late_row_errors(np.asarray(g), np.asarray(w), np.asarray(want_rows[last][2]), check["late_rows_margin"])
            for g, w in zip(rows[last][:2], want_rows[last][:2])
        ]
        judge_late_rows(np.concatenate(late), check, f"    layer {last}'s K and index-key rows")
    jax.effects_barrier()


if __name__ == "__main__":
    import argparse

    p = argparse.ArgumentParser(description=fault_readings.__doc__.split("\n\n")[0])
    p.add_argument("--faults", action="store_true", required=True)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--tokens", type=int, default=None)
    fault_readings(p.parse_args().seed, p.parse_args().tokens)
