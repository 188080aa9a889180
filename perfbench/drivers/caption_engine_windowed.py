"""Drives a ``CaptionEngine`` that serves a decoder with window and full attention
layers mixed over two KV pools, sparse experts held in part (Trinity-Large as one
chip of an expert-parallel deployment) as the same offline batch as
``drivers/caption_engine.py``: its closed loop and ramp (``SpreadLoop``, imported
as it is: the mix's lengths are the generator's own draw for (seed, index)) and
the shape of its window, kept line for line. What differs is what this flavor
needs:

- the configuration file is checked against the flavor by its own keys (afmoe's:
  the layer kinds, the window, the router's counts and score function, the share
  held, the two lanes of which one passes 4,096 positions);
- seeded parameters are made in the serving types directly, and the router's
  selection bias is drawn small and seeded (a fresh init leaves it zero);
- the warmers hand the programs both pools and both tables, and the decode
  program's rider; for ``check*`` requests the K rows of three layers in the two
  pools after the prompt, the tokens, the decode steps' logits and the head of
  the row's window table are kept (``_WindowedPrivate``; decode results are read
  at ``_decode_collect``);
- while a slice is traced, the live rows of every prefill program are recorded
  (their write offsets and valid lengths), which the prefill roofline reads; the
  trace is reduced a second time for the experts' grouped matmul, and the
  programs' own device seconds are summed by kind (``program_seconds``);
- ``correct`` compares with ``reference/trinity_afmoe.py``, computed in blocks of
  queries: first-step logits after prompts under the window and about 9,000
  tokens long (past window + chunk twice over: the window table wraps and both
  kernels' lower bounds are inside what is compared), at prompts whose routing
  is no near-tie, judged on the median over the prompts found (never an empty
  list); K rows out of both pools; decode steps after the long prompt against
  the reference's ONE full forward; requests from the shared prefix's blocks:
  the short ones must REFERENCE the prefix's window blocks, the long ones copy
  them and wrap, and a short one served again must not notice.

``python -m perfbench.drivers.caption_engine_windowed --lower-precision`` prints
what ``check``'s limits read when the reference itself computes in fewer bits:
the second of the two readings each limit lies between (PERF.md).
"""

from __future__ import annotations

import dataclasses
import os
import time

import numpy as np

from perfbench import measure, trace_reduce
from perfbench.catalog import Cell, load_module
from perfbench.drivers.caption_engine import HOST_SPANS, _Private, reachable
from perfbench.drivers.caption_engine_hybrid import SpreadLoop, _judge, _rms_err, _serve
from perfbench.drivers.caption_engine_latent import EXPERT_KERNELS, _judge_median, judge_late_rows, late_row_errors
from perfbench.measure import annotate, log

# the custom calls a device trace names: both kinds of layer call the same two
KERNELS = {"paged_decode": r"^_?paged_decode", "paged_prefill": r"^_?paged_prefill"}
# the programs a device trace names on its own line, one event a run of a jitted function
PROGRAMS = {"prefill": r"^jit_prefill_batch", "decode": r"^jit_decode_step"}
PROGRAM_LINE = "XLA Modules"
BIAS_STD = 0.02  # the seeded selection bias (the configuration file's `assumed`)


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
    # no block size is handed over: the engine takes its own for these lanes, as
    # SharedCaptionEngine.get builds it (the file's is checked against it)
    return flavor.cfg, flavor.kv_lanes, None, int(conf["serving"]["prefill_chunk"]), flavor.prefill_rows


def program_sizes(cfg) -> dict:
    """The flavor's sizes under the configuration file's (HF afmoe's) keys."""
    m = cfg.moe
    kinds = {True: "sliding_attention", False: "full_attention"}
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
        "sliding_window": cfg.sliding_window,
        "layer_types": [kinds[i in cfg.window_layers] for i in range(cfg.n_layers)],
        "moe_intermediate_size": m.hidden,
        "num_shared_experts": m.shared_hidden // m.hidden,
        "num_experts": m.held_experts[1],
        "num_experts_per_tok": m.top_k,
        "num_dense_layers": m.first_dense,
        "n_group": m.n_group,
        "topk_group": m.topk_group,
        "route_norm": m.norm_topk_prob,
        "route_scale": m.routed_scaling_factor,
        "score_func": m.score_func,
        "mup_enabled": cfg.embedding_multiplier == cfg.dim**0.5,
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
    mechanisms = (cfg.attention_gate, cfg.sandwich_norm, cfg.qk_norm, not cfg.full_attention_rope, m.selection_bias)
    if not all(mechanisms):
        bad["assumed"] = ("gate, sandwich norm, q/k norm, no rope on full layers, selection bias", mechanisms)
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
    THE TYPES THE ENGINE SERVES FROM (a float32 tree of this cut is 17.6 GB),
    the routers' selection bias drawn normal(0, ``BIAS_STD``): an untrained
    model's is zero, and a zero bias would leave its addition untested."""
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
        for i in range(cfg.moe.first_dense, cfg.n_layers):
            moe = tree["params"][f"layer_{i}"]["moe"]
            moe["router_bias"] = BIAS_STD * jax.random.normal(
                jax.random.fold_in(key, 1000 + i), moe["router_bias"].shape, jnp.float32
            )
        return tree

    # the hardware generator: threefry spends ten seconds on two billion draws
    return jax.jit(plain)(jax.random.key(seed, impl="rbg"))


# -- traffic ------------------------------------------------------------------


def lengths_in_blocks(traffic) -> None:
    """The mix's lengths drawn WITHOUT REPLACEMENT: every run of ``len(grid)``
    requests in the order they are sent holds each length of the grid once, in
    an order drawn from (seed, run). Each request's length is still uniform
    over the grid and a pure function of (seed, index); what goes is the
    chance of five 11,200-token prompts in a row. A 40 s window holds about a
    hundred requests whose cost is all but proportional to their prompts, so
    with independent draws tokens out follow the mean length the seed happened
    to draw (spread 0.16-0.17 over seeds, four times what a cell is admitted
    at: PERF.md, PR 38). The generator is an existing file: its ``request`` is
    wrapped here, and a length the caller fixes stays fixed."""
    draw, grid = traffic.request, traffic.grid

    def request(i: int, *, name=None, prompt_len=None, max_new_tokens=None):
        if prompt_len is None:
            run, k = divmod(int(i), len(grid))
            prompt_len = int(np.random.default_rng([traffic.seed, 3, run]).permutation(grid)[k])
        return draw(i, name=name, prompt_len=prompt_len, max_new_tokens=max_new_tokens)

    traffic.request = request


# -- the engine's private face ------------------------------------------------


class _WindowedPrivate(_Private):
    """``_Private`` for an engine with two pools whose decode program carries the
    experts' count. For ``check*`` requests the K rows in the pools after the
    prompt (``rows[name][layer]``: ``[positions, Hkv * D]`` from ``rows_from``
    on), the tokens and the logits of every decode step are kept, and for
    ``check-prefix*`` requests the entries of the row's window table that cover
    the prefix's whole blocks; while ``prefill_rows`` is a list, the live rows
    of every prefill program."""

    ROW_LAYERS = "check-text-long"  # the request whose K rows are read: the FIRST long text prompt

    def __init__(self, engine) -> None:
        super().__init__(engine)  # first-step logits of check* requests
        self.rows: dict[str, dict[int, np.ndarray]] = {}
        self.rows_from: dict[str, int] = {}
        self.tokens: dict[str, list[int]] = {}
        self.decode_logits: dict[str, list[np.ndarray]] = {}
        self.window_heads: dict[str, tuple[int, ...]] = {}
        self.prefill_rows: list | None = None
        start_slot, finish, collect = engine._start_slot, engine._maybe_finish, engine._decode_collect
        run_prefill = engine._run_prefill
        cfg, bs = engine.cfg, engine.block_size

        def on_start(lane, slot_idx, req, t_valid, *rest):
            if req.request_id.startswith("check-prefix"):
                whole = len(req.prefix_ids) // bs
                self.window_heads[req.request_id] = tuple(int(b) for b in lane.wtable[slot_idx][:whole])
            if req.request_id.startswith(self.ROW_LAYERS) and not self.rows:
                # read BEFORE the slot can finish and its blocks be claimed again.
                # The window layers' rows: the last `window` positions, all the
                # ring is sure to hold; the full layer's: every position
                first = max(t_valid - cfg.sliding_window, 0) // bs * bs
                blocks = np.arange(first // bs, -(-t_valid // bs))
                self.rows_from[req.request_id] = first
                kept = {}
                for layer in (cfg.window_layers[1], cfg.window_layers[-1]):
                    pages = engine._wpool_k[cfg.window_layers.index(layer)][lane.wtable[slot_idx][blocks]]
                    kept[layer] = self._positions(pages, first, t_valid)
                full = cfg.full_layers[-1]
                pages = engine._pool_k[cfg.full_layers.index(full)][lane.table[slot_idx][: -(-t_valid // bs)]]
                kept[full] = self._positions(pages, 0, t_valid)
                self.rows[req.request_id] = kept
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
                for i in flight.emitted(lane).keys() & wanted.keys():
                    self.decode_logits.setdefault(wanted[i], []).append(logits[i])
            return collect(lane, flight)

        def on_prefill(lane, slots_arr, tables, embeds, write_index, t_valid, *rest):
            if self.prefill_rows is not None:
                live = {int(s): (int(w), int(v)) for s, w, v in zip(slots_arr, write_index, t_valid)}
                self.prefill_rows.append(sorted(live.values()))  # padding rows repeat row 0
            return run_prefill(lane, slots_arr, tables, embeds, write_index, t_valid, *rest)

        engine._start_slot, engine._maybe_finish, engine._decode_collect = on_start, on_finish, on_collect
        engine._run_prefill = on_prefill

    @staticmethod
    def _positions(pages, first: int, end: int) -> np.ndarray:
        """Pool pages ``[n, Hkv, bs, D]`` (logical blocks from position ``first``
        on) as rows ``[end - first, Hkv * D]``."""
        pages = np.asarray(pages, np.float32)
        n, hk, bs, d = pages.shape
        return pages.transpose(0, 2, 1, 3).reshape(n * bs, hk * d)[: end - first]

    def _zero_tables(self, lane, rows: int):
        import jax.numpy as jnp

        zeros = np.zeros((rows, lane.length // self.e.block_size), np.int32)
        return (jnp.asarray(zeros), jnp.asarray(zeros))

    def warm_prefill(self, lane, rows: int, t: int) -> None:
        """One call of the prefill program of this shape, every row writing its
        one valid position into the garbage block of both pools."""
        import jax.numpy as jnp

        e, cfg = self.e, self.e.cfg
        logits, *pools = e._prefill_batch(
            e.params, *e._pools(), self._zero_tables(lane, rows),
            jnp.asarray(np.zeros((rows, t, cfg.dim), np.float32)),
            jnp.asarray(np.zeros(rows, np.int32)), jnp.asarray(np.ones(rows, np.int32)),
            jnp.asarray(np.zeros((rows, t), np.int32)), None,
        )
        e._keep_pools(*pools)
        np.asarray(logits)

    def warm_decode(self, lane) -> None:
        import jax.numpy as jnp

        e = self.e
        zeros = jnp.asarray(np.zeros(lane.n_slots, np.int32))
        greedy, _logits, *pools, e._expert_held = e._decode(
            e.params, *e._pools(), self._zero_tables(lane, lane.n_slots), zeros, zeros, zeros,
            e._expert_held,
        )
        e._keep_pools(*pools)
        np.asarray(greedy)


# -- correctness --------------------------------------------------------------
#
# Routing with seeded weights is chaotic under bfloat16 (PERF.md section 6, PR
# 33): logits are compared where the reference's own routing margin is wide, on
# the median over the prompts found; the K rows on a quantile and an outlier
# share over thousands of positions. The configuration file's `check` has each
# limit's reason and its two readings.


def _text_only(traffic, name: str, n: int, j: int):
    """Candidate ``j`` of a seeded text-only request of ``n`` prompt ids and no
    shared prefix (``traffic.text_only`` draws one prompt a length)."""
    spec = traffic.request(2 * 10**6 + 100 * int(n) + j, name=name, prompt_len=int(n), max_new_tokens=1)
    return dataclasses.replace(spec, prefix_ids=[])


def widest_margins(ref, params, sizes, make, *, candidates: int, prompts: int, least: float, what: str) -> list:
    """[(spec, the reference's logits at its last position, its routing margin)]:
    the first ``prompts`` of ``candidates`` seeded requests whose last
    position's routing margin is at least ``least``; where fewer qualify, the
    candidates with the WIDEST margins make up the number (said on a line: a
    near-tie among them is then what the median is for). Never empty."""
    import jax.numpy as jnp

    seen = []
    for j in range(int(candidates)):
        spec = make(j)
        ids = jnp.asarray(list(spec.prefix_ids) + list(spec.prompt_ids), jnp.int32)
        want, margin = ref.last_logits(params, ids, **sizes)
        seen.append((dataclasses.replace(spec, request_id=f"{spec.request_id}-{j}"), np.asarray(want, np.float32), float(margin)))
        if sum(m >= least for _, _, m in seen) == int(prompts):
            break
    wide = [c for c in seen if c[2] >= least]
    if len(wide) < int(prompts):
        rest = sorted((c for c in seen if c[2] < least), key=lambda c: -c[2])
        log(
            f"correct: {what}: {len(wide)} of {len(seen)} candidates have a routing margin of {least}; judged with the "
            f"widest of the others, margins {[round(c[2], 4) for c in rest[: int(prompts) - len(wide)]]}"
        )
        wide += rest[: int(prompts) - len(wide)]
    return wide


def check_against_reference(engine, private, traffic, cfg, check) -> bool:
    """The engine's timed path (its own programs at the timed sizes: chunked
    prefill in the lanes' programs, then decode, through both pools) against the
    plain float32 forward pass on the same parameter tree. Three groups of
    prompts of TWO lengths in all (a reference pass is compiled for a length):
    the mix's shortest request with the shared prefix (under the window: it
    references the prefix's blocks in both pools), a text-only prompt as long as
    the mix's request nearest ``long_tokens`` (past window + chunk twice over:
    the ring wraps), and that request itself (it copies the prefix's window
    blocks, then wraps)."""
    import jax.numpy as jnp

    ref = load_module("reference", "trinity_afmoe")
    sizes = ref.model_kwargs(cfg)
    grid, n_prefix = traffic.grid, len(traffic.prefix_ids)
    short = grid[0]
    long_ = min(grid, key=lambda n: abs(n - int(check["long_tokens"])))
    lengths = {"prefix-short": short, "long": n_prefix + long_, "prefix-long": long_}

    def make(group: str, j: int):
        n = lengths[group]
        if group.startswith("prefix"):
            spec = traffic.request(10**6 + 100 * n + j, prompt_len=n)
            return dataclasses.replace(spec, request_id=f"check-{group}")
        return _text_only(traffic, f"check-text-{group}", n, j)

    ok, found = True, {}
    hits0 = engine.prefix_cache_hits
    for group, n in lengths.items():
        kind = group.split("-")[-1]
        found[group] = widest_margins(
            ref, engine.params, sizes, lambda j: make(group, j), candidates=check[f"candidates_{kind}"],
            prompts=check[f"prompts_{kind}"], least=check["routing_margin"], what=f"{group} prompt of {n} tokens",
        )
        if group == "prefix-short":  # the build, so that every judged request is a hit
            build = found[group][0][0]
            ok &= _serve(engine, traffic, "check-prefix-build", build.prompt_ids, build.prefix_ids)
        served = [c for c in found[group] if _serve(engine, traffic, c[0].request_id, c[0].prompt_ids, c[0].prefix_ids)]
        ok &= len(served) == len(found[group])
        ok &= _judge_median(
            f"{group}: {len(served[0][0].prefix_ids) if served else 0}+{n}-token prompts (margins "
            f"{[round(c[2], 3) for c in served]}), first-step logits vs float32 reference",
            [(private.first_logits[c[0].request_id], c[1]) for c in served], check["reference_rel_tol"],
        )
    if engine.prefix_cache_hits - hits0 < len(found["prefix-short"]) + len(found["prefix-long"]):
        log("correct: a prefix request did not start from the cached prefix's blocks: FAILED")
        ok = False
    ok &= _prefix_blocks_shared(private, found, n_prefix // engine.block_size)
    # the first short prefix request again: the long ones have copied the
    # prefix's window blocks and wrapped their rings since
    spec = found["prefix-short"][0][0]
    before = private.first_logits[spec.request_id]
    ok &= _serve(engine, traffic, "check-prefix-again", spec.prompt_ids, spec.prefix_ids)
    ok &= _judge(
        "the short prefix request again after the long ones wrapped their rings: first-step logits unmoved",
        private.first_logits.get("check-prefix-again", np.full_like(before, np.nan)), before, check["prefix_unmoved_tol"],
    )

    # K rows out of both pools after the first long prompt: through the wrapped table
    early, late_w, full = cfg.window_layers[1], cfg.window_layers[-1], cfg.full_layers[-1]
    spec = found["long"][0][0]
    late = []
    if spec.request_id in private.rows:
        ids, first = jnp.asarray(spec.prompt_ids, jnp.int32), private.rows_from[spec.request_id]
        got = private.rows[spec.request_id]
        want = ref.cache_rows(engine.params, ids, (early, full, late_w), **sizes)
        ok &= _judge(
            f"{len(spec.prompt_ids)}-token prompt, layer {early}'s K rows in the window pool, positions {first} on, "
            "vs float32 reference", got[early], np.asarray(want[early][0])[first:], check["rows_rms_tol"], _rms_err,
        )
        for layer, start in ((full, 0), (late_w, first)):
            rows, margin = want[layer]
            late.append(late_row_errors(got[layer], np.asarray(rows)[start:], np.asarray(margin)[start:], check["late_rows_margin"]))
    what = f"layers {full} (full pool) and {late_w} (window pool), K rows vs float32 reference"
    if "least_positions" in check:  # the rehearsal: tens of positions, not thousands
        ok &= _few_rows(late, check)
    else:
        ok &= judge_late_rows(np.concatenate(late) if late else [], check, what)

    # decode after a long prompt: the window layers' walk starts a window back, the
    # full layer's at 0. The prompt is cut by the steps, so that the reference's ONE
    # forward over prompt + generated ids is as long as the long prompts were
    steps = int(check["decode_steps"])
    prompt = list(spec.prompt_ids)[: len(spec.prompt_ids) - steps]
    name = "check-decode"
    if not _serve(engine, traffic, name, prompt, max_new=steps + 1):
        return False
    generated, seen = private.tokens.get(name, []), private.decode_logits.get(name, [])
    if len(generated) != len(seen) + 1 or not min(steps, 4) <= len(seen) <= steps:
        log(f"correct: {name} made {len(generated)} tokens in {len(seen)} steps: FAILED")
        return False
    if len(seen) < steps:  # greedy decoding met the end-of-sequence id: the steps made are compared
        log(f"correct: {name} ended on EOS after {len(seen)} of {steps} steps")
        steps = len(seen)
    ids = jnp.asarray(prompt + generated[:steps], jnp.int32)
    t = len(prompt)
    want, margins = ref.logits_at(engine.params, ids, list(range(t, t + steps)), **sizes)
    wide = [s for s in range(steps) if float(margins[s]) >= check["decode_routing_margin"]]
    if len(wide) < 4:  # the median over every step is robust too, with more flips in it
        wide = list(range(steps))
    ok &= _judge_median(
        f"logits after decode steps {[s + 1 for s in wide]} of {steps} (the others' routing is a near-tie) vs the "
        f"reference's ONE full forward over {t + steps} ids",
        [(seen[s], want[s]) for s in wide], check["decode_rel_tol"],
    )
    return bool(ok)


def _prefix_blocks_shared(private, found, whole: int) -> bool:
    """What the prefix's whole window blocks are to each kind of row: the short
    requests never wrap and must all REFERENCE the same blocks; the long ones
    wrap, so each must hold private copies. Without a whole block of prefix
    nothing is referenced, and the request served again could not notice a
    prefix block written over: failed, not passed unseen."""
    short = {private.window_heads.get(c[0].request_id) for c in found["prefix-short"]}
    long_ = [private.window_heads.get(c[0].request_id) for c in found["prefix-long"]]
    good = (
        whole > 0 and len(short) == 1 and None not in short and None not in long_
        and all(set(h).isdisjoint(*short) for h in long_)
    )
    log(
        f"correct: the prefix's {whole} whole window blocks: the short rows' table entries {sorted(short, key=str)}, "
        f"the wrapping rows' {long_}: referenced by the first, copied by the second {'ok' if good else 'FAILED'}"
    )
    return bool(good)


def _few_rows(late, check) -> bool:
    """The rehearsal's K rows: tens of positions, not thousands."""
    errs = np.concatenate(late) if late else np.zeros(0)
    q90 = float(np.quantile(errs, 0.9)) if errs.size else float("nan")
    good = bool(errs.size >= check["least_positions"] and q90 <= check["late_rows_q90_tol"])
    log(f"correct: K rows of the late layers at {errs.size} positions: 90th percentile {q90:.5f} (tol {check['late_rows_q90_tol']}) {'ok' if good else 'FAILED'}")
    return good


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
        private = _WindowedPrivate(engine)
    traffic_mod = load_module("traffic", cell.traffic["generator"])
    tparams = cell.traffic_params(rehearse)
    if int(tparams["frames"]):
        raise ValueError(f"{cell.name}: the flavor is text only and the mix sends frames")
    traffic = traffic_mod.CaptionTraffic(tparams, seed, vocab=cfg.vocab, image_size=cfg.vision.image_size)
    lengths_in_blocks(traffic)
    use_lanes, lengths = reachable(engine, traffic, engine.prefill_chunk)
    loop = SpreadLoop(engine, private, traffic, sum(l.n_slots for l in use_lanes), int(tparams["backlog"]))
    stats = engine.stats()
    ring = engine._ring_blocks * engine.block_size
    log(
        f"lanes {[(l.length, l.n_slots) for l in engine.lanes]}; the mix reaches "
        f"{[(l.length, l.n_slots) for l in use_lanes]}, prefill lengths {lengths}, "
        f"prompt grid {traffic.grid[0]}..{traffic.grid[-1]} step {tparams['prompt_tokens']['step']}; "
        f"resident: parameters {stats['param_bytes_per_chip'] / 2**30:.2f} GiB, full pool "
        f"{stats['full_pool_bytes_per_chip'] / 2**30:.2f} GiB ({engine.kv_blocks_total} blocks x {len(cfg.full_layers)} "
        f"layers), window pool {stats['window_pool_bytes_per_chip'] / 2**30:.2f} GiB "
        f"({engine._wallocator.capacity} blocks x {len(cfg.window_layers)} layers: a ring of {ring} positions a row, "
        f"{sum(min(l.length, ring) * l.n_slots for l in engine.lanes)} positions for the rows)"
    )

    with clock.part("warm_programs"):
        for lane in use_lanes:
            rows = 1
            # prompts in prefill at once: as many as a program takes (the
            # flavor's prefill_rows) or the lane has slots; every such program
            # is warmed, so a burst after a stall compiles nothing in the window
            while rows <= min(int(tparams["warm_rows"]), lane.n_slots, prefill_rows or lane.n_slots):
                for t in lengths:
                    t0 = time.monotonic()
                    private.warm_prefill(lane, rows, t)
                    log(f"warm: prefill lane {lane.length} rows {rows} T {t}: {time.monotonic() - t0:.2f} s")
                rows *= 2
            t0 = time.monotonic()
            private.warm_decode(lane)
            log(f"warm: decode lane {lane.length} rows {lane.n_slots}: {time.monotonic() - t0:.2f} s")

    check = dict(conf["check"], **(conf["rehearse"].get("check", {}) if rehearse else {}))
    # the check's instruction is long enough to fill whole blocks of both pools
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
        # the two pools, the walk and the experts held, as the engine counts them: each has a reader
        "windowed": {
            "window_pool_bytes_per_chip": stats1["window_pool_bytes_per_chip"],
            "full_pool_bytes_per_chip": stats1["full_pool_bytes_per_chip"],
        }
        | {
            k: stats1[k] - stats0[k]
            for k in ("paged_decode_pages_walked", "paged_decode_pages_spanned", "expert_assignments_held")
        },
    }
    if tracer is not None:
        planes = trace_reduce.load_xplane(tracer.xplane())
        measure.keep_trace_for_reading(
            planes, cell.name + (".rehearsal" if rehearse else ""), HOST_SPANS
        )
        try:
            summary = trace_reduce.reduce(planes, kernels=KERNELS, host_spans=HOST_SPANS, chips=len(devices))
        except LookupError as e:
            # a slice in which no prompt was prefilled: the decode kernel alone
            log(f"WARNING: {e}; reduced with the decode kernel alone")
            summary = trace_reduce.reduce(
                planes, kernels={"paged_decode": KERNELS["paged_decode"]}, host_spans=HOST_SPANS,
                chips=len(devices),
            )
        experts = trace_reduce.reduce(planes, kernels=EXPERT_KERNELS, chips=len(devices))
        programs = program_seconds(planes)
        tracer.discard()
        record["trace"] = summary
        record["slice"] = {
            "decode_lengths": decode_lengths,
            "prefill_rows": prefill_rows_seen,
            "window_shape": dict(
                n_full=len(cfg.full_layers), n_window=len(cfg.window_layers), window=cfg.sliding_window,
                n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads, head_dim=cfg.head_dim, dtype_bytes=2,
            ),
        }
        if summary is not None:
            record["expert_trace"] = {"kernel_s": experts.kernel_s, "kernel_calls": experts.kernel_calls}
            record["program_s"] = programs
            log(
                f"traced slice {summary.window_s:.3f} s, {summary.events} device events: busy "
                f"{summary.busy_s:.3f} s, paged kernels {summary.kernel_s} calls {summary.kernel_calls}, "
                f"grouped matmul {experts.kernel_s} calls {experts.kernel_calls}, programs by kind {programs}, "
                f"{len(decode_lengths)} decode and {len(prefill_rows_seen)} prefill programs recorded, gaps {summary.gap_s}"
            )
    return record


def program_seconds(planes) -> dict | None:
    """{kind: [device seconds, runs]} of the first chip's programs inside the
    traced slice, from the line that holds one event a run of a jitted function
    (``jit_prefill_batch_paged(...)``, ``jit_decode_step_counted(...)``), clipped
    to the slice; what is neither kind (the copies of prefix blocks, the host's
    embedding lookups) is ``other``. None where the trace has no such line."""
    import re

    chips = sorted((int(m.group(1)), p) for p in planes if (m := trace_reduce.DEVICE_PLANE.match(p.name)))
    window = trace_reduce.slice_window(planes)
    line = chips[0][1].line(PROGRAM_LINE) if chips else None
    if line is None or window is None:
        return None
    lo, hi = window
    kinds = {kind: re.compile(rx) for kind, rx in PROGRAMS.items()}
    out = {kind: [0.0, 0] for kind in (*kinds, "other")}
    for name, start, duration in line.events:
        inside = min(start + duration, hi) - max(start, lo)
        if inside > 0:
            entry = out[next((kind for kind, rx in kinds.items() if rx.search(name)), "other")]
            entry[0] += inside / 1e9
            entry[1] += 1
    return out


# -- the second reading of check's limits --------------------------------------


def lower_precision_readings(seed: int) -> None:
    """What ``check``'s statistics read when the reference itself computes in
    fewer bits, against the same reference in float32, on seeded parameters at
    the configuration's full size: the second of the two readings each limit
    lies between. What the configuration states in float32 (router, norms,
    head) in bfloat16; its bfloat16 activations as they are (what the engine
    computes in: must pass) and in an 8-bit float; assignments dropped."""
    import jax
    import jax.numpy as jnp

    from cosmos_curate_tpu.models.vlm import model as vlm_model
    from perfbench.catalog import load_cell
    from perfbench.traffic.caption_requests import CaptionTraffic

    cell = load_cell("trinity-large-ep8.digest-1k-12k")
    cfg = vlm_model.vlm_flavor(cell.config["flavor"]).cfg
    check = cell.config["check"]
    ref = load_module("reference", "trinity_afmoe")
    params = make_params(cfg, seed)
    traffic = CaptionTraffic(cell.traffic_params(False), seed, vocab=cfg.vocab, image_size=cfg.vision.image_size)
    sizes = ref.model_kwargs(cfg)
    early, late_w, full = cfg.window_layers[1], cfg.window_layers[-1], cfg.full_layers[-1]
    grid, n_prefix = traffic.grid, len(traffic.prefix_ids)
    lengths = {"short": n_prefix + grid[0], "long": n_prefix + min(grid, key=lambda n: abs(n - int(check["long_tokens"])))}
    found = {
        kind: widest_margins(
            ref, params, sizes, lambda j: _text_only(traffic, f"check-text-{kind}", n, j),
            candidates=check[f"candidates_{kind}"], prompts=check[f"prompts_{kind}"],
            least=check["routing_margin"], what=f"{kind} prompt of {n} tokens",
        )
        for kind, n in lengths.items()
    }
    ids = jnp.asarray(found["long"][0][0].prompt_ids, jnp.int32)
    wanted = ref.cache_rows(params, ids, (early, full, late_w), **sizes)
    for what, low in (
        ("a bfloat16 router, bfloat16 norms and a bfloat16 head (what the configuration states in float32)",
         dict(router_mantissa_bits=7, norm_mantissa_bits=7, head_mantissa_bits=7)),
        ("a bfloat16 router alone", dict(router_mantissa_bits=7)),
        ("bfloat16 activations (what the engine computes in)", dict(activation_mantissa_bits=7)),
        ("8-bit float activations (3 bits of mantissa)", dict(activation_mantissa_bits=3)),
        ("one assignment in a hundred dropped", dict(drop_every=100)),
        ("one assignment in ten dropped", dict(drop_every=10)),
    ):
        log(f"the reference with {what}, against itself in float32:")
        for kind, prompts in found.items():
            pairs = [
                (ref.last_logits(params, jnp.asarray(spec.prompt_ids, jnp.int32), **sizes, **low)[0], want)
                for spec, want, _ in prompts
            ]
            _judge_median(f"    {kind} prompts, first-step logits", pairs, check["reference_rel_tol"])
        got = ref.cache_rows(params, ids, (early, full, late_w), **sizes, **low)
        late = [
            late_row_errors(got[layer][0], wanted[layer][0], wanted[layer][1], check["late_rows_margin"])
            for layer in (full, late_w)
        ]
        judge_late_rows(np.concatenate(late), check, f"    layers {full} and {late_w}, K rows")
        _judge(f"    layer {early}'s K rows", got[early][0], wanted[early][0], check["rows_rms_tol"], _rms_err)
    jax.effects_barrier()


if __name__ == "__main__":
    import argparse

    p = argparse.ArgumentParser(description=lower_precision_readings.__doc__.split("\n\n")[0])
    p.add_argument("--lower-precision", action="store_true", required=True)
    p.add_argument("--seed", type=int, default=0)
    lower_precision_readings(p.parse_args().seed)
