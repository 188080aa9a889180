"""Drives a ``CaptionEngine`` that serves a decoder with window and full attention
layers mixed over two KV pools, EVERY expert of every layer held, behind a shared
prefix LONGER than a row's ring of window blocks (Mellum2-12B-A2.5B as the first
of four pipeline stages) as the same offline batch as
``drivers/caption_engine_windowed.py``. Nothing of a loop or a judge is written
here: the indexed driver's bounded ramp and its lengths in pairs of one sum
(``DigestLoop``, ``lengths_in_pairs``), the windowed driver's spies of both pools
(``_WindowedPrivate``: K rows through the wrapped table, tokens, decode logits,
the prefill programs' live rows), its kernels' names and its programs' device
seconds, the latent driver's seeded parameters, the hybrid driver's ``_serve``
and the conv driver's comparison that FOLLOWS the program's choice of experts
(``follow``, ``judge_logits``, ``judge_choice``, ``check_router``, its spy's
``choice_of``) are imported. What is this driver's own:

- the configuration file is checked against the flavor by its own keys (HF
  ``mellum``'s: ``layer_types``, ``rope_parameters`` by layer type, the router's
  counts, all experts held, two lanes of which one reaches 32,768 positions);
- the spies keep what a ``check*`` request's ROUTERS CHOSE: this flavor's PAGED
  programs hand out every token's experts in every layer as their last output
  (``MoEConfig.hand_out_choice``), and a request's are put together from the
  shared prefix's build, its prefill chunks and its decode steps; the decode
  warmer unpacks one output more;
- ``correct`` at the timed sizes, TWO lengths behind the mix's own 2,048-token
  prefix (one pass of the ring; the ring written round fourteen times, the full
  layers at 20k, YaRN engaged), each served as a HIT (the prefix's full blocks
  referenced, its window tail copied into the row's ring) and as a text-only
  prompt of the same ids (no entry): first-step logits AND the logits after 8
  decode steps through both pools against the reference's ONE full forward
  under the request's own choice, the K rows of a late window layer (through the
  wrapped table) and of the last full layer (the prefix's shared blocks
  included), the choice itself wherever the reference's margin is wide, and the
  program's float32 router on inputs nothing has rounded;
- the cell's own readers (files no ``BENCHMARK.json`` entry names yet) go on a
  line of the traced run.

``python -m perfbench.drivers.caption_engine_mellum --lower-precision [router
activations stated]`` puts the reference itself, computing in fewer bits, in the
PROGRAM'S place in the same judges: the second of the two readings each limit
lies between (PERF.md). It exits 1 when a control comes out not ``correct``, as
the two below the stated precisions must (``stated``, bfloat16 activations alone,
is what the file states and exits 0). ``--rehearse``: the tiny preset on the CPU.
"""

from __future__ import annotations

import dataclasses
import json
import os
import time

import numpy as np

from perfbench import measure, trace_reduce
from perfbench.catalog import Cell, load_module
from perfbench.drivers.caption_engine import HOST_SPANS, reachable
from perfbench.drivers.caption_engine_conv import _ConvPrivate, check_router, follow, judge_choice, judge_logits
from perfbench.drivers.caption_engine_hybrid import _judge, _rms_err, _serve
from perfbench.drivers.caption_engine_latent import EXPERT_KERNELS, make_params
from perfbench.drivers.caption_engine_sparse import DigestLoop, lengths_in_pairs
from perfbench.drivers.caption_engine_windowed import KERNELS, _WindowedPrivate, program_seconds
from perfbench.measure import annotate, log

REFERENCE = "mellum2_moe"
CELL = "mellum2-12b-a2.5b-pp4.digest-2k-30k-rubric-2k"
# files under layer_metrics/ that the harness does not read for this cell yet (PERF.md section 7): this PR's two,
# and the window and whole-expert readers whose lists a `benchmark` PR extends
OWN_READERS = (
    "engine.prefix_reuse_share", "engine.prefix_tail_blocks_per_hit", "engine.window_pool_gib",
    "kernel.window_pages_skipped_share", "kernel.window_decode_hbm_share", "kernel.window_prefill_roofline_share",
    "kernel.whole_moe_expert_matmul_roofline_share", "kernel.whole_moe_expert_time_share",
    "engine.whole_moe_assignments_per_program",
)


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
    """The flavor's sizes under the configuration file's (HF ``mellum``'s) keys."""
    m, y = cfg.moe, cfg.full_attention_yarn
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
        "rms_norm_eps": cfg.rms_eps,
        "tie_word_embeddings": cfg.tied_embeddings,
        "attention_bias": cfg.qkv_bias,
        "sliding_window": cfg.sliding_window,
        "layer_types": [kinds[i in cfg.window_layers] for i in range(cfg.n_layers)],
        "mlp_layer_types": ["sparse" if i >= m.first_dense else "dense" for i in range(cfg.n_layers)],
        "moe_intermediate_size": m.hidden,
        "num_experts": m.n_experts,
        "num_experts_per_tok": m.top_k,
        "norm_topk_prob": m.norm_topk_prob,
        "rope_parameters": {
            "full_attention": {
                "rope_type": "yarn", "rope_theta": int(cfg.rope_theta), "factor": int(y.factor),
                "original_max_position_embeddings": y.original_max, "beta_fast": int(y.beta_fast),
                "beta_slow": int(y.beta_slow), "attention_factor": y.attention_factor,
            },
            "sliding_attention": {"rope_type": "default", "rope_theta": int(cfg.rope_theta)},
        },
    }


def check_config_file(conf: dict, cfg, lanes, prefill_rows) -> None:
    """The file under ``configs/`` holds the sizes as run: refuse to measure a
    program whose flavor has moved away from it."""
    from cosmos_curate_tpu.models.vlm.engine import default_block_size

    m = cfg.moe
    bad = {k: (conf[k], v) for k, v in program_sizes(cfg).items() if conf[k] != v}
    counts = conf["published_counts"]
    if counts["router_outputs"] != m.n_experts or list(counts["held_experts"]) != list(m.held_experts) or m.held is not None:
        bad["published_counts"] = (counts, (m.n_experts, m.held))
    # the points the config is silent on: the file's `assumed`, the program's fields
    assumed = conf["assumed"]
    program = {"scoring_func": m.score_func, "router_precision": m.router_precision, "hand_out_choice": m.hand_out_choice}
    bad.update({f"assumed.{k}": (assumed[k], v) for k, v in program.items() if assumed[k] != v})
    block = (cfg.pre_norm, cfg.sandwich_norm, cfg.qk_norm, cfg.qk_norm_whole, cfg.use_rope, cfg.full_attention_rope,
             cfg.attention_gate, m.shared_hidden, m.first_dense, m.selection_bias, m.dispatch, cfg.mla, cfg.indexer)
    if block != (True, False, True, False, True, True, False, 0, 0, False, "sorted", None, None):
        bad["assumed.block"] = (assumed["block"], block)
    if [list(l) for l in lanes] != conf["serving"]["kv_lanes"]:
        bad["kv_lanes"] = (conf["serving"]["kv_lanes"], lanes)
    if conf["serving"]["block_size"] != default_block_size(lanes):
        bad["block_size"] = (conf["serving"]["block_size"], default_block_size(lanes))
    if conf["serving"]["prefill_rows"] != prefill_rows:
        bad["prefill_rows"] = (conf["serving"]["prefill_rows"], prefill_rows)
    if bad:
        raise ValueError(f"configs/{conf['name']}.json (file, program) disagree: {bad}")


def seeded_params(cfg, seed: int):
    """The latent driver's seeded parameters (plain arrays, made on the device in
    the serving types). Its init runs a few tokens against a slot cache of
    ``max_seq`` positions, which no parameter's shape depends on: 64 do, where
    32,768 cost the set-up half a minute of compile."""
    return make_params(dataclasses.replace(cfg, max_seq=64), seed)


# -- the engine's private face ------------------------------------------------


class _MellumPrivate(_WindowedPrivate):
    """``_WindowedPrivate`` (both pools ride in the warmers' calls; a ``check*``
    request's first logits, tokens, decode logits and, for the request named
    ``ROW_LAYERS``, the K rows of three layers out of the two pools are kept)
    for programs that hand out one output more, the experts' choice, LAST.
    While ``keep_choice`` is set a ``check*`` request's choice is put together
    from the shared prefix's build, its prefill chunks and its decode steps, as
    the conv driver's spy does it (``choice_of`` is that spy's own)."""

    ROW_LAYERS = "check-hit-long"  # the request whose K rows are read: referenced full blocks, a copied tail, a wrapped ring
    choice_of = _ConvPrivate.choice_of

    def __init__(self, engine) -> None:
        super().__init__(engine)
        self.keep_choice = False  # reading a program's choice waits for the program: `correct` alone does
        self.prefix_choice: np.ndarray | None = None  # [L, T, k] of the last prefix built
        self.chunks: dict[tuple, list] = {}  # (lane, slot) -> [(write index, valid, [L, T, k])] since the slot last started
        self.prompt_choice: dict[str, list] = {}
        self.step_choice: dict[str, list[np.ndarray]] = {}  # [L, k] a decode program
        start_slot, decode = engine._start_slot, engine._decode
        prefill_batch, run_prefill, prefix_prefill = engine._prefill_batch, engine._run_prefill, engine._prefix_prefill
        last = []

        def on_prefill_batch(*args):
            out = prefill_batch(*args)
            last[:] = [out[-1]]
            return out

        def on_run_prefill(lane, slots_arr, tables, embeds, write_index, t_valid, *rest):
            logits = run_prefill(lane, slots_arr, tables, embeds, write_index, t_valid, *rest)
            if self.keep_choice:  # (padding rows repeat row 0: the same chunk twice)
                choice = np.asarray(last[0])
                for j, slot_idx in enumerate(np.asarray(slots_arr)):
                    self.chunks.setdefault((lane.length, int(slot_idx)), []).append(
                        (int(write_index[j]), int(t_valid[j]), choice[:, j])
                    )
            return logits

        def on_prefix(*args):
            out = prefix_prefill(*args)
            if self.keep_choice:
                self.prefix_choice = np.asarray(out[-1])
            return out

        def on_start(lane, slot_idx, req, *rest):
            chunks = self.chunks.pop((lane.length, int(slot_idx)), [])  # the next tenant's start from nothing
            if req.request_id.startswith("check"):
                self.prompt_choice[req.request_id] = chunks
            return start_slot(lane, slot_idx, req, *rest)

        def on_decode(params, pool_k, pool_v, tables, *rest):
            out = decode(params, pool_k, pool_v, tables, *rest)
            if self.keep_choice:
                lane = next(l for l in engine.lanes if l.table.shape == tables[0].shape)
                for slot_idx, slot in lane.slots.items():
                    if slot.request.request_id.startswith("check"):
                        self.step_choice.setdefault(slot.request.request_id, []).append(np.asarray(out[-1][:, slot_idx, 0]))
            return out

        engine._start_slot, engine._decode = on_start, on_decode
        engine._prefill_batch, engine._run_prefill, engine._prefix_prefill = on_prefill_batch, on_run_prefill, on_prefix

    def warm_decode(self, lane) -> None:
        import jax.numpy as jnp

        e = self.e
        zeros = jnp.asarray(np.zeros(lane.n_slots, np.int32))
        greedy, _logits, *pools, e._expert_held, _choice = e._decode(
            e.params, *e._pools(), self._zero_tables(lane, lane.n_slots), zeros, zeros, zeros, e._expert_held,
        )
        e._keep_pools(*pools)
        np.asarray(greedy)


# -- correctness --------------------------------------------------------------
#
# With every expert held no routing margin can be counted on over eight layers
# (``MoEConfig.hand_out_choice``'s comment; the conv driver's section
# "correctness" has the whole argument): the float32 reference FOLLOWS the
# program's own choice of experts, every request is then held to bfloat16
# rounding (the largest over the requests, not a median), and the choice is
# judged apart, where the reference's own margin is wide.


def check_requests(traffic, check):
    """[(kind, spec)]: the two lengths of ``check['prompt_tokens']`` behind the
    mix's own prefix, as seeded requests of the mix (their ids a pure function of
    (seed, length))."""
    kinds = ("short", "long")
    return [
        (kind, traffic.request(10**6 + 100 * int(n), name=f"check-{kind}", prompt_len=int(n)))
        for kind, n in zip(kinds, check["prompt_tokens"])
    ]


def check_against_reference(engine, private, traffic, cfg, check, params=None) -> bool:
    """The engine's timed path (its own programs at the timed sizes: the prefix's
    build, chunked prefill in the lanes' programs, then decode, through both
    pools) against the plain float32 forward pass that follows its choice of
    experts, on ``params`` (the engine's own tree)."""
    import jax.numpy as jnp

    ref = load_module("reference", REFERENCE)
    sizes = ref.model_kwargs(cfg)
    params = engine.params if params is None else params
    steps = int(check["decode_steps"])
    prefix = list(traffic.prefix_ids)
    n_prefix = len(prefix)
    late_w, full = cfg.window_layers[-1], cfg.full_layers[-1]
    private.keep_choice = True
    ok = True
    # the build, so that every judged prefix request is a HIT: one chunk of prompt behind the prefix
    build = traffic.request(10**6 + 1, prompt_len=engine.prefill_chunk)
    ok &= _serve(engine, traffic, "check-build", build.prompt_ids, prefix)
    stats0 = engine.stats()
    first, after, choices = [], [], []
    for kind, spec in check_requests(traffic, check):
        prompt = list(spec.prompt_ids)
        for how, (ids, pre) in {"hit": (prompt, prefix), "text": (prefix + prompt, [])}.items():
            name = f"check-{how}-{kind}"
            if not _serve(engine, traffic, name, ids, pre, max_new=steps + 1):
                return False
            generated, seen = private.tokens.get(name, []), private.decode_logits.get(name, [])
            made = min(len(seen), steps)
            if len(generated) != len(seen) + 1 or made < min(steps, 4):
                log(f"correct: {name} made {len(generated)} tokens in {len(seen)} steps: FAILED")
                return False
            if made < steps:  # greedy decoding met the end-of-sequence id: the steps made are compared
                log(f"correct: {name} ended on EOS after {made} of {steps} steps")
            t = n_prefix + len(prompt)
            choice = private.choice_of(name, len(pre), t, made)
            if choice is None:
                log(f"correct: {name}'s programs handed out no choice for some position: FAILED")
                return False
            rows = {}
            wanted = (late_w, full) if name == private.ROW_LAYERS else ()
            f = follow(ref, params, sizes, prefix + prompt + generated[:made], choice, rows_of=wanted, rows=rows)
            want = np.asarray(ref.logits_of(params, f.h[jnp.arange(t - 1, t + made)], **sizes), np.float32)
            first.append((private.first_logits[name], want[0]))
            after += list(zip(seen[:made], want[1:]))
            choices.append((choice, f.own, f.margins))
            if wanted:
                got, start = private.rows.get(name), private.rows_from.get(name, 0)
                if got is None:
                    log(f"correct: the K rows of {name} were not read: FAILED")
                    return False
                ok &= _judge(
                    f"{name}: layer {late_w}'s K rows in the window pool through the wrapped table, positions {start}-{t}, "
                    "vs the float32 reference that follows the program's choice",
                    got[late_w], np.asarray(rows[late_w])[start:t], check["rows_rms_tol"], _rms_err,
                )
                ok &= _judge(
                    f"{name}: layer {full}'s K rows in the full pool, positions 0-{t} (the first {n_prefix} the prefix's shared "
                    "blocks), vs the same", got[full], np.asarray(rows[full])[:t], check["rows_rms_tol"], _rms_err,
                )
    stats1 = engine.stats()
    what = f"{n_prefix}+{check['prompt_tokens']}-token prompts, as hits of the shared prefix and as text of the same ids"
    ok &= judge_logits(f"{what}, first-step logits vs the float32 reference that follows the program's choice", first,
                       check["reference_rel_tol"])
    ok &= judge_logits(f"{what}, logits after each of {steps} decode steps vs the reference's ONE full forward over prompt + "
                       "generated ids", after, check["decode_rel_tol"])
    ok &= judge_choice(what, choices, check)
    hits = stats1["prefix_cache_hits"] - stats0["prefix_cache_hits"]
    copied = stats1["prefix_tail_blocks_copied"] - stats0["prefix_tail_blocks_copied"]
    tail = -(-n_prefix // engine.block_size) - (n_prefix - cfg.sliding_window) // engine.block_size
    shared = hits == 2 and copied == 2 * tail and stats1["prefix_window_blocks_held"] == tail
    log(
        f"correct: the two prefix requests were hits ({hits}), each copied the entry's window tail of {tail} blocks into its ring "
        f"({copied} copied; the entry holds {stats1['prefix_window_blocks_held']} window blocks of "
        f"{-(-n_prefix // engine.block_size)} logical ones): {'ok' if shared else 'FAILED'}"
    )
    ok &= shared
    ok &= check_router(ref, cfg, params, sizes, traffic, check)
    private.keep_choice = False
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
        params = seeded_params(cfg, seed)
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
        private = _MellumPrivate(engine)
    traffic_mod = load_module("traffic", cell.traffic["generator"])
    tparams = cell.traffic_params(rehearse)
    if int(tparams["frames"]):
        raise ValueError(f"{cell.name}: the flavor is text only and the mix sends frames")
    traffic = traffic_mod.CaptionTraffic(tparams, seed, vocab=cfg.vocab, image_size=cfg.vision.image_size)
    lengths_in_pairs(traffic)
    use_lanes, lengths = reachable(engine, traffic, engine.prefill_chunk)
    loop = DigestLoop(engine, private, traffic, sum(l.n_slots for l in use_lanes), int(tparams["backlog"]))
    stats = engine.stats()
    ring = engine._ring_blocks * engine.block_size
    if len(traffic.prefix_ids) <= ring:
        raise ValueError(f"{cell.name}: the mix's prefix of {len(traffic.prefix_ids)} tokens fits a row's ring of {ring}")
    log(
        f"lanes {[(l.length, l.n_slots) for l in engine.lanes]}; the mix reaches "
        f"{[(l.length, l.n_slots) for l in use_lanes]}, prefill lengths {lengths}, a shared prefix of "
        f"{len(traffic.prefix_ids)} tokens, prompt grid {traffic.grid[0]}..{traffic.grid[-1]} step "
        f"{tparams['prompt_tokens']['step']}; resident: parameters {stats['param_bytes_per_chip'] / 2**30:.2f} GiB, full pool "
        f"{stats['full_pool_bytes_per_chip'] / 2**30:.2f} GiB ({engine.kv_blocks_total} blocks x {len(cfg.full_layers)} "
        f"layers), window pool {stats['window_pool_bytes_per_chip'] / 2**30:.2f} GiB "
        f"({engine._wallocator.capacity} blocks x {len(cfg.window_layers)} layers: a ring of {ring} positions a row)"
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
    with clock.part("correct"):
        t0 = time.monotonic()
        correct = check_against_reference(engine, private, traffic, cfg, check)
        engine.run_until_complete()  # the last hold request ends
        private.rows.clear()
        log(f"correct: {bool(correct)} in {time.monotonic() - t0:.1f} s")

    with clock.part("ramp"):
        loop.ramp(timeout_s=240.0)
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
    walked = ("paged_decode_pages_walked", "paged_decode_pages_spanned", "expert_assignments_held")
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
        # the two pools, the walk and the experts held, as the engine counts them (the windowed readers' keys)
        "windowed": {
            "window_pool_bytes_per_chip": stats1["window_pool_bytes_per_chip"],
            "full_pool_bytes_per_chip": stats1["full_pool_bytes_per_chip"],
        } | {k: stats1[k] - stats0[k] for k in walked},
        # every expert held (the whole-expert readers' key)
        "conv": {"expert_assignments_held_live": stats1["expert_assignments_held"] - stats0["expert_assignments_held"]},
        # the shared prefix past the ring: what the entry saved, what its hits copied
        "prefix": {"window_blocks_held": stats1["prefix_window_blocks_held"]} | {
            k: stats1[k] - stats0[k] for k in ("prefix_cache_hits", "prefix_tokens_saved", "prefix_tail_blocks_copied")
        },
    }
    if tracer is not None:
        planes = trace_reduce.load_xplane(tracer.xplane())
        measure.keep_trace_for_reading(planes, cell.name + (".rehearsal" if rehearse else ""), HOST_SPANS)
        try:
            summary = trace_reduce.reduce(planes, kernels=KERNELS, host_spans=HOST_SPANS, chips=len(devices))
        except LookupError as e:
            # a slice in which no prompt was prefilled: the decode kernel alone
            log(f"WARNING: {e}; reduced with the decode kernel alone")
            summary = trace_reduce.reduce(
                planes, kernels={"paged_decode": KERNELS["paged_decode"]}, host_spans=HOST_SPANS, chips=len(devices),
            )
        experts = trace_reduce.reduce(planes, kernels=EXPERT_KERNELS, chips=len(devices))
        programs = program_seconds(planes)
        tracer.discard()
        record["trace"] = summary
        m = cfg.moe
        record["slice"] = {
            "decode_lengths": decode_lengths,
            "prefill_rows": prefill_rows_seen,
            "prefill_valid": [[v for _, v in rows] for rows in prefill_rows_seen],
            "window_shape": dict(
                n_full=len(cfg.full_layers), n_window=len(cfg.window_layers), window=cfg.sliding_window,
                n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads, head_dim=cfg.head_dim, dtype_bytes=2,
            ),
            "expert_shape": dict(
                dim=cfg.dim, width=m.hidden, held=m.held_experts[1], dtype_bytes=2,
                sparse_layers=cfg.n_layers - m.first_dense, router_outputs=m.n_experts, top_k=m.top_k,
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
    if trace:
        log(f"the cell's own readers: {json.dumps(own_readers(record, devices, rehearse))}")
    return record


def own_readers(record: dict, devices, rehearse: bool) -> dict:
    """What the readers no ``BENCHMARK.json`` entry lists for this cell read of
    ``record`` (a share or a time from the CPU is never written under a device
    metric's name; a reader that raises on this cell's record says so)."""
    seen = dict(record, device=measure.device_block(devices))
    own = {}
    for name in OWN_READERS:
        reader = load_module("layer_metrics", name)
        if rehearse and reader.SOURCE != "program_counter":
            own[name] = None
            continue
        try:
            own[name] = reader.read(seen)
        except Exception as e:  # the window and whole-expert readers were written for their own cells' records
            own[name] = f"{type(e).__name__}: {e}"
    return own


# -- the second reading of check's limits --------------------------------------

# the reference's own knobs; `stated` is the precision the file states (the
# engine's bfloat16 activations over a float32 router): it must pass
CONTROLS = {
    "router": ("a bfloat16 router (its outputs and its probabilities rounded to bfloat16)", dict(router_mantissa_bits=7)),
    "activations": ("8-bit-float activations (3 bits of mantissa)", dict(activation_mantissa_bits=3)),
    "stated": ("bfloat16 activations (what the engine computes in)", dict(activation_mantissa_bits=7)),
}


def lower_precision(seed: int, names, rehearse: bool = False) -> dict[str, bool]:
    """``check``'s judges with the reference itself, computing in fewer bits, in
    the PROGRAM'S place (its logits, its K rows AND its choice of experts, which
    the float32 reference then follows as it follows the program's), on seeded
    parameters at the configuration's full size: the second of the two readings
    each limit lies between. {control: whether it came out ``correct``}."""
    import jax
    import jax.numpy as jnp

    from perfbench.catalog import load_cell

    cell = load_cell(CELL)
    cfg, *_ = _program_config(cell, rehearse)
    check = dict(cell.config["check"], **(cell.config["rehearse"].get("check", {}) if rehearse else {}))
    ref = load_module("reference", REFERENCE)
    params = seeded_params(cfg, seed)
    traffic = load_module("traffic", cell.traffic["generator"]).CaptionTraffic(
        cell.traffic_params(rehearse), seed, vocab=cfg.vocab, image_size=cfg.vision.image_size
    )
    sizes = ref.model_kwargs(cfg)
    steps = int(check["decode_steps"])
    late_w, full = cfg.window_layers[-1], cfg.full_layers[-1]
    more = traffic.request(10**6 + 7, prompt_len=steps).prompt_ids  # seeded tokens in the generated ones' place
    verdicts = {}
    for name in names:
        what, low = CONTROLS[name]
        log(f"control: the reference with {what} in the program's place")
        ok, first, after, choices = True, [], [], []
        for kind, spec in check_requests(traffic, check):
            ids = list(spec.prefix_ids) + list(spec.prompt_ids) + list(more)
            t = len(ids) - steps
            got_rows, want_rows = {}, {}
            got = follow(ref, params, sizes, ids, rows_of=(late_w, full), rows=got_rows, **low)
            want = follow(ref, params, sizes, ids, got.own, rows_of=(late_w, full), rows=want_rows)
            at = jnp.arange(t - 1, t + steps)
            lg, lw = (np.asarray(ref.logits_of(params, f.h[at], **sizes), np.float32) for f in (got, want))
            first.append((lg[0], lw[0]))
            after += list(zip(lg[1:], lw[1:]))
            choices.append((got.own, want.own, want.margins))
            if kind == "long":
                start = max(t - cfg.sliding_window, 0)
                for layer, lo in ((late_w, start), (full, 0)):
                    ok &= _judge(f"    layer {layer}'s K rows, positions {lo}-{t}", np.asarray(got_rows[layer])[lo:t],
                                 np.asarray(want_rows[layer])[lo:t], check["rows_rms_tol"], _rms_err)
        ok &= judge_logits("    first-step logits vs the float32 reference that follows the control's choice", first,
                           check["reference_rel_tol"])
        ok &= judge_logits(f"    logits at the {steps} positions after the prompt", after, check["decode_rel_tol"])
        ok &= judge_choice("    the control's choice", choices, check)
        ok &= check_router(ref, cfg, params, sizes, traffic, check, low=low)
        verdicts[name] = bool(ok)
        log(f"control: the reference with {what}: correct {bool(ok)}")
    jax.effects_barrier()
    return verdicts


if __name__ == "__main__":
    import argparse
    import sys

    p = argparse.ArgumentParser(description=lower_precision.__doc__.split("\n\n")[0])
    p.add_argument("--lower-precision", nargs="*", choices=list(CONTROLS), required=True)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--rehearse", action="store_true", help="the tiny preset on the CPU: the control flow, no reading")
    args = p.parse_args()
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    sys.exit(0 if all(lower_precision(args.seed, args.lower_precision or list(CONTROLS), args.rehearse).values()) else 1)
