"""Drives a ``CaptionEngine`` that serves a latent-attention decoder with sparse
experts (DeepSeek-V2 as one chip of an expert-parallel deployment: a latent
paged pool, absorbed attention, a sorted dispatch over the experts held) as the
same offline batch as ``drivers/caption_engine.py``: its closed loop (less the
first fill's cut outputs: ``SpreadLoop``), its ramp and the shape of its window,
imported or kept line for line. What differs is what this flavor needs:

- the configuration file is checked against the flavor by its own keys (the
  latent ranks and head sizes, YaRN's numbers, the router's counts, the share
  held);
- seeded parameters are made in the serving types directly (a float32 tree of
  this cut is 18 GB);
- the warmers carry the decode program's rider (the device's count of the
  assignments on held experts), and for ``check*`` requests the latent rows of
  layer 1 in the pool, the tokens and the decode steps' logits are kept
  (``_LatentPrivate``; decode results are read at ``_decode_collect``, where the
  look-ahead engine reads them);
- ``correct`` compares with ``reference/deepseek_v2.py`` at positions whose
  routing is no near-tie (the config file's ``routing_margin_why``): first-step
  logits after prompts of two lengths (the longer over three prefill chunks)
  and of one request that starts from the shared prefix's blocks, the latent
  rows of layer 1, the logits after decode steps through the latent pool
  against the reference's ONE full forward over prompt + generated ids, and the
  kernel engine against the engine's own XLA attention path
  (``paged_attention='gather'``);
- the traced slice is reduced twice: the two latent-attention kernels into
  ``record['trace']`` as every caption cell has its attention kernels there
  (``kernel.paged_attention_time_share`` sums them), the grouped matrix product
  into ``record['expert_trace']``.

``python -m perfbench.drivers.caption_engine_latent --lower-precision`` prints
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
from perfbench.drivers.caption_engine import HOST_SPANS, _Private, _rel_err, reachable
from perfbench.drivers.caption_engine_hybrid import SpreadLoop, _judge, _rms_err, _serve
from perfbench.measure import annotate, log

# the custom calls a device trace names: the pallas_call's own name= for the
# latent kernel (ops/latent_attention.py), the jitted wrapper's for JAX's gmm
KERNELS = {"mla_decode": r"^mla_decode", "mla_prefill": r"^mla_prefill"}
EXPERT_KERNELS = {"expert_matmul": r"^gmm"}


# -- configuration ------------------------------------------------------------


def _program_config(cell: Cell, rehearse: bool):
    """(VLMConfig, kv_lanes, prefill_chunk, prefill_rows) as the program defines them."""
    from cosmos_curate_tpu.models.vlm import model as vlm_model

    conf = cell.config
    if rehearse:
        r = conf["rehearse"]
        cfg = dataclasses.replace(getattr(vlm_model, r["preset"]), **r.get("replace", {}))
        return cfg, tuple(map(tuple, r["kv_lanes"])), int(r["prefill_chunk"]), r.get("prefill_rows")
    flavor = vlm_model.vlm_flavor(conf["flavor"])
    check_config_file(conf, flavor.cfg, flavor.kv_lanes, flavor.prefill_rows)
    return flavor.cfg, flavor.kv_lanes, int(conf["serving"]["prefill_chunk"]), flavor.prefill_rows


def program_sizes(cfg) -> dict:
    """The flavor's sizes under the configuration file's (HF's) keys."""
    a, m = cfg.mla, cfg.moe
    return {
        "hidden_size": cfg.dim,
        "intermediate_size": int(round(cfg.dim * cfg.hidden_mult)),
        "num_hidden_layers": cfg.n_layers,
        "num_attention_heads": cfg.n_heads,
        "num_key_value_heads": cfg.n_kv_heads,
        "vocab_size": cfg.vocab,
        "max_position_embeddings": cfg.max_seq,
        "rope_theta": cfg.rope_theta,
        "rms_norm_eps": cfg.rms_eps,
        "tie_word_embeddings": cfg.tied_embeddings,
        "attention_bias": cfg.qkv_bias,
        "q_lora_rank": a.q_lora_rank,
        "kv_lora_rank": a.kv_lora_rank,
        "qk_nope_head_dim": a.qk_nope_head_dim,
        "qk_rope_head_dim": a.qk_rope_head_dim,
        "v_head_dim": a.v_head_dim,
        "moe_intermediate_size": m.hidden,
        "n_shared_experts": m.shared_hidden // m.hidden,
        "n_routed_experts": m.held_experts[1],
        "num_experts_per_tok": m.top_k,
        "n_group": m.n_group,
        "topk_group": m.topk_group,
        "norm_topk_prob": m.norm_topk_prob,
        "routed_scaling_factor": m.routed_scaling_factor,
        "first_k_dense_replace": m.first_dense,
        "scoring_func": "softmax",
        "topk_method": "group_limited_greedy" if m.n_group > 1 else "greedy",
    }


def check_config_file(conf: dict, cfg, lanes, prefill_rows) -> None:
    """The file under ``configs/`` holds the sizes as run: refuse to measure a
    program whose flavor has moved away from it."""
    a, m = cfg.mla, cfg.moe
    bad = {k: (conf[k], v) for k, v in program_sizes(cfg).items() if conf[k] != v}
    yarn = {
        "type": "yarn", "factor": a.yarn_factor, "original_max_position_embeddings": a.yarn_original_max,
        "beta_fast": a.yarn_beta_fast, "beta_slow": a.yarn_beta_slow, "mscale": a.yarn_mscale,
        "mscale_all_dim": a.yarn_mscale_all_dim,
    }
    if conf["rope_scaling"] != yarn:
        bad["rope_scaling"] = (conf["rope_scaling"], yarn)
    counts = conf["published_counts"]
    if counts["router_outputs"] != m.n_experts or list(counts["held_experts"]) != list(m.held_experts):
        bad["published_counts"] = (counts, (m.n_experts, m.held_experts))
    if [list(l) for l in lanes] != conf["serving"]["kv_lanes"]:
        bad["kv_lanes"] = (conf["serving"]["kv_lanes"], lanes)
    if conf["serving"]["prefill_rows"] != prefill_rows:
        bad["prefill_rows"] = (conf["serving"]["prefill_rows"], prefill_rows)
    if bad:
        raise ValueError(f"configs/{conf['name']}.json (file, program) disagree: {bad}")


# -- parameters ---------------------------------------------------------------


def make_params(cfg, seed: int):
    """Seeded parameters, plain arrays, made on the device in one jitted call
    IN THE TYPES THE ENGINE SERVES FROM (``VLM.param_dtype``: bfloat16 matmul
    kernels, embedding and expert tables; float32 norms, router and head), so
    that the engine keeps every leaf as it is and no wider tree ever exists."""
    import flax.linen as nn
    import jax
    import jax.numpy as jnp

    from cosmos_curate_tpu.models.vlm.model import VLM, init_cache

    model = VLM(cfg, param_dtype=VLM.dtype)

    def plain(key):
        size = cfg.vision.image_size
        return nn.unbox(model.init(
            key, jnp.zeros((1, 1, size, size, 3), jnp.uint8), jnp.zeros((1, 4), jnp.int32),
            *init_cache(cfg, 1), method=model.init_everything,
        ))

    # the hardware generator: threefry spends ten seconds on two billion draws
    return jax.jit(plain)(jax.random.key(seed, impl="rbg"))


# -- the engine's private face ------------------------------------------------


class _LatentPrivate(_Private):
    """``_Private`` for an engine whose decode program carries the experts'
    count: it rides in the warmer's call. For ``check*`` requests the latent
    rows of layer 1 in the pool after the prompt, the tokens, and the logits
    of every decode step are kept too."""

    def __init__(self, engine) -> None:
        super().__init__(engine)  # first-step logits of check* requests
        self.rows: dict[str, np.ndarray] = {}  # layer 1's and the last layer's latent rows after the prompt
        self.tokens: dict[str, list[int]] = {}
        self.decode_logits: dict[str, list[np.ndarray]] = {}
        start_slot, finish, collect = engine._start_slot, engine._maybe_finish, engine._decode_collect

        def on_start(lane, slot_idx, req, t_valid, *rest):
            if req.request_id.startswith("check"):
                # read BEFORE the slot can finish and its blocks be claimed again
                table = np.asarray(lane.table[slot_idx])
                last = engine._pool_k.shape[0] - 1
                pages = np.asarray(engine._pool_k[np.array([1, last])][:, table, 0], np.float32)
                self.rows[req.request_id] = pages.reshape(2, -1, pages.shape[-1])  # [2, S, W]
            return start_slot(lane, slot_idx, req, t_valid, *rest)

        def on_finish(lane, slot_idx, slot):
            name = slot.request.request_id
            if name.startswith("check") and len(slot.generated) >= slot.request.sampling.max_new_tokens:
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

        engine._start_slot, engine._maybe_finish, engine._decode_collect = on_start, on_finish, on_collect

    def warm_decode(self, lane) -> None:
        import jax.numpy as jnp

        e = self.e
        zeros = jnp.asarray(np.zeros(lane.n_slots, np.int32))
        greedy, _logits, e._pool_k, e._pool_v, e._expert_held = e._decode(
            e.params, e._pool_k, e._pool_v, jnp.asarray(np.zeros_like(lane.table)),
            zeros, zeros, zeros, e._expert_held,
        )
        np.asarray(greedy)


# -- correctness --------------------------------------------------------------
#
# With seeded weights a token's last expert taken and first left out are often
# a few per cent apart, and by the last layers the engine's bfloat16 hidden
# state is 2% from the float32 reference's, so the engine takes another expert
# than the reference at one token in five a layer. That is rounding, not a
# fault, and it moves that position's hidden state by tens of per cent. The
# reference reports a routing margin for every position (how far its choice of
# held experts is from changing). A wide margin makes a flip rare, not
# impossible: at 0.05 and over, 1 position in 120 still differed by over 10%,
# and 2 of 15 first-step comparisons did (my chip runs, PR 33). So nothing here
# is judged on ONE position: first-step logits on the MEDIAN of five prompts
# with a margin of 0.1, decode logits on the median over the steps, the last
# layer's latent rows on a quantile and an outlier share over hundreds of
# positions (the config file's ``check`` has each limit's two readings).


def _text_only(traffic, name: str, n: int, j: int):
    """Candidate ``j`` of a seeded text-only request of ``n`` prompt ids and no
    shared prefix (``traffic.text_only`` draws one prompt a length)."""
    spec = traffic.request(2 * 10**6 + 1000 * int(n) + j, name=name, prompt_len=int(n), max_new_tokens=1)
    return dataclasses.replace(spec, prefix_ids=[])


def _with_a_wide_margin(ref, params, sizes, check, make, what: str) -> list:
    """[(spec, the reference's logits at its last position)] of the first
    ``check['prompts']`` of ``check['candidates']`` seeded requests whose last
    position's routing margin is at least ``check['routing_margin']``."""
    import jax.numpy as jnp

    found = []
    for j in range(int(check["candidates"])):
        spec = make(j)
        ids = jnp.asarray(list(spec.prefix_ids) + list(spec.prompt_ids), jnp.int32)
        want, margin = ref.last_logits(params, ids, **sizes)
        if float(margin) >= check["routing_margin"]:
            found.append((dataclasses.replace(spec, request_id=f"{spec.request_id}-{j}"), np.asarray(want, np.float32)))
            if len(found) == int(check["prompts"]):
                return found
    log(f"correct: {what}: {len(found)} of {check['candidates']} candidates have a routing margin of {check['routing_margin']}, {check['prompts']} wanted: FAILED")
    return []


def _judge_median(what: str, pairs, tol: float) -> bool:
    """The MEDIAN of the relative errors of ``pairs`` of (got, want) logits
    against ``tol``: one comparison in some hundreds meets a flipped expert."""
    errs = [_rel_err(np.asarray(g, np.float32), np.asarray(w, np.float32))[0] for g, w in pairs]
    mid = float(np.median(errs)) if errs else float("nan")
    good = bool(np.isfinite(mid) and mid <= tol)
    log(f"correct: {what}: rel err {[round(e, 5) for e in errs]}, median {mid:.5f} (tol {tol}) {'ok' if good else 'FAILED'}")
    return good


def late_row_errors(got, want, margin, least: float):
    """Per position with a routing margin of at least ``least``: the rms
    difference of its latent row over the rms of the reference's row."""
    wide = np.asarray(margin) >= least
    got, want = np.asarray(got, np.float64)[wide], np.asarray(want, np.float64)[wide]
    return np.sqrt(np.square(got - want).mean(axis=-1) / np.square(want).mean(axis=-1))


def judge_late_rows(errs, check, what: str) -> bool:
    """The LAST layer's latent rows at the wide-margin positions of the text
    prompts: the one observable that carries EVERY token's experts (a
    first-step logit carries the last token's). Two limits: a quantile, which
    a dispatch that drops one assignment in ten breaks; and the share of
    positions far off, which one in a hundred breaks in most runs."""
    errs = np.asarray(errs)
    q90 = float(np.quantile(errs, 0.9)) if errs.size else float("nan")
    far = float((errs > check["late_rows_far"]).mean()) if errs.size else float("nan")
    good = bool(errs.size >= 30 and q90 <= check["late_rows_q90_tol"] and far <= check["late_rows_far_share"])
    log(
        f"correct: {what}: {errs.size} positions with a wide routing margin: rms err a position median "
        f"{np.median(errs):.5f}, 90th percentile {q90:.5f} (tol {check['late_rows_q90_tol']}), over "
        f"{check['late_rows_far']}: {far:.4f} of them (tol {check['late_rows_far_share']}) {'ok' if good else 'FAILED'}"
    )
    return good


def check_against_reference(engine, private, traffic, cfg, check, lengths):
    """The engine's timed path against the plain float32 forward pass on the
    same parameter tree. Returns (ok, the prefix requests' specs): the XLA-path
    check serves them again."""
    import jax.numpy as jnp

    ref = load_module("reference", "deepseek_v2")
    sizes = ref.model_kwargs(cfg)
    used, last = cfg.mla.kv_lora_rank + cfg.mla.qk_rope_head_dim, cfg.n_layers - 1
    ok, late = True, []
    for n in lengths:
        found = _with_a_wide_margin(
            ref, engine.params, sizes, check,
            lambda j: _text_only(traffic, f"check-text-{n}", n, j), f"{n}-token prompt",
        )
        served = [(s, w) for s, w in found if _serve(engine, traffic, s.request_id, s.prompt_ids)]
        ok &= bool(found) and len(served) == len(found)
        ok &= _judge_median(
            f"{n}-token prompts, first-step logits vs float32 reference",
            [(private.first_logits[s.request_id], w) for s, w in served], check["reference_rel_tol"],
        )
        for k, (spec, _) in enumerate(served):
            ids, got = jnp.asarray(spec.prompt_ids, jnp.int32), private.rows[spec.request_id][:, : int(n)]
            if k == 0 and n == lengths[0]:
                rows, _ = ref.cache_rows(engine.params, ids, 1, **sizes)
                ok &= _judge(
                    f"{n}-token prompt, layer 1's latent rows in the pool vs float32 reference",
                    got[0, :, :used], rows, check["latent_rms_tol"], _rms_err,
                )
            if got[..., used:].any():
                log("correct: the padding lanes of the latent rows are not zeros: FAILED")
                ok = False
            want, margin = ref.cache_rows(engine.params, ids, last, **sizes)
            late.append(late_row_errors(got[1, :, :used], want, margin, check["late_rows_margin"]))
    ok &= judge_late_rows(np.concatenate(late) if late else [], check, f"layer {last}'s latent rows in the pool vs float32 reference")

    # through the prefix cache: the build, then requests that are hits
    found = _with_a_wide_margin(
        ref, engine.params, sizes, check,
        lambda j: dataclasses.replace(traffic.request(10**6 + 100 + j, prompt_len=traffic.grid[0]), request_id="check-prefix"),
        "request with the shared prefix",
    )
    if not found:
        return False, []
    hits0 = engine.prefix_cache_hits
    if not _serve(engine, traffic, "check-prefix-build", found[0][0].prompt_ids, found[0][0].prefix_ids):
        return False, []
    served = [(s, w) for s, w in found if _serve(engine, traffic, s.request_id, s.prompt_ids, s.prefix_ids)]
    ok &= len(served) == len(found)
    ok &= _judge_median(
        f"{len(found[0][0].prefix_ids)}+{len(found[0][0].prompt_ids)}-token requests from the shared prefix's "
        "blocks, first-step logits vs float32 reference",
        [(private.first_logits[s.request_id], w) for s, w in served], check["reference_rel_tol"],
    )
    if engine.prefix_cache_hits - hits0 < len(served):
        log("correct: a prefix request did not start from the cached prefix's blocks: FAILED")
        ok = False

    # decode through the latent pool
    steps = int(check["decode_steps"])
    spec = traffic.text_only("check-decode", int(lengths[0]))
    if not _serve(engine, traffic, spec.request_id, spec.prompt_ids, max_new=steps + 1):
        return False, [s for s, _ in served]
    generated, seen = private.tokens.get("check-decode", []), private.decode_logits.get("check-decode", [])
    if len(generated) != steps + 1 or len(seen) != steps:
        log(f"correct: check-decode made {len(generated)} tokens in {len(seen)} steps: FAILED")
        return False, [s for s, _ in served]
    ids = jnp.asarray(list(spec.prompt_ids) + generated[:steps], jnp.int32)
    t = len(spec.prompt_ids)
    want, margins = ref.logits_at(engine.params, ids, list(range(t, t + steps)), **sizes)
    wide = [s for s in range(steps) if float(margins[s]) >= check["decode_routing_margin"]]
    if len(wide) < 4:  # the median over every step is robust too, with more flips in it
        wide = list(range(steps))
    ok &= _judge_median(
        f"logits after decode steps {[s + 1 for s in wide]} of {steps} (the others' routing is a near-tie) vs the "
        f"reference's ONE full forward over {t + steps} ids",
        [(seen[s], want[s]) for s in wide], check["decode_rel_tol"],
    )
    return bool(ok), [s for s, _ in served]


def check_against_xla_path(engine, private, traffic, cfg, check, specs) -> bool:
    """The prefix requests once more (wide margins at their last positions): the
    kernel engine (Pallas latent attention, gmm) against the engine's own XLA
    attention path (``paged_attention='gather'``), same parameters, one slot."""
    from cosmos_curate_tpu.models.vlm import CaptionEngine

    other = CaptionEngine(
        cfg, kv_lanes=((engine.lanes[0].length, 1),), params=engine.params,
        paged_attention="gather", prefill_chunk=engine.prefill_chunk, block_size=engine.block_size,
    )
    other.setup()
    other_private = _LatentPrivate(other)
    pairs = []
    for k, spec in enumerate(specs):
        name = f"check-xla-{k}"
        if not _serve(engine, traffic, name, spec.prompt_ids, spec.prefix_ids) or not _serve(
            other, traffic, name, spec.prompt_ids, spec.prefix_ids, hold=False
        ):
            other.shutdown()
            return False
        pairs.append((private.first_logits[name], other_private.first_logits[name]))
    other.shutdown()
    return _judge_median(
        "the prefix requests, kernels vs the engine's XLA path, first-step logits", pairs,
        check["xla_path_rel_tol"],
    )


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
    cfg, lanes, chunk, prefill_rows = _program_config(cell, rehearse)
    compiles = measure.CompileCounter()

    with clock.part("params"):
        params = make_params(cfg, seed)
        jax.block_until_ready(params)
    n_params = sum(x.size for x in jax.tree.leaves(params))
    log(f"{n_params / 1e9:.3f} B parameters made from seed {seed}, in the serving types")

    with clock.part("engine"):
        engine = CaptionEngine(
            cfg, kv_lanes=lanes, async_prep=bool(conf["serving"]["async_prep"]),
            paged_attention=conf["serving"]["paged_attention"],
            block_size=int(conf["serving"]["block_size"]), prefill_chunk=chunk, params=params,
            max_prefill_rows=prefill_rows,
        )
        engine.setup(seed)
        private = _LatentPrivate(engine)
    traffic_mod = load_module("traffic", cell.traffic["generator"])
    tparams = cell.traffic_params(rehearse)
    if int(tparams["frames"]):
        raise ValueError(f"{cell.name}: the flavor is text only and the mix sends frames")
    traffic = traffic_mod.CaptionTraffic(tparams, seed, vocab=cfg.vocab, image_size=cfg.vision.image_size)
    use_lanes, lengths = reachable(engine, traffic, engine.prefill_chunk)
    loop = SpreadLoop(
        engine, private, traffic, sum(l.n_slots for l in use_lanes), int(tparams["backlog"])
    )
    stats = engine.stats()
    log(
        f"lanes {[(l.length, l.n_slots) for l in engine.lanes]}; the mix reaches "
        f"{[(l.length, l.n_slots) for l in use_lanes]}, prefill lengths {lengths}, "
        f"prompt grid {traffic.grid[0]}..{traffic.grid[-1]} step {tparams['prompt_tokens']['step']}; "
        f"resident: parameters {stats['param_bytes_per_chip'] / 2**30:.2f} GiB, latent pool "
        f"{stats['latent_pool_bytes_per_chip'] / 2**30:.2f} GiB ({engine.kv_blocks_total} blocks of "
        f"{engine.block_size} positions x {cfg.cache_row_elems} lanes x {len(cfg.kv_layers)} layers)"
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
        text_lengths = conf["rehearse"]["text_tokens"] if rehearse else check["text_tokens"]
        correct, prefix_specs = check_against_reference(engine, private, traffic, cfg, check, text_lengths)
        correct &= bool(prefix_specs) and check_against_xla_path(engine, private, traffic, cfg, check, prefix_specs)
        engine.run_until_complete()  # the last hold request ends

    with clock.part("ramp"):
        loop.ramp(timeout_s=600.0)
    setup_s = clock.close()

    # ---- the measured window (drivers/caption_engine.py's, line for line) ----
    tracer = measure.Tracer(cell.name) if trace else None
    trace_from = 0.25 * seconds
    trace_for = float(tparams["trace_seconds"])
    stats0, phases0 = engine.stats(), engine.phase_seconds
    done0, lost_base = len(loop.results), loop.submitted - len(loop.results) - private.in_engine()
    slice_span = None
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
                elif tracer.active and now >= tracer.started_at + trace_for:
                    slice_span.__exit__(None, None, None)
                    tracer.stop()
                    decode_lengths, loop.decode_lengths = loop.decode_lengths, None
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
        # the latent pool and the experts held, as the engine counts them
        "latent": {"latent_pool_bytes_per_chip": stats1["latent_pool_bytes_per_chip"]}
        | {
            k: stats1[k] - stats0[k]
            for k in ("expert_assignments_held", "mla_decode_calls", "decode_programs_ahead")
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
                planes, kernels={"mla_decode": KERNELS["mla_decode"]}, host_spans=HOST_SPANS,
                chips=len(devices),
            )
        experts = trace_reduce.reduce(planes, kernels=EXPERT_KERNELS, chips=len(devices))
        tracer.discard()
        record["trace"] = summary
        a, m = cfg.mla, cfg.moe
        record["slice"] = {
            "decode_lengths": decode_lengths,
            "mla_shape": dict(
                n_layers=len(cfg.kv_layers), n_heads=cfg.n_heads,
                key_width=a.kv_lora_rank + a.qk_rope_head_dim, value_width=a.kv_lora_rank,
                block_size=engine.block_size, dtype_bytes=2,
            ),
            "expert_shape": dict(
                dim=cfg.dim, width=m.hidden, held=m.held_experts[1], dtype_bytes=2,
                sparse_layers=cfg.n_layers - m.first_dense,
            ),
        }
        if summary is not None:
            record["expert_trace"] = {"kernel_s": experts.kernel_s, "kernel_calls": experts.kernel_calls}
            log(
                f"traced slice {summary.window_s:.3f} s, {summary.events} device events: busy "
                f"{summary.busy_s:.3f} s, latent kernels {summary.kernel_s} calls {summary.kernel_calls}, "
                f"grouped matmul {experts.kernel_s} calls {experts.kernel_calls}, gaps {summary.gap_s}"
            )
    return record


# -- the second reading of check's limits --------------------------------------


def lower_precision_readings(seed: int, lengths=(200, 700)) -> None:
    """What ``check``'s statistics read when the reference itself computes in
    fewer bits (the router's scores and softmax in bfloat16; its activations
    rounded to bfloat16, as the engine's are, or to an 8-bit float; one
    assignment in a hundred, or in ten, dropped), against the same reference
    in float32, on seeded parameters at the configuration's full size: the
    second of the two readings each limit lies between. Layer by layer on the
    device."""
    import jax
    import jax.numpy as jnp

    from cosmos_curate_tpu.models.vlm import model as vlm_model
    from perfbench.catalog import load_cell
    from perfbench.traffic.caption_requests import CaptionTraffic

    cell = load_cell("deepseek-v2-ep8.text-rewrite")
    cfg = vlm_model.vlm_flavor(cell.config["flavor"]).cfg
    check = cell.config["check"]
    ref = load_module("reference", "deepseek_v2")
    params = make_params(cfg, seed)
    traffic = CaptionTraffic(cell.traffic_params(False), seed, vocab=cfg.vocab, image_size=cfg.vision.image_size)
    sizes, last = ref.model_kwargs(cfg), cfg.n_layers - 1
    prompts = {
        n: _with_a_wide_margin(
            ref, params, sizes, check, lambda j: _text_only(traffic, f"check-text-{n}", n, j), f"{n}-token prompt"
        )
        for n in lengths
    }
    for what, low in (
        ("a bfloat16 router (scores and softmax)", dict(router_mantissa_bits=7)),
        ("bfloat16 activations (what the engine computes in)", dict(activation_mantissa_bits=7)),
        ("8-bit float activations (3 bits of mantissa)", dict(activation_mantissa_bits=3)),
        ("one assignment in a hundred dropped", dict(drop_every=100)),
        ("one assignment in ten dropped", dict(drop_every=10)),
    ):
        log(f"the reference with {what}, against itself in float32:")
        late = []
        for n, found in prompts.items():
            pairs = []
            for spec, want in found:
                ids = jnp.asarray(spec.prompt_ids, jnp.int32)
                pairs.append((ref.last_logits(params, ids, **sizes, **low)[0], want))
                rows, margin = ref.cache_rows(params, ids, last, **sizes)
                got, _ = ref.cache_rows(params, ids, last, **sizes, **low)
                late.append(late_row_errors(got, rows, margin, check["late_rows_margin"]))
            _judge_median(f"    {n}-token prompts, first-step logits", pairs, check["reference_rel_tol"])
        judge_late_rows(np.concatenate(late), check, f"    layer {last}'s latent rows")
        ids = jnp.asarray(prompts[lengths[0]][0][0].prompt_ids, jnp.int32)
        _judge(
            "    layer 1's latent rows", ref.cache_rows(params, ids, 1, **sizes, **low)[0],
            ref.cache_rows(params, ids, 1, **sizes)[0], check["latent_rms_tol"], _rms_err,
        )
    jax.effects_barrier()


if __name__ == "__main__":
    import argparse

    p = argparse.ArgumentParser(description=lower_precision_readings.__doc__.split("\n\n")[0])
    p.add_argument("--lower-precision", action="store_true", required=True)
    p.add_argument("--seed", type=int, default=0)
    lower_precision_readings(p.parse_args().seed)
