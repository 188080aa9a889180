"""Drives a ``CaptionEngine`` that serves a hybrid decoder whose recurrent layers
are Kimi Delta Attention (a delta rule whose decay is a vector a head) beside a
gated attention layer, EVERY layer over sparse experts with a sorted dispatch
(Solar-Open2 as one chip of an expert-parallel stage: the recurrent store and
the held experts in one program) as the same offline batch as
``drivers/caption_engine_delta.py``: that driver's spies, its window and its
scope reading, the hybrid driver's closed loop and ``_serve``, the latent
driver's wide-margin prompts and medians, the windowed driver's seeded
parameters (a selection bias that is not zero) are imported. What is this
driver's own:

- the configuration file is checked against the flavor by its own keys
  (``linear_attn_config``, ``gqa_layers``, the router's counts, the share held);
- the warmers carry the decode program's rider beside the store (the device's
  count of the assignments on held experts);
- the ramp is BOUNDED: the whole target at once and one turnover of the slots
  (the indexed driver's ``DigestLoop``), never a wait for a lull that a 256-row
  lane does not have;
- ``correct`` compares with ``reference/solar_open2.py`` at positions whose
  routing is no near-tie, on medians: first-step logits after prompts of two
  lengths (the longer over three prefill chunks) and of requests that start from
  the shared prefix's blocks and state snapshot, the first linear-attention
  layer's state in the store (a median too: a state sums every token's write),
  the logits of 8 decode steps of three requests through store and pool against
  the reference's ONE full forward, and the kernel engine against the engine's
  own XLA path with the first token handed over; and, because every one of
  those carries the engine's bfloat16 activations (0.03-0.07, more than a
  bfloat16 state or router moves them), the two precisions the file states
  beside them are held DIRECTLY, on inputs that nothing has rounded: the
  program's router on the reference's own float32 hidden states, and the
  program's decode recurrence over a request's 192 steps on a store of the
  engine's type (``check_stated_precisions``);
- the traced slice is reduced three times (the paged kernels, ``_delta_decode``,
  ``gmm``), the plain-XLA scan is timed by scope and the programs' own device
  seconds are summed by kind (the windowed driver's ``program_seconds``).

``python -m perfbench.drivers.caption_engine_kda --lower-precision [state router
activations stated]`` puts the reference itself, computing in fewer bits, in the
PROGRAM'S place in the same judges: the second of the two readings each limit
lies between (PERF.md). It exits 1 when a control comes out not ``correct``, as
each of the three below the stated precisions must (``stated``, bfloat16
activations alone, is what the file states and exits 0).
"""

from __future__ import annotations

import dataclasses
import functools
import os
import time

import numpy as np

from perfbench import measure, trace_reduce
from perfbench.catalog import Cell, load_module
from perfbench.drivers import caption_engine_sparse as scoped
from perfbench.drivers.caption_engine import HOST_SPANS, KERNELS, _rel_err, reachable
from perfbench.drivers.caption_engine_delta import (
    DELTA_KERNELS, _DeltaPrivate, hand_first_logits, scope_maps, store_layout,
)
from perfbench.drivers.caption_engine_hybrid import SpreadLoop, _HybridPrivate, _rms_err, _serve
from perfbench.drivers.caption_engine_latent import (
    EXPERT_KERNELS, _judge_median, _text_only, _with_a_wide_margin,
)
from perfbench.drivers.caption_engine_windowed import make_params, program_seconds
from perfbench.measure import annotate, log

REFERENCE = "solar_open2"


# -- configuration ------------------------------------------------------------


def _program_config(cell: Cell, rehearse: bool):
    """(VLMConfig, kv_lanes, prefill_chunk, prefill_rows) as the program defines them."""
    from cosmos_curate_tpu.models.vlm import model as vlm_model

    conf = cell.config
    if rehearse:
        r = conf["rehearse"]
        lanes = tuple(map(tuple, r["kv_lanes"]))
        return getattr(vlm_model, r["preset"]), lanes, int(r["prefill_chunk"]), r.get("prefill_rows")
    flavor = vlm_model.vlm_flavor(conf["flavor"])
    check_config_file(conf, flavor.cfg, flavor.kv_lanes, flavor.prefill_rows)
    return flavor.cfg, flavor.kv_lanes, int(conf["serving"]["prefill_chunk"]), flavor.prefill_rows


def program_sizes(cfg) -> dict:
    """The flavor's sizes under the configuration file's (HF's) keys."""
    d, m = cfg.gated_delta, cfg.moe
    return {
        "hidden_size": cfg.dim,
        "intermediate_size": int(round(cfg.dim * cfg.hidden_mult)),
        "num_hidden_layers": cfg.n_layers,
        "num_attention_heads": cfg.n_heads,
        "head_dim": cfg.head_dim,
        "num_key_value_heads": cfg.n_kv_heads,
        "vocab_size": cfg.vocab,
        "max_position_embeddings": cfg.max_seq,
        "rms_norm_eps": cfg.rms_eps,
        "tie_word_embeddings": cfg.tied_embeddings,
        "use_rope": cfg.use_rope,
        "use_gqa_gate": cfg.attention_gate,
        "gqa_layers": [i for i, kind in enumerate(cfg.layer_types) if kind == "full_attention"],
        "linear_attn_config": {
            "short_conv_kernel_size": d.d_conv, "head_dim": d.key_dim, "num_heads": d.n_heads, "num_kv_heads": None,
        },
        "kda_allow_neg_eigval": d.allow_neg_eigval,
        "kda_use_full_proj": d.decay_rank is None,
        "moe_intermediate_size": m.hidden,
        "n_shared_experts": m.shared_hidden // m.hidden,
        "n_routed_experts": m.held_experts[1],
        "num_experts_per_tok": m.top_k,
        "norm_topk_prob": m.norm_topk_prob,
        "routed_scaling_factor": m.routed_scaling_factor,
        "first_k_dense_replace": m.first_dense,
    }


def check_config_file(conf: dict, cfg, lanes, prefill_rows) -> None:
    """The file under ``configs/`` holds the sizes as run: refuse to measure a
    program whose flavor has moved away from it."""
    d, m = cfg.gated_delta, cfg.moe
    bad = {k: (conf[k], v) for k, v in program_sizes(cfg).items() if conf[k] != v}
    counts = conf["published_counts"]
    if counts["router_outputs"] != m.n_experts or list(counts["held_experts"]) != list(m.held_experts):
        bad["published_counts"] = (counts, (m.n_experts, m.held_experts))
    # the points the config is silent on: the file's `assumed`, the program's fields
    assumed = conf["assumed"]
    program = {
        "kda_value_head_dim": d.value_dim, "kda_decay_rank": d.decay_rank, "kda_gate_rank": d.gate_rank,
        "scoring_func": m.score_func, "selection_bias": m.selection_bias, "router_precision": m.router_precision,
    }
    bad.update({f"assumed.{k}": (assumed[k], v) for k, v in program.items() if assumed[k] != v})
    block = (cfg.pre_norm, cfg.sandwich_norm, cfg.qk_norm, cfg.qk_norm_whole, cfg.qkv_bias, cfg.mla, cfg.indexer)
    if block != (True, False, False, False, False, None, None):
        bad["assumed.block"] = (assumed["block"], block)
    if [list(l) for l in lanes] != conf["serving"]["kv_lanes"]:
        bad["kv_lanes"] = (conf["serving"]["kv_lanes"], lanes)
    if conf["serving"]["prefill_rows"] != prefill_rows:
        bad["prefill_rows"] = (conf["serving"]["prefill_rows"], prefill_rows)
    if bad:
        raise ValueError(f"configs/{conf['name']}.json (file, program) disagree: {bad}")


# -- the engine's private face ------------------------------------------------


class _KdaPrivate(_DeltaPrivate):
    """``_DeltaPrivate`` (the store rides in the warmers' calls; a ``check*``
    request's first linear layer's state, tokens and decode logits, the warmed
    programs and the prefill programs' valid tokens are kept) whose decode
    warmer carries the held-assignment rider beside the store."""

    def warm_decode(self, lane) -> None:
        import jax.numpy as jnp

        e = self.e
        zeros = jnp.asarray(np.zeros(lane.n_slots, np.int32))
        greedy, _logits, e._pool_k, e._pool_v, e._ssm, e._conv, e._expert_held = e._decode(
            e.params, e._pool_k, e._pool_v, jnp.asarray(np.zeros_like(lane.table)),
            zeros, zeros, zeros, e._ssm, e._conv, zeros, e._expert_held,
        )
        np.asarray(greedy)


# -- correctness --------------------------------------------------------------
#
# As in the latent driver: with seeded weights a token's last expert taken and
# first left out are often close, the engine's bfloat16 hidden state differs
# from the float32 reference's, and the engine then takes another expert at
# some tokens. That is rounding, not a fault. So the logits are compared at
# positions whose routing margin is wide, on the MEDIAN of several prompts.


def _judge_rms(what: str, pairs, tol: float, of=np.median) -> bool:
    """The MEDIAN (``of``) of the root-mean-square errors of ``pairs`` of (got,
    want) states against ``tol``: a state sums every earlier token's write, so ONE
    prompt's carries whichever of its tokens took another expert in the layer
    before (0.018-0.060 on single prompts, my chip runs, PR 49). ``of=np.max``
    where nothing has rounded the inputs: every row must hold."""
    errs = [_rms_err(np.asarray(g, np.float32), np.asarray(w, np.float32))[0] for g, w in pairs]
    mid = float(of(errs)) if errs else float("nan")
    good = bool(np.isfinite(mid) and mid <= tol)
    log(f"correct: {what}: rms err {[float(f'{e:.3g}') for e in errs]}, {of.__name__} {mid:.3g} (tol {tol}) {'ok' if good else 'FAILED'}")
    return good


# What the file states of the state's and the router's precision. A bfloat16
# state moves the statistics above by 0.010-0.019 and a bfloat16 router by
# 0.04-0.07 (the reference against itself, PERF.md), the engine's own bfloat16
# activations by 0.03-0.07: no limit on those can tell. Both are held where no
# activation has been rounded yet.


def _judge_router(what: str, got, want, margin, check) -> bool:
    """``got`` / ``want``: (weights ``[T, k]``, experts ``[T, k]``) of a router on
    the same float32 inputs. At the tokens whose routing margin is at least
    ``precision_routing_margin`` the experts must be the same and the weights
    within ``router_weight_tol`` of the largest weight."""
    wide = np.asarray(margin) >= check["precision_routing_margin"]
    by_expert = [np.argsort(np.asarray(idx), axis=-1) for _, idx in (got, want)]
    (w, idx), (w0, idx0) = (
        tuple(np.take_along_axis(np.asarray(x), order, axis=-1) for x in pair)
        for pair, order in zip((got, want), by_expert)
    )
    same = (idx == idx0).all(axis=-1)
    err = float(np.abs(w - w0)[wide & same].max() / np.abs(w0).max()) if (wide & same).any() else float("nan")
    flipped = int((wide & ~same).sum())
    good = bool(flipped == 0 and np.isfinite(err) and err <= check["router_weight_tol"])
    log(
        f"correct: {what}: {int(wide.sum())} of {wide.size} tokens with a routing margin of "
        f"{check['precision_routing_margin']}: {flipped} chose other experts, weights off by {err:.3g} of the largest "
        f"(tol {check['router_weight_tol']}) {'ok' if good else 'FAILED'}"
    )
    return good


def program_router(cfg, moe_params, n):
    """The PROGRAM's router on hidden states ``n`` ``[T, dim]`` float32: the
    ``router`` product as ``MoEFFN`` itself computes it (its precision is the
    module's own), then ``model.route``. (weights, experts)."""
    import jax.numpy as jnp

    from cosmos_curate_tpu.models.vlm import model as vlm_model

    _, seen = vlm_model.MoEFFN(cfg, dtype=jnp.bfloat16, param_dtype=jnp.bfloat16).apply(
        {"params": moe_params}, n[None], mutable=["intermediates"],
        capture_intermediates=lambda module, _method: module.name == "router",
    )
    (logits,) = seen["intermediates"]["router"]["__call__"]
    return vlm_model.route(cfg.moe, logits, moe_params.get("router_bias"))


def draw_steps(cfg, seed: int, rows: int, steps: int) -> list[np.ndarray]:
    """Seeded inputs of ``steps`` decode steps of ``rows`` rows of ONE
    linear-attention layer, ``[steps, rows, H, ...]`` float32, as the mixer hands
    them to the recurrence: q and k l2-normed a head (q times ``dk^-0.5``), v a
    silu of a normal, beta a sigmoid (times 2), the decay ``-A[h] dt[h, d] x``
    with ``A`` in (0.001, 16) a head, ``dt`` log-uniform in [0.001, 0.1] a
    channel (the configuration's ``decay_init``) and ``x`` in (0.5, 2) a token."""
    d = cfg.gated_delta
    rng = np.random.default_rng([int(seed), 49])
    shape = (steps, rows, d.n_heads)

    def unit(x):
        return x / np.sqrt(np.sum(x * x, axis=-1, keepdims=True) + 1e-6)

    q = unit(rng.standard_normal((*shape, d.key_dim))) * d.key_dim**-0.5
    k = unit(rng.standard_normal((*shape, d.key_dim)))
    z = rng.standard_normal((*shape, d.value_dim))
    beta = (2.0 if d.allow_neg_eigval else 1.0) / (1.0 + np.exp(-rng.standard_normal(shape)))
    a = rng.uniform(0.001, 16.0, (d.n_heads, 1))
    dt = np.exp(rng.uniform(np.log(0.001), np.log(0.1), (d.n_heads, d.key_dim)))
    g = -a * dt * rng.uniform(0.5, 2.0, (*shape, d.key_dim))
    return [x.astype(np.float32) for x in (q, k, z / (1.0 + np.exp(-z)), g, beta)]


def program_steps(cfg, dtype, inputs) -> np.ndarray:
    """The PROGRAM's decode recurrence over those steps (``ops.delta_rule.
    delta_decode``, what a decode program calls a layer: on the chip the Pallas
    kernel, in place) on a store of ``dtype``, the engine's own, from zeros:
    ``[rows, dk, H * dv]``."""
    import jax.numpy as jnp

    from cosmos_curate_tpu.ops import delta_rule

    d = cfg.gated_delta
    q, k, v, g, beta = (jnp.asarray(x) for x in inputs)
    steps, rows = beta.shape[:2]
    store = jnp.zeros((1, rows + 1, d.key_dim, d.n_heads * d.value_dim), dtype)  # row 0: the garbage row
    at = jnp.arange(1, rows + 1, dtype=jnp.int32)
    for t in range(steps):
        _, store = delta_rule.delta_decode(store, 0, at, q[t], k[t], v[t], g[t], beta[t])
    return np.asarray(store[0, 1:], np.float32)


def reference_steps(ref, cfg, inputs, **low) -> list[np.ndarray]:
    """The reference's recurrence over the same steps, a row at a time, in the
    store's layout; ``low``: :func:`reference.solar_open2.delta_steps`'s."""
    import jax
    import jax.numpy as jnp

    d = cfg.gated_delta
    run = jax.jit(functools.partial(ref.delta_steps, **low))
    zeros = jnp.zeros((d.n_heads, d.key_dim, d.value_dim), jnp.float32)
    return [store_layout(run(zeros, *(jnp.asarray(x[:, row]) for x in inputs))[0]) for row in range(inputs[0].shape[1])]


def check_stated_precisions(cfg, params, state_dtype, traffic, check, seed: int, low=None) -> bool:
    """The float32 router and the float32 state of the configuration's file,
    each on inputs that nothing has rounded. The router: the first layer's, on
    the reference's own float32 hidden states of one seeded prompt. The state:
    ``state_steps`` decode steps (one request's whole output) of the program's
    recurrence on seeded inputs, on a store of the engine's type, against the
    reference's token-by-token recurrence. ``low`` (the lower-precision
    readings): the reference in fewer bits stands in the program's place."""
    import jax
    import jax.numpy as jnp

    ref = load_module("reference", REFERENCE)
    sizes = ref.model_kwargs(cfg)
    spec = _text_only(traffic, "check-router", int(check["router_tokens"]), 900)
    n, *want, margin = ref.first_router(params, jnp.asarray(spec.prompt_ids, jnp.int32), **sizes)
    moe_params = params["params"]["layer_0"]["moe"]
    if low is None:
        got, who = program_router(cfg, moe_params, n), "the program's router"
    else:
        with jax.default_matmul_precision("highest"):
            got = jax.jit(functools.partial(
                ref.route, moe=sizes["moe"], router_mantissa_bits=low.get("router_mantissa_bits", 23)
            ))(n, moe_params)[:2]
        who = "the reference's router in the control's bits"
    ok = _judge_router(
        f"{who} vs the float32 reference's on the same float32 hidden states ({len(spec.prompt_ids)}-token prompt, "
        "the first layer)", got, want, margin, check,
    )
    inputs = draw_steps(cfg, seed, int(check["state_rows"]), int(check["state_steps"]))
    if low is None:
        got, who = program_steps(cfg, state_dtype, inputs), f"the program's decode recurrence on a {np.dtype(state_dtype).name} store"
    else:
        got = reference_steps(ref, cfg, inputs, state_mantissa_bits=low.get("state_mantissa_bits", 23))
        who = "the reference's recurrence in the control's bits"
    return ok & _judge_rms(
        f"{who} vs the float32 reference's, the states of {len(got)} rows after {check['state_steps']} seeded decode steps",
        list(zip(got, reference_steps(ref, cfg, inputs))), check["state_steps_rms_tol"], of=np.max,
    )


def check_against_reference(engine, private, traffic, cfg, check, lengths):
    """The engine's timed path against the plain float32 forward pass on the same
    parameter tree. Returns (ok, the prefix requests' specs): the XLA-path check
    serves them again."""
    import jax.numpy as jnp

    ref = load_module("reference", REFERENCE)
    sizes = ref.model_kwargs(cfg)

    def states_ok(what, served, got):
        """``served``: [(ids, request id)]; ``got``: the engine's states by request id."""
        pairs = [
            (got[name], store_layout(ref.first_ssm_state(engine.params, jnp.asarray(ids, jnp.int32), **sizes)))
            for ids, name in served
        ]
        return _judge_rms(
            f"{what}, first linear-attention layer's state in the store vs float32 reference", pairs,
            check["state_rms_tol"],
        )

    ok = True
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
        ok &= states_ok(f"{n}-token prompts", [(s.prompt_ids, s.request_id) for s, _ in served], private.state)

    # through the prefix cache: the build, then requests that are hits
    found = _with_a_wide_margin(
        ref, engine.params, sizes, check,
        lambda j: dataclasses.replace(traffic.request(10**6 + 100 + j, prompt_len=traffic.grid[0]), request_id="check-prefix"),
        "request with the shared prefix",
    )
    if not found:
        return False, []
    snapshots0 = engine.stats()["prefix_state_snapshots"]
    if not _serve(engine, traffic, "check-prefix-build", found[0][0].prompt_ids, found[0][0].prefix_ids):
        return False, []
    served = [(s, w) for s, w in found if _serve(engine, traffic, s.request_id, s.prompt_ids, s.prefix_ids)]
    ok &= len(served) == len(found)
    ok &= _judge_median(
        f"{len(found[0][0].prefix_ids)}+{len(found[0][0].prompt_ids)}-token requests from the shared prefix's "
        "blocks and state snapshot, first-step logits vs float32 reference",
        [(private.first_logits[s.request_id], w) for s, w in served], check["reference_rel_tol"],
    )
    ok &= states_ok(
        "the same", [(list(s.prefix_ids) + list(s.prompt_ids), s.request_id) for s, _ in served], private.state
    )
    if engine.stats()["prefix_state_snapshots"] - snapshots0 < len(served):
        log("correct: a prefix request did not start from a state snapshot: FAILED")
        ok = False
    specs = [s for s, _ in served]

    # decode through the store and the pool: a few requests, so that the end states have a median too
    steps, logits, ends = int(check["decode_steps"]), [], []
    for j in range(int(check["decode_requests"])):
        spec = _text_only(traffic, f"check-decode-{j}", int(lengths[0]), 500 + j)
        name = spec.request_id
        if not _serve(engine, traffic, name, spec.prompt_ids, max_new=steps + 1):
            return False, specs
        generated, seen = private.tokens.get(name, []), private.decode_logits.get(name, [])
        if len(generated) != steps + 1 or len(seen) != steps:
            log(f"correct: {name} made {len(generated)} tokens in {len(seen)} steps: FAILED")
            return False, specs
        ids = list(spec.prompt_ids) + generated[:steps]
        t = len(spec.prompt_ids)
        want, margins = ref.logits_at(engine.params, jnp.asarray(ids, jnp.int32), list(range(t, t + steps)), **sizes)
        wide = [s for s in range(steps) if float(margins[s]) >= check["decode_routing_margin"]]
        logits += [(seen[s], want[s]) for s in (wide if len(wide) >= 4 else range(steps))]
        ends.append((ids, name))
    ok &= _judge_median(
        f"logits after the decode steps of {len(ends)} requests whose routing is no near-tie ({len(logits)} of "
        f"{steps * len(ends)}) vs the reference's ONE full forward over prompt + generated ids",
        logits, check["decode_rel_tol"],
    )
    ok &= states_ok(f"after those {steps} decode steps (the decode kernel's updates)", ends, private.end_state)
    return bool(ok), specs


def check_against_xla_path(engine, private, traffic, cfg, check, specs) -> bool:
    """The prefix requests once more (wide margins at their last positions): the
    kernel engine (the chunked scan, the Pallas decode recurrence, paged kernels,
    ``gmm``) against the engine's own XLA path (``paged_attention='gather'``: the
    recurrence token by token, attention over gathered views), same parameters,
    one slot. The XLA engine's first logits are its own and its first TOKEN is the
    kernel engine's (``hand_first_logits``), so the first decode step is compared
    on the same ids whatever the margin at the top of the logits."""
    from cosmos_curate_tpu.models.vlm import CaptionEngine

    os.environ.update(CURATE_FLASH_DECODE="0", CURATE_FLASH_PREFILL="0")
    other = CaptionEngine(
        cfg, kv_lanes=((engine.lanes[0].length, 1),), params=engine.params,
        paged_attention="gather", prefill_chunk=engine.prefill_chunk, block_size=engine.block_size,
    )
    other.setup()
    names = [f"check-xla-{k}" for k in range(len(specs))]
    first, step = [], []
    try:
        for name, spec in zip(names, specs):
            if not _serve(engine, traffic, name, spec.prompt_ids, spec.prefix_ids, max_new=2):
                return False
        for name in names:  # BEFORE the spies, which keep the engine's own row
            hand_first_logits(other, name, private.first_logits[name])
        other_private = _HybridPrivate(other)
        for name, spec in zip(names, specs):
            if not _serve(other, traffic, name, spec.prompt_ids, spec.prefix_ids, max_new=2, hold=False):
                return False
            if other_private.tokens[name][0] != private.tokens[name][0]:
                log(f"correct: the XLA engine decoded {name} from token {other_private.tokens[name][0]}, not {private.tokens[name][0]}: FAILED")
                return False
            first.append((private.first_logits[name], other_private.first_logits[name]))
            step.append((private.decode_logits[name][0], other_private.decode_logits[name][0]))
    finally:
        other.shutdown()
    ok = _judge_median(
        "the prefix requests, kernels vs the engine's XLA path, first-step logits", first, check["xla_path_rel_tol"]
    )
    return ok & _judge_median(
        "the same, logits of the first decode step (both from the kernel engine's first token)", step,
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
        private = _KdaPrivate(engine)
    traffic_mod = load_module("traffic", cell.traffic["generator"])
    tparams = cell.traffic_params(rehearse)
    if int(tparams["frames"]):
        raise ValueError(f"{cell.name}: the flavor is text only and the mix sends frames")
    traffic = traffic_mod.CaptionTraffic(tparams, seed, vocab=cfg.vocab, image_size=cfg.vision.image_size)
    use_lanes, lengths = reachable(engine, traffic, engine.prefill_chunk)
    loop = scoped.DigestLoop(engine, private, traffic, sum(l.n_slots for l in use_lanes), int(tparams["backlog"]))
    stats = engine.stats()
    log(
        f"lanes {[(l.length, l.n_slots) for l in engine.lanes]}; the mix reaches "
        f"{[(l.length, l.n_slots) for l in use_lanes]}, prefill lengths {lengths}, "
        f"prompt grid {traffic.grid[0]}..{traffic.grid[-1]} step {tparams['prompt_tokens']['step']}; "
        f"resident: parameters {stats['param_bytes_per_chip'] / 2**30:.2f} GiB, recurrent store "
        f"{stats['recurrent_state_bytes_per_chip'] / 2**30:.2f} GiB ({stats['recurrent_rows_total']} rows), "
        f"KV pool {stats['kv_pool_bytes_per_chip'] / 2**30:.2f} GiB"
    )

    with clock.part("warm_programs"):
        if trace and not rehearse:  # a traced run's own: the end-to-end runs pay nothing for it
            private.programs = {}
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
        maps = None
        if private.programs is not None:
            t0 = time.monotonic()
            maps = scope_maps(private.programs)
            log(f"scopes: the compiled text of the warmed programs read in {time.monotonic() - t0:.2f} s")
        private.programs = None

    check = dict(conf["check"], **(conf["rehearse"].get("check", {}) if rehearse else {}))
    with clock.part("correct"):
        text_lengths = conf["rehearse"]["text_tokens"] if rehearse else check["text_tokens"]
        correct, prefix_specs = check_against_reference(engine, private, traffic, cfg, check, text_lengths)
        correct &= bool(prefix_specs) and check_against_xla_path(engine, private, traffic, cfg, check, prefix_specs)
        correct &= check_stated_precisions(cfg, engine.params, engine._ssm.dtype, traffic, check, seed)
        engine.run_until_complete()  # the last hold request ends
        private.place.clear()  # nothing of the loop is a check request

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
    with compiles.window():
        t_start = time.monotonic()
        tokens0 = loop.tokens_emitted()
        marks: list[tuple[float, int]] = []  # (seconds into the window, tokens so far), every 5 s
        longest = (0.0, 0.0)  # the longest turn of the loop and when it began: a stall shows here
        while (now := time.monotonic()) < t_start + seconds:
            if now - t_start >= 5.0 * (len(marks) + 1):
                marks.append((round(now - t_start, 3), loop.tokens_emitted() - tokens0))
            if tracer is not None:
                if tracer.started_at is None and now >= t_start + trace_from:
                    tracer.start()
                    slice_span = annotate(trace_reduce.SLICE_SPAN)
                    slice_span.__enter__()
                    loop.decode_lengths, private.prefill_valid = [], []
                elif tracer.active and now >= tracer.started_at + trace_for:
                    slice_span.__exit__(None, None, None)
                    tracer.stop()
                    decode_lengths, loop.decode_lengths = loop.decode_lengths, None
                    prefill_valid, private.prefill_valid = private.prefill_valid, None
            loop.turn()
            if (took := time.monotonic() - now) > longest[0]:
                longest = (took, now - t_start)
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
    log(f"tokens by time into the window: {marks}; longest turn {longest[0]:.3f} s at {longest[1]:.2f} s")
    log(f"engine stats at window end (since the engine started): {stats1}")
    log(f"decode programs in window: {stats1['paged_kernel_steps'] - stats0['paged_kernel_steps']}")
    log(f"engine phase seconds in window: { {k: round(phases1[k] - phases0[k], 3) for k in phases1} }")

    record = {
        "correct": bool(correct),
        "attempted": finished + lost,
        "failed": lost,
        "setup_s": setup_s,
        "window_s": window_s,
        "end_to_end": {"output_tok_per_s": tokens / window_s, "setup_s": setup_s},
        "stats_delta": {k: stats1[k] - stats0[k] for k in ("decode_tokens", "decode_s", "prefill_tokens", "prefill_s", "paged_kernel_steps")},
        "phase_delta": {k: phases1[k] - phases0[k] for k in phases1},
        "compiles_in_window": compiles.count,
        "devices": devices,
        "rehearse": rehearse,
        "trace": None,
        "delta_trace": None,
        "expert_trace": None,
        "program_s": None,
        "scope_s": None,
        # the store and the experts held, as the engine counts them
        "kda": {
            k: stats1[k] for k in (
                "recurrent_state_bytes_per_chip", "recurrent_rows_total", "recurrent_rows_used_peak",
            )
        } | {
            k: stats1[k] - stats0[k] for k in (
                "prefix_state_snapshots", "delta_decode_calls", "delta_prefill_chunks", "expert_assignments_held",
                "expert_assignments_held_live",
            )
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
                planes, kernels={"paged_decode": KERNELS["paged_decode"]}, host_spans=HOST_SPANS,
                chips=len(devices),
            )
        # the prefill scan is plain XLA: no `_delta_prefill` to find
        delta = trace_reduce.reduce(planes, kernels={"delta_decode": DELTA_KERNELS["delta_decode"]}, chips=len(devices))
        experts = trace_reduce.reduce(planes, kernels=EXPERT_KERNELS, chips=len(devices))
        scopes = scoped.scope_seconds(planes, maps) if maps else None
        programs = program_seconds(planes)
        tracer.discard()
        record["trace"] = summary
        d, m = cfg.gated_delta, cfg.moe
        record["slice"] = {
            "decode_lengths": decode_lengths,
            "prefill_valid": prefill_valid,
            # the pool's L: the ATTENTION layers alone hold K/V
            "kv_shape": dict(
                n_layers=len(cfg.kv_layers), n_kv_heads=cfg.n_kv_heads, head_dim=cfg.head_dim,
                block_size=engine.block_size, dtype_bytes=2,
            ),
            "attention_shape": dict(n_layers=len(cfg.kv_layers), n_heads=cfg.n_heads, head_dim=cfg.head_dim),
            "kda_shape": dict(
                n_layers=len(cfg.ssm_layers), n_heads=d.n_heads, key_dim=d.key_dim, value_dim=d.value_dim,
            ),
            "expert_shape": dict(
                dim=cfg.dim, width=m.hidden, held=m.held_experts[1], dtype_bytes=2,
                sparse_layers=cfg.n_layers - m.first_dense, router_outputs=m.n_experts, top_k=m.top_k,
            ),
        }
        if summary is not None:
            record["delta_trace"] = {"kernel_s": delta.kernel_s, "kernel_calls": delta.kernel_calls}
            record["expert_trace"] = {"kernel_s": experts.kernel_s, "kernel_calls": experts.kernel_calls}
            record["scope_s"] = scopes
            record["program_s"] = programs
            log(
                f"traced slice {summary.window_s:.3f} s, {summary.events} device events: busy "
                f"{summary.busy_s:.3f} s, paged kernels {summary.kernel_s} calls {summary.kernel_calls}, "
                f"delta-rule kernel {delta.kernel_s} calls {delta.kernel_calls}, grouped matmul "
                f"{experts.kernel_s} calls {experts.kernel_calls}, programs by kind {programs}, device seconds by scope "
                f"{ {f'{k}:{s}': round(v, 4) for (k, s), v in sorted((scopes or {}).items())} }, "
                f"{len(decode_lengths)} decode and {len(prefill_valid)} prefill programs in the slice, gaps {summary.gap_s}"
            )
    return record


# -- the second reading of check's limits --------------------------------------

# the reference's own knobs; `stated` is the precision the file states (the
# engine's bfloat16 activations over a float32 state and router): it must pass
CONTROLS = {
    "state": ("a bfloat16 state", dict(state_mantissa_bits=7)),
    "router": ("a bfloat16 router (its outputs and its scores rounded to bfloat16)", dict(router_mantissa_bits=7)),
    "activations": ("8-bit-float activations (3 bits of mantissa)", dict(activation_mantissa_bits=3)),
    "stated": ("bfloat16 activations (what the engine computes in)", dict(activation_mantissa_bits=7)),
}


def lower_precision(seed: int, names, rehearse: bool = False) -> dict[str, bool]:
    """``check``'s judges with the reference itself, computing in fewer bits, in
    the PROGRAM'S place, against the same reference in float32, on seeded
    parameters at the configuration's full size (layer by layer on the device):
    the second of the two readings each limit lies between. Where the engine's
    run compares two bfloat16 computations (kernels against the XLA path), the
    control stands against the reference with bfloat16 activations. {control:
    whether it came out ``correct``}."""
    import jax
    import jax.numpy as jnp

    from perfbench.catalog import load_cell

    cell = load_cell("solar-open2-ep8.text-rewrite")
    conf = cell.config
    cfg = _program_config(cell, rehearse)[0]
    check = dict(conf["check"], **(conf["rehearse"].get("check", {}) if rehearse else {}))
    lengths = conf["rehearse"]["text_tokens"] if rehearse else check["text_tokens"]
    ref = load_module("reference", REFERENCE)
    params = make_params(cfg, seed)
    tparams = cell.traffic_params(rehearse)
    traffic = load_module("traffic", cell.traffic["generator"]).CaptionTraffic(
        tparams, seed, vocab=cfg.vocab, image_size=cfg.vision.image_size
    )
    sizes = ref.model_kwargs(cfg)
    bf16 = CONTROLS["stated"][1]

    # what the engine's run serves, and the float32 reference's answers
    text = {
        n: _with_a_wide_margin(
            ref, params, sizes, check, lambda j, n=n: _text_only(traffic, f"check-text-{n}", n, j), f"{n}-token prompt"
        )
        for n in lengths
    }
    prefix = _with_a_wide_margin(
        ref, params, sizes, check,
        lambda j: dataclasses.replace(traffic.request(10**6 + 100 + j, prompt_len=traffic.grid[0]), request_id="check-prefix"),
        "request with the shared prefix",
    )
    steps = int(check["decode_steps"])
    decode = []  # (ids of a prompt and `steps` seeded tokens more, the positions after the prompt, logits, wide steps)
    for j in range(int(check["decode_requests"])):
        spec = _text_only(traffic, f"check-decode-{j}", int(lengths[0]) + steps, 500 + j)
        ids, t = jnp.asarray(spec.prompt_ids, jnp.int32), len(spec.prompt_ids) - steps
        want, margins = ref.logits_at(params, ids, list(range(t, t + steps)), **sizes)
        wide = [i for i in range(steps) if float(margins[i]) >= check["decode_routing_margin"]]
        decode.append((ids, list(range(t, t + steps)), np.asarray(want), wide if len(wide) >= 4 else list(range(steps))))

    def ids_of(spec):
        return jnp.asarray(list(spec.prefix_ids) + list(spec.prompt_ids), jnp.int32)

    served = [s for found in (*text.values(), prefix) for s, _ in found]
    want_state = {s.request_id: np.asarray(ref.first_ssm_state(params, ids_of(s), **sizes)) for s in served}

    def states_ok(what, specs, low):
        return _judge_rms(
            f"{what}, first linear-attention layer's state",
            [(ref.first_ssm_state(params, ids_of(s), **sizes, **low), want_state[s.request_id]) for s in specs],
            check["state_rms_tol"],
        )

    # the XLA path's place: the prefix requests and one seeded token more, bfloat16 activations
    after = [jnp.concatenate([ids_of(s), jnp.asarray([cfg.vocab // 2 + 1], jnp.int32)]) for s, _ in prefix]
    two = [[ids.shape[0] - 2, ids.shape[0] - 1] for ids in after]
    other = [np.asarray(ref.logits_at(params, ids, at, **sizes, **bf16)[0]) for ids, at in zip(after, two)]

    verdicts = {}
    for name in names:
        what, low = CONTROLS[name]
        log(f"control: the reference with {what} in the program's place")
        ok = True
        for n, found in text.items():
            ok &= bool(found) and _judge_median(
                f"{n}-token prompts, first-step logits vs float32 reference",
                [(ref.last_logits(params, ids_of(s), **sizes, **low)[0], want) for s, want in found],
                check["reference_rel_tol"],
            )
            ok &= states_ok(f"{n}-token prompts", [s for s, _ in found], low)
        ok &= bool(prefix) and _judge_median(
            "requests with the shared prefix, first-step logits vs float32 reference",
            [(ref.last_logits(params, ids_of(s), **sizes, **low)[0], want) for s, want in prefix],
            check["reference_rel_tol"],
        )
        ok &= states_ok("the same", [s for s, _ in prefix], low)
        logits = []
        for ids, positions, want, wide in decode:
            got = np.asarray(ref.logits_at(params, ids, positions, **sizes, **low)[0])
            logits += [(got[i], want[i]) for i in wide]
        ok &= _judge_median(
            f"logits at the {steps} positions after the prompt of {len(decode)} sequences whose routing is no near-tie "
            f"({len(logits)} of {steps * len(decode)}) vs float32 reference", logits, check["decode_rel_tol"],
        )
        # the kernel engine's place against the XLA path's: a bfloat16 computation under the control
        # against a bfloat16 computation, first-step logits and the step after (one seeded token more)
        got = [np.asarray(ref.logits_at(params, ids, at, **sizes, **{**bf16, **low})[0]) for ids, at in zip(after, two)]
        first, step = [(g[0], o[0]) for g, o in zip(got, other)], [(g[1], o[1]) for g, o in zip(got, other)]
        ok &= _judge_median(
            "the prefix requests, the control vs the reference with bfloat16 activations (the XLA path's place), "
            "first-step logits", first, check["xla_path_rel_tol"],
        )
        ok &= _judge_median("the same, logits of the position after", step, check["xla_path_rel_tol"])
        ok &= check_stated_precisions(cfg, params, jnp.float32, traffic, check, seed, low=low)
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
