"""Drives a ``CaptionEngine`` that serves a hybrid decoder whose recurrent layers
are the gated delta rule (Olmo-Hybrid: a matrix state a head beside the paged
KV pool) as the same offline batch as ``drivers/caption_engine_hybrid.py``:
that driver's closed loop (``SpreadLoop``), its spies and warmers
(``_HybridPrivate``: the store rides in every call whatever it holds), its
``_serve`` and ``_judge`` and its seeded parameters are imported. What differs is
what this recurrence changes:

- the configuration file is checked against the flavor by its own keys (the
  ``linear_*`` sizes, the layer pattern, the Olmo block's fields);
- ``correct`` compares with ``reference/olmo_hybrid.py``, and the state in the
  store, 30 heads side by side a row, is laid out as the reference's before it
  is compared;
- the comparison with the engine's own XLA path hands the XLA engine the kernel
  engine's first token (``check_against_xla_path``: seeded logits over 100,352
  words have near-ties at the top, and two engines that round differently may
  choose differently; their decode steps then answer different questions);
- the traced slice is reduced a second time for ``_delta_decode`` (and
  ``_delta_prefill`` once the scan is a kernel) into ``record['delta_trace']``,
  and the scan, which is plain XLA, is timed by the instructions that the
  warmed programs' compiled text puts under ``delta.prefill_scan`` /
  ``delta.conv`` (``caption_engine_sparse.scope_maps`` / ``scope_seconds``,
  told this flavor's scopes) into ``record['scope_s']``.

``python -m perfbench.drivers.caption_engine_delta --lower-precision`` prints
what ``check``'s limits read when the reference itself computes in fewer bits:
the second of the two readings each limit lies between (PERF.md).
"""

from __future__ import annotations

import os
import re
import time
from unittest import mock

import numpy as np

from perfbench import measure, trace_reduce
from perfbench.catalog import Cell, load_module
from perfbench.drivers import caption_engine_sparse as scoped
from perfbench.drivers.caption_engine import HOST_SPANS, KERNELS, _rel_err, reachable
from perfbench.drivers.caption_engine_hybrid import (
    SpreadLoop, _HybridPrivate, _judge, _rms_err, _serve, make_params,
)
from perfbench.measure import annotate, log

# the delta rule's custom calls a device trace names (the jitted wrapper of the
# pallas_call in ops/delta_rule.py), and the scopes its plain-XLA parts stand under
DELTA_KERNELS = {"delta_decode": r"^_?delta_decode", "delta_prefill": r"^_?delta_prefill"}
DELTA_SCOPES = re.compile(r"delta\.prefill_scan|delta\.conv|delta\.gate_norm|delta\.decode|attn\.full")
REFERENCE = "olmo_hybrid"


# -- configuration ------------------------------------------------------------


def _program_config(cell: Cell, rehearse: bool):
    """(VLMConfig, kv_lanes, prefill_chunk) as the program defines them."""
    from cosmos_curate_tpu.models.vlm import model as vlm_model

    conf = cell.config
    if rehearse:
        r = conf["rehearse"]
        return getattr(vlm_model, r["preset"]), tuple(map(tuple, r["kv_lanes"])), int(r["prefill_chunk"])
    flavor = vlm_model.vlm_flavor(conf["flavor"])
    check_config_file(conf, flavor.cfg, flavor.kv_lanes)
    return flavor.cfg, flavor.kv_lanes, int(conf["serving"]["prefill_chunk"])


def check_config_file(conf: dict, cfg, lanes) -> None:
    """The file under ``configs/`` holds the sizes as run: refuse to measure a
    program whose flavor has moved away from it."""
    m = cfg.gated_delta
    got = {
        "hidden_size": cfg.dim,
        "intermediate_size": int(cfg.dim * cfg.hidden_mult),
        "num_hidden_layers": cfg.n_layers,
        "num_attention_heads": cfg.n_heads,
        "num_key_value_heads": cfg.n_kv_heads,
        "vocab_size": cfg.vocab,
        "max_position_embeddings": cfg.max_seq,
        "rms_norm_eps": cfg.rms_eps,
        "tie_word_embeddings": cfg.tied_embeddings,
        "attention_bias": cfg.qkv_bias,
        "layer_types": list(cfg.layer_types),
        "rope_parameters": {"rope_theta": cfg.rope_theta if cfg.use_rope else None},
        "linear_num_key_heads": m.n_heads,
        "linear_num_value_heads": m.n_heads,
        "linear_key_head_dim": m.key_dim,
        "linear_value_head_dim": m.value_dim,
        "linear_conv_kernel_dim": m.d_conv,
        "linear_allow_neg_eigval": m.allow_neg_eigval,
    }
    bad = {k: (conf[k], v) for k, v in got.items() if conf[k] != v}
    # the points the config is silent on: the file's `assumed`, the program's fields
    block = (cfg.pre_norm, cfg.sandwich_norm, cfg.qk_norm_whole, cfg.qk_norm, cfg.moe, cfg.mla)
    if block != (False, True, True, False, None, None):
        bad["assumed.block"] = (conf["assumed"]["block"], block)
    if conf["assumed"]["head_dim"] != cfg.head_dim:
        bad["assumed.head_dim"] = (conf["assumed"]["head_dim"], cfg.head_dim)
    if [list(l) for l in lanes] != conf["serving"]["kv_lanes"]:
        bad["kv_lanes"] = (conf["serving"]["kv_lanes"], lanes)
    if bad:
        raise ValueError(f"configs/{conf['name']}.json (file, program) disagree: {bad}")


# -- the engine's private face ------------------------------------------------


class _DeltaPrivate(_HybridPrivate):
    """``_HybridPrivate`` (the store rides in the warmers' calls; a ``check*``
    request's first layer's state, tokens and decode logits are kept) that also
    keeps, while ``programs`` is a dict, every warmed program with its abstract
    arguments (``scope_maps`` compiles them for their text) and, while
    ``prefill_valid`` is a list, the valid tokens of every live row of every
    prefill program (the scan's chunks in the traced slice)."""

    def __init__(self, engine) -> None:
        jitted = {"prefill": engine._prefill_batch, "decode": engine._decode}
        super().__init__(engine)  # (wraps `_decode` in a spy: the jitted ones are kept above)
        self.programs: dict | None = None
        self.prefill_valid: list | None = None
        run_prefill = engine._run_prefill

        def noting(kind, call):
            def noted(*args):
                if self.programs is not None:
                    import jax

                    shapes = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), args)
                    self.programs.setdefault(kind, []).append((jitted[kind], shapes))
                return call(*args)

            return noted

        def on_prefill(lane, slots_arr, tables, embeds, write_index, t_valid, *rest):
            if self.prefill_valid is not None:  # padding rows repeat row 0
                self.prefill_valid.append(sorted({int(s): int(v) for s, v in zip(slots_arr, t_valid)}.values()))
            return run_prefill(lane, slots_arr, tables, embeds, write_index, t_valid, *rest)

        engine._prefill_batch = noting("prefill", engine._prefill_batch)
        engine._decode = noting("decode", engine._decode)
        engine._run_prefill = on_prefill


def scope_maps(programs: dict) -> dict:
    """``caption_engine_sparse.scope_maps`` looking for this flavor's scopes
    (its pattern is a constant of its module, which this PR may not edit)."""
    with mock.patch.object(scoped, "SCOPES", DELTA_SCOPES):
        return scoped.scope_maps(programs)


# -- correctness --------------------------------------------------------------


def store_layout(state: np.ndarray) -> np.ndarray:
    """The reference's ``S`` ``[heads, dk, dv]`` as a row of the engine's store
    holds it: ``[dk, heads * dv]``, the heads side by side."""
    heads, dk, dv = state.shape
    return np.moveaxis(np.asarray(state), 0, 1).reshape(dk, heads * dv)


def check_against_reference(engine, private, traffic, cfg, check, lengths) -> bool:
    """The engine's timed path against the plain float32 forward pass on the
    same parameter tree: first-step logits and the first linear-attention
    layer's state after prompts of ``lengths`` tokens, one request that starts
    from the shared prefix's blocks and state snapshot, and the logits after
    ``decode_steps`` decode steps through the store against the reference's
    ONE full forward over prompt + generated ids."""
    import jax.numpy as jnp

    ref = load_module("reference", REFERENCE)
    sizes = ref.model_kwargs(cfg)

    def compare(what, name, ids, state, logits, tol):
        ids = jnp.asarray(ids, jnp.int32)
        ok = _judge(f"{what} vs float32 reference", logits, ref.last_logits(engine.params, ids, **sizes), tol)
        return ok & _judge(
            f"{name}, first linear-attention layer's state in the store vs float32 reference",
            state, store_layout(ref.first_ssm_state(engine.params, ids, **sizes)),
            check["state_rms_tol"], _rms_err,
        )

    ok = True
    for n in lengths:
        spec = traffic.text_only(f"check-text-{n}", int(n))
        if not _serve(engine, traffic, spec.request_id, spec.prompt_ids):
            ok = False
            continue
        ok &= compare(
            f"{n}-token prompt, first-step logits", f"{n}-token prompt", spec.prompt_ids,
            private.state[spec.request_id], private.first_logits[spec.request_id], check["reference_rel_tol"],
        )

    # through the prefix cache: the build, then a request that is a hit
    spec = traffic.request(10**6 + 100, prompt_len=traffic.grid[0])
    hits0 = engine.stats()["prefix_state_snapshots"]
    for name in ("check-prefix-build", "check-prefix-hit"):
        if not _serve(engine, traffic, name, spec.prompt_ids, spec.prefix_ids):
            return False
    ok &= compare(
        f"{len(spec.prefix_ids)}+{len(spec.prompt_ids)}-token request from the prefix's state snapshot, "
        "first-step logits", "the same", spec.prefix_ids + spec.prompt_ids,
        private.state["check-prefix-hit"], private.first_logits["check-prefix-hit"], check["reference_rel_tol"],
    )
    if engine.stats()["prefix_state_snapshots"] - hits0 < 1:
        log("correct: the prefix request did not start from a state snapshot: FAILED")
        ok = False

    # decode through the store
    steps = int(check["decode_steps"])
    spec = traffic.text_only("check-decode", int(lengths[0]))
    if not _serve(engine, traffic, spec.request_id, spec.prompt_ids, max_new=steps + 1):
        return False
    generated = private.tokens["check-decode"]
    if len(generated) != steps + 1 or len(private.decode_logits["check-decode"]) != steps:
        log(f"correct: check-decode made {len(generated)} tokens in {len(private.decode_logits['check-decode'])} steps: FAILED")
        return False
    ids = spec.prompt_ids + generated[:steps]
    ok &= compare(
        f"logits after {steps} decode steps vs the reference's full forward over {len(ids)} ids",
        f"after those {steps} decode steps (the decode kernel's updates)", ids,
        private.end_state["check-decode"], private.decode_logits["check-decode"][-1], check["decode_rel_tol"],
    )
    return bool(ok)


def hand_first_logits(engine, name: str, logits_row) -> None:
    """Request ``name``'s first token on ``engine`` is sampled from
    ``logits_row`` and not from the engine's own row. Called BEFORE the
    engine's ``_HybridPrivate`` is made, so that its spies, which wrap this,
    keep the engine's own row."""
    start_slot = engine._start_slot

    def start(lane, slot_idx, req, t_valid, next_rope, own_row):
        row = logits_row if req.request_id == name else own_row
        return start_slot(lane, slot_idx, req, t_valid, next_rope, row)

    engine._start_slot = start


def check_against_xla_path(engine, private, traffic, cfg, check) -> bool:
    """One request of the mix (shared prefix and all): the kernel engine (the
    chunked scan, the Pallas decode recurrence, paged attention kernels) against
    the engine's own XLA path (``paged_attention='gather'``: the recurrence
    token by token, attention over gathered views), same parameters, one slot.

    The hybrid driver's comparison lets each engine choose its own first token.
    Seeded weights give logits over 100,352 words whose two largest can lie
    closer than the two engines' roundings differ (seed 1036307914, the one of
    some twenty so far: first steps 0.014 apart, the decode steps' logits 1.51,
    my chip run, PR 44), and then the decode steps read different inputs. Here
    the XLA engine's first logits are kept as its own and its first TOKEN is
    the kernel engine's: the decode steps are compared on the same ids whatever
    the margin."""
    from cosmos_curate_tpu.models.vlm import CaptionEngine

    spec = traffic.request(10**6 + 200)
    if not _serve(engine, traffic, "check-xla", spec.prompt_ids, spec.prefix_ids, max_new=2):
        return False
    os.environ.update(CURATE_FLASH_DECODE="0", CURATE_FLASH_PREFILL="0")
    other = CaptionEngine(
        cfg, kv_lanes=((engine.lanes[0].length, 1),), params=engine.params,
        paged_attention="gather", prefill_chunk=engine.prefill_chunk, block_size=engine.block_size,
    )
    other.setup()
    hand_first_logits(other, "check-xla", private.first_logits["check-xla"])
    other_private = _HybridPrivate(other)
    served = _serve(other, traffic, "check-xla", spec.prompt_ids, spec.prefix_ids, max_new=2, hold=False)
    other.shutdown()
    if not served:
        return False
    first, other_first = private.tokens["check-xla"][0], other_private.tokens["check-xla"][0]
    own = int(np.argmax(other_private.first_logits["check-xla"]))
    note = "" if own == first else " (a near-tie: it was handed the kernel engine's)"
    log(f"correct: check-xla first token {first}; the XLA engine's own choice {own}{note}")
    if other_first != first:
        log(f"correct: the XLA engine decoded from token {other_first}, not {first}: FAILED")
        return False
    ok = _judge(
        "a request of the mix, kernels vs the engine's XLA path, first-step logits",
        private.first_logits["check-xla"], other_private.first_logits["check-xla"],
        check["xla_path_rel_tol"],
    )
    return ok & _judge(
        "the same, logits of the first decode step (both from the kernel engine's first token)",
        private.decode_logits["check-xla"][0], other_private.decode_logits["check-xla"][0],
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
    cfg, lanes, chunk = _program_config(cell, rehearse)
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
        )
        engine.setup(seed)
        private = _DeltaPrivate(engine)
    traffic_mod = load_module("traffic", cell.traffic["generator"])
    tparams = cell.traffic_params(rehearse)
    if int(tparams["frames"]):
        raise ValueError(f"{cell.name}: the flavor is text only and the mix sends frames")
    traffic = traffic_mod.CaptionTraffic(tparams, seed, vocab=cfg.vocab, image_size=cfg.vision.image_size)
    use_lanes, lengths = reachable(engine, traffic, engine.prefill_chunk)
    loop = SpreadLoop(engine, private, traffic, sum(l.n_slots for l in use_lanes), int(tparams["backlog"]))
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
            # every row count a lane's prefill program can meet: a power of two
            # up to the lane's slots, and no more prompts than the mix keeps waiting
            while rows <= min(int(tparams["warm_rows"]), lane.n_slots):
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
        correct = check_against_reference(engine, private, traffic, cfg, check, text_lengths)
        correct &= check_against_xla_path(engine, private, traffic, cfg, check)
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
    stats1, phases1 = engine.stats(), engine.phase_seconds
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
        "scope_s": None,
        # the second kind of state, as the engine counts it
        "recurrent": {
            k: stats1[k] for k in (
                "recurrent_state_bytes_per_chip", "recurrent_rows_total", "recurrent_rows_used_peak",
            )
        } | {k: stats1[k] - stats0[k] for k in ("prefix_state_snapshots", "delta_decode_calls", "delta_prefill_chunks")},
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
        try:
            delta = trace_reduce.reduce(planes, kernels=DELTA_KERNELS, chips=len(devices))
        except LookupError:  # the prefill scan is plain XLA: no `_delta_prefill` to find
            delta = trace_reduce.reduce(
                planes, kernels={"delta_decode": DELTA_KERNELS["delta_decode"]}, chips=len(devices)
            )
        scopes = scoped.scope_seconds(planes, maps) if maps else None
        tracer.discard()
        record["trace"] = summary
        m = cfg.gated_delta
        record["slice"] = {
            "decode_lengths": decode_lengths,
            "prefill_valid": prefill_valid,
            # the pool's L: the ATTENTION layers alone hold K/V
            "kv_shape": dict(
                n_layers=len(cfg.kv_layers), n_kv_heads=cfg.n_kv_heads, head_dim=cfg.head_dim,
                block_size=engine.block_size, dtype_bytes=2,
            ),
            "attention_shape": dict(n_layers=len(cfg.kv_layers), n_heads=cfg.n_heads, head_dim=cfg.head_dim),
            "delta_shape": dict(
                n_layers=len(cfg.ssm_layers), n_heads=m.n_heads, key_dim=m.key_dim, value_dim=m.value_dim,
            ),
        }
        if summary is not None:
            record["delta_trace"] = {"kernel_s": delta.kernel_s, "kernel_calls": delta.kernel_calls}
            record["scope_s"] = scopes
            log(
                f"traced slice {summary.window_s:.3f} s, {summary.events} device events: busy "
                f"{summary.busy_s:.3f} s, paged kernels {summary.kernel_s} calls {summary.kernel_calls}, "
                f"delta-rule kernels {delta.kernel_s} calls {delta.kernel_calls}, device seconds by scope "
                f"{ {f'{k}:{s}': round(v, 4) for (k, s), v in sorted((scopes or {}).items())} }, "
                f"{len(decode_lengths)} decode and {len(prefill_valid)} prefill programs in the slice, gaps {summary.gap_s}"
            )
    return record


# -- the second reading of check's limits --------------------------------------


def lower_precision_readings(seed: int, lengths=(200, 700)) -> None:
    """What ``check``'s comparisons read when the reference itself computes in
    fewer bits (its state rounded to bfloat16 after every token; its activations
    rounded to bfloat16, as the engine's are, or to an 8-bit float), against the
    same reference in float32, on seeded parameters at the configuration's full
    size: the second of the two readings each limit lies between. Layer by
    layer on the device."""
    import jax
    import jax.numpy as jnp

    from cosmos_curate_tpu.models.vlm import model as vlm_model
    from perfbench.catalog import load_cell
    from perfbench.traffic.caption_requests import CaptionTraffic

    cell = load_cell("olmo-hybrid-7b-pp2.text-rewrite")
    cfg = vlm_model.vlm_flavor(cell.config["flavor"]).cfg
    ref = load_module("reference", REFERENCE)
    params = make_params(cfg, seed)
    traffic = CaptionTraffic(cell.traffic_params(False), seed, vocab=cfg.vocab, image_size=cfg.vision.image_size)
    sizes = ref.model_kwargs(cfg)
    for n in lengths:
        ids = jnp.asarray(traffic.text_only(f"check-text-{n}", int(n)).prompt_ids, jnp.int32)
        want = np.asarray(ref.last_logits(params, ids, **sizes), np.float32)
        want_state = np.asarray(ref.first_ssm_state(params, ids, **sizes), np.float32)
        for what, low in (
            ("a bfloat16 state", dict(state_mantissa_bits=7)),
            ("bfloat16 activations (what the engine computes in)", dict(activation_mantissa_bits=7)),
            ("8-bit float activations (3 bits of mantissa)", dict(activation_mantissa_bits=3)),
        ):
            err, scale = _rel_err(np.asarray(ref.last_logits(params, ids, **sizes, **low), np.float32), want)
            log(f"reference with {what} vs float32 reference, {n} tokens, first-step logits: rel err {err:.5f} (scale {scale:.4g})")
        low_state = np.asarray(ref.first_ssm_state(params, ids, **sizes, state_mantissa_bits=7), np.float32)
        err, scale = _rms_err(low_state, want_state)
        log(f"reference with a bfloat16 state vs float32 reference, {n} tokens, first linear-attention layer's state: rms err {err:.5f} (scale {scale:.4g})")
    jax.effects_barrier()


if __name__ == "__main__":
    import argparse

    p = argparse.ArgumentParser(description=lower_precision_readings.__doc__.split("\n\n")[0])
    p.add_argument("--lower-precision", action="store_true", required=True)
    p.add_argument("--seed", type=int, default=0)
    lower_precision_readings(p.parse_args().seed)
