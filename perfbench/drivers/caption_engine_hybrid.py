"""Drives a ``CaptionEngine`` that serves a hybrid decoder (Mamba-2 state beside
the paged KV pool: Granite-4.0-H) as the same offline batch as
``drivers/caption_engine.py``: its closed loop (less the first fill's cut
outputs: ``SpreadLoop``), its ramp and the shape of its window, imported or
kept line for line. What differs is what a hybrid needs:

- the configuration file is checked against the flavor by its own keys (layer
  pattern, Mamba-2 sizes, the four multipliers; no vision tower, no m-rope);
- seeded parameters are made in the serving types directly (a float32 tree of
  11.9 GiB would not fit beside the recurrent store);
- the warmers and the reaches past the engine's public face carry the
  recurrent store (``_HybridPrivate``);
- ``correct`` compares with ``reference/granite_hybrid.py``: first-step logits
  and the first state-space layer's state in the store after prompts of two
  lengths (the longer over three prefill chunks), one request through the
  shared prefix's state snapshot, the logits after some decode steps against
  the reference's full forward over prompt + generated ids, and the kernel
  engine against the engine's own XLA path (``paged_attention='gather'``);
- the traced slice is reduced twice: the paged-attention kernels into
  ``record['trace']`` as every caption cell has them, the state-space kernels
  into ``record['ssm_trace']`` (``kernel.paged_attention_time_share`` sums
  every kernel of the first, so they must not meet there).

``python -m perfbench.drivers.caption_engine_hybrid --lower-precision`` prints
what ``check``'s limits read when the reference itself keeps a bfloat16 state:
the second of the two readings each limit lies between (PERF.md).
"""

from __future__ import annotations

import dataclasses
import os
import time

import numpy as np

from perfbench import measure, trace_reduce
from perfbench.catalog import Cell, load_module
from perfbench.drivers.caption_engine import (
    HOST_SPANS, KERNELS, ClosedLoop, _Private, _rel_err, reachable,
)
from perfbench.measure import annotate, log

# the state-space custom calls a device trace names (the jitted wrapper of the
# pallas_call in ops/ssm.py; the prefill scan is plain XLA and has no name)
SSM_KERNELS = {"ssm_decode": r"^_?ssm_decode"}


# -- configuration ------------------------------------------------------------


def _program_config(cell: Cell, rehearse: bool):
    """(VLMConfig, kv_lanes, prefill_chunk) as the program defines them."""
    from cosmos_curate_tpu.models.vlm import model as vlm_model

    conf = cell.config
    if rehearse:
        r = conf["rehearse"]
        cfg = dataclasses.replace(getattr(vlm_model, r["preset"]), **r.get("replace", {}))
        return cfg, tuple(map(tuple, r["kv_lanes"])), int(r["prefill_chunk"])
    flavor = vlm_model.vlm_flavor(conf["flavor"])
    check_config_file(conf, flavor.cfg, flavor.kv_lanes)
    return flavor.cfg, flavor.kv_lanes, int(conf["serving"]["prefill_chunk"])


def check_config_file(conf: dict, cfg, lanes) -> None:
    """The file under ``configs/`` holds the sizes as run: refuse to measure a
    program whose flavor has moved away from it."""
    m = cfg.mamba
    got = {
        "hidden_size": cfg.dim,
        "intermediate_size": int(round(cfg.dim * cfg.hidden_mult)),
        "shared_intermediate_size": int(round(cfg.dim * cfg.hidden_mult)),
        "num_hidden_layers": cfg.n_layers,
        "num_attention_heads": cfg.n_heads,
        "num_key_value_heads": cfg.n_kv_heads,
        "vocab_size": cfg.vocab,
        "max_position_embeddings": cfg.max_seq,
        "rms_norm_eps": cfg.rms_eps,
        "tie_word_embeddings": cfg.tied_embeddings,
        "layer_types": list(cfg.layer_types),
        "position_embedding_type": "rope" if cfg.use_rope else "nope",
        "attention_multiplier": cfg.attention_multiplier,
        "embedding_multiplier": cfg.embedding_multiplier,
        "residual_multiplier": cfg.residual_multiplier,
        "logits_scaling": cfg.logits_scaling,
        "mamba_n_heads": m.n_heads,
        "mamba_d_head": m.head_dim,
        "mamba_d_state": m.d_state,
        "mamba_d_conv": m.d_conv,
        "mamba_chunk_size": m.chunk,
        "mamba_expand": m.d_inner // cfg.dim,
        "mamba_n_groups": 1,
        "mamba_conv_bias": True,
        "mamba_proj_bias": False,
        "attention_bias": cfg.qkv_bias,
        "num_local_experts": 0 if cfg.moe is None else cfg.moe.n_experts,
    }
    bad = {k: (conf[k], v) for k, v in got.items() if conf[k] != v}
    if conf["assumed"]["head_dim"] != cfg.head_dim:
        bad["head_dim"] = (conf["assumed"]["head_dim"], cfg.head_dim)
    if [list(l) for l in lanes] != conf["serving"]["kv_lanes"]:
        bad["kv_lanes"] = (conf["serving"]["kv_lanes"], lanes)
    if bad:
        raise ValueError(f"configs/{conf['name']}.json (file, program) disagree: {bad}")


# -- parameters ---------------------------------------------------------------


def make_params(cfg, seed: int):
    """Seeded parameters, plain arrays, made on the device in one jitted call
    IN THE TYPES THE ENGINE SERVES FROM (``VLM.param_dtype``: bfloat16 matmul
    kernels and embedding table; float32 whatever computes in float32), so
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


# -- the closed loop ----------------------------------------------------------


class SpreadLoop(ClosedLoop):
    """``ClosedLoop`` without the first fill's cut outputs. That cut spreads
    the slots' phases where the slots fill in a fraction of a request's life
    (12 slots, 1.6 s a request: the 2B's cell). Here 56 slots fill one at a
    time in about 28 s and a request lives about 21 s, so the starts are
    spread already, and cutting the later starters' outputs made all of the
    first fill END inside one 9 s band: bursts of prefill and lulls of pure
    decode, one of which held a whole traced slice (no ``paged_prefill`` event
    in 8 s: my chip run, PR 30). Every request keeps the mix's output length."""

    def feed(self) -> None:
        with annotate("feed"):
            while self.submitted - len(self.results) < self.target:
                # the very first request meets an idle engine, which prefills it whole:
                # the shortest prompt of the mix keeps that to a program warmed anyway
                spec = self.traffic.request(
                    self.submitted, prompt_len=self.traffic.grid[0] if self.submitted == 0 else None
                )
                self.engine.add_request(self._request(spec))
                self.submitted += 1


# -- the engine's private face ------------------------------------------------


class _HybridPrivate(_Private):
    """``_Private`` for an engine with a recurrent store: the store rides in
    the warmers' calls, and for ``check*`` requests the first state-space
    layer's state, the tokens and the decode steps' logits are kept too."""

    def __init__(self, engine) -> None:
        super().__init__(engine)  # first-step logits of check* requests
        self.state: dict[str, np.ndarray] = {}  # after the prompt
        self.end_state: dict[str, np.ndarray] = {}  # after the last decode step
        self.place: dict[str, tuple] = {}  # request id -> (lane, slot index)
        self.tokens: dict[str, list[int]] = {}
        self.decode_logits: dict[str, list[np.ndarray]] = {}
        start_slot, finish, decode = engine._start_slot, engine._maybe_finish, engine._decode

        def on_start(lane, slot_idx, req, *rest):
            if req.request_id.startswith("check"):
                # read BEFORE the slot can finish and its row be claimed again
                row = int(engine._state_rows(lane, slot_idx))
                self.state[req.request_id] = np.asarray(engine._ssm[0, row])
                self.place[req.request_id] = (lane, slot_idx)
            return start_slot(lane, slot_idx, req, *rest)

        def on_finish(lane, slot_idx, slot):
            name = slot.request.request_id
            done = name.startswith("check") and len(slot.generated) >= slot.request.sampling.max_new_tokens
            if done:  # the state its decode steps left, before the row is claimed again
                self.tokens[name] = list(slot.generated)
                self.end_state[name] = np.asarray(engine._ssm[0, int(engine._state_rows(lane, slot_idx))])
            return finish(lane, slot_idx, slot)

        def on_decode(params, pool_k, pool_v, tables, *rest):
            out = decode(params, pool_k, pool_v, tables, *rest)
            for name, (lane, slot_idx) in self.place.items():
                slot = lane.slots.get(slot_idx)
                if slot is not None and slot.request.request_id == name and tables.shape == lane.table.shape:
                    self.decode_logits.setdefault(name, []).append(np.asarray(out[1][slot_idx], np.float32))
            return out

        engine._start_slot, engine._maybe_finish, engine._decode = on_start, on_finish, on_decode

    def warm_prefill(self, lane, rows: int, t: int) -> None:
        """One call of the prefill program of this shape: every row writes
        its one valid position into the garbage block and, being a row of
        the store's garbage row 0, advances nobody's state."""
        import jax.numpy as jnp

        e, cfg = self.e, self.e.cfg
        zeros = jnp.asarray(np.zeros(rows, np.int32))
        logits, e._pool_k, e._pool_v, e._ssm, e._conv = e._prefill_batch(
            e.params, e._pool_k, e._pool_v,
            jnp.asarray(np.zeros((rows, lane.length // e.block_size), np.int32)),
            jnp.asarray(np.zeros((rows, t, cfg.dim), np.float32)),
            zeros, jnp.asarray(np.ones(rows, np.int32)),
            jnp.asarray(np.zeros((rows, t), np.int32)), None, e._ssm, e._conv, zeros,
        )
        np.asarray(logits)

    def warm_decode(self, lane) -> None:
        import jax.numpy as jnp

        e = self.e
        zeros = jnp.asarray(np.zeros(lane.n_slots, np.int32))
        greedy, _logits, e._pool_k, e._pool_v, e._ssm, e._conv = e._decode(
            e.params, e._pool_k, e._pool_v, jnp.asarray(np.zeros_like(lane.table)),
            zeros, zeros, zeros, e._ssm, e._conv, zeros,
        )
        np.asarray(greedy)


# -- correctness --------------------------------------------------------------


def _rms_err(got: np.ndarray, want: np.ndarray) -> tuple[float, float]:
    """Root of the summed squared difference over that of the reference: for
    a state of half a million elements, where the largest single difference
    (``_rel_err``) is an extreme value and this is the typical one."""
    scale = float(np.sqrt(np.mean(np.square(want, dtype=np.float64))))
    return float(np.sqrt(np.mean(np.square(got - want, dtype=np.float64)))) / scale, scale


def _judge(what: str, got, want, tol: float, err_of=_rel_err) -> bool:
    err, scale = err_of(np.asarray(got, np.float32), np.asarray(want, np.float32))
    kind = "rel err" if err_of is _rel_err else "rms err"
    good = bool(np.isfinite(err) and err <= tol)
    log(f"correct: {what}: {kind} {err:.5f} (tol {tol}, scale {scale:.4g}) {'ok' if good else 'FAILED'}")
    return good


HOLD_TOKENS = 48  # a hold request's output: it outlasts the admission of the check it covers


def _hold_decoding(engine, least_left: int) -> bool:
    return any(
        s.request.request_id.startswith("hold")
        and s.request.sampling.max_new_tokens - len(s.generated) >= least_left
        for s in engine.slots.values()
    )


def _serve(
    engine, traffic, name: str, prompt_ids, prefix_ids=(), max_new: int = 1, hold: bool = True
) -> bool:
    """Serve one check request to its end. With ``hold``, while another
    request decodes: the engine prefills a prompt whole only while nothing
    decodes, so this is what sends a long prompt through CHUNKED prefill,
    its state carried from chunk to chunk through the store and its row an
    idle row of the decode programs in between, as in the measured loop."""
    from cosmos_curate_tpu.models.vlm import CaptionRequest, SamplingConfig

    def request(rid, prompt, prefix, n):
        return CaptionRequest(
            request_id=rid, prompt_ids=list(prompt), prefix_ids=list(prefix),
            sampling=SamplingConfig(max_new_tokens=n),
        )

    deadline = time.monotonic() + 300.0

    def step():
        if time.monotonic() > deadline:
            raise TimeoutError(f"correct: {name} is still not served")
        if any(l.slots or l.pending for l in engine.lanes) or engine._ready or not engine.async_prep:
            engine.step()
        else:
            time.sleep(0.002)  # only background prep is outstanding

    if hold and not _hold_decoding(engine, HOLD_TOKENS // 2):
        engine.add_request(request(f"hold-{name}", traffic.text_only("hold", 16).prompt_ids, (), HOLD_TOKENS))
        while not _hold_decoding(engine, HOLD_TOKENS // 2):
            step()
    engine.add_request(request(name, prompt_ids, prefix_ids, max_new))
    while engine.has_work():
        step()
        if any(r.request_id == name for r in engine.completed):
            engine.completed = [r for r in engine.completed if r.request_id != name]
            return True
    log(f"correct: the engine lost {name}")
    return False


def check_against_reference(engine, private, traffic, cfg, check, lengths) -> bool:
    """The engine's timed path against the plain float32 forward pass on the
    same parameter tree: first-step logits and the first state-space layer's
    state after prompts of ``lengths`` tokens, one request that starts from
    the shared prefix's blocks and state snapshot, and the logits after
    ``decode_steps`` decode steps through the store against the reference's
    full forward over prompt + generated ids."""
    import jax.numpy as jnp

    ref = load_module("reference", "granite_hybrid")
    sizes = ref.model_kwargs(cfg)

    def ids_of(tokens):
        return jnp.asarray(tokens, jnp.int32)

    ok = True
    for n in lengths:
        spec = traffic.text_only(f"check-text-{n}", int(n))
        if not _serve(engine, traffic, spec.request_id, spec.prompt_ids):
            ok = False
            continue
        ids = ids_of(spec.prompt_ids)
        ok &= _judge(
            f"{n}-token prompt, first-step logits vs float32 reference",
            private.first_logits[spec.request_id], ref.last_logits(engine.params, ids, **sizes),
            check["reference_rel_tol"],
        )
        ok &= _judge(
            f"{n}-token prompt, first state-space layer's state in the store vs float32 reference",
            private.state[spec.request_id], ref.first_ssm_state(engine.params, ids, **sizes),
            check["state_rms_tol"], _rms_err,
        )

    # through the prefix cache: the build, then a request that is a hit
    spec = traffic.request(10**6 + 100, prompt_len=traffic.grid[0])
    hits0 = engine.stats()["prefix_state_snapshots"]
    for name in ("check-prefix-build", "check-prefix-hit"):
        if not _serve(engine, traffic, name, spec.prompt_ids, spec.prefix_ids):
            return False
    ids = ids_of(spec.prefix_ids + spec.prompt_ids)
    want = ref.last_logits(engine.params, ids, **sizes)
    ok &= _judge(
        f"{len(spec.prefix_ids)}+{len(spec.prompt_ids)}-token request from the prefix's state snapshot, "
        "first-step logits vs float32 reference",
        private.first_logits["check-prefix-hit"], want, check["reference_rel_tol"],
    )
    ok &= _judge(
        "the same, first state-space layer's state",
        private.state["check-prefix-hit"], ref.first_ssm_state(engine.params, ids, **sizes),
        check["state_rms_tol"], _rms_err,
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
    ids = ids_of(spec.prompt_ids + generated[:steps])
    ok &= _judge(
        f"logits after {steps} decode steps vs the reference's full forward over {ids.shape[0]} ids",
        private.decode_logits["check-decode"][-1], ref.last_logits(engine.params, ids, **sizes),
        check["decode_rel_tol"],
    )
    ok &= _judge(
        f"first state-space layer's state after those {steps} decode steps (the decode kernel's updates)",
        private.end_state["check-decode"], ref.first_ssm_state(engine.params, ids, **sizes),
        check["state_rms_tol"], _rms_err,
    )
    return bool(ok)


def check_against_xla_path(engine, private, traffic, cfg, check) -> bool:
    """One request of the mix (shared prefix and all): the kernel engine (SSD
    prefill, Pallas decode recurrence, paged attention kernels) against the
    engine's own XLA path (``paged_attention='gather'``: the recurrence token
    by token, attention over gathered views), same parameters, one slot."""
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
    other_private = _HybridPrivate(other)
    served = _serve(other, traffic, "check-xla", spec.prompt_ids, spec.prefix_ids, max_new=2, hold=False)
    other.shutdown()
    if not served:
        return False
    ok = _judge(
        "a request of the mix, kernels vs the engine's XLA path, first-step logits",
        private.first_logits["check-xla"], other_private.first_logits["check-xla"],
        check["xla_path_rel_tol"],
    )
    return ok & _judge(
        "the same, logits of the first decode step",
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
        private = _HybridPrivate(engine)
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
        f"resident: parameters {stats['param_bytes_per_chip'] / 2**30:.2f} GiB, recurrent store "
        f"{stats['recurrent_state_bytes_per_chip'] / 2**30:.2f} GiB ({stats['recurrent_rows_total']} rows), "
        f"KV pool {stats['kv_pool_bytes_per_chip'] / 2**30:.2f} GiB"
    )

    with clock.part("warm_programs"):
        for lane in use_lanes:
            rows = 1
            # prompts in prefill at once: never more than half a lane's slots,
            # since the ramp spreads the slots' phases over a request's life
            while rows <= min(int(tparams["warm_rows"]), max(1, lane.n_slots // 2)):
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
    log(f"tokens by time into the window: {marks}")
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
        "ssm_trace": None,
        # the second kind of state, as the engine counts it
        "recurrent": {
            k: stats1[k] for k in (
                "recurrent_state_bytes_per_chip", "recurrent_rows_total", "recurrent_rows_used_peak",
            )
        } | {k: stats1[k] - stats0[k] for k in ("prefix_state_snapshots", "ssm_decode_calls")},
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
        ssm = trace_reduce.reduce(planes, kernels=SSM_KERNELS, chips=len(devices))
        tracer.discard()
        record["trace"] = summary
        m = cfg.mamba
        record["slice"] = {
            "decode_lengths": decode_lengths,
            # the pool's L: the ATTENTION layers alone hold K/V
            "kv_shape": dict(
                n_layers=len(cfg.kv_layers), n_kv_heads=cfg.n_kv_heads, head_dim=cfg.head_dim,
                block_size=engine.block_size, dtype_bytes=2,
            ),
            "attention_shape": dict(
                n_layers=len(cfg.kv_layers), n_heads=cfg.n_heads, head_dim=cfg.head_dim
            ),
            "ssm_shape": dict(
                n_layers=len(cfg.ssm_layers), n_heads=m.n_heads, head_dim=m.head_dim, d_state=m.d_state,
            ),
        }
        if summary is not None:
            record["ssm_trace"] = {"kernel_s": ssm.kernel_s, "kernel_calls": ssm.kernel_calls}
            log(
                f"traced slice {summary.window_s:.3f} s, {summary.events} device events: busy "
                f"{summary.busy_s:.3f} s, paged kernels {summary.kernel_s} calls {summary.kernel_calls}, "
                f"state-space kernels {ssm.kernel_s} calls {ssm.kernel_calls}, gaps {summary.gap_s}"
            )
    return record


# -- the second reading of check's limits --------------------------------------


def lower_precision_readings(seed: int, lengths=(200, 700)) -> None:
    """What ``check``'s comparisons read when the reference itself computes
    in fewer bits (its state rounded to bfloat16 after every token; its
    activations rounded to bfloat16, as the engine's are, or to an 8-bit
    float), against the same reference in float32, on seeded parameters at
    the configuration's full size: the second of the two readings each limit
    lies between. Layer by layer on the device."""
    import jax
    import jax.numpy as jnp

    from cosmos_curate_tpu.models.vlm import model as vlm_model
    from perfbench.catalog import load_cell
    from perfbench.traffic.caption_requests import CaptionTraffic

    cell = load_cell("granite-4.0-h-micro.text-rewrite")
    cfg = vlm_model.vlm_flavor(cell.config["flavor"]).cfg
    ref = load_module("reference", "granite_hybrid")
    params = make_params(cfg, seed)
    traffic = CaptionTraffic(cell.traffic_params(False), seed, vocab=cfg.vocab, image_size=cfg.vision.image_size)
    sizes = ref.model_kwargs(cfg)
    bf16_state = dict(sizes, ssm=dict(sizes["ssm"], state_mantissa_bits=7))
    for n in lengths:
        ids = jnp.asarray(traffic.text_only(f"check-text-{n}", int(n)).prompt_ids, jnp.int32)
        want = np.asarray(ref.last_logits(params, ids, **sizes), np.float32)
        want_state = np.asarray(ref.first_ssm_state(params, ids, **sizes), np.float32)
        for what, low in (
            ("a bfloat16 state", bf16_state),
            ("bfloat16 activations (what the engine computes in)", dict(sizes, activation_mantissa_bits=7)),
            ("8-bit float activations (3 bits of mantissa)", dict(sizes, activation_mantissa_bits=3)),
        ):
            err, scale = _rel_err(np.asarray(ref.last_logits(params, ids, **low), np.float32), want)
            log(f"reference with {what} vs float32 reference, {n} tokens, first-step logits: rel err {err:.5f} (scale {scale:.4g})")
        err, scale = _rms_err(np.asarray(ref.first_ssm_state(params, ids, **bf16_state), np.float32), want_state)
        log(f"reference with a bfloat16 state vs float32 reference, {n} tokens, first state-space layer's state: rms err {err:.5f} (scale {scale:.4g})")
    jax.effects_barrier()


if __name__ == "__main__":
    import argparse

    p = argparse.ArgumentParser(description=lower_precision_readings.__doc__.split("\n\n")[0])
    p.add_argument("--lower-precision", action="store_true", required=True)
    p.add_argument("--seed", type=int, default=0)
    lower_precision_readings(p.parse_args().seed)
