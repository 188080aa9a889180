"""Drives a ``CaptionEngine`` as an offline batch: a closed loop that keeps every
slot of the engine busy and a small backlog waiting, so the engine is never
short of work, and counts the output tokens it emits while the window is open.

Set-up (all of it before the first measured instant, all of it ``setup_s``):
seeded parameters made on the device in one jitted call; the engine built the
way ``SharedCaptionEngine.get`` builds it for the flavor (its KV lanes,
background prep, paged attention ``auto``), with the parameters handed in; the
prefill and decode programs of every shape this cell's traffic can reach, run
once on the garbage block; the comparisons that decide ``correct``; then the
loop itself is ramped until every slot is taken and every prompt length of
the mix has been prepared once. The window opens on a running engine.

What is read from the program: ``engine.step()``, ``add_request``,
``engine.completed``, ``engine.slots`` (token lists), ``stats()`` and
``phase_seconds()``. What has to reach past its public face is gathered in
``_Private`` below and listed in PERF.md for the ``tracing`` issue.
"""

from __future__ import annotations

import dataclasses
import os
import time

import numpy as np

from perfbench import measure, trace_reduce
from perfbench.catalog import Cell, load_module
from perfbench.measure import annotate, log

HOST_SPANS = ("engine.step", "feed", "collect", "idle.wait")
# What the device trace prints for the two Pallas kernels of
# ops/paged_attention.py, read off a trace by hand (PR 22): their pallas_call
# carries no name=, so the custom call is named after the jitted wrapper,
# ``%_paged_decode.28 = ... custom-call(..., custom_call_target="tpu_custom_call")``.
KERNELS = {"paged_decode": r"^_?paged_decode", "paged_prefill": r"^_?paged_prefill"}


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
    cfg, lanes = flavor.cfg, flavor.kv_lanes
    check_config_file(conf, cfg, lanes)
    return cfg, lanes, int(conf["serving"]["prefill_chunk"])


def check_config_file(conf: dict, cfg, lanes) -> None:
    """The file under ``configs/`` holds the sizes as run: refuse to measure a
    program whose flavor has moved away from it."""
    got = {
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
    }
    bad = {k: (conf[k], v) for k, v in got.items() if conf[k] != v}
    if conf["assumed"]["head_dim"] != cfg.head_dim:
        bad["head_dim"] = (conf["assumed"]["head_dim"], cfg.head_dim)
    if list(conf["rope_scaling"]["mrope_section"]) != list(cfg.mrope_section or ()):
        bad["mrope_section"] = (conf["rope_scaling"]["mrope_section"], cfg.mrope_section)
    vis, qv = conf["vision_config"], cfg.qwen_vision
    for key, value in (
        ("depth", qv.depth), ("num_heads", qv.num_heads), ("patch_size", qv.patch_size),
        ("spatial_merge_size", qv.spatial_merge_size),
        ("temporal_patch_size", qv.temporal_patch_size),
    ):
        if vis[key] != value:
            bad[f"vision_config.{key}"] = (vis[key], value)
    if [list(l) for l in lanes] != conf["serving"]["kv_lanes"]:
        bad["kv_lanes"] = (conf["serving"]["kv_lanes"], lanes)
    if bad:
        raise ValueError(f"configs/{conf['name']}.json (file, program) disagree: {bad}")


# -- parameters ---------------------------------------------------------------


def make_params(cfg, seed: int, mesh):
    """Seeded parameters, plain arrays in float32 (the type the program
    serves from today), made on the device in one jitted call. With a mesh
    every leaf is created already split by the model's own partition specs,
    so no chip ever holds the whole."""
    import flax.linen as nn
    import jax
    import jax.numpy as jnp

    from cosmos_curate_tpu.models.vlm.model import VLM, init_cache

    model = VLM(cfg, mesh=mesh)
    size = cfg.qwen_vision.image_size if cfg.qwen_vision else cfg.vision.image_size

    def boxed(key):
        return model.init(
            key,
            jnp.zeros((1, 1, size, size, 3), jnp.uint8),
            jnp.zeros((1, 4), jnp.int32),
            *init_cache(cfg, 1),
            method=model.init_everything,
        )

    def plain(key):
        return nn.unbox(boxed(key))

    # the hardware generator: threefry spends ten seconds on two billion draws
    key = jax.random.key(seed, impl="rbg")
    if mesh is None:
        return jax.jit(plain)(key)
    from cosmos_curate_tpu.parallel.sharding import spec_sharding

    specs = nn.get_partition_spec(jax.eval_shape(boxed, key))
    shardings = jax.tree.map(
        lambda spec: spec_sharding(mesh, spec), specs,
        is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec),
    )
    return jax.jit(plain, out_shardings=shardings)(key)


# -- the engine's private face ------------------------------------------------


class _Private:
    """Every reach past the engine's public face, in one place."""

    def __init__(self, engine) -> None:
        self.e = engine
        self.first_logits: dict[str, np.ndarray] = {}
        start_slot = engine._start_slot

        def spy(lane, slot_idx, req, t_valid, next_rope, logits_row):
            if req.request_id.startswith("check"):
                self.first_logits[req.request_id] = np.array(logits_row, np.float32)
            return start_slot(lane, slot_idx, req, t_valid, next_rope, logits_row)

        engine._start_slot = spy  # chip_smoke.py's way; there is no public hook

    def warm_prefill(self, lane, rows: int, t: int) -> None:
        """One call of the prefill program of this shape, every row writing
        its one valid position into the garbage block (table of zeros)."""
        import jax.numpy as jnp

        e, cfg = self.e, self.e.cfg
        rope = (rows, t, 3) if cfg.mrope_section is not None else (rows, t)
        ds = (
            jnp.asarray(np.zeros((e._ds_levels, rows, t, cfg.dim), np.float32))
            if e._ds_levels else None
        )
        logits, e._pool_k, e._pool_v = e._prefill_batch(
            e.params, e._pool_k, e._pool_v,
            jnp.asarray(np.zeros((rows, lane.length // e.block_size), np.int32)),
            jnp.asarray(np.zeros((rows, t, cfg.dim), np.float32)),
            jnp.asarray(np.zeros(rows, np.int32)),
            jnp.asarray(np.ones(rows, np.int32)),
            jnp.asarray(np.zeros(rope, np.int32)),
            ds,
        )
        np.asarray(logits)

    def warm_decode(self, lane) -> None:
        import jax.numpy as jnp

        e = self.e
        zeros = np.zeros(lane.n_slots, np.int32)
        greedy, _logits, e._pool_k, e._pool_v = e._decode(
            e.params, e._pool_k, e._pool_v, jnp.asarray(np.zeros_like(lane.table)),
            jnp.asarray(zeros), jnp.asarray(zeros), jnp.asarray(zeros),
        )
        np.asarray(greedy)

    def in_engine(self) -> int:
        e = self.e
        with e._work_cv:
            return len(e.waiting) + len(e._prep_requests()) + len(e.slots) + len(e.pending)


# -- the closed loop ----------------------------------------------------------


class ClosedLoop:
    def __init__(self, engine, private: _Private, traffic, reachable_slots: int, backlog: int) -> None:
        self.engine, self.private, self.traffic = engine, private, traffic
        self.reachable_slots = reachable_slots
        self.target = 1  # grows to slots + backlog once the first request decodes
        self.full_target = reachable_slots + backlog
        self.submitted = 0
        self.results = []
        self.done_tokens = 0
        self.warm_done = 0
        self.early_eos = 0
        self.decode_lengths: list[list[int]] | None = None  # per decode call, while traced

    def _request(self, spec):
        from cosmos_curate_tpu.models.vlm import CaptionRequest, SamplingConfig

        return CaptionRequest(
            request_id=spec.request_id,
            prompt_ids=spec.prompt_ids,
            prefix_ids=spec.prefix_ids,
            frames=spec.frames,
            sampling=SamplingConfig(max_new_tokens=spec.max_new_tokens),
        )

    def feed(self) -> None:
        with annotate("feed"):
            while self.submitted - len(self.results) < self.target:
                # the very first request meets an idle engine, which prefills it whole:
                # the shortest prompt of the mix keeps that to a program warmed anyway
                spec = self.traffic.request(
                    self.submitted, prompt_len=self.traffic.grid[0] if self.submitted == 0 else None
                )
                if self.submitted < self.reachable_slots:
                    # the first fill: spread the phases. The first request keeps its
                    # whole length: it has to decode until the last warmer is through
                    spec.max_new_tokens = max(
                        1,
                        spec.max_new_tokens * (self.reachable_slots - self.submitted)
                        // self.reachable_slots,
                    )
                self.engine.add_request(self._request(spec))
                self.submitted += 1

    def collect(self) -> None:
        with annotate("collect"):
            if not self.engine.completed:
                return
            done, self.engine.completed = self.engine.completed, []
            for r in done:
                if r.request_id.startswith("warm"):
                    self.warm_done += 1
                    continue
                self.results.append(r)
                self.done_tokens += r.num_output_tokens
                if len(self.results) > self.reachable_slots and r.num_output_tokens < int(
                    self.traffic.params["output_tokens"]
                ):
                    self.early_eos += 1

    def tokens_emitted(self) -> int:
        """Output tokens so far, from the outputs themselves: those of the
        finished requests and those the running ones hold."""
        return self.done_tokens + sum(len(s.generated) for s in self.engine.slots.values())

    def turn(self) -> None:
        self.feed()
        active = any(l.slots or l.pending for l in self.engine.lanes)
        with annotate("engine.step"):
            self.engine.step()
        if self.decode_lengths is not None:
            for lane in self.engine.lanes:
                if lane.slots:
                    self.decode_lengths.append([s.position for s in lane.slots.values()])
        self.collect()
        if not active and not any(l.slots or l.pending for l in self.engine.lanes):
            with annotate("idle.wait"):  # only background prep is outstanding
                time.sleep(0.002)

    def _started(self, request_id: str) -> bool:
        """Decoding, or already done."""
        return any(
            s.request.request_id == request_id for s in self.engine.slots.values()
        ) or any(r.request_id == request_id for r in self.results)

    def _turn_until(self, done, deadline: float, what: str) -> None:
        while not done():
            self.turn()
            if time.monotonic() > deadline:
                raise TimeoutError(f"ramp: still waiting for {what}")

    def ramp(self, timeout_s: float) -> None:
        """To a steady state, with nothing but chunked prefills on the way.
        The engine prefills a prompt whole (one program per row count and
        power-of-two length) only while no slot decodes, so: one request
        first; while it decodes, one prefill-only request of every prompt
        length of the mix (the program's host-side operations are specialised
        to the length); then the other slots are filled one at a time. The
        requests of this first fill have their outputs cut to 1/n, 2/n, ... of
        the mix's length (the first keeps all of it), so that the slots' phases
        are spread over a request's life and never again all end together."""
        t0 = time.monotonic()
        deadline = t0 + timeout_s
        self._turn_until(lambda: bool(self.engine.slots), deadline, "the first request to decode")
        t1 = time.monotonic()
        for i, n in enumerate(self.traffic.grid):  # one at a time: one row a prefill
            spec = self.traffic.request(10**6 + 1 + i, name=f"warm{n}", prompt_len=n, max_new_tokens=1)
            self.engine.add_request(self._request(spec))
            self._turn_until(lambda: self.warm_done == i + 1, deadline, f"the warmer of length {n}")
        t2 = time.monotonic()
        while self.target < self.reachable_slots:
            self.target += 1
            self.turn()  # feeds request number target - 1, and no other
            self._turn_until(
                lambda: self._started(f"w{self.target - 1}")
                and not any(l.pending for l in self.engine.lanes),
                deadline, f"slot {self.target} to decode",
            )
        self.target = self.full_target
        log(
            f"ramp: first request {t1 - t0:.2f} s, {len(self.traffic.grid)} warmers {t2 - t1:.2f} s, "
            f"filling {self.reachable_slots} slots {time.monotonic() - t2:.2f} s"
        )


def reachable(engine, traffic, chunk: int):
    """(lanes the mix can land in, prefill lengths it can need while decode is
    active). A request needs prefix + vision + prompt + output + 1 positions;
    ``_route`` gives it the shortest lane that holds them, or a longer one
    when that is full. The shared prefix is cached, so a prefill covers vision
    + prompt: in chunks of ``chunk`` when longer than that, else in one
    power-of-two bucket."""
    from cosmos_curate_tpu.models.batching import next_pow2  # the engine's own bucketing

    p = traffic.params
    n_vis = engine.cfg.qwen_vision.tokens_out(int(p["frames"])) if int(p["frames"]) else 0
    fixed = int(p["prefix_tokens"]) + n_vis + int(p["output_tokens"]) + 1
    if engine.lanes[-1].length < fixed + max(traffic.grid):
        raise ValueError(f"no lane holds the mix's longest request ({fixed + max(traffic.grid)} positions)")
    lanes = [l for l in engine.lanes if l.length >= fixed + min(traffic.grid)]
    lengths = set()
    for n in traffic.grid:
        t = n_vis + n
        lengths.add(chunk if t > chunk else next_pow2(t))
    return lanes, sorted(lengths)


# -- correctness --------------------------------------------------------------


def _rel_err(got: np.ndarray, want: np.ndarray) -> tuple[float, float]:
    scale = float(np.abs(want).max())
    return float(np.abs(got - want).max()) / scale, scale


def check_against_reference(engine, private, traffic, cfg, conf, lengths) -> bool:
    """First-step logits of seeded text-only requests against the plain
    float32 forward pass on the same parameter tree. (Jitting the reference
    over the mesh instead aborted the chip's compiler in an all-reduce
    emitter, PERF.md PR 22: the reference runs on one chip, layer by layer.)"""
    import jax
    import jax.numpy as jnp

    from cosmos_curate_tpu.models.vlm import CaptionRequest, SamplingConfig

    ref = load_module("reference", "qwen2_decoder")
    first = jax.tree.leaves(engine.params)[0].sharding.device_set
    one_chip = min(first, key=lambda d: d.id)

    def place(tree):  # a layer at a time onto one chip (a no-op without a mesh)
        return jax.device_put(tree, one_chip)

    ok = True
    for n in lengths:
        spec = traffic.text_only(f"check-text-{n}", int(n))
        engine.add_request(
            CaptionRequest(
                request_id=spec.request_id, prompt_ids=spec.prompt_ids,
                sampling=SamplingConfig(max_new_tokens=1),
            )
        )
        done = engine.run_until_complete()
        if [r.request_id for r in done] != [spec.request_id]:
            log(f"correct: the engine lost {spec.request_id}")
            ok = False
            continue
        ids = jax.device_put(jnp.asarray(spec.prompt_ids, jnp.int32), one_chip)
        want = np.asarray(ref.last_logits(engine.params, ids, place=place, **ref.model_kwargs(cfg)))
        err, scale = _rel_err(private.first_logits[spec.request_id], want)
        good = np.isfinite(err) and err <= conf["reference_rel_tol"]
        log(
            f"correct: {n}-token prompt, first-step logits vs float32 reference: "
            f"rel err {err:.4f} (tol {conf['reference_rel_tol']}, max |logit| {scale:.3f}) "
            f"{'ok' if good else 'FAILED'}"
        )
        ok &= bool(good)
    return ok


def check_against_xla_path(engine, private, traffic, cfg, conf, lanes_longest) -> bool:
    """One window request: the paged-kernel engine against the engine's own
    XLA path (``paged_attention='gather'``, flash kernels off), same
    parameters, one slot in the longest lane."""
    from cosmos_curate_tpu.models.vlm import CaptionEngine, CaptionRequest, SamplingConfig

    spec = traffic.request(10**6)  # a window of its own, never part of the loop

    def req():
        return CaptionRequest(
            request_id="check-window", prompt_ids=spec.prompt_ids, prefix_ids=spec.prefix_ids,
            frames=spec.frames, sampling=SamplingConfig(max_new_tokens=1), share_prefix=False,
        )

    engine.add_request(req())
    if len(engine.run_until_complete()) != 1:
        log("correct: the engine lost check-window")
        return False
    got = private.first_logits.pop("check-window")
    os.environ.update(CURATE_FLASH_DECODE="0", CURATE_FLASH_PREFILL="0")
    other = CaptionEngine(
        cfg, kv_lanes=((lanes_longest, 1),), params=engine.params, paged_attention="gather",
        prefill_chunk=engine.prefill_chunk,
    )
    other.setup()
    other_private = _Private(other)
    other.add_request(req())
    lost = len(other.run_until_complete()) != 1
    other.shutdown()
    if lost:
        log("correct: the XLA-path engine lost check-window")
        return False
    err, scale = _rel_err(got, other_private.first_logits["check-window"])
    good = np.isfinite(err) and err <= conf["xla_path_rel_tol"]
    log(
        f"correct: window request, paged kernels vs the engine's XLA path: rel err {err:.4f} "
        f"(tol {conf['xla_path_rel_tol']}, max |logit| {scale:.3f}) {'ok' if good else 'FAILED'}"
    )
    return bool(good)


# -- the run ------------------------------------------------------------------


def run(cell: Cell, *, seed: int, seconds: float, trace: bool, rehearse: bool, devices, clock) -> dict:
    import jax
    from jax.sharding import Mesh

    from cosmos_curate_tpu.models.registry import WEIGHTS_DIR_ENV
    from cosmos_curate_tpu.models.vlm import CaptionEngine
    from cosmos_curate_tpu.parallel.axes import MODEL
    from cosmos_curate_tpu.utils.jax_cache import enable_persistent_cache

    conf = cell.config
    # the program looks for staged weights and tokenizers under /tmp unless told
    # where: nothing is staged here, and nothing outside the checkout is read
    os.environ[WEIGHTS_DIR_ENV] = str(measure.CACHE_DIR / "weights" / "none")
    log(f"compile cache at {enable_persistent_cache()}")
    cfg, lanes, chunk = _program_config(cell, rehearse)
    mesh = Mesh(np.array(devices), (MODEL,)) if "mesh" in conf else None
    compiles = measure.CompileCounter()

    with clock.part("params"):
        params = make_params(cfg, seed, mesh)
        jax.block_until_ready(params)
    n_params = sum(x.size for x in jax.tree.leaves(params))
    log(f"{n_params / 1e9:.3f} B parameters made from seed {seed}" + (f" over {dict(mesh.shape)}" if mesh else ""))

    with clock.part("engine"):
        engine = CaptionEngine(
            cfg, kv_lanes=lanes, async_prep=bool(conf["serving"]["async_prep"]),
            paged_attention=conf["serving"]["paged_attention"],
            block_size=int(conf["serving"]["block_size"]), prefill_chunk=chunk,
            params=params, mesh=mesh,
        )
        engine.setup(seed)
        private = _Private(engine)
    traffic_mod = load_module("traffic", cell.traffic["generator"])
    tparams = cell.traffic_params(rehearse)
    size = cfg.qwen_vision.image_size if cfg.qwen_vision else cfg.vision.image_size
    traffic = traffic_mod.CaptionTraffic(tparams, seed, vocab=cfg.vocab, image_size=size)
    use_lanes, lengths = reachable(engine, traffic, engine.prefill_chunk)
    loop = ClosedLoop(
        engine, private, traffic, sum(l.n_slots for l in use_lanes), int(tparams["backlog"])
    )
    log(
        f"lanes {[(l.length, l.n_slots) for l in engine.lanes]}; the mix reaches "
        f"{[(l.length, l.n_slots) for l in use_lanes]}, prefill lengths {lengths}, "
        f"prompt grid {traffic.grid[0]}..{traffic.grid[-1]} step {tparams['prompt_tokens']['step']}"
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
        # one comparison for every lane this cell's traffic lands in
        text_lengths = [
            n for n in text_lengths
            if next(l for l in engine.lanes if l.length >= n + 2) in use_lanes
        ]
        correct = check_against_reference(engine, private, traffic, cfg, check, text_lengths)
        if check["xla_path_frames"] and int(tparams["frames"]):
            correct &= check_against_xla_path(
                engine, private, traffic, cfg, check, engine.lanes[-1].length
            )
        elif int(tparams["frames"]):
            log(f"correct: no XLA-path comparison here: {check['xla_path_why_not']}")

    with clock.part("ramp"):
        loop.ramp(timeout_s=240.0)
    setup_s = clock.close()

    # ---- the measured window ----
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
    }
    if tracer is not None:
        planes = trace_reduce.load_xplane(tracer.xplane())
        measure.keep_trace_for_reading(
            planes, cell.name + (".rehearsal" if rehearse else ""), HOST_SPANS
        )
        summary = trace_reduce.reduce(planes, kernels=KERNELS, host_spans=HOST_SPANS, chips=len(devices))
        tracer.discard()
        record["trace"] = summary
        tp = mesh.shape[MODEL] if mesh else 1
        record["slice"] = {
            "decode_lengths": decode_lengths,
            # one chip's share of the heads under a mesh: the kernel time is one chip's
            "kv_shape": dict(
                n_layers=cfg.n_layers, n_kv_heads=cfg.n_kv_heads // tp, head_dim=cfg.head_dim,
                block_size=engine.block_size, dtype_bytes=2,
            ),
            "attention_shape": dict(
                n_layers=cfg.n_layers, n_heads=cfg.n_heads // tp, head_dim=cfg.head_dim
            ),
        }
        if summary is not None:
            log(
                f"traced slice {summary.window_s:.3f} s, {summary.events} device events: busy "
                f"{summary.busy_s:.3f} s by chip {[round(b, 3) for b in summary.busy_s_by_chip]}, "
                f"kernels {summary.kernel_s} calls {summary.kernel_calls}, "
                f"collectives {summary.collective_s:.3f} s, gaps {summary.gap_s}"
            )
    return record
