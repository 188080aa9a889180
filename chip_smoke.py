"""The quickest proof that the system still starts on the chip.

    python chip_smoke.py             # one TPU chip: kernels, split, caption-2b, caption-hybrid, caption-latent
    python chip_smoke.py --chips 4   # four chips: the sharded paths ONLY

One process does everything, so the chip has one owner (the streaming
runner's spawned workers are CPU-pinned by the engine itself). Every phase
raises on failure; nothing is caught and continued. Without a TPU the
script fails before any phase, prints no result and exits non-zero. The
last line of standard output is the result:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

Phases, default run (one chip), all at full model WIDTH with seeded random
weights:

- ``kernels``    the Pallas kernels (paged decode, paged prefill, flash
                 attention) compiled (not interpreted) and run at
                 Qwen2-VL-2B shapes against the XLA reference;
- ``split``      ``cosmos-curate-tpu local split`` in-process on 8 seeded
                 720p videos — fixed-stride split, ViT-B/16 video embedder,
                 the default ``base`` captioner — then a 2-video
                 shot-detection pass under the streaming runner;
- ``caption-2b`` the caption engine at full Qwen2-VL-2B width AND depth;
- ``caption-hybrid`` Granite-4.0-H-Micro's widths, one period of its layer
                 pattern (9 Mamba-2 mixers, 1 attention layer): the
                 state-space decode kernel and the chunked prefill scan
                 against the XLA recurrence, then the engine (recurrent store
                 beside the ``D`` = 64 paged pool) against its ``gather`` path;
- ``caption-latent`` DeepSeek-V2's widths, its dense layer and two sparse ones
                 (20 of 160 experts held): the latent-attention kernel and the
                 grouped matrix product against XLA, then the engine (latent
                 pool, absorbed attention, sorted dispatch) against its
                 ``gather`` path and the float32 reference.

``--chips 4`` runs only what exists across chips and what it is compared
with: the head-parallel caption engine at Qwen2.5-VL-7B widths against the
same seeded model on one device (depth 4), mesh k-means against
single-device, then the 7B with nothing cut, built as the caption stage
builds it, against the plain float32 reference of the whole model
(perfbench/reference/qwen25vl.py): a 32-frame window and a 1,500-token text
request, first-step logits and 8 decode steps each; and the names the decode
program gives its collectives.
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import gc
import json
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent
sys.path.insert(0, str(REPO))

# bf16 inputs, fp32 online softmax in the kernel vs fp32 dense softmax with
# bf16 probabilities in the reference: outputs are O(1), so a few bf16 ulps
# (2^-8 relative) of disagreement — the tolerance tests/ops uses for bf16.
BF16_ATOL = BF16_RTOL = 3e-2
# First-step LOGITS after a whole forward pass (28 layers of bf16
# activations): kernel and reference paths may drift by this fraction of
# the largest logit magnitude.
LOGITS_REL_TOL = 5e-2

PHASES = ("kernels", "split", "caption-2b", "caption-hybrid", "caption-latent")


_T0 = time.monotonic()


def log(msg: str) -> None:
    print(f"[{time.monotonic() - _T0:6.1f}s] {msg}", flush=True)


def require_tpu():
    """First act after importing JAX: no TPU, no run."""
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(
            f"chip_smoke: JAX found no TPU (platform={dev.platform!r}); this "
            "script proves the system on the chip and does not run without one."
        )
    return dev


def describe_installation() -> None:
    from importlib import metadata

    import jax

    from cosmos_curate_tpu import native
    from cosmos_curate_tpu.utils.jax_cache import enable_persistent_cache

    versions = {p: metadata.version(p) for p in ("jax", "jaxlib", "libtpu", "flax")}
    dev = jax.devices()[0]
    log(f"chip_smoke: versions {versions}")
    log(f"chip_smoke: device_kind {dev.device_kind!r}, {len(jax.devices())} device(s)")
    log(f"chip_smoke: compile cache at {enable_persistent_cache()}")
    loaded = {
        "curate_native": native.load_native() is not None,
        "h264_encoder": native.load_h264() is not None,
        "mv_extract": native.load_mv() is not None,
    }
    log(f"chip_smoke: native helpers loaded {loaded}")


def _assert_close(name: str, got, want, *, atol=BF16_ATOL, rtol=BF16_RTOL) -> float:
    import numpy as np

    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    if got.shape != want.shape:
        raise AssertionError(f"{name}: shape {got.shape} != reference {want.shape}")
    if not np.isfinite(got).all():
        raise AssertionError(f"{name}: non-finite output")
    err = float(np.abs(got - want).max())
    np.testing.assert_allclose(got, want, atol=atol, rtol=rtol, err_msg=name)
    return err


def _assert_program_holds(name: str, lowered, *ops: str) -> None:
    """The compiled program's text names each of ``ops``: the Mosaic kernel
    (``tpu_custom_call``), a collective."""
    text = lowered.compile().as_text()
    missing = [op for op in ops if op not in text]
    if missing:
        raise AssertionError(f"{name} program lacks {missing}")


# -- phase: kernels ----------------------------------------------------------


def _paged_kernels_against_xla(
    what: str, hkv: int, group: int, head_dim: int, rng, *, bs: int = 16,
    cases=((1024, 8), (4096, 4)), window: int | None = None,
) -> None:
    """The two paged kernels at one flavor's widths, out of a pool stored as
    the engine stores it (``heads_per_row`` KV heads a 128-lane row), against
    the XLA reference over the same K/V one head a row: ``cases`` of (context,
    rows), pages of ``bs`` positions, block tables fragmented so logical order
    never matches pool order; under a ``window`` the chunks' writes are spread
    over the lane, so some lie inside it and some past it. One line each for
    decode and prefill."""
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np

    from cosmos_curate_tpu.ops.paged_attention import (
        _paged_reference,
        heads_per_row,
        join_rows,
        paged_attention,
    )

    layers, layer, chunk = 2, 1, 256
    r = heads_per_row(hkv, head_dim)
    edge = {} if window is None else {"window": window}
    for context, rows in cases:
        nbl = context // bs
        n_blocks = rows * nbl + 8  # block 0 is the engine's garbage block
        shape = (layers, n_blocks, hkv, bs, head_dim)
        plain_k = jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)
        plain_v = jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)
        pool_k, pool_v = join_rows(plain_k, r), join_rows(plain_v, r)
        ids = rng.permutation(np.arange(1, n_blocks))[: rows * nbl]
        tables = jnp.asarray(ids.reshape(rows, nbl), jnp.int32)
        where = f"{what} ({hkv} x {head_dim}, {r} a row, blocks of {bs}, window {window}) ctx {context}"
        reference = functools.partial(
            _paged_reference, layer_index=layer, sm_scale=head_dim**-0.5, **edge
        )

        # decode: one token per row at ragged valid lengths
        kv_len = jnp.asarray(rng.integers(context // 2, context + 1, rows), jnp.int32)
        q1 = jnp.asarray(rng.standard_normal((rows, 1, hkv, group, head_dim)), jnp.bfloat16)
        args = (tables, kv_len - 1, kv_len)
        want = reference(q1, plain_k, plain_v, *args)
        paged = jax.jit(
            functools.partial(
                paged_attention, layer_index=layer, use_kernel=True, interpret=False, **edge
            )
        )
        _assert_program_holds("paged decode", paged.lower(q1, pool_k, pool_v, *args), "tpu_custom_call")
        err = _assert_close(f"paged_decode@{where}", paged(q1, pool_k, pool_v, *args), want)
        log(f"kernels: paged_decode      {where} max_err {err:.4f}")

        # chunked prefill: a chunk written mid-context, causal inside it
        if window is None:
            write = rng.integers(0, context - chunk + 1, rows)
        else:  # the first row inside the window, the last at the lane's end
            write = np.linspace(window // 2, context - chunk, rows).astype(np.int64)
        write = jnp.asarray(write, jnp.int32)
        qt = jnp.asarray(
            rng.standard_normal((rows, chunk, hkv, group, head_dim)), jnp.bfloat16
        )
        args = (tables, write, write + chunk)
        want = reference(qt, plain_k, plain_v, *args)
        err = _assert_close(f"paged_prefill@{where}", paged(qt, pool_k, pool_v, *args), want)
        log(f"kernels: paged_prefill     {where} max_err {err:.4f}")


def _prefill_call_ms(hkv: int, group: int, head_dim: int, rng, *, bs: int, lane: int, rows: int,
                     write: int, window: int | None, reps: int = 16) -> float:
    """Device milliseconds of ONE call of the paged prefill kernel over a
    256-token chunk of ``rows`` rows written at ``write``: ``reps`` calls
    chained inside one program (each call's queries depend on the call
    before), the program's time over ``reps``, so the host's dispatch (0.7 ms
    a call from here) stays out of it."""
    import functools
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from cosmos_curate_tpu.ops.paged_attention import paged_attention

    chunk, nbl = 256, lane // bs
    shape = (1, rows * nbl + 1, hkv, bs, head_dim)
    pool_k = jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)
    pool_v = jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)
    tables = jnp.asarray(rng.permutation(np.arange(1, rows * nbl + 1)).reshape(rows, nbl), jnp.int32)
    q = jnp.asarray(rng.standard_normal((rows, chunk, hkv, group, head_dim)), jnp.bfloat16)
    at = jnp.full((rows,), write, jnp.int32)
    edge = {} if window is None else {"window": window}
    attend = functools.partial(paged_attention, layer_index=0, use_kernel=True, interpret=False, **edge)

    @jax.jit
    def chain(q, pool_k, pool_v, tables, at):
        out = attend(q, pool_k, pool_v, tables, at, at + chunk)
        for _ in range(reps - 1):
            out = attend(q + out * jnp.asarray(1e-6, q.dtype), pool_k, pool_v, tables, at, at + chunk)
        return out

    chain(q, pool_k, pool_v, tables, at).block_until_ready()
    t0 = time.perf_counter()
    for _ in range(4):
        out = chain(q, pool_k, pool_v, tables, at)
    out.block_until_ready()
    return (time.perf_counter() - t0) / 4 / reps * 1e3


def phase_kernels(*, hkv: int = 2, group: int = 6, head_dim: int = 128, seed: int = 0) -> None:
    """Each kernel, compiled for this chip, against the XLA reference
    (``reference_attention`` over the gathered pages / the einsum lines of
    ``layers.Attention``) on the same chip: the paged kernels at Qwen2-VL-2B's
    widths, at Granite-4.0-H-Micro's (64-wide heads, two a pool row) and at
    Trinity-Large's (8 x 6 x 128 in blocks of 128 over the 12,288 lane, full
    and under the window of 4096, crossed), the prefill kernel's device time
    a call at Trinity's shapes in blocks of 128 and of 16 and at the 2B's
    (the microbenchmark of PERF.md PR 39; the kernel before it read 4.98 /
    12.08 ms full and 4.20 / 4.37 under the window at 128, 30.5 / 74.2 and
    26.3 / 27.4 at 16, and 0.51 ms at the 2B's), then flash attention at the
    2B's."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from cosmos_curate_tpu.ops.flash_attention import flash_attention

    rng = np.random.default_rng(seed)
    _paged_kernels_against_xla("qwen2vl-2b", hkv, group, head_dim, rng)
    _paged_kernels_against_xla("granite-4.0-h-micro", 8, 4, 64, rng)
    for window in (None, 4096):
        _paged_kernels_against_xla(
            "trinity-large-ep8", 8, 6, 128, rng, bs=128, cases=((12288, 2),), window=window
        )
    for bs in (128, 16):
        for window in (None, 4096):
            ms = [
                _prefill_call_ms(8, 6, 128, rng, bs=bs, lane=12288, rows=4, write=write, window=window)
                for write in (3840, 12032)
            ]
            log(
                f"kernels: paged_prefill     trinity-large-ep8 4 rows x 256, blocks of {bs}, window {window}: "
                f"{ms[0]:.3f} ms a call at 4k context, {ms[1]:.3f} at 12k"
            )
    ms = _prefill_call_ms(hkv, group, head_dim, rng, bs=16, lane=4096, rows=1, write=1024, window=None)
    log(f"kernels: paged_prefill     qwen2vl-2b 1 row x 256, blocks of 16: {ms:.3f} ms a call at 1.3k context")

    # encoder self-attention (layers.Attention above FLASH_MIN_SEQ); 2049 is
    # InternVideo2's 8x256+1 tokens — the ragged tail pads inside the op
    heads = hkv * group
    for s, causal in ((2048, True), (2049, False)):
        q, k, v = (
            jnp.asarray(rng.standard_normal((1, heads, s, head_dim)), jnp.bfloat16)
            for _ in range(3)
        )
        logits = jnp.einsum("bhqd,bhkd->bhqk", q * head_dim**-0.5, k).astype(jnp.float32)
        if causal:
            logits = jnp.where(jnp.tril(jnp.ones((s, s), bool)), logits, -jnp.inf)
        probs = jax.nn.softmax(logits, axis=-1)
        want = jnp.einsum("bhqk,bhkd->bhqd", probs.astype(jnp.bfloat16), v)
        got = flash_attention(q, k, v, causal=causal, interpret=False)
        err = _assert_close(f"flash_attention@{s}", got, want)
        log(f"kernels: flash_attention   seq {s} causal={causal} max_err {err:.4f}")


# -- phase: split ------------------------------------------------------------


def _run_split_cli(vids: Path, out: Path, *flags: str) -> tuple[dict, list[dict]]:
    """``cosmos-curate-tpu local split`` in-process. The default runner
    dead-letters a batch whose stage died and still exits 0, so the counts
    are what is checked, from the live status; returns (summary.json,
    clip metadata)."""
    from cosmos_curate_tpu.cli.main import main as cli_main

    rc = cli_main(
        ["local", "split", "--input-path", str(vids), "--output-path", str(out), *flags]
    )
    if rc != 0:
        raise AssertionError(f"local split returned {rc}")
    summary = json.loads((out / "summary.json").read_text())
    status = json.loads((out / "report" / "live" / "status.json").read_text())
    metas = [json.loads(p.read_text()) for p in sorted((out / "metas" / "v0").glob("*.json"))]
    dead = {n: s["dead_lettered"] + s["errored"] for n, s in status["stages"].items()}
    if any(dead.values()) or summary["num_errors"]:
        raise AssertionError(
            f"split lost work: dead-lettered/errored per stage {dead}, "
            f"num_errors {summary['num_errors']}"
        )
    return summary, metas


def _stage_quiet_shot_detector(weights_dir: Path, seed: int) -> None:
    """Stage seeded TransNetV2 weights whose head is biased to 'no
    transition'. An untrained detector answers ~0.5 on every frame, which
    the 0.4 threshold reads as a cut at every frame and the minimum clip
    length then drops everything; with the bias it reports one scene per
    video, and the stages behind it get real clips."""
    import flax.serialization
    import jax
    import jax.numpy as jnp

    from cosmos_curate_tpu.models.transnetv2 import INPUT_H, INPUT_W, WINDOW, TransNet, TransNetConfig

    # jitted: one compile, not one per eager op of a 3-D conv net
    params = jax.jit(TransNet(TransNetConfig()).init)(
        jax.random.PRNGKey(seed), jnp.zeros((1, WINDOW, INPUT_H, INPUT_W, 3), jnp.uint8)
    )
    head = params["params"]["head"]
    head["bias"] = jnp.full_like(head["bias"], -6.0)
    path = weights_dir / "transnetv2-tpu" / "params.msgpack"
    path.parent.mkdir(parents=True)
    path.write_bytes(flax.serialization.to_bytes(params))


def phase_split(tmp: Path, *, seed: int = 0) -> None:
    from cosmos_curate_tpu.models.registry import WEIGHTS_DIR_ENV
    from cosmos_curate_tpu.models.vlm import SharedCaptionEngine
    from cosmos_curate_tpu.models.vlm.model import vlm_flavor
    from perfbench.traffic import video_corpus  # the benchmark's generator, seeded per video

    # 8 videos of 720p, two 2 s scenes each: four one-second clips a video
    corpus = dict(width=1280, height=720, fps=24, scenes=2, scene_frames=48, distinct=8, n_videos=8, warm_videos=0)
    vids, _, _ = video_corpus.make_corpus(corpus, seed, tmp / "corpus")
    log(f"split: corpus of {corpus['n_videos']} videos made")
    want = corpus["n_videos"] * (corpus["scenes"] * corpus["scene_frames"] // corpus["fps"])

    t0 = time.monotonic()
    summary, metas = _run_split_cli(
        vids, tmp / "out_fixed",
        "--splitting-algorithm", "fixed-stride", "--fixed-stride-len-s", "1.0",
        "--min-clip-len-s", "0.5", "--embedding-model", "video",
        "--captioning", "--caption-model", "base",
    )
    if (summary["num_clips"], summary["num_with_embeddings"], summary["num_with_captions"]) != (
        want, want, want
    ):
        raise AssertionError(f"split: want {want} clips, all embedded and captioned: {summary}")
    # Random weights now and then answer with tokens that decode to nothing
    # (eos first, or ids past the tokenizer's vocabulary): an empty text is
    # the model's, a missing one would be the system's. Most must read.
    texts = [w["captions"]["default"] for m in metas for w in m["windows"]]
    non_empty = sum(1 for t in texts if t)
    if len(texts) != want or 2 * non_empty < want:
        raise AssertionError(f"split: {non_empty} non-empty of {len(texts)} captions, want {want}")
    base = vlm_flavor("base")
    stats = SharedCaptionEngine.get(base.cfg, model_id=base.model_id).stats()
    if stats["paged_kernel_steps"] <= 0:
        raise AssertionError(f"split: the paged kernel path never ran: {stats}")
    log(
        f"split: {want} clips embedded and captioned ({non_empty} non-empty texts) in "
        f"{time.monotonic() - t0:.1f}s (compiles included); paged_kernel_steps {stats['paged_kernel_steps']}, "
        f"decode_tokens {stats['decode_tokens']}"
    )
    SharedCaptionEngine.reset()  # hand the base captioner's memory back
    log("split: caption engine released")

    # Second pass: the streaming engine — this chip-owning process runs the
    # TPU stages, spawned CPU-pinned workers run the rest.
    _stage_quiet_shot_detector(tmp / "weights", seed)
    os.environ[WEIGHTS_DIR_ENV] = str(tmp / "weights")
    two = tmp / "corpus2"
    two.mkdir()
    for p in sorted(vids.glob("*.mp4"))[:2]:
        shutil.copy(p, two / p.name)
    log("split: seeded shot-detector weights staged")
    t0 = time.monotonic()
    summary, metas = _run_split_cli(
        two, tmp / "out_streaming",
        "--splitting-algorithm", "transnetv2", "--motion-filter", "score-only",
        "--embedding-model", "video", "--runner", "streaming",
    )
    per_video: dict[str, int] = {}
    for m in metas:
        per_video[m["source_video"]] = per_video.get(m["source_video"], 0) + 1
    if len(per_video) != 2 or summary["num_with_embeddings"] != summary["num_clips"]:
        raise AssertionError(
            f"streaming split: clips per video {per_video}, "
            f"{summary['num_with_embeddings']}/{summary['num_clips']} embedded"
        )
    log(
        f"split: streaming runner pass, {summary['num_clips']} clips from 2 videos, "
        f"all embedded, in {time.monotonic() - t0:.1f}s"
    )


# -- phase: caption-2b -------------------------------------------------------


def _requests(cfg, totals, *, n_frames: int, max_new: int, seed: int):
    """Seeded caption requests: ``n_frames`` frames plus random prompt ids
    so that vision + text tokens make each of ``totals``."""
    import numpy as np

    from cosmos_curate_tpu.models.vlm import CaptionRequest, SamplingConfig

    rng = np.random.default_rng(seed)
    size = cfg.qwen_vision.image_size if cfg.qwen_vision else cfg.vision.image_size
    n_vis = cfg.qwen_vision.tokens_out(n_frames) if cfg.qwen_vision else cfg.vision_tokens
    return [
        CaptionRequest(
            request_id=f"r{i}",
            # ids from the upper half: clear of the tokenizer's specials
            prompt_ids=rng.integers(cfg.vocab // 2, cfg.vocab, total - n_vis).tolist(),
            frames=rng.integers(0, 255, (n_frames, size, size, 3), np.uint8),
            sampling=SamplingConfig(max_new_tokens=max_new),
        )
        for i, total in enumerate(totals)
    ]


def _capture_first_logits(engine) -> dict:
    """request_id -> the prefill's last-position logits row, read where the
    engine samples its first token from it."""
    seen: dict = {}
    start_slot = engine._start_slot

    def spy(lane, slot_idx, req, t_valid, next_rope, logits_row):
        seen[req.request_id] = logits_row.copy()
        return start_slot(lane, slot_idx, req, t_valid, next_rope, logits_row)

    engine._start_slot = spy
    return seen


def _drain(engine, requests) -> dict:
    for r in requests:
        engine.add_request(r)
    done = {r.request_id: r for r in engine.run_until_complete()}
    if len(done) != len(requests):  # the engine logs and drops a failed request
        raise AssertionError(f"engine finished {sorted(done)} of {len(requests)} requests")
    return done


def _capture_decode_logits(engine) -> dict:
    """request_id -> [(token fed, the logits row it gave)], one entry for
    every decode step the request took part in. Read where the engine reads
    a decode program (a program is dispatched one step before): a row's last
    token is then the one the program was fed, and a row that ended a token
    earlier is discarded there as it is here."""
    import numpy as np

    seen: dict = {}
    collect = engine._decode_collect

    def spy(lane, flight):
        logits = np.asarray(flight.logits, np.float32)
        for i, s in flight.emitted(lane).items():
            seen.setdefault(s.request.request_id, []).append((s.generated[-1], logits[i]))
        return collect(lane, flight)

    engine._decode_collect = spy
    return seen


def reference_logit_errors(
    engine, first: dict, steps: dict, req, *, n_steps: int, place=lambda tree: tree,
    t_scale: float = 1.0, vision_wrong: dict | None = None, decoder_wrong: dict | None = None,
) -> list[float]:
    """The engine's first-step logits of ``req`` and those of its first
    ``n_steps`` decode steps (through the paged pool), each against
    perfbench/reference/qwen25vl.py: ONE full float32 forward pass over the
    prompt and the tokens the engine emitted. Error as the benchmark's
    ``_rel_err``: largest difference over the reference's largest logit.
    ``first`` / ``steps`` are the two spies' records; ``*_wrong`` override
    the reference's sizes (the tests compute it wrong on purpose)."""
    import numpy as np

    from perfbench.reference import qwen25vl as ref

    cfg = engine.cfg
    fed = [token for token, _row in steps[req.request_id][:n_steps]]
    got = [first[req.request_id]] + [row for _token, row in steps[req.request_id][:n_steps]]
    if len(got) != n_steps + 1:
        raise AssertionError(f"{req.request_id}: {len(got) - 1} decode steps seen, {n_steps} wanted")
    vision, grid = None, None
    if req.frames is not None:
        vk = dict(ref.vision_kwargs(cfg), **(vision_wrong or {}))
        vision = ref.vision_tower(engine.params, req.frames, place=place, **vk)
        n, height, width, _ = req.frames.shape
        unit = vk["patch"] * vk["merge"]
        grid = (-(-n // vk["temporal_patch"]), height // unit, width // unit)
    positions = ref.mrope_positions(len(req.prefix_ids), grid, len(req.prompt_ids), t_scale)
    t = len(positions)
    want = np.asarray(
        ref.logits_at(
            engine.params, req.prefix_ids, vision, list(req.prompt_ids) + fed,
            ref.continue_positions(positions, n_steps), list(range(t - 1, t + n_steps)),
            place=place, **dict(ref.decoder_kwargs(cfg), **(decoder_wrong or {})),
        )
    )
    return [float(np.abs(g - w).max() / np.abs(w).max()) for g, w in zip(got, want)]


def _logits_agree(name: str, got: dict, want: dict) -> None:
    import numpy as np

    for rid, ref in want.items():
        scale = float(np.abs(ref).max())
        err = _assert_close(
            f"{name} first-step logits {rid}", got[rid], ref,
            atol=LOGITS_REL_TOL * scale, rtol=0,
        )
        log(f"{name}: {rid} first-step logits max_err {err:.4f} (max |logit| {scale:.3f})")


def _log_params(name: str, engine) -> None:
    """What the engine serves from: bytes on one chip and leaves by dtype."""
    import jax

    by_dtype: dict = {}  # dtype -> [leaves, bytes of the whole leaves]
    for x in jax.tree.leaves(engine.params):
        seen = by_dtype.setdefault(str(x.dtype), [0, 0])
        seen[0] += 1
        seen[1] += x.nbytes
    per_chip = engine.stats()["param_bytes_per_chip"]
    log(
        f"{name}: param_bytes_per_chip {per_chip} ({per_chip / 2**30:.3f} GiB); leaves by dtype "
        + ", ".join(f"{d}: {n} ({b / 2**30:.3f} GiB whole)" for d, (n, b) in sorted(by_dtype.items()))
    )


def phase_caption_2b(*, seed: int = 0) -> None:
    """Qwen2-VL-2B, nothing cut: 28 layers, 1536 wide, GQA 12/2, vocab
    151936, the 32x1280 vision tower; the flavor's own KV lanes."""
    import jax

    from cosmos_curate_tpu.models.vlm import CaptionEngine
    from cosmos_curate_tpu.models.vlm.model import vlm_flavor

    flavor = vlm_flavor("qwen2vl-2b")
    cfg, lanes = flavor.cfg, flavor.kv_lanes
    t0 = time.monotonic()
    engine = CaptionEngine(cfg, kv_lanes=lanes)
    engine.setup(seed)
    jax.block_until_ready(engine.params)
    log(f"caption-2b: engine set-up {time.monotonic() - t0:.1f}s (seeded init, no compiles yet)")
    _log_params("caption-2b", engine)

    # 200-2000 prompt tokens: four ride the 1024 lane, four the 4096 lane
    totals = (200, 250, 600, 900, 1200, 1500, 1800, 2000)
    logits = _capture_first_logits(engine)
    t0 = time.monotonic()
    done = _drain(engine, _requests(cfg, totals, n_frames=4, max_new=64, seed=seed))
    short = {rid: r.num_output_tokens for rid, r in done.items() if r.num_output_tokens != 64}
    stats = engine.stats()
    if short or stats["paged_kernel_steps"] <= 0:
        raise AssertionError(f"caption-2b: short generations {short}; stats {stats}")
    log(
        f"caption-2b: 8 x 64 tokens in {time.monotonic() - t0:.1f}s (compiles included); "
        f"paged_kernel_steps {stats['paged_kernel_steps']}, "
        f"prefill_tokens {stats['prefill_tokens']}"
    )

    # the same model through the XLA path: gathered views + einsum attention
    reference = CaptionEngine(cfg, kv_lanes=lanes, params=engine.params, paged_attention="gather")
    reference.setup(seed)
    ref_logits = _capture_first_logits(reference)
    picks = [r for r in _requests(cfg, totals, n_frames=4, max_new=1, seed=seed)
             if r.request_id in ("r1", "r7")]  # one per lane
    _drain(reference, picks)
    _logits_agree("caption-2b", logits, ref_logits)
    peak = jax.devices()[0].memory_stats()["peak_bytes_in_use"]
    log(f"caption-2b: peak device memory {peak / 2**30:.2f} GiB")


# -- phase: caption-hybrid ---------------------------------------------------


def _text_requests(cfg, seed: int, max_new: int):
    """Three seeded text requests behind one shared 64-token prefix: one chunk,
    two chunks, three chunks of 256."""
    import numpy as np

    from cosmos_curate_tpu.models.vlm import CaptionRequest, SamplingConfig

    r = np.random.default_rng(seed + 1)
    prefix = r.integers(cfg.vocab // 2, cfg.vocab, 64).tolist()
    return [
        CaptionRequest(
            request_id=f"r{i}", prefix_ids=prefix,
            prompt_ids=r.integers(cfg.vocab // 2, cfg.vocab, n).tolist(),
            sampling=SamplingConfig(max_new_tokens=max_new),
        )
        for i, n in enumerate((144, 400, 592))
    ]


def phase_caption_hybrid(*, seed: int = 0) -> None:
    """Granite-4.0-H-Micro, every width, one period of ten layers: first the
    two state-space operations of ops/ssm.py as the chip runs them against
    the XLA recurrence, then the engine against its own ``gather`` path."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from cosmos_curate_tpu.models.vlm import CaptionEngine
    from cosmos_curate_tpu.models.vlm.model import vlm_flavor
    from cosmos_curate_tpu.ops import ssm

    full = vlm_flavor("granite-4.0-h-micro").cfg
    cfg = dataclasses.replace(full, n_layers=10, layer_types=full.layer_types[:10])
    m = cfg.mamba
    rng = np.random.default_rng(seed)

    def normal(*shape):
        return jnp.asarray(rng.normal(size=shape), jnp.float32)

    # kernels against XLA: 8 rows of a 12-row store, two of them idle
    rows = jnp.asarray([3, 5, 0, 7, 1, 0, 9, 11], jnp.int32)
    store = normal(2, 12, m.n_heads, m.head_dim, m.d_state)
    a = -jnp.arange(1, m.n_heads + 1, dtype=jnp.float32)
    d = jnp.ones(m.n_heads)
    x, dt = normal(8, m.n_heads, m.head_dim), jnp.asarray(rng.uniform(0.001, 0.1, (8, m.n_heads)), jnp.float32)
    dt = dt * (rows > 0)[:, None]
    b, c = normal(8, m.d_state), normal(8, m.d_state)
    y_ref, s_ref = jax.jit(lambda st: ssm.ssm_decode(st, 1, rows, x, dt, a, b, c, d, use_kernel=False))(store)
    y, s = jax.jit(lambda st: ssm.ssm_decode(st, 1, rows, x, dt, a, b, c, d, use_kernel=True, interpret=False))(store)
    err = _assert_close("ssm decode kernel y", y, y_ref, atol=1e-3, rtol=1e-4)
    s_err = _assert_close("ssm decode kernel store", s[:, 1:], s_ref[:, 1:], atol=1e-4, rtol=1e-5)
    log(f"caption-hybrid: _ssm_decode vs XLA step: y max_err {err:.2e}, store max_err {s_err:.2e}")
    xs, dts = normal(2, 512, m.n_heads, m.head_dim), jnp.asarray(rng.uniform(0.001, 0.1, (2, 512, m.n_heads)), jnp.float32)
    bs_, cs_ = normal(2, 512, m.d_state), normal(2, 512, m.d_state)
    y_ref, s_ref = jax.jit(ssm.ssm_scan_reference)(store[0, :2], xs, dts, a, bs_, cs_, d)
    y, s = jax.jit(lambda *v: ssm.ssd_chunk_scan(*v, chunk=m.chunk))(store[0, :2], xs, dts, a, bs_, cs_, d)
    scale = float(jnp.abs(y_ref).max())
    err = _assert_close("ssd prefill scan y", y, y_ref, atol=BF16_ATOL * scale, rtol=0)
    s_err = _assert_close("ssd prefill scan state", s, s_ref, atol=2e-3 * float(jnp.abs(s_ref).max()), rtol=0)
    log(f"caption-hybrid: SSD scan (2 x 512, chunk {m.chunk}) vs the recurrence: y max_err {err:.4f} of {scale:.2f}, state max_err {s_err:.2e}")

    # the engine, kernels against its XLA path: a shared prefix, two chunks, decode
    requests = functools.partial(_text_requests, cfg, seed)

    lanes = ((1024, 8), (4096, 2))
    t0 = time.monotonic()
    engine = CaptionEngine(cfg, kv_lanes=lanes)
    engine.setup(seed)
    # the pool holds two 64-wide KV heads a 128-lane row, so no program copies
    # it whole: read off the compiled decode program of the short lane
    zeros = jnp.zeros(lanes[0][1], jnp.int32)
    compiled = engine._decode.lower(
        engine.params, engine._pool_k, engine._pool_v, jnp.asarray(engine.lanes[0].table),
        zeros, zeros, zeros, engine._ssm, engine._conv, zeros,
    ).compile().as_text()
    from scripts.pool_copies import whole_array_copies

    pool = "bf16[" + ",".join(map(str, engine._pool_k.shape)) + "]"
    copies = {k: n for k, n in whole_array_copies(compiled).items() if k[1].startswith(pool)}
    log(
        f"caption-hybrid: KV pool {engine._pool_k.shape}, {engine.stats()['kv_heads_per_pool_row']} heads a row; "
        f"the compiled decode program holds {sum(copies.values())} pool-shaped copies {copies}"
    )
    if copies or engine._pool_k.shape[-1] != 128:
        raise AssertionError(f"caption-hybrid: the decode program copies the pool: {copies}")
    logits = _capture_first_logits(engine)
    done = _drain(engine, requests(32))
    stats = engine.stats()
    short = {rid: r.num_output_tokens for rid, r in done.items() if r.num_output_tokens != 32}
    if short or stats["ssm_decode_calls"] <= 0 or stats["prefix_state_snapshots"] < 2:
        raise AssertionError(f"caption-hybrid: short generations {short}; stats {stats}")
    log(
        f"caption-hybrid: 3 x 32 tokens in {time.monotonic() - t0:.1f}s (set-up and compiles included); "
        f"ssm_decode_calls {stats['ssm_decode_calls']}, prefix_state_snapshots {stats['prefix_state_snapshots']}, "
        f"recurrent store {stats['recurrent_state_bytes_per_chip'] / 2**30:.2f} GiB"
    )
    reference = CaptionEngine(cfg, kv_lanes=lanes, params=engine.params, paged_attention="gather")
    reference.setup(seed)
    ref_logits = _capture_first_logits(reference)
    _drain(reference, requests(1))
    _logits_agree("caption-hybrid", logits, ref_logits)


def phase_caption_latent(*, seed: int = 0) -> None:
    """DeepSeek-V2's widths over its dense layer and two sparse ones (this
    chip's 20 experts of 160): the latent-attention kernel and the grouped
    matrix product as the chip runs them against their XLA forms, then the
    engine against its own ``gather`` path and against the float32 reference
    (perfbench/reference/deepseek_v2.py)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from cosmos_curate_tpu.models.vlm import CaptionEngine
    from cosmos_curate_tpu.models.vlm.model import vlm_flavor
    from cosmos_curate_tpu.ops.grouped_matmul import grouped_matmul
    from cosmos_curate_tpu.ops.latent_attention import latent_attention
    from perfbench.reference import deepseek_v2 as ref

    cfg = dataclasses.replace(vlm_flavor("deepseek-v2-ep8").cfg, n_layers=3)
    mla, moe = cfg.mla, cfg.moe
    rng = np.random.default_rng(seed)
    width, used, bs, nbl = mla.cache_width, mla.kv_lora_rank + mla.qk_rope_head_dim, 16, 64

    def rows_of(*shape):  # latent rows and absorbed queries: the padding lanes are zeros
        x = rng.normal(size=(*shape, width)).astype(np.float32)
        x[..., used:] = 0
        return jnp.asarray(x, jnp.bfloat16)

    pool = rows_of(2, 600, 1, bs)
    for what, b, t, write in (("decode", 8, 1, (5, 17, 300, 1022, 0, 64, 511, 255)), ("prefill", 2, 256, (64, 512))):
        tables = jnp.asarray(np.stack([rng.permutation(599)[:nbl] + 1 for _ in range(b)]), jnp.int32)
        write = jnp.asarray(write, jnp.int32)
        valid = write + (t if what == "decode" else jnp.asarray([t, t - 68]))
        q = rows_of(b, t, cfg.n_heads)
        attend = functools.partial(
            latent_attention, layer_index=1, sm_scale=mla.softmax_scale, v_width=mla.kv_lora_rank
        )
        want = jax.jit(functools.partial(attend, use_kernel=False))(q, pool, tables, write, valid)
        t0 = time.monotonic()
        got = jax.jit(functools.partial(attend, use_kernel=True, interpret=False))(q, pool, tables, write, valid)
        live = (np.arange(t)[None] < np.asarray(valid - write)[:, None])[..., None, None]
        err = _assert_close(f"latent {what} kernel", np.where(live, got, 0), np.where(live, want, 0), atol=3e-2, rtol=3e-2)
        log(f"caption-latent: mla_{what} kernel vs XLA, {b} x {t} queries: max_err {err:.4f} ({time.monotonic() - t0:.1f}s)")

    first, count = moe.held_experts
    sizes = jnp.asarray(rng.multinomial(700, np.ones(count) / count), jnp.int32)
    lhs = jnp.asarray(rng.normal(size=(1536, cfg.dim)), jnp.bfloat16)
    table = jnp.asarray(rng.normal(size=(count, cfg.dim, 2 * moe.hidden)) * 0.02, jnp.bfloat16)
    want = jax.jit(functools.partial(grouped_matmul, use_kernel=False))(lhs, table, sizes)
    got = jax.jit(functools.partial(grouped_matmul, use_kernel=True, interpret=False))(lhs, table, sizes)
    err = _assert_close("grouped matmul", got[:700], want[:700], atol=3e-2, rtol=3e-2)
    log(f"caption-latent: grouped matmul (gmm) vs ragged_dot, 700 of 1536 rows over {count} tables: max_err {err:.4f}")

    requests = functools.partial(_text_requests, cfg, seed)

    lanes = ((1024, 16), (4096, 2))
    t0 = time.monotonic()
    engine = CaptionEngine(cfg, kv_lanes=lanes)
    engine.setup(seed)
    logits = _capture_first_logits(engine)
    done = _drain(engine, requests(32))
    stats = engine.stats()
    # (a seeded model's argmax may be the EOS id: a short generation is no fault)
    lengths = {rid: r.num_output_tokens for rid, r in done.items()}
    if max(lengths.values()) != 32 or stats["mla_decode_calls"] <= 0 or stats["expert_assignments_held"] <= 0:
        raise AssertionError(f"caption-latent: generations {lengths}; stats {stats}")
    log(
        f"caption-latent: 3 x 32 tokens in {time.monotonic() - t0:.1f}s (set-up and compiles included); "
        f"mla_decode_calls {stats['mla_decode_calls']}, expert_assignments_held {stats['expert_assignments_held']}, "
        f"decode_programs_ahead {stats['decode_programs_ahead']} of {stats['paged_kernel_steps']}, "
        f"latent pool {stats['latent_pool_bytes_per_chip'] / 2**30:.2f} GiB"
    )
    _log_params("caption-latent", engine)
    reference = CaptionEngine(cfg, kv_lanes=lanes, params=engine.params, paged_attention="gather")
    reference.setup(seed)
    ref_logits = _capture_first_logits(reference)
    _drain(reference, requests(1))
    _logits_agree("caption-latent", logits, ref_logits)
    kwargs = ref.model_kwargs(cfg)
    for r in requests(1):
        want, margin = ref.last_logits(engine.params, jnp.asarray(r.prefix_ids + r.prompt_ids, jnp.int32), **kwargs)
        want = np.asarray(want)
        err = float(np.abs(logits[r.request_id] - want).max() / np.abs(want).max())
        log(f"caption-latent: {r.request_id} first-step logits vs float32 reference: rel err {err:.4f} (routing margin {float(margin):.3f})")
        # under a margin of 0.1 another choice of expert is rounding, and moves the logits by tens of per cent
        if float(margin) >= 0.1 and not err <= 0.1:
            raise AssertionError(f"caption-latent: {r.request_id} is {err:.3f} from the reference")


# -- --chips 4: the sharded paths and what they are compared with ------------


def _shard_bytes(tree) -> dict:
    """device id -> bytes of ``tree`` resident there."""
    import jax

    out: dict = {}
    for leaf in jax.tree.leaves(tree):
        for shard in leaf.addressable_shards:
            out[shard.device.id] = out.get(shard.device.id, 0) + shard.data.nbytes
    return out


def phase_sharded(cfg, lanes, totals, *, n_frames: int = 8, max_new: int = 16, seed: int = 0) -> None:
    """(a) the caption engine head-parallel over a ``model`` mesh of every
    device against the same seeded model on one device; (b) mesh k-means
    against single-device k-means."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from cosmos_curate_tpu.dedup.kmeans import kmeans_fit
    from cosmos_curate_tpu.models.vlm import CaptionEngine
    from cosmos_curate_tpu.parallel.mesh import best_effort_mesh, model_mesh

    devices = jax.devices()
    n = len(devices)
    t0 = time.monotonic()
    single = CaptionEngine(cfg, kv_lanes=lanes)
    single.setup(seed)
    sharded = CaptionEngine(cfg, kv_lanes=lanes, params=single.params, mesh=model_mesh(n))
    sharded.setup(seed)
    jax.block_until_ready(sharded.params)
    log(f"sharded: both engines set up in {time.monotonic() - t0:.1f}s")
    _log_params("sharded", sharded)

    # placement: every device holds its 1/n of what is partitioned — the
    # parameters by their annotations, the KV pool by its head planes
    one = _shard_bytes(single.params)
    total = sum(one.values())
    per_device = _shard_bytes(sharded.params)
    replicated = (sum(per_device.values()) - total) / (n - 1) if n > 1 else 0
    log(
        f"sharded: parameters {total / 2**30:.2f} GiB on one device; per device of the mesh "
        f"{ {d: round(b / 2**30, 2) for d, b in per_device.items()} } GiB "
        f"({replicated / 2**20:.1f} MiB replicated on each)"
    )
    want = (total - replicated) / n + replicated
    if len(per_device) != n or any(abs(b - want) > 0.02 * want for b in per_device.values()):
        raise AssertionError(f"parameters not spread 1/{n} per device: {per_device}")
    pool = _shard_bytes((sharded._pool_k, sharded._pool_v))
    if len(pool) != n or set(pool.values()) != {sharded.kv_bytes() // n}:
        raise AssertionError(f"KV pool not split 1/{n} by head planes: {pool}")
    log(f"sharded: KV pool {sharded.kv_bytes() / 2**20:.0f} MiB, 1/{n} on each device")
    for d in devices:
        stats = d.memory_stats()  # None on backends that do not report
        if stats:
            log(f"sharded: device {d.id} bytes_in_use {stats['bytes_in_use'] / 2**30:.2f} GiB")

    # the decode program: collectives where the row-parallel matmuls end,
    # and (on the chip) the paged kernel inside the shard_map
    lane = sharded.lanes[0]
    zeros = jnp.zeros(lane.n_slots, jnp.int32)
    want_ops = ["all-reduce"] + (["tpu_custom_call"] if devices[0].platform == "tpu" else [])
    _assert_program_holds(
        "sharded decode",
        sharded._decode.lower(
            sharded.params, sharded._pool_k, sharded._pool_v, jnp.asarray(lane.table),
            zeros, zeros, zeros,
        ),
        *want_ops,
    )
    log(f"sharded: decode program holds {want_ops}")

    logits, ref_logits = _capture_first_logits(sharded), _capture_first_logits(single)
    kw = dict(n_frames=n_frames, max_new=max_new, seed=seed)
    done = _drain(sharded, _requests(cfg, totals, **kw))
    ref = _drain(single, _requests(cfg, totals, **kw))
    short = {rid: r.num_output_tokens for rid, r in done.items() if r.num_output_tokens != max_new}
    if short or sharded.stats()["paged_kernel_steps"] <= 0:
        raise AssertionError(f"sharded: short generations {short}; {sharded.stats()}")
    _logits_agree("sharded", logits, ref_logits)
    log(f"sharded: {len(done)} x {max_new} tokens on the mesh, {len(ref)} on one device")
    sharded.shutdown()
    single.shutdown()
    del sharded, single, logits, ref_logits, done, ref
    gc.collect()

    # (b) dedup's k-means: rows over the mesh's data axes vs one device
    rng = np.random.default_rng(seed)
    centers = rng.standard_normal((16, 768)).astype(np.float32)
    data = np.repeat(centers, 512, axis=0) + 0.05 * rng.standard_normal((8192, 768)).astype(np.float32)
    mesh = best_effort_mesh()
    _, on_mesh = kmeans_fit(data, 16, seed=seed, mesh=mesh)
    _, on_one = kmeans_fit(data, 16, seed=seed, mesh=None)
    if mesh.size != n or not np.array_equal(on_mesh, on_one):
        raise AssertionError(
            f"k-means over mesh {dict(mesh.shape)} disagrees with one device on "
            f"{int((on_mesh != on_one).sum())} of {len(on_one)} rows"
        )
    log(f"sharded: k-means over mesh {dict(mesh.shape)} = single-device assignments (8192 rows)")


# The float32 reference against a whole forward pass of bfloat16 activations
# at the published widths: bfloat16 rounds at 2^-8, 28 layers of it measured
# 0.0102-0.0137 of the largest logit at 2B width (PERF.md, PRs 22-25) and
# PERF.md's PR 26 section has the 7B's. A bfloat16 softmax or head, a windowed
# block computed as full attention or m-rope sections swapped all read above
# it (tests/perfbench/test_qwen25vl_reference.py shows the last two at test
# size, where the bound is 0.06: a rounding is a larger share of a logit at
# width 64).
REFERENCE_REL_TOL = 0.03


def phase_reference(
    flavor_name: str, *, n_frames: int = 32, n_prefix: int = 64, n_prompt: int = 96,
    n_text: int = 1500, n_steps: int = 8, tol: float = REFERENCE_REL_TOL, seed: int = 0,
    prefill_chunk: int = 256,
):
    """The flavor's engine, built the way the caption stage builds it (its
    own mesh, lanes and background prep; seeded float32 parameters made
    split, as the benchmark's driver makes them, which the engine narrows to
    the types it serves from), against the plain float32
    reference of the whole model: a text request, prefilled whole, and a
    caption window (frames behind a shared prefix), prefilled in chunks while
    the first decodes beside it; first-step logits and ``n_steps`` decode
    steps each. The reference runs on one chip, a layer's parameters at a
    time. Returns the engine (the caller shuts it down)."""
    import jax
    import numpy as np

    from cosmos_curate_tpu.models.vlm import (
        CaptionEngine, CaptionRequest, SamplingConfig, SharedCaptionEngine,
    )
    from cosmos_curate_tpu.pipelines.video.stages.captioning import resolve_caption_model
    from perfbench.drivers.caption_engine import make_params

    t0 = time.monotonic()
    served = resolve_caption_model(None, flavor_name, max_batch=8)
    cfg, mesh = served.cfg, served._serving_mesh()  # raises on too few chips
    params = make_params(cfg, seed, mesh)
    engine = CaptionEngine(
        cfg, kv_lanes=served.kv_lanes, async_prep=True, params=params, mesh=mesh,
        prefill_chunk=prefill_chunk,
    )
    engine.setup(seed)
    stage_key = SharedCaptionEngine.key_for(cfg, served.model_id, mesh=mesh)
    if engine.mesh_geometry != stage_key.geometry or mesh.size != served.model_chips:
        raise AssertionError(f"reference: engine {engine.mesh_geometry}, stage {stage_key.geometry}")
    stats = engine.stats()
    log(
        f"reference: {flavor_name} over {dict(mesh.shape)} set up in {time.monotonic() - t0:.1f}s; "
        f"one chip holds {stats['param_bytes_per_chip'] / 2**30:.3f} GiB of parameters and "
        f"{stats['kv_pool_bytes_per_chip'] / 2**20:.1f} MiB of KV pool"
    )
    _log_params("reference", engine)
    whole = sum(x.nbytes for x in jax.tree.leaves(engine.params))
    repeated = (stats["param_bytes_per_chip"] * mesh.size - whole) / (mesh.size - 1)
    log(
        f"reference: parameters {whole / 2**30:.3f} GiB whole, {repeated / 2**20:.1f} MiB of them "
        f"repeated on every chip, the rest split 1/{mesh.size}"
    )
    if stats["kv_pool_bytes_per_chip"] * mesh.size != engine.kv_bytes():
        raise AssertionError(f"reference: KV pool not split 1/{mesh.size}: {stats}")

    first, steps = _capture_first_logits(engine), _capture_decode_logits(engine)
    rng = np.random.default_rng(seed)
    size = cfg.qwen_vision.image_size

    def ids(n):  # the upper half: clear of the tokenizer's specials
        return rng.integers(cfg.vocab // 2, cfg.vocab, n).tolist()

    text = CaptionRequest(
        request_id="check-text", prompt_ids=ids(n_text),
        sampling=SamplingConfig(max_new_tokens=4 * n_steps),
    )
    window = CaptionRequest(
        request_id="check-window", prefix_ids=ids(n_prefix), prompt_ids=ids(n_prompt),
        frames=rng.integers(0, 255, (n_frames, size, size, 3), np.uint8),
        sampling=SamplingConfig(max_new_tokens=n_steps + 1),
    )
    t0 = time.monotonic()
    engine.add_request(text)
    while not engine.slots:  # prefilled whole; from here on it decodes
        engine.step()
    chunks0 = engine.stats()["prefill_tokens"]
    engine.add_request(window)  # so this one is prefilled in chunks beside it
    done = {r.request_id: r for r in engine.run_until_complete()}
    if sorted(done) != ["check-text", "check-window"]:
        raise AssertionError(f"reference: the engine finished {sorted(done)}")
    log(
        f"reference: both requests through the engine in {time.monotonic() - t0:.1f}s (compiles "
        f"included); the window's {engine.stats()['prefill_tokens'] - chunks0} tokens prefilled "
        f"in chunks of {engine.prefill_chunk}"
    )
    one_chip = min(mesh.devices.flat, key=lambda d: d.id)

    def place(tree):
        return jax.device_put(tree, one_chip)

    for req in (window, text):
        t0 = time.monotonic()
        errs = reference_logit_errors(engine, first, steps, req, n_steps=n_steps, place=place)
        log(
            f"reference: {req.request_id} vs the float32 reference, rel err first step "
            f"{errs[0]:.4f}, {n_steps} decode steps {' '.join(f'{e:.4f}' for e in errs[1:])} "
            f"(tol {tol}; {time.monotonic() - t0:.1f}s)"
        )
        if not all(np.isfinite(e) and e <= tol for e in errs):
            raise AssertionError(f"reference: {req.request_id} outside {tol}: {errs}")
    return engine


def phase_collective_names(engine) -> None:
    """The mesh engine's decode program names where its collectives come
    from: every layer's two row-parallel all-reduces carry the scope of
    their site (``TP_SCOPES``, models/vlm/model.py) in the compiled program,
    which is what a trace viewer shows for the operation.

    The persistent compile cache keys a program without its debug
    information, so it may hand back a binary that an earlier checkout
    compiled without the scopes: the program is compiled here with the
    metadata in the key."""
    import re
    from collections import Counter

    import jax
    import jax.numpy as jnp

    from cosmos_curate_tpu.models.vlm.model import TP_SCOPES

    lane = engine.lanes[-1]
    zeros = jnp.zeros(lane.n_slots, jnp.int32)
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    try:
        text = engine._decode.lower(
            engine.params, engine._pool_k, engine._pool_v, jnp.asarray(lane.table), zeros, zeros, zeros
        ).compile().as_text()
    finally:
        jax.config.update("jax_compilation_cache_include_metadata_in_key", False)
    collective = re.compile(
        r"^\s*%?((?:all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)[\w.\-]*) = .*"
        r'op_name="([^"]*)"', re.M,
    )
    origins: Counter = Counter()
    for _name, op_name in collective.findall(text):
        site = next((v for v in TP_SCOPES.values() if f"/{v}/" in op_name), None)
        origins[site or "/".join(p for p in op_name.split("/")[-2:] if not p.startswith("layer_"))] += 1
    log(f"collectives: the decode program's, by origin: {dict(sorted(origins.items()))}")
    for site in ("attn_out", "mlp_down"):
        if origins[TP_SCOPES[site]] != engine.cfg.n_layers:
            raise AssertionError(
                f"collectives: {origins[TP_SCOPES[site]]} under {TP_SCOPES[site]!r}, "
                f"{engine.cfg.n_layers} layers"
            )


def run_sharded() -> None:
    """Qwen2.5-VL-7B widths (3584 wide, 28/4 heads: one KV head per chip),
    untouched. Depth cut 28 -> 4 so that device 0 can ALSO hold the same
    seeded model whole (made in fp32, then narrowed to what the engine serves
    from), for the comparison."""
    from cosmos_curate_tpu.models.vlm.model import vlm_flavor

    flavor = vlm_flavor("qwen25vl-7b")
    cfg = dataclasses.replace(flavor.cfg, n_layers=4)
    phase_sharded(cfg, flavor.kv_lanes, totals=(300, 900, 1500, 2000))
    # then nothing cut: full depth, every published width, against float32
    engine = phase_reference("qwen25vl-7b")
    phase_collective_names(engine)
    engine.shutdown()


# -- entry -------------------------------------------------------------------


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument(
        "--chips", type=int, choices=(1, 4), default=1,
        help="4: run only the sharded paths (needs four chips)",
    )
    parser.add_argument(
        "--phases", default=",".join(PHASES),
        help=f"one-chip phases to run, comma-separated (default: {','.join(PHASES)})",
    )
    args = parser.parse_args(argv)
    phases = args.phases.split(",")
    unknown = sorted(set(phases) - set(PHASES))
    if unknown:
        parser.error(f"unknown phases {unknown}")

    import jax

    dev = require_tpu()
    count = len(jax.devices())
    if args.chips == 4 and count != 4:
        sys.exit(f"chip_smoke: --chips 4 needs four chips, JAX reports {count}")
    describe_installation()

    t_start = time.monotonic()
    if args.chips == 4:
        run_sharded()
    else:
        tmp = Path(tempfile.mkdtemp(prefix="chip_smoke_"))
        try:
            for name in phases:
                t0 = time.monotonic()
                if name == "kernels":
                    phase_kernels()
                elif name == "split":
                    phase_split(tmp)
                elif name == "caption-2b":
                    phase_caption_2b()
                elif name == "caption-hybrid":
                    phase_caption_hybrid()
                else:
                    phase_caption_latent()
                log(f"chip_smoke: phase {name} passed in {time.monotonic() - t0:.1f}s")
                gc.collect()
        finally:
            shutil.rmtree(tmp, ignore_errors=True)
    log(f"chip_smoke: all phases passed in {time.monotonic() - t_start:.1f}s")
    print(
        json.dumps(
            {
                "ok": True,
                "device": {"platform": dev.platform, "kind": dev.device_kind, "count": count},
            }
        ),
        flush=True,
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
