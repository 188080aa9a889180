"""Drives ``run_split`` as ``local split`` builds it: one warm pass over a few
videos (compiles what the embed stage dispatches), then ONE measured pass over
the cell's corpus under ``PipelinedRunner(raise_on_error=False)``. The work is
fixed by the traffic file, not by ``--seconds``: ``n_videos`` was set when the
cell was defined so that the pass fills most of a run.

``clips_per_s`` = clips written with an embedding / wall of the measured pass,
runner start and drain included (a user's job pays both).
"""

from __future__ import annotations

import json
import os
import shutil
import time
from pathlib import Path

import numpy as np

from perfbench import measure
from perfbench.catalog import Cell, load_module
from perfbench.measure import log

def make_weights(vit_cfg, seed: int):
    """Seeded ViT parameters, plain float32 arrays, one jitted call."""
    import flax.linen as nn
    import jax
    import jax.numpy as jnp

    from cosmos_curate_tpu.models.vit import ViT, preprocess_frames

    model = ViT(vit_cfg)
    size = vit_cfg.image_size

    def init(key):
        dummy = jnp.zeros((1, size, size, 3), jnp.uint8)
        pixels = preprocess_frames(dummy, image_size=size, mode=vit_cfg.preprocess)
        return nn.unbox(model.init(key, pixels))

    return jax.jit(init)(jax.random.PRNGKey(seed))


def stage_weights(params, model_id: str, root: Path) -> None:
    """As the model registry's own ``params.msgpack``, so that the stage
    serves exactly the parameters the reference is given."""
    import flax.serialization

    path = root / model_id / "params.msgpack"
    if path.exists():
        return
    path.parent.mkdir(parents=True, exist_ok=True)
    tmp = path.with_suffix(f".{os.getpid()}")
    tmp.write_bytes(flax.serialization.to_bytes(params))
    tmp.rename(path)


def _split_args(conf: dict, vids: Path, out: Path):
    from cosmos_curate_tpu.pipelines.video.split import SplitPipelineArgs

    p = conf["pipeline"]
    return SplitPipelineArgs(
        input_path=str(vids),
        output_path=str(out),
        splitting_algorithm=p["splitting_algorithm"],
        fixed_stride_len_s=float(p["fixed_stride_len_s"]),
        min_clip_len_s=float(p["min_clip_len_s"]),
        motion_filter=p["motion_filter"],
        extract_fps=tuple(p["extract_fps"]),
        extract_resize_hw=tuple(p["extract_resize_hw"]) if p["extract_resize_hw"] else None,
        embedding_model=p["embedding_model"],
    )


def _pass(conf, vids: Path, out: Path):
    """One ``run_split`` under a fresh default single-host runner. Returns
    (summary, status, runner, wall seconds)."""
    from cosmos_curate_tpu.core.pipelined_runner import PipelinedRunner
    from cosmos_curate_tpu.observability.stage_timer import reset_dispatch_stats, reset_stage_flow
    from cosmos_curate_tpu.pipelines.video.split import run_split

    shutil.rmtree(out, ignore_errors=True)
    reset_dispatch_stats()
    reset_stage_flow()
    runner = PipelinedRunner(raise_on_error=False)
    t0 = time.monotonic()
    summary = run_split(_split_args(conf, vids, out), runner=runner)
    wall = time.monotonic() - t0
    status = json.loads((out / "report" / "live" / "status.json").read_text())
    return summary, status, runner, wall


_READ_PARQUET = """
import sys, glob, numpy as np, pyarrow.parquet as pq
ids, rows = [], []
for path in sorted(glob.glob(sys.argv[1] + "/*.parquet")):
    t = pq.read_table(path).to_pydict()
    ids += [str(u) for u in t["clip_uuid"]]
    rows += t["embedding"]
np.savez(sys.argv[2], ids=np.asarray(ids), embeddings=np.asarray(rows, np.float32).reshape(len(ids), -1))
"""


def _written(out: Path, model_id: str) -> tuple[dict[str, np.ndarray], list[dict]]:
    """(clip uuid -> written embedding, clip metadata records). The parquet
    files are read in a child process that never touches JAX: after a profiler
    trace, the first thread pyarrow starts in this process dies in its
    allocator (SIGSEGV in mi_thread_init; PERF.md, PR 22)."""
    import subprocess
    import sys

    npz = out / "embeddings.npz"
    subprocess.run(
        [sys.executable, "-c", _READ_PARQUET, str(out / "embeddings" / model_id), str(npz)],
        check=True, timeout=120,
    )
    with np.load(npz) as data:
        embeddings = {str(u): e for u, e in zip(data["ids"], data["embeddings"])}
    metas = [json.loads(p.read_text()) for p in sorted((out / "metas" / "v0").glob("*.json"))]
    return embeddings, metas


def check_outputs(conf, out: Path, params, vit_cfg, n_check: int, seed: int, expected: int) -> tuple[bool, int]:
    """(correct, clips written with a finite unit embedding)."""
    import jax
    import jax.numpy as jnp

    from cosmos_curate_tpu.video.decode import extract_frames_at_fps

    check = conf["check"]
    model_id = conf["embedding_model_id"]
    embeddings, metas = _written(out, model_id)
    good = {
        uid: e for uid, e in embeddings.items()
        if np.isfinite(e).all() and abs(float(np.linalg.norm(e)) - 1.0) <= check["unit_norm_tol"]
    }
    ok = len(good) == len(embeddings) == expected
    log(f"correct: {len(embeddings)} embeddings written, {len(good)} finite and unit, {expected} expected")
    ref = load_module("reference", "clip_vit")
    sizes = dict(patch=vit_cfg.patch_size, layers=vit_cfg.layers, heads=vit_cfg.heads, ln_eps=vit_cfg.ln_eps)
    forward = jax.jit(lambda p, f: ref.clip_embedding(p, f, **sizes))
    uids = sorted(m["uuid"] for m in metas)
    picks = np.random.default_rng([seed, 7]).choice(len(uids), size=min(n_check, len(uids)), replace=False)
    p = conf["pipeline"]
    for i in sorted(picks.tolist()):
        uid = uids[i]
        clip_path = out / "clips" / f"{uid}.mp4"
        frames = extract_frames_at_fps(
            str(clip_path), target_fps=float(p["extract_fps"][0]),
            resize_hw=tuple(p["extract_resize_hw"]) if p["extract_resize_hw"] else None,
        )
        want = np.asarray(forward(params, jnp.asarray(frames)))
        got = embeddings.get(uid)
        err = float(np.abs(got - want).max()) if got is not None else float("inf")
        fine = err <= check["embedding_tol"]
        log(
            f"correct: clip {uid[:8]} ({frames.shape[0]} frames) written embedding vs float32 "
            f"reference: max err {err:.5f} (tol {check['embedding_tol']}) {'ok' if fine else 'FAILED'}"
        )
        ok &= fine
    return ok, len(good)


def run(cell: Cell, *, seed: int, seconds: float, trace: bool, rehearse: bool, devices, clock) -> dict:
    from cosmos_curate_tpu.models import clip as clip_model
    from cosmos_curate_tpu.models.registry import WEIGHTS_DIR_ENV
    from cosmos_curate_tpu.observability.stage_timer import dispatch_summaries, stage_flow_summaries
    from cosmos_curate_tpu.utils.jax_cache import enable_persistent_cache

    conf = cell.config
    tparams = cell.traffic_params(rehearse)
    log(f"compile cache at {enable_persistent_cache()}")
    compiles = measure.CompileCounter()
    work = measure.CACHE_DIR / "split" / cell.name
    weights_root = measure.CACHE_DIR / "weights" / f"seed{seed}"
    os.environ[WEIGHTS_DIR_ENV] = str(weights_root)
    os.environ["CURATE_DLQ_DIR"] = str(work / "dlq")
    model_id = conf["embedding_model_id"]
    vit_cfg = clip_model._CONFIGS[model_id]
    file_vis = conf["vision_config"]
    got = (vit_cfg.width, vit_cfg.layers, vit_cfg.heads, vit_cfg.patch_size, vit_cfg.image_size, vit_cfg.projection_dim)
    want = (file_vis["hidden_size"], file_vis["num_hidden_layers"], file_vis["num_attention_heads"],
            file_vis["patch_size"], file_vis["image_size"], conf["projection_dim"])
    if got != want:
        raise ValueError(f"configs/{conf['name']}.json {want} and the program's {model_id} {got} disagree")

    with clock.part("corpus"):
        traffic_mod = load_module("traffic", cell.traffic["generator"])
        vids, warm_vids, cached = traffic_mod.make_corpus(tparams, seed, measure.CACHE_DIR)
    log(f"corpus of {tparams['n_videos']} videos at {vids} ({'cached' if cached else 'rendered'})")
    with clock.part("weights"):
        params = make_weights(vit_cfg, seed)
        stage_weights(params, model_id, weights_root)
    with clock.part("warm_pass"):
        summary, status, _runner, wall = _pass(conf, warm_vids, work / "out_warm")
        log(f"warm pass: {summary['num_clips']} clips, {summary['num_with_embeddings']} embedded, {wall:.2f} s")
    setup_s = clock.close()

    # ---- the measured pass ----
    clips_per_video = int(
        tparams["scenes"] * tparams["scene_frames"] / tparams["fps"] / conf["pipeline"]["fixed_stride_len_s"]
    )
    attempted = int(tparams["n_videos"]) * clips_per_video
    pipeline_name = f"clip/{model_id}"
    tracer = measure.Tracer(cell.name) if trace else None
    if tracer is not None:
        # The whole pass is traced, from this thread, device events only: the
        # chip idles nearly all of it, so the trace stays small. (A trace
        # started and stopped from a side thread, with host events on, died
        # with SIGSEGV in a runner thread's allocator: PERF.md, PR 22.)
        tracer.start(host_events=False)
    with compiles.window():
        summary, status, runner, wall = _pass(conf, vids, work / "out")
    if tracer is not None:
        tracer.stop()
    flow = stage_flow_summaries()
    dispatch = dispatch_summaries()
    log(f"measured pass: wall {wall:.3f} s ({wall / seconds:.2f} of --seconds), summary "
        + json.dumps({k: summary[k] for k in ("num_clips", "num_with_embeddings", "num_errors") if k in summary}))
    log("stage flow: " + json.dumps(flow))
    log("dispatch counts: " + json.dumps({k: {"dispatches": v["dispatches"], "rows": v["rows"], "padded_rows": v["padded_rows"]} for k, v in dispatch.items()}))
    dead = {n: s["dead_lettered"] + s["errored"] for n, s in status["stages"].items()}
    if any(dead.values()):
        log(f"dead-lettered/errored batches per stage: {dead}")

    correct, embedded = check_outputs(
        conf, work / "out", params, vit_cfg, int(tparams["check_clips"]), seed, attempted
    )
    frames_per_clip = int(round(conf["pipeline"]["extract_fps"][0] * conf["pipeline"]["fixed_stride_len_s"]))
    record = {
        "correct": bool(correct and not any(dead.values()) and not summary.get("num_errors")),
        "attempted": attempted,
        "failed": attempted - embedded,
        "setup_s": setup_s,
        "window_s": wall,
        "end_to_end": {"clips_per_s": embedded / wall, "setup_s": setup_s},
        "clips": embedded,
        "stage_flow": flow,
        "overlap_frac": runner.overlap_frac,
        "compiles_in_window": compiles.count,
        "devices": devices,
        "rehearse": rehearse,
        "trace": None,
    }
    if tracer is not None:
        from perfbench import trace_reduce

        planes = trace_reduce.load_xplane(tracer.xplane())
        measure.keep_trace_for_reading(planes, cell.name + (".rehearsal" if rehearse else ""))
        # inside the runner's threads the harness has no call to wrap: gaps
        # are reported unattributed
        summary_t = trace_reduce.reduce(
            planes, kernels={}, host_spans=(), chips=len(devices),
            window_s=tracer.stopped_at - tracer.started_at,
        )
        tracer.discard()
        record["trace"] = summary_t
        rows = int(dispatch.get(pipeline_name, {}).get("rows", 0))
        record["slice"] = {"clips_embedded": rows / frames_per_clip}
        if summary_t is not None:
            log(
                f"traced slice {summary_t.window_s:.3f} s, {summary_t.events} device events: busy "
                f"{summary_t.busy_s:.4f} s, {record['slice']['clips_embedded']:.1f} clips embedded in it"
            )
    shutil.rmtree(work, ignore_errors=True)
    return record
