"""Benchmark harness: split+annotate throughput on this host's TPU.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N}

Mirrors the reference's canonical benchmark shape
(benchmarks/split_pipeline/invoke.json + benchmarks/summary.py in
/root/reference): a fixed corpus of videos through download → fixed-stride
split → transcode → frame-extract → TPU video embedding → write, measuring
end-to-end clips/sec (model compile excluded via warmup; fixture synthesis
excluded). ``vs_baseline`` compares against the recorded value in
BENCH_REF.json (first recorded round = 1.0); the reference repo publishes no
absolute numbers to compare against directly (BASELINE.md).

The split+annotate measurement runs TWICE and the second (warm-cache) pass
is the headline: r03→r05 drifted 0.215→0.182 on identical code paths, which
is warmup noise (first-touch page faults, lazy imports, allocator growth)
that must not be recorded as signal. The cold pass rides along as
``value_cold``. Per-dispatch device timings (models/device_pipeline.py) are
summarized per pipeline; ``dispatch_gap_frac`` < 0.2 on the embed pipeline
is the acceptance bar that H2D/compute actually overlap. With the default
pipelined runner (core/pipelined_runner.py) the record also carries
``pipeline_overlap_frac`` — the fraction of summed host-stage work hidden
behind other stages; > 0 proves decode/transcode ran concurrently with the
embed stage instead of in lockstep.
"""

from __future__ import annotations

import dataclasses
import json
import os
import sys
import tempfile
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent
sys.path.insert(0, str(REPO))

NUM_VIDEOS = int(os.environ.get("BENCH_NUM_VIDEOS", "64"))
SCENE_FRAMES = 48
NUM_SCENES = 2  # 4 s per video at 24 fps
STRIDE_S = 1.0
# 720p: flat 320x240 color cards made decode/transcode look free — real
# corpora make the CPU stages earn their allocation (ROADMAP item #2)
FRAME_W, FRAME_H = 1280, 720


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def _scene_frames(rng, vid_idx: int, scene_idx: int):
    """One scene's frames: a moving diagonal gradient (global motion a
    codec cannot collapse to a still) over a per-scene noise texture
    (spatial detail that survives resize), plus a tracked high-contrast
    block. Vectorized per frame; deterministic per (video, scene)."""
    import cv2
    import numpy as np

    # per-scene palette and motion parameters from the seeded rng only —
    # regenerating the corpus yields byte-comparable content per video
    c0 = rng.integers(0, 255, 3).astype(np.float32)
    c1 = rng.integers(0, 255, 3).astype(np.float32)
    angle = rng.uniform(0, 2 * np.pi)
    speed = rng.uniform(2.0, 8.0)  # gradient pixels/frame
    # quarter-res noise field upscaled: texture without a 720p RNG bill
    noise = rng.integers(0, 60, (FRAME_H // 4, FRAME_W // 4, 3), dtype=np.uint8)
    noise = cv2.resize(noise, (FRAME_W, FRAME_H), interpolation=cv2.INTER_LINEAR)
    yy, xx = np.mgrid[0:FRAME_H, 0:FRAME_W]
    proj = (np.cos(angle) * xx + np.sin(angle) * yy).astype(np.float32)
    span = float(proj.max() - proj.min()) or 1.0
    bx = int(rng.integers(0, FRAME_W - 160))
    bvx = int(rng.integers(3, 11)) * (1 if scene_idx % 2 == 0 else -1)
    for f in range(SCENE_FRAMES):
        phase = ((proj + f * speed) % span) / span
        frame = (c0[None, None] * (1 - phase[..., None]) + c1[None, None] * phase[..., None])
        frame = np.clip(frame + noise.astype(np.float32) - 30.0, 0, 255).astype(np.uint8)
        x = (bx + f * bvx) % (FRAME_W - 160)
        frame[280:440, x : x + 160] = (255 - c0).astype(np.uint8)
        yield frame


def make_corpus(root: Path) -> Path:
    import cv2
    import numpy as np

    vids = root / "videos"
    vids.mkdir(parents=True, exist_ok=True)
    for i in range(NUM_VIDEOS):
        # one rng per video, seeded by index: adding videos never reshuffles
        # earlier ones, so BENCH rows stay comparable across corpus sizes
        rng = np.random.default_rng(1000 + i)
        path = vids / f"bench_{i}.mp4"
        w = cv2.VideoWriter(
            str(path), cv2.VideoWriter_fourcc(*"mp4v"), 24.0, (FRAME_W, FRAME_H)
        )
        for s in range(NUM_SCENES):
            for frame in _scene_frames(rng, i, s):
                w.write(frame)
        w.release()
    return vids


def require_accelerator() -> None:
    """The benchmark measures the chip. Unless the caller pinned the CPU
    itself (``JAX_PLATFORMS=cpu``: the CI smoke of this harness, whose row
    says ``"backend": "cpu"``), a missing accelerator is an error — never a
    quiet CPU number. The probe runs in a child that exits, so the caption
    child below still finds the chip free."""
    from cosmos_curate_tpu.utils.health import accelerator_health_gate

    accelerator_health_gate(attempts=1, probe_timeout_s=150)


def main() -> int:
    require_accelerator()
    import numpy as np

    from cosmos_curate_tpu.core.runner import SequentialRunner
    from cosmos_curate_tpu.models.embedder import VIDEO_EMBED_BASE, VideoEmbedder
    from cosmos_curate_tpu.pipelines.video.split import SplitPipelineArgs, run_split

    log(f"bench: synthesizing {NUM_VIDEOS} videos")
    tmp = Path(tempfile.mkdtemp(prefix="curate_bench_"))
    vids = make_corpus(tmp)

    # Caption throughput rides along in the same driver artifact (reference
    # SPEED_OF_LIGHT.md:22-52: "output tokens/s is THE metric"). Run it
    # FIRST, before this process initializes JAX: a chip belongs to one
    # process, so a child launched after the parent grabs the chip would
    # fail or hang. Subprocess also means an engine failure can't void the
    # clips/s measurement.
    caption: dict = {}
    caption_cfg = "tiny" if os.environ.get("JAX_PLATFORMS") == "cpu" else "base"
    try:
        import subprocess

        proc = subprocess.run(
            [
                sys.executable,
                "-m",
                "benchmarks.caption_benchmark",
                "--config",
                caption_cfg,
                "--requests",
                os.environ.get("BENCH_CAPTION_REQUESTS", "8"),
                "--max-new",
                "48",
            ],
            capture_output=True,
            text=True,
            timeout=2400,
            cwd=str(REPO),
            env=dict(os.environ),
        )
        caption = json.loads(proc.stdout.strip().splitlines()[-1])
        log(
            f"bench: caption {caption['value']} tok/s "
            f"(backend={caption.get('backend')}, config={caption_cfg})"
        )
    except Exception as e:  # noqa: BLE001
        log(f"bench: caption benchmark failed ({e}); clips/s still valid")

    # Warm up the embedder compile outside the timed window. The device
    # pipeline dispatches pow2 BUCKET micro-batches (cap-sized chunks plus
    # a pow2 remainder, models/device_pipeline.py:plan_micro_batches), so
    # the compiled-shape universe for any run batch is exactly {pow2 <=
    # cap}: warm all of them, or a remainder bucket compiles inside the
    # timed window and masquerades as throughput loss.
    log("bench: warming up embedder compiles")
    warm = VideoEmbedder(VIDEO_EMBED_BASE)
    warm.setup()
    expected_clips_per_video = int(NUM_SCENES * SCENE_FRAMES / 24.0 / STRIDE_S)
    from cosmos_curate_tpu.models.batching import next_pow2
    from cosmos_curate_tpu.models.device_pipeline import micro_batch_cap

    from cosmos_curate_tpu.pipelines.video.stages.embedding import EMBED_STAGE_TASK_BATCH

    # The embed stage batches across tasks, so the run hits bucket shapes
    # up to min(cap, full task-batch clip count).
    full = next_pow2(expected_clips_per_video * min(EMBED_STAGE_TASK_BATCH, NUM_VIDEOS))
    cap = micro_batch_cap()
    # every pow2 <= min(full, cap): when full > cap the loop's last
    # iteration is cap itself, the only chunk shape used beyond it
    shapes = set()
    b = 1
    while b <= min(full, cap):
        shapes.add(b)
        b *= 2
    for b in sorted(shapes):
        warm.encode_clips(
            np.zeros((b, VIDEO_EMBED_BASE.num_frames, 224, 224, 3), np.uint8)
        )
    del warm

    # The reference's canonical perf config is transnet shot detection +
    # motion + aesthetics + embeddings (benchmarks/split_pipeline/
    # invoke.json:1-45). Run that as the headline whenever trained transnet
    # weights are staged; fall back to fixed-stride (the round-1/2 config)
    # when they are not, and say which one was measured.
    transnet_weights = (REPO / "weights" / "transnetv2-tpu" / "params.msgpack").exists()
    config_name = "transnet+motion+embed" if transnet_weights else "fixed-stride+embed"
    args = SplitPipelineArgs(
        input_path=str(vids),
        output_path=str(tmp / "out"),
        splitting_algorithm="transnetv2" if transnet_weights else "fixed-stride",
        fixed_stride_len_s=STRIDE_S,
        min_clip_len_s=0.5,
        motion_filter="score-only" if transnet_weights else "disable",
        extract_fps=(8.0,),
        extract_resize_hw=(224, 224),
        embedding_model="video",
    )
    # Runner selection (BENCH_RUNNER=sequential|pipelined|engine). The
    # single-host default is the pipelined runner: stage worker-thread
    # pools overlap CPU decode/transcode with the device embed stage
    # (core/pipelined_runner.py) without the engine's worker-spawn
    # overhead, which dominates on small boxes. The streaming engine stays
    # opt-in here — its process pools pay off when decode fans out across
    # many real cores or across hosts.
    choice = os.environ.get("BENCH_RUNNER", "auto")
    cores = os.cpu_count() or 1
    if choice not in ("auto", "sequential", "pipelined", "engine"):
        # a typo must not silently bench the wrong runner under the typo's
        # name in the JSON record (same guard default_runner applies)
        raise SystemExit(f"unknown BENCH_RUNNER={choice!r}")
    if choice == "auto":
        choice = "pipelined"
    use_engine = choice == "engine"

    def make_runner():
        if choice == "engine":
            from cosmos_curate_tpu.engine.runner import StreamingRunner

            return StreamingRunner()
        if choice == "pipelined":
            from cosmos_curate_tpu.core.pipelined_runner import PipelinedRunner

            # production semantics (engine parity): a dropped batch shows up
            # as missing clips in the summary, not as an aborted bench
            return PipelinedRunner(raise_on_error=False)
        return SequentialRunner()

    from cosmos_curate_tpu.observability.stage_timer import (
        DISPATCH_DUMP_DIR_ENV,
        dispatch_summaries,
        load_dumped_summaries,
        reset_dispatch_stats,
        reset_stage_flow,
        stage_flow_summaries,
    )

    # Two passes over identical inputs: pass 1 absorbs residual warmup
    # (page faults, lazy imports, allocator growth — the r03→r05 drift);
    # pass 2 (warm) is the headline. Fresh runner + output dir per pass.
    passes = []
    for label in ("cold", "warm"):
        runner = make_runner()
        # the warm (headline) pass runs traced: spans are a boolean check +
        # buffered NDJSON appends, and the flight recorder turns them into
        # report/run_report.json — the artifact every BENCH row references
        # (`cosmos-curate-tpu report <path>` renders the critical path).
        # A bench-scale run emits a few dozen spans, far below measurement
        # noise, but value/vs_baseline do carry that overhead vs pre-trace
        # baselines and vs the untraced cold pass
        pass_args = dataclasses.replace(
            args, output_path=str(tmp / f"out_{label}"), tracing=label == "warm"
        )
        reset_dispatch_stats()  # per-dispatch stats reflect ONE pass
        reset_stage_flow()  # per-stage queue/busy aggregates too
        # engine mode runs stages in spawned workers: have each worker dump
        # its dispatch aggregates at exit so the warm pass still reports
        os.environ[DISPATCH_DUMP_DIR_ENV] = str(tmp / f"dispatch_{label}")
        log(f"bench: running split+annotate [{label}] ({choice}, {cores} cores)")
        t0 = time.monotonic()
        summary = run_split(pass_args, runner=runner)
        elapsed = time.monotonic() - t0
        passes.append((summary, elapsed, runner))
        log(
            f"bench[{label}]: {summary['num_clips']} clips "
            f"({summary['num_with_embeddings']} embedded) in {elapsed:.1f}s; "
            f"video_hours_per_day_per_chip={summary['video_hours_per_day_per_chip']:.1f}"
        )

    cold_summary, cold_elapsed, _ = passes[0]
    summary, elapsed, runner = passes[1]
    clips = summary["num_clips"]
    embedded = summary["num_with_embeddings"]
    value = clips / elapsed if elapsed > 0 else 0.0
    value_cold = (
        cold_summary["num_clips"] / cold_elapsed if cold_elapsed > 0 else 0.0
    )

    ref_path = REPO / "BENCH_REF.json"
    vs = 1.0
    if ref_path.exists():
        try:
            ref = json.loads(ref_path.read_text())
            if ref.get("value"):
                vs = value / float(ref["value"])
        except Exception as e:
            log(f"bench: unreadable BENCH_REF.json: {e}")
    import jax

    backend = jax.devices()[0].platform
    record = {
        "metric": "clips_per_sec_split_annotate",
        "value": round(value, 3),
        "value_cold": round(value_cold, 3),
        "passes": 2,
        "unit": "clips/s",
        "vs_baseline": round(vs, 3),
        "config": config_name,
        "runner": choice,
    }
    # Stage-overlap signal (pipelined runner): fraction of summed host
    # stage work hidden behind other stages — 0 means lockstep (sequential
    # behavior), >0 means decode/transcode ran while the device embedded.
    overlap = getattr(runner, "overlap_frac", None)
    if overlap is not None:
        record["pipeline_overlap_frac"] = round(overlap, 4)
    flow = stage_flow_summaries()
    if flow:
        log("bench: stage flow (warm pass): " + json.dumps(flow))
    # MFU + embed-stage wall for the warm pass (reference SPEED_OF_LIGHT.md's
    # efficiency method via models/flops.py): a device metric, so only a
    # chip run reports it — against that chip's own peak.
    from cosmos_curate_tpu.models.flops import chip_peak_flops, mfu, video_embed_forward_flops

    embed_s = getattr(runner, "stage_times", {}).get("ClipEmbeddingStage", 0.0)
    if backend == "tpu" and embedded and embed_s > 0:
        flops = embedded * video_embed_forward_flops(VIDEO_EMBED_BASE)
        record["mfu"] = round(mfu(flops, embed_s), 4)
        record["embed_stage_s"] = round(embed_s, 2)
        record["peak_flops"] = chip_peak_flops()
    # Per-dispatch device-pipeline timings (warm pass): gap_frac ≈ 0 means
    # H2D/compute/readback actually overlapped; the acceptance bar is the
    # embed pipeline's dispatch gap < 20% of its device window. In-process
    # stats (sequential runner) merge with any worker dumps (engine mode).
    dispatch = dispatch_summaries()
    for name, agg in load_dumped_summaries(str(tmp / "dispatch_warm")).items():
        dispatch.setdefault(name, agg)
    embed_pipes = {k: v for k, v in dispatch.items() if k.startswith("embed/")}
    if embed_pipes:
        gap = sum(v["gap_s"] for v in embed_pipes.values())
        busy = sum(v["gap_s"] + v["compute_s"] for v in embed_pipes.values())
        record["dispatch_gap_s"] = round(gap, 3)
        record["dispatch_gap_frac"] = round(gap / busy, 4) if busy > 0 else 0.0
        record["dispatches"] = sum(v["dispatches"] for v in embed_pipes.values())
    if dispatch:
        log("bench: per-dispatch timings (warm pass): " + json.dumps(dispatch))
    elif use_engine:
        # no worker dump landed (workers killed before atexit, or a stage
        # never dispatched) — nothing to report this pass
        log("bench: no dispatch stats collected from engine workers")
    if backend != "tpu":
        # a CPU-pinned harness smoke must be machine-detectable
        record["backend"] = backend

    # Corpus-index bench (dedup/corpus_index.py): the scenario the index
    # exists for — one run's clips arriving against an already-indexed
    # corpus ≥10x the run's size (BENCH_INDEX_CORPUS_MULT, default 20x —
    # production corpora dwarf one run). Measures fragment-add and query
    # rates plus the headline comparison: incremental dedup via index
    # queries vs a full `semantic_dedup` re-cluster over corpus+run (the
    # acceptance bar is ≥5x). The run's REAL embeddings (warm pass parquet
    # output) are the query batch; the corpus is synthesized AROUND them —
    # half jittered copies of the run's content, half interpolations
    # between run vectors — the continuum structure real curated corpora
    # have (new clips resemble old ones; cluster boundaries are ambiguous,
    # so Lloyd pays its real iteration count instead of snapping in 3).
    try:
        from cosmos_curate_tpu.dedup.corpus_index import CorpusIndex, incremental_dedup
        from cosmos_curate_tpu.dedup.kmeans import semantic_dedup
        from cosmos_curate_tpu.pipelines.video.dedup import load_embeddings

        run_ids, run_vecs, emb_model = load_embeddings(str(tmp / "out_warm"))
        rng = np.random.default_rng(11)
        run_n, dim = run_vecs.shape
        mult = max(10, int(os.environ.get("BENCH_INDEX_CORPUS_MULT", "20")))
        corpus_n = max(mult * run_n, 640)
        half = corpus_n // 2
        similar = (
            np.repeat(run_vecs, (half + run_n - 1) // run_n, 0)[:half]
            + 0.2 * rng.standard_normal((half, dim))
        ).astype(np.float32)
        a = rng.integers(0, run_n, corpus_n - half)
        b = rng.integers(0, run_n, corpus_n - half)
        alpha = rng.uniform(0, 1, (corpus_n - half, 1)).astype(np.float32)
        between = (
            alpha * run_vecs[a] + (1 - alpha) * run_vecs[b]
            + 0.25 * rng.standard_normal((corpus_n - half, dim))
        ).astype(np.float32)
        corpus_vecs = np.concatenate([similar, between])
        corpus_ids = [f"corpus-{i}" for i in range(corpus_n)]
        log(
            f"bench: index bench — {len(run_ids)} run clips vs "
            f"{corpus_n}-vector corpus (dim {run_vecs.shape[1]})"
        )
        index = CorpusIndex.build(
            str(tmp / "bench_index"), corpus_ids, corpus_vecs,
            model=emb_model, metrics_name="bench_index",
        )
        # Both paths warm once outside their timed windows (bench policy:
        # compile excluded via warmup; the persistent compile cache makes
        # production compiles disk hits). Incremental runs on the pre-built
        # index BEFORE the run is added — the production scenario is "new
        # clips arrive against the existing corpus".
        incremental_dedup(index, run_ids, run_vecs, eps=0.07)
        t0 = time.monotonic()
        inc = incremental_dedup(index, run_ids, run_vecs, eps=0.07)
        inc_s = time.monotonic() - t0
        t0 = time.monotonic()
        index.query(run_vecs)
        query_s = time.monotonic() - t0
        t0 = time.monotonic()
        index.add(run_ids, run_vecs)
        add_s = time.monotonic() - t0
        full_input = np.concatenate([corpus_vecs, run_vecs])
        full_ids = corpus_ids + run_ids
        semantic_dedup(full_input, full_ids, eps=0.07)  # warm the Lloyd jits
        t0 = time.monotonic()
        semantic_dedup(full_input, full_ids, eps=0.07)
        full_s = time.monotonic() - t0
        record["index_add_clips_per_sec"] = round(len(run_ids) / add_s, 1) if add_s > 0 else 0.0
        record["index_queries_per_sec"] = round(len(run_ids) / query_s, 1) if query_s > 0 else 0.0
        record["dedup_incremental_s"] = round(inc_s, 3)
        record["dedup_full_recluster_s"] = round(full_s, 3)
        record["dedup_speedup"] = round(full_s / inc_s, 1) if inc_s > 0 else 0.0
        record["dedup_corpus_size"] = corpus_n
        log(
            f"bench: incremental dedup {inc_s:.2f}s vs full re-cluster "
            f"{full_s:.2f}s ({record['dedup_speedup']}x); "
            f"add {record['index_add_clips_per_sec']} clips/s, "
            f"query {record['index_queries_per_sec']} q/s"
        )
        # Search-serving bench (dedup/index_server.py): the /v1/search hot
        # path over the SAME 20x corpus — single-vector requests through the
        # micro-batching server, cold (fresh server, no warmup: every probe
        # faults shards in from storage) vs warm (warmed cache + resident
        # probe union). p50/p99 are the SLO headline; search_qps drives 8
        # concurrent clients so micro-batching across requests is measured,
        # not serial round-trips.
        from concurrent.futures import ThreadPoolExecutor

        from cosmos_curate_tpu.dedup.index_server import IndexServer

        def _latencies(server, qs):
            out = []
            for v in qs:
                t = time.monotonic()
                server.search(v, top_k=5)
                out.append((time.monotonic() - t) * 1e3)
            return out

        n_lat = min(64, len(run_vecs))
        cold_srv = IndexServer(str(tmp / "bench_index"), warmup=False,
                               metrics_name="bench_search_cold")
        try:
            cold = _latencies(cold_srv, run_vecs[:n_lat])
        finally:
            cold_srv.close()
        warm_srv = IndexServer(str(tmp / "bench_index"), metrics_name="bench_search")
        try:
            _latencies(warm_srv, run_vecs[:n_lat])  # fill the probe union
            warm = _latencies(warm_srv, run_vecs[:n_lat])
            qps_n = max(128, 2 * len(run_vecs))
            t0 = time.monotonic()
            with ThreadPoolExecutor(8) as pool:
                list(pool.map(
                    lambda i: warm_srv.search(run_vecs[i % len(run_vecs)], top_k=5),
                    range(qps_n),
                ))
            qps_wall = time.monotonic() - t0
        finally:
            warm_srv.close()
        record["search_qps"] = round(qps_n / qps_wall, 1) if qps_wall > 0 else 0.0
        record["search_latency_p50_ms"] = round(float(np.percentile(warm, 50)), 3)
        record["search_latency_p99_ms"] = round(float(np.percentile(warm, 99)), 3)
        record["search_latency_cold_p50_ms"] = round(float(np.percentile(cold, 50)), 3)
        record["search_latency_cold_p99_ms"] = round(float(np.percentile(cold, 99)), 3)
        log(
            f"bench: search — warm p50 {record['search_latency_p50_ms']}ms "
            f"p99 {record['search_latency_p99_ms']}ms (cold p50 "
            f"{record['search_latency_cold_p50_ms']}ms), "
            f"{record['search_qps']} qps over 8 concurrent clients"
        )
    except Exception as e:  # noqa: BLE001
        log(f"bench: index bench failed ({e}); clips/s still valid")

    # flight-recorder artifact for the warm pass (written by run_split's
    # finalize since the pass ran with tracing): every BENCH row points at
    # the report that explains its number
    from cosmos_curate_tpu.observability.flight_recorder import report_path

    rp = report_path(str(tmp / "out_warm"))
    if Path(rp).exists():
        record["run_report"] = rp
        try:
            rep = json.loads(Path(rp).read_text())
            record["trace_connected"] = bool(rep.get("connected"))
        except Exception as e:  # noqa: BLE001
            log(f"bench: unreadable run report {rp}: {e}")
    else:
        log("bench: warm pass produced no run report")

    # caption_attention micro-section: per-decode-step attention time for
    # the paged programs ("kernel" — on CPU this is the byte-parity XLA
    # reference, same structural win: no gathered working set) vs the
    # legacy gather-view programs, at two context lengths on the tiny
    # config. The counters prove which path ran; the paged step must not
    # lose to gather at the longer context, where the per-step O(context)
    # view copy it deletes is largest.
    try:
        from cosmos_curate_tpu.models.vlm import (
            CaptionEngine,
            CaptionRequest,
            SamplingConfig,
            VLM_TINY_TEST,
        )

        def _decode_step_ms(mode: str, ctx_tokens: int) -> tuple[float, dict]:
            eng = CaptionEngine(
                VLM_TINY_TEST,
                max_batch=1,
                kv_lanes=((VLM_TINY_TEST.max_seq, 1),),
                paged_attention=mode,
                enable_prefix_cache=False,
            )
            eng.setup()

            def drive(rid: str) -> None:
                eng.add_request(
                    CaptionRequest(
                        request_id=rid,
                        prompt_ids=[1 + (i * 7) % 250 for i in range(ctx_tokens)],
                        sampling=SamplingConfig(max_new_tokens=24),
                    )
                )
                eng.run_until_complete()

            drive("warm")  # compiles land outside the measured window
            # best-of-3: a tiny-config decode step is microseconds of real
            # work, so a single scheduler hiccup would swamp the comparison
            best, stats = float("inf"), {}
            for rep in range(3):
                eng.reset_stats()
                drive(f"measure-{rep}")
                stats = eng.stats()
                steps = max(1, stats["decode_tokens"])
                best = min(best, stats["decode_attention_s"] * 1e3 / steps)
            return best, stats

        contexts = (32, 96)
        attn: dict = {"contexts": list(contexts)}
        for mode in ("kernel", "gather"):
            per_ctx = []
            for ctx in contexts:
                step_ms, stats = _decode_step_ms(mode, ctx)
                per_ctx.append(round(step_ms, 4))
            attn[f"{mode}_step_ms"] = per_ctx
            if mode == "kernel":
                attn["decode_attention_s"] = stats["decode_attention_s"]
                attn["kv_gather_bytes_avoided"] = stats["kv_gather_bytes_avoided"]
                attn["paged_kernel_steps"] = stats["paged_kernel_steps"]
        record["caption_attention"] = attn
        log(
            f"bench: caption_attention — kernel {attn['kernel_step_ms']} ms/step "
            f"vs gather {attn['gather_step_ms']} at contexts {list(contexts)}; "
            f"{attn['kv_gather_bytes_avoided']} gathered-view bytes avoided"
        )
    except Exception as e:  # noqa: BLE001
        log(f"bench: caption_attention micro-bench failed ({e}); clips/s still valid")

    if caption:
        record["caption_output_tokens_per_sec"] = caption["value"]
        record["caption_config"] = caption_cfg
        if "caption_pipeline_efficiency" in caption:
            # SPEED_OF_LIGHT.md:67-81 — in-pipeline ÷ standalone tok/s on
            # identical requests through the same engine
            record["caption_pipeline_efficiency"] = caption["caption_pipeline_efficiency"]
            record["caption_pipeline_tokens_per_sec"] = caption["pipeline_tokens_per_sec"]
        # decomposition of the caption number: per-phase seconds (prep /
        # vision-encode / prefill / decode / idle) + shared-prefix KV cache
        # traffic for the in-pipeline pass
        if "pipeline_phases" in caption:
            record["caption_phase_breakdown"] = caption["pipeline_phases"]
        for key in (
            "prefill_tokens",
            "prefix_cache_hits",
            "prefix_tokens_saved",
        ):
            if f"pipeline_{key}" in caption:
                record[f"caption_{key}"] = caption[f"pipeline_{key}"]
        # paged-KV accounting: per-request reservation vs the slot-row
        # engine's worst-case lane row, and the copy-free prefix sharing
        # proof (block refs > 0 with zero whole-prefix copy dispatches)
        for key in (
            "kv_bytes_per_request",
            "kv_bytes_per_request_worst_case",
            "kv_block_size",
            "kv_block_size_requested",
            "kv_blocks_total",
            "kv_blocks_peak",
            "prefix_block_refs",
            "prefix_copy_dispatches",
            "kv_cow_copies",
            "paged_attention",
            "paged_kernel_steps",
            "kv_gather_bytes_avoided",
            "decode_attention_s",
        ):
            if key in caption:
                record[f"caption_{key}"] = caption[key]
        # cross-job continuous batching: two owners sharing one engine must
        # interleave decode steps (per-owner tokens ride along)
        if "cross_job" in caption:
            record["caption_cross_job"] = caption["cross_job"]
        if caption.get("backend") == "tpu":
            record["decode_mfu"] = caption.get("decode_mfu", 0.0)
        elif caption.get("backend") != backend:
            # a cross-backend caption number must be machine-detectable
            record["caption_backend"] = caption.get("backend")
    # the BENCH_r*.json tail row is a durable surface: scripts/bench_trend.py
    # validates rounds against the bench-row golden before comparing them
    from cosmos_curate_tpu.utils import schema_stamp

    schema_stamp.stamp(record, "bench-row")
    print(json.dumps(record))
    return 0


if __name__ == "__main__":
    sys.exit(main())
