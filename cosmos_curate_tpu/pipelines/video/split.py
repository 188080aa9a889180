"""The split-annotate pipeline: assembly + entry point.

Equivalent capability of the reference's flagship splitting pipeline
(cosmos_curate/pipelines/video/splitting_pipeline.py: ``_assemble_stages``
:333-884, ``split``:887): download → clip-extract (fixed-stride or shot
detection) → transcode → frame-extract → [filters] → [embed] → [caption] →
write. Model stages are appended as they come online; every configuration
runs end-to-end through the same assembly.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field

from cosmos_curate_tpu.core.pipeline import PipelineConfig, run_pipeline
from cosmos_curate_tpu.core.runner import RunnerInterface
from cosmos_curate_tpu.core.stage import Stage, StageSpec
from cosmos_curate_tpu.data.model import FrameExtractionSignature
from cosmos_curate_tpu.pipelines.video.input_discovery import discover_split_tasks
from cosmos_curate_tpu.pipelines.video.stages.clip_extraction import (
    ClipTranscodingStage,
    FixedStrideExtractorStage,
)
from cosmos_curate_tpu.pipelines.video.stages.download import VideoDownloadStage
from cosmos_curate_tpu.pipelines.video.stages.frame_extraction import ClipFrameExtractionStage
from cosmos_curate_tpu.pipelines.video.stages.writer import ClipWriterStage
from cosmos_curate_tpu.utils.logging import get_logger
from cosmos_curate_tpu.utils.summary import build_summary, write_summary

logger = get_logger(__name__)


@dataclass
class SplitPipelineArgs:
    input_path: str = ""
    output_path: str = ""
    limit: int = 0
    # clip extraction
    splitting_algorithm: str = "fixed-stride"  # or "transnetv2"
    fixed_stride_len_s: float = 10.0
    min_clip_len_s: float = 2.0
    transnetv2_threshold: float = 0.4
    max_clip_len_s: float = 60.0
    # transcode
    transcode_cpus: int = 4
    clip_chunk_size: int = 64
    # super-resolution after transcode (reference --sr-*,
    # splitting_pipeline.py:1313-1337 / super_resolution_stage.py:189)
    sr: bool = False
    sr_variant: str = "diffusion"  # diffusion | srnet
    sr_window_frames: int = 128
    sr_overlap_frames: int = 64
    sr_sp_size: int = 1
    # frame extraction (uniform size so model stages can stack across clips)
    extract_fps: tuple[float, ...] = (2.0,)
    extract_resize_hw: tuple[int, int] = (224, 224)
    # model stages (enabled as they come online)
    motion_filter: str = "disable"  # disable | score-only | enable
    # estimator: auto (codec MVs with frame-diff fallback) | mv | frame-diff
    motion_backend: str = "auto"
    # calibrated for the frame-diff estimator (see stages/motion_filter.py)
    motion_global_threshold: float = 0.004
    motion_patch_threshold: float = 0.0  # see motion_filter.py: opt-in criterion
    # calibrated for the codec-MV estimator (|mv|/height scale)
    motion_mv_global_threshold: float = 0.001
    motion_mv_patch_threshold: float = 0.0
    aesthetic_threshold: float | None = None
    text_filter: str = "disable"  # disable | score-only | enable
    text_filter_threshold: float = 0.5
    semantic_filter: str = "disable"  # disable | score-only | enable
    semantic_filter_prompt: str = "default"
    embedding_model: str = ""  # "" | "clip" | "video"
    # persistent corpus index (dedup/corpus_index.py): write pending index
    # fragments in-pipeline (ClipWriterStage) and consolidate them into
    # per-cluster shards at end of run
    corpus_index: bool = False
    index_path: str = ""  # "" = <output>/index
    # incremental dedup against that index as clips flow (disable |
    # score-only | enable); enable drops duplicates before the writer
    incremental_dedup: str = "disable"
    dedup_eps: float = 0.07
    dedup_nprobe: int = 0  # 0 = index default
    # multicam sessions: input_path holds <session>/<camera>.mp4 dirs;
    # spans come from the primary camera, aux cameras split time-aligned
    multicam: bool = False
    primary_camera: str = ""  # filename stem; "" = lexicographically first
    captioning: bool = False
    caption_window_len: int = 256
    caption_prompt_variant: str = "default"
    # named VLM flavor (models/vlm/model.py VLM_FLAVORS): base |
    # qwen2vl-2b | qwen25vl-7b | tiny-test
    caption_model: str = "base"
    enhance_captions: bool = False
    t5_embeddings: bool = False
    previews: bool = False
    tracking: bool = False
    tracking_annotated: bool = False
    per_event_captions: bool = False  # implies tracking
    # execution
    num_chips: int = 0  # 0 = discover
    perf_profile: bool = False
    profile_cpu: bool = False
    profile_memory: bool = False
    tracing: bool = False
    stage_save_rate: float = 0.0  # sampled process_data input recording
    stage_save_stages: tuple[str, ...] = ()
    extra_stages: list[Stage | StageSpec] = field(default_factory=list)


def assemble_stages(args: SplitPipelineArgs) -> list[Stage | StageSpec]:
    stages: list[Stage | StageSpec] = [VideoDownloadStage()]
    if args.splitting_algorithm == "transnetv2":
        from cosmos_curate_tpu.pipelines.video.stages.shot_detection import (
            TransNetV2ClipExtractionStage,
        )

        stages.append(
            TransNetV2ClipExtractionStage(
                threshold=args.transnetv2_threshold,
                min_clip_len_s=args.min_clip_len_s,
                max_clip_len_s=args.max_clip_len_s,
            )
        )
    else:
        stages.append(
            FixedStrideExtractorStage(
                clip_len_s=args.fixed_stride_len_s, min_clip_len_s=args.min_clip_len_s
            )
        )
    stages.append(
        ClipTranscodingStage(num_threads=args.transcode_cpus, chunk_size=args.clip_chunk_size)
    )
    if args.sr:
        from cosmos_curate_tpu.pipelines.video.stages.super_resolution import (
            SuperResolutionStage,
        )

        if args.sr_overlap_frames >= args.sr_window_frames:
            # fail fast: the stage's per-clip error handling would otherwise
            # swallow the ValueError and ship a full non-SR output set
            raise ValueError(
                f"--sr-overlap-frames ({args.sr_overlap_frames}) must be < "
                f"--sr-window-frames ({args.sr_window_frames})"
            )

        # directly after transcode (reference inserts SR there,
        # splitting_pipeline.py:553): filters and frame extraction then see
        # the upscaled clips
        stages.append(
            SuperResolutionStage(
                variant=args.sr_variant,
                window_len=args.sr_window_frames,
                overlap=args.sr_overlap_frames,
                sp_size=args.sr_sp_size,
            )
        )
    if args.motion_filter != "disable":
        from cosmos_curate_tpu.pipelines.video.stages.motion_filter import MotionFilterStage

        stages.append(
            MotionFilterStage(
                score_only=args.motion_filter == "score-only",
                global_threshold=args.motion_global_threshold,
                per_patch_threshold=args.motion_patch_threshold,
                backend=args.motion_backend,
                mv_global_threshold=args.motion_mv_global_threshold,
                mv_patch_threshold=args.motion_mv_patch_threshold,
            )
        )
    stages.append(
        ClipFrameExtractionStage(
            signatures=tuple(FrameExtractionSignature("fps", f) for f in args.extract_fps),
            resize_hw=args.extract_resize_hw,
        )
    )
    primary_sig = FrameExtractionSignature("fps", args.extract_fps[0])
    if args.aesthetic_threshold is not None:
        from cosmos_curate_tpu.pipelines.video.stages.aesthetic_filter import AestheticFilterStage

        stages.append(
            AestheticFilterStage(threshold=args.aesthetic_threshold, extraction=primary_sig)
        )
    if args.text_filter != "disable":
        from cosmos_curate_tpu.pipelines.video.stages.artificial_text_filter import (
            ArtificialTextFilterStage,
        )

        stages.append(
            ArtificialTextFilterStage(
                threshold=args.text_filter_threshold,
                score_only=args.text_filter == "score-only",
                extraction=primary_sig,
            )
        )
    if args.semantic_filter != "disable":
        from cosmos_curate_tpu.pipelines.video.stages.semantic_filter import SemanticFilterStage

        stages.append(
            SemanticFilterStage(
                prompt_variant=args.semantic_filter_prompt,
                score_only=args.semantic_filter == "score-only",
                extraction=primary_sig,
                model_flavor=args.caption_model,
            )
        )
    if args.embedding_model:
        from cosmos_curate_tpu.pipelines.video.stages.embedding import ClipEmbeddingStage

        stages.append(ClipEmbeddingStage(variant=args.embedding_model, extraction=primary_sig))
    if args.incremental_dedup != "disable":
        from cosmos_curate_tpu.pipelines.video.stages.dedup_stage import (
            IncrementalDedupStage,
        )

        if not args.embedding_model:
            raise ValueError(
                "--incremental-dedup needs an --embedding-model: dedup "
                "queries the corpus index with this run's clip embeddings"
            )
        # directly after embedding: duplicates are flagged/dropped before
        # captioning, previews, and the writer's embedding/index writes
        stages.append(
            IncrementalDedupStage(
                resolve_index_path(args),
                eps=args.dedup_eps,
                nprobe=args.dedup_nprobe,
                score_only=args.incremental_dedup == "score-only",
            )
        )
    if args.captioning:
        from cosmos_curate_tpu.pipelines.video.stages.captioning import (
            CaptionPrepStage,
            CaptionStage,
        )

        stages.append(
            CaptionPrepStage(window_len=args.caption_window_len, extraction=primary_sig)
        )
        stages.append(
            CaptionStage(
                prompt_variant=args.caption_prompt_variant,
                model_flavor=args.caption_model,
            )
        )
    if args.enhance_captions:
        from cosmos_curate_tpu.pipelines.video.stages.enhance_caption import EnhanceCaptionStage

        stages.append(EnhanceCaptionStage(prompt_variant=args.caption_prompt_variant, model_flavor=args.caption_model))
    if args.t5_embeddings:
        from cosmos_curate_tpu.pipelines.video.stages.caption_embedding import (
            CaptionEmbeddingStage,
        )

        stages.append(CaptionEmbeddingStage(prompt_variant=args.caption_prompt_variant))
    if args.previews:
        from cosmos_curate_tpu.pipelines.video.stages.preview import PreviewStage

        stages.append(PreviewStage(extraction=primary_sig))
    if args.tracking or args.per_event_captions:
        from cosmos_curate_tpu.pipelines.video.stages.tracking import TrackingStage

        stages.append(TrackingStage(write_annotated=args.tracking_annotated))
    if args.per_event_captions:
        from cosmos_curate_tpu.pipelines.video.stages.per_event_caption import (
            PerEventCaptionStage,
        )

        stages.append(PerEventCaptionStage(model_flavor=args.caption_model))
    stages.extend(args.extra_stages)
    stages.append(
        ClipWriterStage(
            args.output_path,
            index_path=resolve_index_path(args) if args.corpus_index else "",
        )
    )
    return stages


def resolve_index_path(args: SplitPipelineArgs) -> str:
    """The corpus-index root this run writes fragments to / queries:
    explicit ``index_path`` or ``<output>/index``."""
    return (args.index_path or f"{args.output_path.rstrip('/')}/index").rstrip("/")


def run_split(
    args: SplitPipelineArgs,
    *,
    runner: RunnerInterface | None = None,
    config: PipelineConfig | None = None,
) -> dict:
    """Build inputs (with resume), run, write summary.json; returns summary."""
    t0 = time.monotonic()
    # retrying accelerator gate (reference gpu_start_helper): catch a chip
    # that does not answer BEFORE spawning workers so the failure mode is
    # one clear error, not N crashed model setups. Opt-in (probing costs a
    # subprocess jax import): CURATE_HEALTH_GATE=on. The gate passes or
    # raises; it never moves the run to the CPU.
    import os as _os

    if _os.environ.get("CURATE_HEALTH_GATE", "off") in ("on", "strict"):
        from cosmos_curate_tpu.utils.health import accelerator_health_gate

        accelerator_health_gate(attempts=3, probe_timeout_s=120, backoff_s=30)
    # live ops plane: export the snapshot dir derived from the output root
    # BEFORE resolving the runner, so every runner (and the workers it
    # spawns) publishes <output>/report/live/status.json — the live
    # counterpart of run_report.json (`top`, `report --follow`, and the
    # service's /v1/jobs/<id>/status all read it)
    from cosmos_curate_tpu.observability.live_status import export_live_status_dir

    export_live_status_dir(args.output_path)
    if runner is None:
        # resolve the default HERE, not inside run_pipeline: the finalize
        # path hands the flight recorder the instance that actually ran,
        # so runner-sourced report sections (dead-letter counts, stage
        # times, overlap) reflect this run instead of falling to empties
        from cosmos_curate_tpu.core.runner import default_runner

        runner = default_runner()
    from cosmos_curate_tpu.parallel.distributed import (
        maybe_initialize_distributed,
        partition_tasks_for_node,
    )

    maybe_initialize_distributed()
    # work-stealing runs call run_pipeline() once per stolen batch, and each
    # run() resets the runner's DLQ accounting — accumulate drops here so
    # finalize reports the whole node, not the last batch
    steal_dead: dict = {"count": 0, "dirs": []}
    index_extra: dict = {}
    run_root = None
    # tracing setup sits immediately before the try whose finally tears it
    # down: anything risky in between (runner resolution, distributed init)
    # raising would otherwise leave tracing enabled with an unexported root
    if args.tracing:
        from cosmos_curate_tpu.observability.flight_recorder import (
            clear_trace_artifacts,
        )
        from cosmos_curate_tpu.observability.tracing import (
            TRACEPARENT_ENV,
            attach_traceparent,
            enable_tracing,
            format_traceparent,
            start_span,
        )
        from cosmos_curate_tpu.parallel.distributed import node_rank_and_count

        rank, num_nodes = node_rank_and_count()
        # a re-run into the same output root must start from a clean trace:
        # stale rotation parts / collected worker files / node-stats
        # sidecars carry the old run's trace ids and drop counts. Multi-node
        # scopes the clear to this rank's own files (peers may already be
        # writing to the shared root)
        clear_trace_artifacts(
            args.output_path, rank=rank if num_nodes > 1 else None
        )
        name = "driver.ndjson" if num_nodes <= 1 else f"driver-n{rank}.ndjson"
        enable_tracing(f"{args.output_path.rstrip('/')}/profile/traces/{name}")
        # join an orchestrator-stamped trace when present, then root every
        # span this node emits on ONE run span: work-stealing calls
        # runner.run() once per claim batch, and each run() opens its own
        # pipeline.run span — without a shared parent a multi-batch run
        # fragments into N trace ids and the flight recorder reports a
        # disconnected trace. The root rides the
        # process-level parent, not the contextvar stack, so it survives
        # any thread hop between claim batches.
        attach_traceparent(_os.environ.get(TRACEPARENT_ENV))
        run_root = start_span("run.split", output_path=args.output_path)
        attach_traceparent(format_traceparent(run_root))
    try:
        if args.multicam:
            from cosmos_curate_tpu.pipelines.video.input_discovery import (
                discover_multicam_tasks,
            )

            if args.splitting_algorithm != "fixed-stride":
                raise ValueError(
                    "multicam sessions split fixed-stride only (time-aligned "
                    "spans across cameras; reference MULTICAM.md scope)"
                )
            tasks = discover_multicam_tasks(
                args.input_path,
                args.output_path,
                primary_camera=args.primary_camera,
                limit=args.limit,
            )
        else:
            tasks = discover_split_tasks(
                args.input_path, args.output_path, limit=args.limit
            )
        stages = assemble_stages(args)
        stages = _apply_observability_wrappers(stages, args)
        from cosmos_curate_tpu.parallel.distributed import node_rank_and_count
        from cosmos_curate_tpu.parallel.work_stealing import (
            run_with_stealing,
            stealing_enabled,
        )

        _, n_nodes = node_rank_and_count()
        if n_nodes > 1 and stealing_enabled():
            # shared-ledger mode: nodes pull claim batches until dry, so a
            # skewed input split rebalances instead of idling fast nodes
            from cosmos_curate_tpu.pipelines.video.input_discovery import (
                _processed_video_ids,
            )
            from cosmos_curate_tpu.pipelines.video.stages.writer import video_record_id

            done_cache = {"ts": 0.0, "ids": set()}

            def _task_done(t) -> bool:
                # resume records are the completion signal; one listing per
                # linger poll, not per task
                now = time.monotonic()
                if now - done_cache["ts"] > 5.0:
                    done_cache["ids"] = _processed_video_ids(args.output_path)
                    done_cache["ts"] = now
                return video_record_id(t.video.path) in done_cache["ids"]

            def _run_batch(batch):
                res = run_pipeline(batch, stages, config=config, runner=runner)
                dlq = getattr(runner, "dlq", None)
                n = int(
                    getattr(runner, "dead_lettered", 0)
                    or getattr(dlq, "recorded", 0)
                    or 0
                )
                if n:
                    steal_dead["count"] += n
                    if dlq is not None and getattr(dlq, "recorded", 0):
                        steal_dead["dirs"].append(str(dlq.run_dir))
                return res

            out = run_with_stealing(
                tasks,
                args.output_path,
                _run_batch,
                record_id=lambda t: video_record_id(t.video.path),
                is_done=_task_done,
            )
        else:
            # default: each node takes a disjoint task slice (host-level
            # data parallelism; resume records keep re-runs consistent)
            tasks = partition_tasks_for_node(tasks)
            out = run_pipeline(tasks, stages, config=config, runner=runner) or []
        if args.corpus_index and n_nodes == 1:
            # end-of-run consolidation, BEFORE finalize so its
            # pipeline_index_* aggregates land in run_report.json
            index_extra = _consolidate_corpus_index(args)
    finally:
        if args.tracing:
            from cosmos_curate_tpu.observability.tracing import (
                disable_tracing,
                end_span,
            )

            if run_root is not None:
                end_span(run_root)
            disable_tracing()  # flushes buffered spans through storage
        if args.tracing or args.profile_cpu or args.profile_memory:
            from cosmos_curate_tpu.observability.artifacts import (
                collect_artifacts,
                finalize_delivery,
            )
            from cosmos_curate_tpu.parallel.distributed import node_rank_and_count

            collect_artifacts(args.output_path)
            rank, count = node_rank_and_count()
            extra = None
            if steal_dead["count"]:
                # the last stolen batch's drops are already in the
                # accumulator, so this replaces (not adds to) the
                # runner's last-run()-scoped accounting
                extra = {"dead_lettered": steal_dead["count"]}
                if steal_dead["dirs"]:
                    extra["dlq_run_dir"] = ",".join(dict.fromkeys(steal_dead["dirs"]))
            if count == 1:
                # single node: this process is also the delivery driver.
                # Multi-node runs finalize from the merge-summaries step
                # (cli/local_cli.py), once every node has collected.
                finalize_delivery(args.output_path)
                if args.tracing:
                    # flight recorder: merge spans + dispatch/flow aggregates
                    # + DLQ counts into report/run_report.json (render with
                    # `cosmos-curate-tpu report <output>`)
                    try:
                        from cosmos_curate_tpu.observability.flight_recorder import (
                            write_run_report,
                        )

                        write_run_report(args.output_path, runner=runner, extra=extra)
                    except Exception:
                        logger.exception(
                            "flight recorder failed (run output unaffected)"
                        )
            elif args.tracing:
                # multi-node: the merged report is built at merge-summaries
                # time, when this runner's memory is gone — persist the
                # runner-sourced sections (dead-letter counts, stage times,
                # dispatch/flow aggregates) as a per-node sidecar now
                try:
                    from cosmos_curate_tpu.observability.flight_recorder import (
                        write_node_stats,
                    )

                    write_node_stats(args.output_path, rank, runner, extra=extra)
                except Exception:
                    logger.exception(
                        "node stats sidecar failed (run output unaffected)"
                    )
    elapsed = time.monotonic() - t0
    from cosmos_curate_tpu.parallel.distributed import node_rank_and_count
    from cosmos_curate_tpu.parallel.mesh import tpu_chip_count

    num_chips = args.num_chips or tpu_chip_count()
    rank, _ = node_rank_and_count()
    summary = build_summary(
        out, pipeline_run_time_s=elapsed, num_chips=num_chips, extra=index_extra or None
    )
    name = "summary.json" if rank == 0 else f"summary-node{rank}.json"
    write_summary(f"{args.output_path.rstrip('/')}/{name}", summary)
    logger.info(
        "split done: %d videos, %d clips, %.1fs",
        summary["num_videos"], summary["num_clips"], elapsed,
    )
    return summary


def _consolidate_corpus_index(args: SplitPipelineArgs) -> dict:
    """Fold the writer's pending index fragments into per-cluster shards
    (training centroids on the first run). Single-node only: concurrent
    per-node consolidations would race on centroids/meta — multi-node runs
    leave pending fragments for `cosmos-curate-tpu index consolidate`
    after merge (chunk-scoped tags never collide across nodes, so the
    merged pending set folds in one pass; no full `index build` re-read).
    Failures never fail the run."""
    try:
        from cosmos_curate_tpu.dedup.corpus_index import consolidate_index

        mesh = None
        try:
            from cosmos_curate_tpu.parallel.mesh import best_effort_mesh

            mesh = best_effort_mesh()
        except Exception as e:
            logger.warning("no mesh for index consolidation (%s)", e)
        cstats = consolidate_index(resolve_index_path(args), mesh=mesh)
        logger.info(
            "corpus index consolidated: %d vectors in (%d random-provenance refused)",
            cstats["consolidated"], cstats["skipped_random"],
        )
        return {"corpus_index": {**cstats, "path": resolve_index_path(args)}}
    except Exception:
        logger.exception("index consolidation failed (run output unaffected)")
        return {}


def _apply_observability_wrappers(
    stages: list[Stage | StageSpec], args: SplitPipelineArgs
) -> list[Stage | StageSpec]:
    """Inject stage-save and profiling wrappers (dynamic subclassing — the
    reference's zero-stage-code-change approach, profiling.py:1129)."""
    out_root = args.output_path.rstrip("/")
    if args.stage_save_rate > 0:
        from cosmos_curate_tpu.observability.stage_replay import (
            StageSaveConfig,
            stage_save_wrapper,
        )

        cfg = StageSaveConfig(
            output_path=f"{out_root}/stage_save",
            sample_rate=args.stage_save_rate,
            stages=args.stage_save_stages,
        )
        for s in stages:  # wrappers mutate the stage instance in place
            stage_save_wrapper(s.stage if isinstance(s, StageSpec) else s, cfg)
    if args.profile_cpu or args.profile_memory:
        from cosmos_curate_tpu.observability.profiling import (
            ProfilingConfig,
            profiling_wrapper,
        )

        cfg = ProfilingConfig(
            cpu=args.profile_cpu,
            memory=args.profile_memory,
            output_path=f"{out_root}/profile",
        )
        for s in stages:
            profiling_wrapper(s.stage if isinstance(s, StageSpec) else s, cfg)
    return stages
