"""Loopback cross-host soak (driven by scripts/run_crosshost_checks.sh).

One driver + one loopback node agent run a real split pipeline: the
per-node planner must put the CPU stages on the agent and keep the
TPU-declared embed stage in-process on the driver; the run must yield ONE
connected trace and object-plane evidence that push-ahead prefetch
overlapped compute. A real file (not a heredoc) because the driver's local
workers are spawned processes that re-import ``__main__``.
"""

from __future__ import annotations

import json
import os
import socket
import subprocess
import sys
import tempfile
import time
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))


def main() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    tmp = Path(tempfile.mkdtemp(prefix="crosshost_soak_"))
    out = tmp / "out"
    trace_dir = out / "profile" / "traces"
    trace_dir.mkdir(parents=True)

    os.environ.update(
        {
            "CURATE_ENGINE_TOKEN": "crosshost-soak-secret",
            "CURATE_ENGINE_DRIVER_PORT": str(port),
            "CURATE_ENGINE_WAIT_NODES": "1",
            "CURATE_ENGINE_WAIT_S": "90",
            "CURATE_PREWARM": "0",
            "CURATE_TRACE_DIR": str(trace_dir),
        }
    )

    from perfbench.traffic import video_corpus  # the benchmark's generator, seeded per video

    corpus = dict(width=1280, height=720, fps=24, scenes=2, scene_frames=48, distinct=3, n_videos=3, warm_videos=0)
    vids, _, _ = video_corpus.make_corpus(corpus, 0, tmp)
    print(f"soak: corpus of 3 videos at {vids}", flush=True)

    env = {
        **os.environ,
        "JAX_PLATFORMS": "cpu",
        "CURATE_TRACING": "1",  # the agent joins the driver's trace
        "PYTHONPATH": str(REPO),
    }
    agent = subprocess.Popen(
        [
            sys.executable, "-m", "cosmos_curate_tpu.engine.remote_agent",
            "--driver", f"127.0.0.1:{port}",
            "--node-id", "loopback-agent", "--num-cpus", "4",
        ],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
    )
    try:
        from cosmos_curate_tpu.core.pipeline import PipelineConfig
        from cosmos_curate_tpu.engine.runner import StreamingRunner
        from cosmos_curate_tpu.pipelines.video.split import (
            SplitPipelineArgs,
            run_split,
        )

        args = SplitPipelineArgs(
            input_path=str(vids),
            output_path=str(out),
            splitting_algorithm="fixed-stride",
            fixed_stride_len_s=1.0,
            min_clip_len_s=0.5,
            motion_filter="disable",
            extract_fps=(8.0,),
            extract_resize_hw=(224, 224),
            embedding_model="video",
            tracing=True,
        )
        runner = StreamingRunner(poll_interval_s=0.01)
        t0 = time.monotonic()
        summary = run_split(
            args,
            runner=runner,
            # ~half a core locally: the planner must put the CPU stages on
            # the agent while the TPU-declared embed stage stays
            # driver-in-process
            config=PipelineConfig(num_cpus=0.5),
        )
        wall = time.monotonic() - t0
        assert summary["num_clips"] > 0, summary
        print(
            f"soak: {summary['num_clips']} clips "
            f"({summary['num_with_embeddings']} embedded) in {wall:.1f}s",
            flush=True,
        )

        # 1. the per-node plan split the pipeline as prescribed
        plan = runner.node_plan
        assert plan, "no per-node plan was emitted"
        embed = plan.get("ClipEmbeddingStage", {})
        assert set(embed) == {""}, f"embed stage left the driver: {embed}"
        agent_cpu_stages = [
            name
            for name, counts in plan.items()
            if counts.get("loopback-agent", 0) > 0
        ]
        assert agent_cpu_stages, f"no CPU stage placed on the agent: {plan}"
        print(f"soak: agent-placed stages: {agent_cpu_stages}", flush=True)

        # 2. ONE connected trace across driver + agent + workers
        report_file = out / "report" / "run_report.json"
        report = json.loads(report_file.read_text())
        assert report["connected"] and len(report["trace_ids"]) == 1, (
            f"trace fragments: {report['trace_ids']}"
        )

        # 3. object-plane prefetch overlapped compute
        plane = report.get("object_plane") or {}
        moved = sum(
            a.get("fetch_bytes", 0) + a.get("prefetch_bytes", 0)
            for a in plane.values()
        )
        assert moved > 0, f"pipeline_object_plane_bytes_total == 0: {plane}"
        hits = sum(a.get("prefetch_hits", 0) for a in plane.values())
        hit_wait = sum(a.get("prefetch_hit_wait_s", 0.0) for a in plane.values())
        transfer = sum(a.get("prefetch_transfer_s", 0.0) for a in plane.values())
        assert hits > 0, f"prefetch never hit: {plane}"
        assert hit_wait < transfer, (
            f"prefetch wait {hit_wait:.3f}s >= transfer {transfer:.3f}s: "
            "transfers did not overlap compute"
        )
        print(
            f"soak ok: {moved / 1e6:.1f}MB over the object plane, "
            f"{hits} prefetch hits, wait {hit_wait:.3f}s < transfer "
            f"{transfer:.3f}s; report: {report_file}",
            flush=True,
        )
    finally:
        agent.terminate()
        try:
            agent.wait(timeout=10)
        except subprocess.TimeoutExpired:
            agent.kill()
    return 0


if __name__ == "__main__":
    sys.exit(main())
