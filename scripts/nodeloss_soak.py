"""Loopback node-loss soak (driven by scripts/run_nodeloss_checks.sh).

Two loopback node agents run a real split pipeline twice against the same
corpus: an UNFAULTED baseline, then a faulted run where one agent SIGKILLs
itself (chaos ``agent.kill``) right after relaying its first result — the
instant its outputs are referenced downstream but about to die with it.
The faulted run must prove mid-run node death costs only recomputation:

- the run completes, and its clip output set EQUALS the baseline's
  (fixed-stride clips have deterministic uuid5 ids);
- ``pipeline_objects_reconstructed_total`` > 0 (lineage re-execution ran);
- ZERO dead-lettered batches;
- ONE connected trace (reconstruction re-runs stay in the run's trace).

A real file (not a heredoc) because the driver's local workers are spawned
processes that re-import ``__main__``.
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


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _spawn_agent(port: int, node_id: str, extra_env: dict | None = None):
    env = {
        **os.environ,
        "JAX_PLATFORMS": "cpu",
        "CURATE_TRACING": "1",
        "PYTHONPATH": str(REPO),
        **(extra_env or {}),
    }
    return subprocess.Popen(
        [
            sys.executable, "-m", "cosmos_curate_tpu.engine.remote_agent",
            "--driver", f"127.0.0.1:{port}",
            "--node-id", node_id, "--num-cpus", "4",
        ],
        env=env, stdout=subprocess.DEVNULL, stderr=subprocess.STDOUT, text=True,
    )


def _clip_set(out: Path) -> set[str]:
    kept = {p.stem for p in (out / "metas" / "v0").glob("*.json")}
    filtered = {p.stem for p in (out / "metas" / "filtered").glob("*.json")}
    return kept | filtered


def _run_split(out: Path, vids: Path, port: int, agents: list) -> tuple[dict, object]:
    from cosmos_curate_tpu.core.pipeline import PipelineConfig
    from cosmos_curate_tpu.engine.runner import StreamingRunner
    from cosmos_curate_tpu.pipelines.video.split import SplitPipelineArgs, run_split

    os.environ["CURATE_ENGINE_DRIVER_PORT"] = str(port)
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
        args, runner=runner,
        # ~half a core locally: CPU stages place on the agents, so the
        # killed agent provably owned live intermediates
        config=PipelineConfig(num_cpus=0.5),
    )
    print(
        f"soak: {summary['num_clips']} clips in {time.monotonic() - t0:.1f}s "
        f"-> {out}", flush=True,
    )
    return summary, runner


def _lockcheck_verdict(tmp: Path) -> str:
    """With CURATE_LOCKCHECK=1: the driver's in-process recorder plus every
    agent report dumped into the lockcheck dir must be inversion-free —
    the dynamic counterpart of the `lint --concurrency` gate, exercised
    under real node death."""
    from cosmos_curate_tpu.analysis import lock_runtime

    rec = lock_runtime.active()
    if rec is None:
        return "lockcheck: off"
    reports = [rec.report()]
    # agents dump lockcheck-<pid>.json at exit; the SIGKILLed agent
    # never gets the chance — best-effort by design
    for p in sorted((tmp / "lockcheck").glob("lockcheck-*.json")):
        reports.append(json.loads(p.read_text()))
    inversions = [i for r in reports for i in r["inversions"]]
    assert not inversions, f"lock-order inversions under node loss: {inversions}"
    locks = sum(len(r["locks"]) for r in reports)
    return f"lockcheck ok: {len(reports)} report(s), {locks} lock site(s), 0 inversions"


def main() -> int:
    tmp = Path(tempfile.mkdtemp(prefix="nodeloss_soak_"))
    os.environ.update(
        {
            "CURATE_ENGINE_TOKEN": "nodeloss-soak-secret",
            "CURATE_ENGINE_WAIT_NODES": "2",
            "CURATE_ENGINE_WAIT_S": "90",
            "CURATE_PREWARM": "0",
            "CURATE_AGENT_HEARTBEAT_S": "0.5",
            "CURATE_AGENT_HEARTBEAT_MISSES": "3",
            "CURATE_DLQ_DIR": str(tmp / "dlq"),
        }
    )
    if os.environ.get("CURATE_LOCKCHECK"):
        # spawned agents inherit the flag; give every process one report
        # dir so the sweep in _lockcheck_verdict sees them all
        (tmp / "lockcheck").mkdir()
        os.environ["CURATE_LOCKCHECK_REPORT"] = str(tmp / "lockcheck")

    from perfbench.traffic import video_corpus  # the benchmark's generator, seeded per video

    corpus = dict(width=1280, height=720, fps=24, scenes=2, scene_frames=48, distinct=3, n_videos=3, warm_videos=0)
    vids, _, _ = video_corpus.make_corpus(corpus, 0, tmp)
    print(f"soak: corpus of 3 videos at {vids}", flush=True)

    from cosmos_curate_tpu import chaos

    kill_plan = chaos.FaultPlan(
        rules=(
            chaos.FaultRule(
                site=chaos.SITE_AGENT_KILL, kind="crash", count=1,
                worker_re="^doomed-agent$",
            ),
        ),
        seed=13,
    ).to_json()

    # -- pass 1: unfaulted baseline ------------------------------------
    port = _free_port()
    out1 = tmp / "baseline"
    agents = [_spawn_agent(port, "agent-a"), _spawn_agent(port, "agent-b")]
    try:
        summary1, runner1 = _run_split(out1, vids, port, agents)
        assert summary1["num_clips"] > 0, summary1
        baseline = _clip_set(out1)
        assert baseline, "baseline produced no clip metas"
    finally:
        for a in agents:
            a.terminate()
        for a in agents:
            try:
                a.wait(timeout=10)
            except subprocess.TimeoutExpired:
                a.kill()

    # -- pass 2: kill one of two agents mid-run ------------------------
    port = _free_port()
    out2 = tmp / "faulted"
    agents = [
        _spawn_agent(port, "agent-a"),
        _spawn_agent(
            port, "doomed",
            {"CURATE_CHAOS": kill_plan, "CURATE_WORKER_ID": "doomed-agent"},
        ),
    ]
    try:
        summary2, runner2 = _run_split(out2, vids, port, agents)
        assert agents[1].poll() is not None, "chaos agent.kill never fired"

        # 1. same clip output set as the unfaulted run (uuid5 ids are
        # deterministic per video+span: node loss dropped NOTHING)
        faulted = _clip_set(out2)
        assert faulted == baseline, (
            f"clip sets diverged: missing={sorted(baseline - faulted)[:5]} "
            f"extra={sorted(faulted - baseline)[:5]}"
        )

        # 2. the death was declared and lineage reconstruction ran
        assert any(e["node"] == "doomed" for e in runner2.node_events), (
            runner2.node_events
        )
        assert runner2.objects_reconstructed > 0, (
            "node died but nothing was reconstructed"
        )

        # 3. zero dead-letters: recomputation, not data loss
        dead = sum(c["dead_lettered"] for c in runner2.stage_counts.values())
        assert dead == 0, f"dead-lettered batches: {runner2.stage_counts}"

        # 4. ONE connected trace, with node_events in the run report
        report = json.loads((out2 / "report" / "run_report.json").read_text())
        assert report["connected"] and len(report["trace_ids"]) == 1, (
            f"trace fragments: {report['trace_ids']}"
        )
        events = report.get("node_events") or {}
        assert events.get("objects_reconstructed", 0) > 0, events
        print(
            f"soak ok: {len(faulted)} clips match baseline, "
            f"{runner2.objects_reconstructed} object(s) reconstructed in "
            f"{runner2.reconstruction_seconds:.2f}s, 0 dead-letters, "
            f"1 connected trace; report: {out2 / 'report' / 'run_report.json'}",
            flush=True,
        )
        print(f"soak {_lockcheck_verdict(tmp)}", flush=True)
    finally:
        for a in agents:
            a.terminate()
        for a in agents:
            try:
                a.wait(timeout=10)
            except subprocess.TimeoutExpired:
                a.kill()
    return 0


if __name__ == "__main__":
    sys.exit(main())
