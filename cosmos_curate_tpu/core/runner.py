"""Runner abstraction + the in-process SequentialRunner.

Equivalent of the reference's ``RunnerInterface``/``XennaRunner``
(cosmos_curate/core/interfaces/runner_interface.py:37-183) and its test
``SequentialRunner`` (tests/utils/sequential_runner.py:27-69) — promoted here
to a first-class citizen because it is also the right way to run small local
jobs on a single host without the streaming engine.
"""

from __future__ import annotations

import abc
import os
import time

from cosmos_curate_tpu import chaos
from cosmos_curate_tpu.core.pipeline import PipelineSpec
from cosmos_curate_tpu.core.stage import NodeInfo, WorkerMetadata
from cosmos_curate_tpu.core.tasks import PipelineTask
from cosmos_curate_tpu.utils.logging import get_logger

logger = get_logger(__name__)


class RunnerInterface(abc.ABC):
    """Executes a ``PipelineSpec``; returns last-stage outputs (or None)."""

    @abc.abstractmethod
    def run(self, spec: PipelineSpec) -> list[PipelineTask] | None: ...


class SequentialRunner(RunnerInterface):
    """Run every stage in-process, stage by stage, no parallelism.

    Exact lifecycle per stage: ``setup_on_node`` → ``setup`` →
    ``process_data`` over batches → ``destroy``. Honors ``batch_size`` and
    dynamic chunking (a stage may emit more or fewer tasks than it
    received). This is both the test harness and the minimal local runner.
    """

    def __init__(self, *, raise_on_error: bool = True) -> None:
        self.raise_on_error = raise_on_error
        # stage name -> wall seconds of the last run (the run report reads
        # this; observability/flight_recorder.py)
        self.stage_times: dict[str, float] = {}
        # DLQ parity with the engine: permanently dropped batches persist
        # (engine/dead_letter.py); lazy — a clean run creates nothing
        self.dlq = None
        self.dead_lettered = 0

    def run(self, spec: PipelineSpec) -> list[PipelineTask] | None:
        from cosmos_curate_tpu.observability.live_status import LiveStatusPublisher
        from cosmos_curate_tpu.observability.tracing import traced_span

        # fresh run-scoped DLQ state (run_id is fixed at DLQ construction,
        # so reusing one across runs would file run 2's drops under run 1)
        self.dlq = None
        self.dead_lettered = 0
        node = NodeInfo(node_id="local")
        tasks: list[PipelineTask] = list(spec.input_data)
        # live ops plane: snapshots publish between batches (this runner is
        # single-threaded, so a hung batch shows as a STALE snapshot whose
        # last entry is the in-flight batch — `top` flags the staleness)
        self._publisher = LiveStatusPublisher.from_env(runner="sequential")
        self._live_stages: dict[str, dict] = {
            s.stage.name: {"queue_depth": 0, "workers": 0, "completed": 0,
                           "errored": 0, "dead_lettered": 0, "busy_frac": 0.0,
                           "inflight": []}
            for s in spec.stages
        }
        try:
            with traced_span(
                "pipeline.run", runner="sequential", stages=len(spec.stages)
            ):
                for stage_spec in spec.stages:
                    tasks = self._run_stage(stage_spec, node, tasks)
        finally:
            if self._publisher is not None:
                try:
                    self._publisher.finalize({"stages": dict(self._live_stages)})
                except Exception:
                    logger.exception("final live-status publish failed")
        return tasks if spec.config.return_last_stage_outputs else None

    def _run_stage(self, stage_spec, node, tasks: list) -> list:
        from cosmos_curate_tpu.observability.tracing import traced_span

        stage = stage_spec.stage
        meta = WorkerMetadata(
            worker_id=f"{stage.name}-seq-0",
            stage_name=stage.name,
            node=node,
            allocation=stage.resources,
        )
        t0 = time.monotonic()
        out: list[PipelineTask] = []
        with traced_span(f"stage.{stage.name}", stage=stage.name):
            with traced_span(f"stage.{stage.name}.setup"):
                stage.setup_on_node(node, meta)
                stage.setup(meta)
            bs = max(1, stage.batch_size)
            live = getattr(self, "_live_stages", {}).get(stage.name)
            try:
                for i in range(0, len(tasks), bs):
                    batch = tasks[i : i + bs]
                    # per-BATCH baseline: dead_lettered is a run-global
                    # counter, so a drop in an earlier stage must not
                    # misclassify this stage's next success
                    dl_before = self.dead_lettered
                    if live is not None and self._publisher is not None:
                        live.update(
                            queue_depth=max(0, len(tasks) - i - len(batch)),
                            workers=1, busy_frac=1.0,
                            inflight=[{
                                "batch_id": i // bs, "age_s": 0.0, "attempt": 1,
                                "worker": f"{stage.name}-seq-0",
                            }],
                        )
                        self._publisher.maybe_publish(
                            lambda: {"stages": dict(self._live_stages)}
                        )
                    for attempt in range(max(1, stage_spec.num_run_attempts)):
                        try:
                            chaos.fire(chaos.SITE_WORKER_CRASH)  # kind=crash: os._exit
                            chaos.fire(chaos.SITE_WORKER_HANG)  # kind=hang: stuck batch
                            with traced_span(
                                f"stage.{stage.name}.process", batch_size=len(batch)
                            ):
                                result = stage.process_data(batch)
                            break
                        except Exception:
                            if attempt + 1 >= max(1, stage_spec.num_run_attempts):
                                if self.raise_on_error:
                                    raise
                                logger.exception(
                                    "stage %s failed on batch %d; dropping", stage.name, i
                                )
                                self._dead_letter(stage.name, i, batch, attempt + 1)
                                result = None
                    if live is not None:
                        live["inflight"] = []
                        live["busy_frac"] = 0.0
                        # a dropped batch bumped the run-global DLQ counter
                        # inside _dead_letter; everything else completed (a
                        # legit None result is a no-output success)
                        if self.dead_lettered > dl_before:
                            live["errored"] += 1
                            live["dead_lettered"] += self.dead_lettered - dl_before
                        else:
                            live["completed"] += 1
                    if result is None:
                        continue
                    if not isinstance(result, list):
                        raise TypeError(
                            f"stage {stage.name}.process_data must return "
                            f"list[PipelineTask] or None, got {type(result).__name__}"
                        )
                    out.extend(result)
            finally:
                stage.destroy()
        stage_s = time.monotonic() - t0
        self.stage_times[stage.name] = self.stage_times.get(stage.name, 0.0) + stage_s
        logger.info(
            "stage %s: %d -> %d tasks in %.2fs", stage.name, len(tasks), len(out), stage_s
        )
        return out

    def _dead_letter(self, stage_name: str, batch_id: int, tasks: list, attempts: int) -> None:
        """Persist a dropped batch to the durable DLQ — local runs get the
        same recoverability the streaming engine's drop path has. Never
        raises: DLQ failure degrades to the log-only drop above."""
        import traceback

        try:
            from cosmos_curate_tpu.engine.dead_letter import (
                DeadLetterQueue,
                record_exhausted_batch,
            )
        except ImportError:
            return
        if self.dlq is None:
            self.dlq = DeadLetterQueue()
        if record_exhausted_batch(
            self.dlq,
            stage_name=stage_name,
            batch_id=batch_id,
            tasks=tasks,
            attempts=attempts,
            error=traceback.format_exc(),
        ):
            self.dead_lettered += 1


def default_runner() -> RunnerInterface:
    """Production runner selection.

    ``CURATE_RUNNER=sequential|pipelined|engine`` forces a backend. Without
    the override: multi-host runs (a remote data plane is configured via
    ``CURATE_ENGINE_DRIVER_PORT``) use the streaming engine, whose process
    pools span node agents; single-host runs default to the
    ``PipelinedRunner`` — stage-overlapped thread pools that keep the device
    fed by host stages without the engine's worker-spawn overhead.
    """
    choice = os.environ.get("CURATE_RUNNER", "").strip().lower()
    known = ("", "auto", "sequential", "pipelined", "engine", "streaming", "map")
    if choice not in known:
        # a typo must not silently land on the multi-threaded default —
        # an operator forcing `sequential` to debug threading needs to
        # KNOW when the override didn't take
        raise ValueError(
            f"unknown CURATE_RUNNER={choice!r}; expected one of {known[1:]}"
        )
    if choice == "sequential":
        return SequentialRunner()
    if choice == "map":
        from cosmos_curate_tpu.core.map_runner import MapRunner

        return MapRunner()
    if choice in ("engine", "streaming") or (
        choice in ("", "auto") and os.environ.get("CURATE_ENGINE_DRIVER_PORT")
    ):
        try:
            from cosmos_curate_tpu.engine.runner import StreamingRunner
        except ImportError as e:
            # Only the engine itself being absent may degrade; a broken
            # engine module must surface, not silently lose throughput.
            if e.name is None or not e.name.startswith("cosmos_curate_tpu.engine"):
                raise
            logger.warning("streaming engine unavailable; using SequentialRunner")
            return SequentialRunner()
        return StreamingRunner()
    try:
        # the pipelined runner reuses the engine's autoscaler/metrics/DLQ,
        # so engine absence degrades it too
        from cosmos_curate_tpu.core.pipelined_runner import PipelinedRunner
    except ImportError as e:
        if e.name is None or not e.name.startswith(
            ("cosmos_curate_tpu.engine", "cosmos_curate_tpu.core.pipelined_runner")
        ):
            raise
        logger.warning("pipelined runner unavailable; using SequentialRunner")
        return SequentialRunner()
    # production semantics match the streaming engine: an exhausted batch is
    # dead-lettered and the run CONTINUES — one poison batch must not void
    # hours of curation. Tests wanting fail-fast construct the runner
    # directly (raise_on_error defaults to True there, like SequentialRunner).
    return PipelinedRunner(raise_on_error=False)
