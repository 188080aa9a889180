"""PipelinedRunner: stage-overlapped execution on one host.

The reference gets its throughput from stage-parallel actor pools that keep
every stage of the pipeline running concurrently (Cosmos-Xenna's streaming
engine, reference ARCHITECTURE.md:20-110); our ``SequentialRunner`` runs the
stages in lockstep, so the CPU decode/transcode stages sit idle while the
device embeds and vice versa. ``PipelinedRunner`` is the single-host middle
ground: every stage runs in its own worker-thread pool, connected by bounded
inter-stage queues with backpressure, so decode of video N+1 overlaps the
embedding of video N — without the worker-process spawn cost that makes the
streaming engine a poor fit for 1-2 core boxes.

Semantics shared with the other runners (tests/core/test_pipelined_runner.py
locks output-set equivalence against ``SequentialRunner``):

- lifecycle per stage: ``setup_on_node`` → ``setup`` exactly ONCE per stage
  (worker threads share the stage instance — the process-pool runners give
  each worker a private copy instead), ``process_data`` per batch,
  ``destroy`` exactly once when the stage drains or the run aborts;
- ``StageSpec.num_run_attempts`` retries a failing batch in place; an
  exhausted batch aborts the run (``raise_on_error=True``) or is dropped
  through the durable dead-letter queue (engine/dead_letter.py), exactly
  like the streaming engine's permanent-drop path;
- dynamic chunking: a stage may emit more or fewer tasks than it received;
- chaos sites ``worker.batch.crash``/``worker.batch.hang`` fire per batch
  attempt (chaos/harness.py), so fault-injection suites cover this runner.

Placement rules:

- **device stages** — any stage whose model pins dispatch
  (``ModelInterface.pin_to_single_worker``) or that requests TPU resources —
  get exactly ONE worker thread, so the jit/bucket state inside
  ``models/device_pipeline.py`` stays single-threaded;
- **CPU stages fan out** only when they declare ``thread_safe = True``
  (concurrent ``process_data`` on disjoint batches is safe). Pool sizes
  come from the same water-filling planner the streaming engine uses
  (engine/autoscaler.py), re-planned every ``replan_interval_s`` as
  throughput samples arrive — the balanced-throughput problem is identical,
  only the worker unit (thread vs process) differs.

Known limits, both documented engine caveats for in-process workers:
``batch_timeout_s`` is not enforced (threads cannot be killed), and chaos
``worker_re`` filters match the process-wide ``CURATE_WORKER_ID``, not
individual worker threads.
"""

from __future__ import annotations

import contextvars
import os
import threading
import time
import traceback
from collections import deque
from dataclasses import dataclass, field, replace

from cosmos_curate_tpu import chaos
from cosmos_curate_tpu.core.pipeline import PipelineSpec
from cosmos_curate_tpu.core.runner import RunnerInterface
from cosmos_curate_tpu.core.stage import NodeInfo, StageSpec, WorkerMetadata
from cosmos_curate_tpu.core.tasks import PipelineTask

# engine reuse is a hard dependency of this runner (the water-filling
# planner, the gauges, the durable DLQ); importing eagerly lets
# default_runner() degrade to SequentialRunner when the engine is absent
from cosmos_curate_tpu.engine.autoscaler import (
    Budget,
    StageScaleState,
    discover_tpu_chips,
    plan_allocation,
)
from cosmos_curate_tpu.engine.dead_letter import DeadLetterQueue, record_exhausted_batch
from cosmos_curate_tpu.engine.metrics import get_metrics
from cosmos_curate_tpu.utils.logging import get_logger

logger = get_logger(__name__)


class _TaskQueue:
    """Bounded task queue between adjacent stages.

    ``put_many`` blocks while the queue is at capacity (backpressure on the
    producer); ``get_batch`` assembles up to ``max_size`` tasks, lingering
    briefly for a fuller batch while the producer is still alive (fuller
    batches keep device bucket shapes warm). ``close()`` marks the producer
    done: once closed AND empty, ``get_batch`` returns None and the stage's
    workers exit.
    """

    def __init__(self, capacity: int) -> None:
        self.capacity = max(1, capacity)
        self._buf: deque = deque()
        self._cond = threading.Condition()
        self._closed = False

    def __len__(self) -> int:
        with self._cond:
            return len(self._buf)

    def set_capacity(self, capacity: int) -> None:
        with self._cond:
            self.capacity = max(1, capacity)
            self._cond.notify_all()

    def close(self) -> None:
        with self._cond:
            self._closed = True
            self._cond.notify_all()

    @property
    def drained(self) -> bool:
        """Producer done and nothing left to hand out."""
        with self._cond:
            return self._closed and not self._buf

    def put_many(self, tasks: list, should_stop) -> None:
        for t in tasks:
            with self._cond:
                while len(self._buf) >= self.capacity:
                    if should_stop():
                        return
                    self._cond.wait(0.05)
                self._buf.append(t)
                self._cond.notify_all()

    def get_batch(self, max_size: int, should_stop, linger_s: float) -> list | None:
        with self._cond:
            while True:
                if should_stop():
                    return None
                if self._buf:
                    break
                if self._closed:
                    return None
                self._cond.wait(0.05)
            batch = [self._buf.popleft()]
            deadline = time.monotonic() + linger_s
            while len(batch) < max_size:
                if self._buf:
                    batch.append(self._buf.popleft())
                    continue
                if self._closed or should_stop():
                    break
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    break
                self._cond.wait(min(remaining, 0.05))
            self._cond.notify_all()  # wake producers blocked on capacity
            return batch


@dataclass
class _Worker:
    meta: WorkerMetadata
    stop: threading.Event = field(default_factory=threading.Event)
    thread: threading.Thread | None = None


class _StageRuntime:
    """One stage's queue, thread pool, and shared bookkeeping."""

    def __init__(self, idx: int, spec: StageSpec, in_q: _TaskQueue, emit) -> None:
        self.idx = idx
        self.spec = spec
        self.stage = spec.stage
        self.in_q = in_q
        self.emit = emit  # callable(list[PipelineTask]) -> None
        self.workers: list[_Worker] = []
        self.lock = threading.Lock()
        # setup/destroy run exactly once per stage; the first worker thread
        # in claims setup, the rest block on the event
        self.setup_state = "pending"  # pending | running | ok | failed
        self.setup_done = threading.Event()
        self.destroyed = False
        self.finalized = False
        self.next_worker_idx = 0
        self.next_batch_id = 0
        # in-flight batches for the live ops plane: batch_id -> {started,
        # worker, attempt} (guarded by self.lock). A hung process_data is
        # visible here the whole time it hangs — the stuck_batch signal.
        self.inflight: dict[int, dict] = {}
        # accounting (guarded by self.lock)
        self.busy_s = 0.0
        self.samples: deque = deque(maxlen=256)  # (t_end, batch_seconds)
        self.dispatched = 0
        self.completed = 0
        self.errored = 0
        self.dead_lettered = 0
        # busy-fraction window state (main-loop tick only)
        self.tick_busy_s = 0.0
        self.tick_t = time.monotonic()

    def live_workers(self) -> list[_Worker]:
        return [
            w for w in self.workers
            if w.thread is not None and w.thread.is_alive() and not w.stop.is_set()
        ]

    def throughput_per_worker(self, window_s: float) -> float | None:
        """Batches/s one worker achieves (engine/pool.py's formula: the
        inverse mean batch duration over the recent window)."""
        now = time.monotonic()
        with self.lock:
            recent = [dur for (t, dur) in self.samples if t >= now - window_s]
        if not recent:
            return None
        mean_t = sum(recent) / len(recent)
        return 1.0 / mean_t if mean_t > 0 else None


_ABORTED = object()  # worker-loop sentinel: run is aborting, exit now


class PipelinedRunner(RunnerInterface):
    """Run all stages concurrently in thread pools on this host."""

    def __init__(
        self,
        *,
        raise_on_error: bool = True,
        replan_interval_s: float = 2.0,
        queue_capacity: int | None = None,
        batch_linger_s: float = 0.2,
        poll_interval_s: float = 0.02,
        thread_cap: int | None = None,
        metrics_port: int | None = None,
    ) -> None:
        self.raise_on_error = raise_on_error
        self.replan_interval_s = replan_interval_s
        self.queue_capacity = queue_capacity  # None = streaming-spec formula
        self.batch_linger_s = batch_linger_s
        self.poll_interval_s = poll_interval_s
        self.thread_cap = thread_cap or max(4, (os.cpu_count() or 1) * 2)
        self.metrics = get_metrics(metrics_port)
        # stage name -> summed process_data seconds (MFU accounting parity
        # with StreamingRunner's busy seconds / SequentialRunner's wall)
        self.stage_times: dict[str, float] = {}
        self.stage_counts: dict[str, dict] = {}
        self.pipeline_wall_s = 0.0
        # busy seconds of the LAST run only — stage_times accumulates across
        # runs (SequentialRunner parity), which would fabricate overlap
        self._last_run_busy_s = 0.0
        self.dlq = None
        self._abort = threading.Event()
        self._abort_lock = threading.Lock()
        self._abort_exc: BaseException | None = None

    # ------------------------------------------------------------------
    @property
    def overlap_frac(self) -> float:
        """Fraction of total host stage work hidden behind other stages:
        ``1 - wall / sum(stage busy seconds)``, clamped at 0. A strictly
        sequential execution scores 0 (wall == summed busy); a perfectly
        overlapped one approaches ``1 - max/sum``. This is the number the run
        report carries as ``pipeline_overlap_frac``. Computed over the LAST ``run()``
        only (wall and busy from the same run)."""
        busy = self._last_run_busy_s
        if busy <= 0 or self.pipeline_wall_s <= 0:
            return 0.0
        return max(0.0, 1.0 - self.pipeline_wall_s / busy)

    # ------------------------------------------------------------------
    def run(self, spec: PipelineSpec) -> list[PipelineTask] | None:
        from cosmos_curate_tpu.observability.tracing import traced_span

        if not spec.stages:
            return list(spec.input_data) if spec.config.return_last_stage_outputs else None
        # the run-root span rides the contextvar stack; worker threads are
        # started under contextvars.copy_context() (see _start_worker), so
        # their batch spans parent onto it across the thread-pool hop
        with traced_span(
            "pipeline.run", runner="pipelined", stages=len(spec.stages)
        ):
            return self._run_pipelined(spec)

    def _run_pipelined(self, spec: PipelineSpec) -> list[PipelineTask] | None:
        t_start = time.monotonic()
        self._abort.clear()
        self._abort_exc = None
        self.dlq = DeadLetterQueue()  # lazy: writes nothing unless a drop happens
        cfg = spec.config
        node = NodeInfo(
            node_id="local",
            num_cpus=cfg.num_cpus or float(os.cpu_count() or 1),
            num_tpu_chips=discover_tpu_chips(cfg, spec.stages),
        )
        self._node = node

        outputs: list[PipelineTask] = []
        outputs_lock = threading.Lock()

        def collect(tasks: list) -> None:
            if not cfg.return_last_stage_outputs:
                return
            with outputs_lock:
                outputs.extend(tasks)

        # stage i's input queue; queue 0 is pre-seeded and closed (inputs
        # are already materialized in RAM — backpressure matters BETWEEN
        # stages, where new payloads get created)
        queues = [
            _TaskQueue(self._queue_capacity(s, 1, cfg)) for s in spec.stages
        ]
        queues[0].set_capacity(max(queues[0].capacity, len(spec.input_data)))
        runtimes: list[_StageRuntime] = []
        for i, stage_spec in enumerate(spec.stages):
            if i + 1 < len(spec.stages):
                nxt = queues[i + 1]
                emit = lambda tasks, q=nxt: q.put_many(tasks, self._abort.is_set)
            else:
                emit = collect
            runtimes.append(_StageRuntime(i, stage_spec, queues[i], emit))
        queues[0].put_many(list(spec.input_data), self._abort.is_set)
        queues[0].close()

        budget = self._budget(node)
        self._apply_allocation(runtimes, self._plan(runtimes, budget), cfg)

        # live ops plane: snapshots + stall detection, on when run_split
        # (or an operator) exported CURATE_LIVE_STATUS_DIR; zero overhead
        # otherwise. Published from THIS loop — never the worker threads.
        from cosmos_curate_tpu.observability.live_status import LiveStatusPublisher

        publisher = LiveStatusPublisher.from_env(runner="pipelined")

        last_replan = time.monotonic()
        try:
            while not self._abort.is_set():
                for rt in runtimes:
                    if rt.finalized or not rt.in_q.drained:
                        continue
                    if any(w.thread is not None and w.thread.is_alive() for w in rt.workers):
                        continue
                    self._finalize_stage(rt)
                    if rt.idx + 1 < len(queues):
                        queues[rt.idx + 1].close()
                if runtimes[-1].finalized:
                    break
                now = time.monotonic()
                if now - last_replan >= self.replan_interval_s:
                    self._apply_allocation(runtimes, self._plan(runtimes, budget), cfg)
                    self._export_flow(runtimes)
                    last_replan = now
                if publisher is not None:
                    publisher.maybe_publish(
                        lambda: self._build_live_snapshot(runtimes)
                    )
                time.sleep(self.poll_interval_s)
        finally:
            # ANY exit path — normal, abort, or a foreign exception like
            # KeyboardInterrupt in the loop above — must unblock every
            # worker, or the joins below stall 30s per thread. close() is
            # idempotent; stop flags cover workers mid-linger.
            for q in queues:
                q.close()
            for rt in runtimes:
                for w in rt.workers:
                    w.stop.set()
            for rt in runtimes:
                for w in rt.workers:
                    if w.thread is not None:
                        w.thread.join(timeout=30.0)
            for rt in runtimes:
                if rt.finalized:
                    continue
                if any(
                    w.thread is not None and w.thread.is_alive() for w in rt.workers
                ):
                    # a wedged worker (cold compile, stuck decode) outlived
                    # the join grace: leaking its state beats racing
                    # destroy() against a live process_data on the same
                    # shared stage instance
                    logger.error(
                        "stage %s: worker still running after abort grace; "
                        "skipping destroy()", rt.stage.name,
                    )
                    rt.finalized = True
                    continue
                self._finalize_stage(rt)
            self.pipeline_wall_s = time.monotonic() - t_start
            self._export_flow(runtimes)  # final gauge tick (short runs too)
            self._record_run_stats(runtimes)
            if publisher is not None:
                try:
                    publisher.finalize(self._build_live_snapshot(runtimes))
                except Exception:
                    logger.exception("final live-status publish failed")

        if self._abort_exc is not None:
            raise self._abort_exc
        return outputs if cfg.return_last_stage_outputs else None

    # ------------------------------------------------------------------
    # worker side
    def _worker_loop(self, rt: _StageRuntime, w: _Worker) -> None:
        if not self._ensure_setup(rt, w):
            return
        bs = max(1, rt.stage.batch_size)
        attempts = max(1, rt.spec.num_run_attempts)

        def should_stop() -> bool:
            return self._abort.is_set() or w.stop.is_set()

        while True:
            batch = rt.in_q.get_batch(bs, should_stop, self.batch_linger_s)
            if batch is None:
                return
            with rt.lock:
                rt.dispatched += 1
                batch_id = rt.next_batch_id
                rt.next_batch_id += 1
            result = self._run_batch(rt, batch, batch_id, attempts, w.meta.worker_id)
            if result is _ABORTED:
                return
            if result:
                rt.emit(result)

    def _run_batch(
        self, rt: _StageRuntime, batch: list, batch_id: int, attempts: int,
        worker_id: str = "",
    ):
        from cosmos_curate_tpu.observability.stage_timer import record_stage_busy
        from cosmos_curate_tpu.observability.tracing import traced_span

        stage = rt.stage
        for attempt in range(attempts):
            t0 = time.monotonic()
            with rt.lock:
                # live-status visibility: registered BEFORE the chaos sites
                # and process_data, so a hang shows as an aging in-flight
                # batch from its first stuck second
                rt.inflight[batch_id] = {
                    "started": t0, "worker": worker_id, "attempt": attempt + 1,
                }
            try:
                chaos.fire(chaos.SITE_WORKER_CRASH)  # kind=crash: os._exit
                chaos.fire(chaos.SITE_WORKER_HANG)  # kind=hang: stuck batch
                with traced_span(
                    f"stage.{stage.name}.process", batch_size=len(batch)
                ):
                    result = stage.process_data(batch)
                if result is not None and not isinstance(result, list):
                    # contract violation, not a batch failure: deterministic
                    # stage bugs must surface (SequentialRunner parity —
                    # raises regardless of raise_on_error), never burn
                    # retries or masquerade as a dead-lettered batch
                    self._trigger_abort(
                        TypeError(
                            f"stage {stage.name}.process_data must return "
                            f"list[PipelineTask] or None, got {type(result).__name__}"
                        )
                    )
                    return _ABORTED
                elapsed = time.monotonic() - t0
                with rt.lock:
                    rt.busy_s += elapsed
                    rt.samples.append((time.monotonic(), elapsed))
                    rt.completed += 1
                record_stage_busy(stage.name, elapsed)
                self.metrics.observe_result(
                    stage.name, elapsed, 0.0, len(result or [])
                )
                return result or []
            except Exception as e:
                with rt.lock:
                    rt.busy_s += time.monotonic() - t0
                self.metrics.observe_error(stage.name)
                if attempt + 1 < attempts:
                    logger.warning(
                        "stage %s batch %d failed (attempt %d/%d), retrying: %s",
                        stage.name, batch_id, attempt + 1, attempts, e,
                    )
                    continue
                if self.raise_on_error:
                    self._trigger_abort(e)
                    return _ABORTED
                with rt.lock:
                    rt.errored += 1
                logger.exception(
                    "stage %s batch %d failed permanently; dropping %d tasks",
                    stage.name, batch_id, len(batch),
                )
                self._dead_letter(rt, batch_id, batch, attempts)
                return []
            finally:
                with rt.lock:
                    rt.inflight.pop(batch_id, None)
        return []  # unreachable; attempts >= 1

    def _ensure_setup(self, rt: _StageRuntime, w: _Worker) -> bool:
        claim = False
        with rt.lock:
            if rt.setup_state == "pending":
                rt.setup_state = "running"
                claim = True
        if claim:
            from cosmos_curate_tpu.observability.tracing import traced_span

            try:
                with traced_span(f"stage.{rt.stage.name}.setup"):
                    rt.stage.setup_on_node(self._node, w.meta)
                    rt.stage.setup(w.meta)
                rt.setup_state = "ok"
                return True
            except Exception as e:
                rt.setup_state = "failed"
                self._trigger_abort(e)
                return False
            finally:
                rt.setup_done.set()
        while not rt.setup_done.wait(0.1):
            if self._abort.is_set():
                return False
        return rt.setup_state == "ok"

    def _trigger_abort(self, exc: BaseException) -> None:
        with self._abort_lock:
            if self._abort_exc is None:  # first failure wins
                self._abort_exc = exc
        self._abort.set()

    def _dead_letter(self, rt: _StageRuntime, batch_id: int, tasks: list, attempts: int) -> None:
        """Persist a permanently-dropped batch like the streaming engine
        does. Never raises — DLQ failure degrades to the log-only drop."""
        if record_exhausted_batch(
            self.dlq,
            stage_name=rt.stage.name,
            batch_id=batch_id,
            tasks=tasks,
            attempts=attempts,
            error=traceback.format_exc(),
        ):
            with rt.lock:
                rt.dead_lettered += 1

    # ------------------------------------------------------------------
    # planning / scaling
    def _budget(self, node: NodeInfo):
        return Budget(cpus=node.num_cpus, tpus=float(node.num_tpu_chips))

    def _plan(self, runtimes: list[_StageRuntime], budget) -> list[int]:
        states = []
        for rt in runtimes:
            spec = rt.spec
            if _single_worker_only(spec.stage):
                spec = replace(spec, num_workers=1)
            elif spec.num_workers is None:
                cap = spec.max_workers
                spec = replace(
                    spec,
                    max_workers=min(cap, self.thread_cap) if cap else self.thread_cap,
                )
            states.append(
                StageScaleState(
                    spec=spec,
                    current_workers=len(rt.live_workers()),
                    throughput_per_worker=rt.throughput_per_worker(window_s=60.0),
                    queued=len(rt.in_q),
                )
            )
        return plan_allocation(states, budget)

    def _apply_allocation(self, runtimes: list[_StageRuntime], targets: list[int], cfg) -> None:
        for rt, target in zip(runtimes, targets):
            rt.workers = [
                w for w in rt.workers if w.thread is not None and w.thread.is_alive()
            ]
            if rt.finalized:
                continue
            if rt.in_q.drained and rt.setup_state != "pending":
                # stage is winding down — no new workers. A never-started
                # stage (empty input) still gets one below, so the
                # setup→destroy lifecycle runs for every stage, exactly as
                # the sequential runner guarantees.
                continue
            target = max(1, target)
            live = rt.live_workers()
            for _ in range(target - len(live)):
                self._start_worker(rt)
            if len(live) > target:
                for w in live[target:]:  # scale down: drain-and-exit
                    w.stop.set()
            rt.in_q.set_capacity(self._queue_capacity(rt.spec, max(1, target), cfg))

    def _queue_capacity(self, spec: StageSpec, workers: int, cfg) -> int:
        if self.queue_capacity is not None:
            return self.queue_capacity
        s = cfg.streaming
        return max(s.max_queued_lower_bound, int(s.max_queued_multiplier * workers))

    def _start_worker(self, rt: _StageRuntime) -> None:
        widx = rt.next_worker_idx
        rt.next_worker_idx += 1
        meta = WorkerMetadata(
            worker_id=f"{rt.stage.name}-pipe-{widx}",
            stage_name=rt.stage.name,
            node=self._node,
            allocation=rt.stage.resources,
        )
        w = _Worker(meta=meta)
        # carry the caller's context (the run-root trace span) into the
        # worker thread: contextvars survive this hop, threading.local
        # would not
        ctx = contextvars.copy_context()
        w.thread = threading.Thread(
            target=ctx.run,
            args=(self._worker_loop, rt, w),
            daemon=True,
            name=meta.worker_id,
        )
        rt.workers.append(w)
        w.thread.start()

    # ------------------------------------------------------------------
    def _finalize_stage(self, rt: _StageRuntime) -> None:
        if rt.setup_state == "ok" and not rt.destroyed:
            rt.destroyed = True
            try:
                rt.stage.destroy()
            except Exception:
                logger.exception("stage %s destroy failed", rt.stage.name)
        rt.finalized = True

    def _export_flow(self, runtimes: list[_StageRuntime]) -> None:
        """Per-stage queue-depth and busy-fraction gauges, one tick."""
        from cosmos_curate_tpu.observability.stage_timer import record_stage_flow

        now = time.monotonic()
        for rt in runtimes:
            workers = len(rt.live_workers())
            with rt.lock:
                busy = rt.busy_s
            dt = now - rt.tick_t
            window_busy = busy - rt.tick_busy_s
            rt.tick_busy_s = busy
            rt.tick_t = now
            frac = (
                min(1.0, window_busy / (dt * max(1, workers))) if dt > 0 else 0.0
            )
            record_stage_flow(
                rt.stage.name,
                queue_depth=len(rt.in_q),
                busy_frac=frac,
                workers=workers,
            )

    def _build_live_snapshot(self, runtimes: list[_StageRuntime]) -> dict:
        """One live-status snapshot (observability/live_status.py) from
        state the runner already keeps — counters, the throughput sample
        window, and the in-flight registry. Bounded and lock-brief."""
        from cosmos_curate_tpu.observability.live_status import (
            MAX_INFLIGHT_PER_STAGE,
        )

        now = time.monotonic()
        stages: dict[str, dict] = {}
        for rt in runtimes:
            workers = len(rt.live_workers())
            with rt.lock:
                inflight = sorted(
                    rt.inflight.items(), key=lambda kv: kv[1]["started"]
                )[:MAX_INFLIGHT_PER_STAGE]
                durs = sorted(d for (_t, d) in rt.samples)
                busy = rt.busy_s
                counts = (rt.dispatched, rt.completed, rt.errored, rt.dead_lettered)
            # busy fraction over the window since the last replan tick —
            # read-only against the tick state _export_flow owns
            dt = now - rt.tick_t
            frac = (
                min(1.0, (busy - rt.tick_busy_s) / (dt * max(1, workers)))
                if dt > 0
                else 0.0
            )
            stages[rt.stage.name] = {
                "queue_depth": len(rt.in_q),
                "busy_frac": round(frac, 4),
                "workers": workers,
                "dispatched": counts[0],
                "completed": counts[1],
                "errored": counts[2],
                "dead_lettered": counts[3],
                "finished": rt.finalized,
                "p50_s": round(durs[len(durs) // 2], 4) if durs else 0.0,
                "p99_s": (
                    round(durs[min(len(durs) - 1, int(len(durs) * 0.99))], 4)
                    if durs
                    else 0.0
                ),
                "inflight": [
                    {
                        "batch_id": bid,
                        "age_s": round(now - info["started"], 3),
                        "attempt": info.get("attempt", 1),
                        "worker": info.get("worker", ""),
                    }
                    for bid, info in inflight
                ],
            }
        return {"stages": stages}

    def _record_run_stats(self, runtimes: list[_StageRuntime]) -> None:
        self.stage_counts = {}
        self._last_run_busy_s = 0.0
        for rt in runtimes:
            with rt.lock:
                self._last_run_busy_s += rt.busy_s
                self.stage_times[rt.stage.name] = (
                    self.stage_times.get(rt.stage.name, 0.0) + rt.busy_s
                )
                self.stage_counts[rt.stage.name] = {
                    "dispatched": rt.dispatched,
                    "completed": rt.completed,
                    "errored": rt.errored,
                    "dead_lettered": rt.dead_lettered,
                    "workers": rt.next_worker_idx,
                }
            logger.info(
                "stage %s: %d dispatched, %d completed, %d errored, "
                "%d dead-lettered (%.2fs busy, %d workers)",
                rt.stage.name,
                self.stage_counts[rt.stage.name]["dispatched"],
                self.stage_counts[rt.stage.name]["completed"],
                self.stage_counts[rt.stage.name]["errored"],
                self.stage_counts[rt.stage.name]["dead_lettered"],
                rt.busy_s,
                rt.next_worker_idx,
            )
        # export the stage-overlap headline as a real gauge (bench used to
        # be the only reader of this number)
        self.metrics.set_overlap_frac(self.overlap_frac)
        if self.dlq is not None and self.dlq.recorded:
            logger.error(
                "%d dropped batch(es) persisted to the dead-letter queue: "
                "%s — inspect with `cosmos-curate-tpu dlq list`",
                self.dlq.recorded, self.dlq.run_dir,
            )


def _single_worker_only(stage) -> bool:
    """Device stages (pinned model dispatch or TPU resources) and stages
    not annotated ``thread_safe`` run with exactly one worker thread."""
    if stage.resources.uses_tpu:
        return True
    model = stage.model
    if model is not None and getattr(model, "pin_to_single_worker", True):
        return True
    return not getattr(stage, "thread_safe", False)
