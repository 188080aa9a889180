"""Shared async device pipeline for all model stages.

Every model stage used to run the same synchronous loop: build a host
batch, ``jax.device_put`` (implicit), compute under jit, and immediately
block on ``np.asarray`` readback. That serializes four engines that can
run concurrently — host batch prep, the H2D transfer engine, the MXU, and
D2H readback.

``DevicePipeline`` is the one sanctioned dispatch point (the sync-readback
lint rule keeps inline ``np.asarray(jit_fn(...))`` from creeping back):

- **micro-batching**: a shape-grouped host batch is split into fixed
  power-of-two bucket micro-batches (``plan_micro_batches``, reusing the
  ``batching`` pow2 discipline), so one logical batch becomes several
  dispatches that can overlap instead of one monolithic call;
- **double buffering**: JAX dispatch is asynchronous, so submitting
  micro-batch k+1 starts its H2D transfer while k computes. A bounded
  in-flight window (default 2) applies backpressure by settling the
  oldest dispatch — the host-level analogue of the kernel-level DMA
  double buffering in the Pallas guide;
- **deferred readback**: readback is decoupled from dispatch — a result
  is read back when its dispatch settles (compute done; pure D2H that
  overlaps the compute of later batches) and handed out in submission
  order at drain. Device memory stays bounded at the in-flight window —
  settled results live on the host, not in HBM;
- **donation**: on backends with buffer donation (TPU/GPU) the data
  arguments are donated to cut HBM churn; on CPU the knob degrades to a
  no-op (``donate_kwargs`` returns nothing);
- **compile cache**: constructing a pipeline enables the persistent XLA
  compilation cache (``CURATE_COMPILE_CACHE`` knob, utils/jax_cache.py),
  so bucket-shape compiles are paid once per machine, not per process.

Per-dispatch H2D/compute/readback/gap timings flow through
``observability.stage_timer.record_dispatch`` so the overlap is measured,
not assumed.
"""

from __future__ import annotations

import os
import time
import warnings
from dataclasses import dataclass
from typing import Any, Callable

import jax
import numpy as np

from cosmos_curate_tpu.models.batching import next_pow2, pad_to
from cosmos_curate_tpu.observability.stage_timer import DispatchRecord, record_dispatch

MICRO_BATCH_ENV = "CURATE_MICRO_BATCH"
DEFAULT_MICRO_BATCH = 32
DEFAULT_IN_FLIGHT = 2

_DONATABLE_BACKENDS = ("tpu", "gpu", "cuda", "rocm")


def donation_supported() -> bool:
    """Buffer donation is implemented on TPU/GPU; on CPU jax ignores it
    with a per-compile warning, so we gate instead of spamming."""
    try:
        return jax.default_backend() in _DONATABLE_BACKENDS
    except Exception:
        return False


_DONATION_WARNING_FILTERED = False


def donate_kwargs(*argnums: int) -> dict:
    """``jax.jit`` kwargs donating ``argnums`` on supported backends, {}
    on CPU (the donation fallback path). Most stage inputs (uint8 frames)
    cannot alias their f32 outputs, so XLA may still decline the alias —
    donation then only releases the input buffer early; the 'not usable'
    warning for that case is noise and is filtered once per process."""
    global _DONATION_WARNING_FILTERED
    if not donation_supported():
        return {}
    if not _DONATION_WARNING_FILTERED:
        # Process-global by necessity: the warning fires at compile time
        # deep inside jax, so there is no call site of ours to scope a
        # catch_warnings around. The message-exact match keeps every other
        # donation diagnostic (wrong argnums, aliasing bugs) audible.
        warnings.filterwarnings(
            "ignore", message="Some donated buffers were not usable"
        )
        _DONATION_WARNING_FILTERED = True
    return {"donate_argnums": argnums}


def micro_batch_cap(override: int | None = None) -> int:
    """Micro-batch bucket cap: pow2, env-tunable via CURATE_MICRO_BATCH.
    A non-pow2 value rounds DOWN — the cap is an operator-set ceiling on
    per-dispatch device memory, which rounding up would exceed."""
    if override is not None:
        cap = override
    else:
        cap = int(os.environ.get(MICRO_BATCH_ENV, DEFAULT_MICRO_BATCH))
    if cap < 1:
        raise ValueError(f"micro-batch cap must be >= 1, got {cap}")
    return cap if cap & (cap - 1) == 0 else 1 << (cap.bit_length() - 1)


def plan_micro_batches(n: int, cap: int) -> list[tuple[int, int, int]]:
    """Split a batch of ``n`` rows into (start, stop, padded_size) bucket
    micro-batches: full ``cap``-sized chunks, then one remainder padded to
    its next power of two. A batch at or under the cap produces exactly
    the single pow2 bucket the old ``pad_batch`` path compiled, so the
    compiled-shape set (and any warmup that used it) carries over."""
    if n <= 0:
        return []
    plan: list[tuple[int, int, int]] = []
    start = 0
    while n - start > cap:
        plan.append((start, start + cap, cap))
        start += cap
    rest = n - start
    plan.append((start, n, min(next_pow2(rest), cap)))
    return plan


@dataclass
class _InFlight:
    result: Any  # device array or pytree of device arrays; None once read back
    n_valid: int | None
    rows: int
    padded_rows: int
    h2d_s: float
    dispatch_t: float
    postprocess: Callable[[Any], Any] | None = None
    done_t: float | None = None  # set when compute completion is observed
    host: Any = None  # host (numpy) result once read back
    d2h_s: float = 0.0


class DevicePipeline:
    """Micro-batched asynchronous dispatcher over one jitted callable.

    ``fn`` is called as ``fn(*args)`` — typically ``(params, batch)`` but
    any mix of array and non-array leading arguments works (np.ndarray
    args are explicitly ``device_put``; everything else, e.g. an already
    device-resident param pytree or a static int, passes through).

    Not thread-safe: each stage worker owns its own instance (the jitted
    ``fn`` itself is shared across instances by the models' lru-cached
    constructors, so compiles are still paid once).
    """

    def __init__(
        self,
        name: str,
        fn: Callable[..., Any],
        *,
        micro_batch: int | None = None,
        in_flight: int = DEFAULT_IN_FLIGHT,
    ) -> None:
        self.name = name
        self._fn = fn
        self._cap = micro_batch_cap(micro_batch)
        self._depth = max(1, in_flight)
        self._pending: list[_InFlight] = []
        self._settled: list[_InFlight] = []
        # first touch of any model path: make the compile-cache knob real
        from cosmos_curate_tpu.utils.jax_cache import enable_persistent_cache

        enable_persistent_cache()

    # -- core ---------------------------------------------------------------

    def submit(
        self,
        *args: Any,
        n_valid: int | None = None,
        rows: int | None = None,
        postprocess: Callable[[Any], Any] | None = None,
    ) -> None:
        """Dispatch one pre-shaped micro-batch; returns immediately.

        ``n_valid`` trims array results to their first n rows at drain
        (None = no trim — e.g. scalar outputs). ``postprocess`` runs on
        the host arrays at drain, in submission order.

        ANY failure (transfer, backpressure settle, dispatch) aborts the
        whole pipeline before propagating: earlier submissions' results are
        lost, but a caller that catches the error and keeps going can never
        pair leftover results with the wrong later submissions."""
        try:
            t0 = time.monotonic()
            dev = [
                jax.device_put(a) if isinstance(a, np.ndarray) else a for a in args
            ]
            t1 = time.monotonic()
            # backpressure: bounded in-flight window — wait on the oldest
            # dispatch's COMPUTE (block_until_ready holds no readback),
            # keeping at most `depth` micro-batches of activations on device
            while len(self._pending) >= self._depth:
                self._settle_oldest()
            result = self._fn(*dev)
        except Exception:
            self.abort()
            raise
        dispatch_t = time.monotonic()
        padded = 0
        for a in args:
            if isinstance(a, np.ndarray) and a.ndim >= 1:
                padded = int(a.shape[0])
                break
        self._pending.append(
            _InFlight(
                result=result,
                n_valid=n_valid,
                rows=rows if rows is not None else (n_valid or padded),
                padded_rows=padded,
                h2d_s=t1 - t0,
                dispatch_t=dispatch_t,
                postprocess=postprocess,
            )
        )

    def abort(self) -> None:
        """Drop ALL in-flight and settled work. Called internally on any
        settle/readback failure so a caller that catches the error resumes
        with an empty pipeline — losing that burst's results is recoverable
        (the stages mark the affected clips errored); silently pairing the
        survivors with the WRONG submissions on the next drain is not."""
        self._pending.clear()
        self._settled.clear()

    def _settle_oldest(self) -> None:
        """Wait for the oldest dispatch's compute, then read it back.

        The readback happens HERE, not at drain: a settled-but-unread
        result would pin its device buffers until the drain, so a long
        submit burst (the SR window loop) would hold every output in HBM
        at once. Reading back a finished result is pure D2H — it overlaps
        the compute of the still-pending dispatches, and device memory
        stays bounded at the in-flight window."""
        inf = self._pending.pop(0)
        try:
            jax.block_until_ready(inf.result)
            inf.done_t = time.monotonic()
            inf.host = jax.tree_util.tree_map(np.asarray, inf.result)
        except Exception:
            self.abort()
            raise
        inf.d2h_s = time.monotonic() - inf.done_t
        inf.result = None  # release the device buffers
        self._settled.append(inf)

    def drain(self) -> list[Any]:
        """Resolve everything submitted since the last drain, in submission
        order, as host (numpy) values — trimmed to ``n_valid`` and passed
        through ``postprocess`` when given. Settle and readback interleave:
        the D2H of batch k runs while batches k+1.. still compute. Records
        per-dispatch timings. On ANY failure the pipeline aborts (state
        fully cleared) before the exception propagates."""
        from cosmos_curate_tpu.observability.tracing import traced_span

        # take ownership up front: a failure partway must not leave stale
        # results behind to misalign the NEXT drain's zip
        burst = self._settled + self._pending
        self._settled, self._pending = [], []
        out: list[Any] = []
        if not burst:
            return out
        with traced_span(
            f"device.{self.name}.drain",
            dispatches=len(burst),
            rows=sum(inf.rows for inf in burst),
        ):
            out = self._drain_burst(burst)
        return out

    def _drain_burst(self, burst: list) -> list[Any]:
        # gap accounting is local to this submit..drain burst: carrying it
        # across drains would book unrelated stage work (decode, IO between
        # process_data calls) as device idle
        out: list[Any] = []
        last_done: float | None = None
        try:
            for inf in burst:
                if inf.done_t is None:
                    jax.block_until_ready(inf.result)
                    inf.done_t = time.monotonic()
                gap = 0.0
                if last_done is not None:
                    # device idle = it finished the previous batch before
                    # this one was even dispatched; 0 when the next dispatch
                    # was already queued (the overlap working as intended)
                    gap = max(0.0, inf.dispatch_t - last_done)
                compute_start = (
                    inf.dispatch_t if last_done is None else max(inf.dispatch_t, last_done)
                )
                compute_s = max(0.0, inf.done_t - compute_start)
                last_done = inf.done_t
                if inf.host is not None:
                    host, d2h_s = inf.host, inf.d2h_s  # read back at settle
                else:
                    t0 = time.monotonic()
                    host = jax.tree_util.tree_map(np.asarray, inf.result)
                    d2h_s = time.monotonic() - t0
                if inf.n_valid is not None:
                    host = jax.tree_util.tree_map(
                        lambda a, n=inf.n_valid: a[:n] if getattr(a, "ndim", 0) >= 1 else a,
                        host,
                    )
                if inf.postprocess is not None:
                    host = inf.postprocess(host)
                record_dispatch(
                    self.name,
                    DispatchRecord(
                        h2d_s=inf.h2d_s,
                        compute_s=compute_s,
                        d2h_s=d2h_s,
                        gap_s=gap,
                        rows=inf.rows,
                        padded_rows=inf.padded_rows,
                    ),
                )
                out.append(host)
        except Exception:
            self.abort()
            raise
        return out

    @property
    def pending(self) -> int:
        return len(self._pending) + len(self._settled)

    # -- convenience --------------------------------------------------------

    def track(self) -> "SubmissionTracker":
        return SubmissionTracker(self)

    def run(self, params: Any, *arrays: np.ndarray) -> np.ndarray:
        """The full pipelined replacement for ``np.asarray(fn(params,
        padded))[:n]``: split ``arrays`` (shared leading dim) into bucket
        micro-batches, pad each to its bucket, dispatch all, drain, and
        concatenate the valid rows back in order.

        Must not be interleaved with in-flight ``submit`` work on the same
        pipeline (drain resolves everything)."""
        if self.pending:
            raise RuntimeError("run() with submissions in flight; drain() first")
        n = int(arrays[0].shape[0])
        for a in arrays[1:]:
            if a.shape[0] != n:
                # a shorter array would silently pad with repeated rows —
                # plausible-looking wrong results (same hardening class as
                # parallel.sharding.shard_batch)
                raise ValueError(
                    f"run() arrays disagree on leading dim: {n} vs {a.shape[0]}"
                )
        if n == 0:
            # preserve the sync path's empty-batch contract (shape/dtype
            # from an actual zero-row dispatch)
            return np.asarray(self._fn(params, *arrays))
        for start, stop, target in plan_micro_batches(n, self._cap):
            chunk = [pad_to(a[start:stop], target) for a in arrays]
            self.submit(params, *chunk, n_valid=stop - start)
        outs = self.drain()
        return outs[0] if len(outs) == 1 else np.concatenate(outs, axis=0)


class SubmissionTracker:
    """Pairs in-flight submissions with the caller's items (clips, spans).

    The filter stages submit one dispatch per clip and zip the drained
    results back at the end of process_data. This helper owns that
    pending list so the pairing and the abort bookkeeping live in ONE
    place: when a failure aborts the pipeline, the items whose results
    were dropped with it are handed back (``lost_to_abort``) so the stage
    can record per-item errors instead of silently skipping them.
    """

    def __init__(self, pipeline: DevicePipeline) -> None:
        self.pipeline = pipeline
        self._items: list[Any] = []

    def submit(self, item: Any, *args: Any, **kwargs: Any) -> None:
        self.pipeline.submit(*args, **kwargs)
        self._items.append(item)

    def lost_to_abort(self) -> list[Any]:
        """Call from an except handler: if the pipeline aborted (all
        in-flight work cleared), returns the items whose results are gone
        and forgets them — pairing survivors with the wrong results is the
        failure mode this prevents. Returns [] when nothing was lost."""
        if self._items and self.pipeline.pending == 0:
            lost, self._items = self._items, []
            return lost
        return []

    def drain(self) -> list[tuple[Any, Any]]:
        """-> [(item, result)] in submission order. On failure the items
        are kept so the caller's except path can claim them via
        ``lost_to_abort`` and record per-item errors."""
        items, self._items = self._items, []
        try:
            results = self.pipeline.drain()
        except Exception:
            self._items = items
            raise
        return list(zip(items, results))

    def __len__(self) -> int:
        return len(self._items)
