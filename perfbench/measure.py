"""What every driver measures the same way: the clock, the count of compiles,
the device block, and the short profiler trace of a ``--trace 1`` run."""

from __future__ import annotations

import contextlib
import shutil
import sys
import time
from pathlib import Path

from perfbench.catalog import CHECKOUT

# Only files in the checkout outlast a run (the driver gives HOME and TMPDIR
# of its own to each side); caches the benchmark keeps live here.
CACHE_DIR = CHECKOUT / ".perfbench_cache"

_T0 = time.monotonic()  # perfbench.run imports this module first of all


def since_start() -> float:
    """Seconds since the process started importing the benchmark."""
    return time.monotonic() - _T0


def log(msg: str) -> None:
    """Earlier lines of standard output: everything but the result."""
    print(f"[{since_start():7.2f}s] {msg}", flush=True)


class SetupClock:
    """``setup_s`` by part. ``part(name)`` times a block; ``total()`` is
    process start to now, so whatever no part names shows as ``other``."""

    def __init__(self) -> None:
        self.parts: dict[str, float] = {}

    @contextlib.contextmanager
    def part(self, name: str):
        t0 = time.monotonic()
        try:
            yield
        finally:
            self.parts[name] = self.parts.get(name, 0.0) + time.monotonic() - t0

    def close(self) -> float:
        total = since_start()
        self.parts["other"] = total - sum(self.parts.values())
        log("setup_s by part: " + ", ".join(f"{k} {v:.2f}" for k, v in self.parts.items()))
        return total


class CompileCounter:
    """Counts backend compiles (``jax.monitoring`` duration events) while
    ``counting``: inside the measured window there should be none."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self) -> None:
        import jax.monitoring

        self.count = 0
        self.counting = False
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_kw) -> None:
        if self.counting and event == self.EVENT:
            self.count += 1

    @contextlib.contextmanager
    def window(self):
        import jax

        self.counting = True
        jax.config.update("jax_log_compiles", True)  # says what compiled, if anything does
        try:
            yield self
        finally:
            jax.config.update("jax_log_compiles", False)
            self.counting = False
            if self.count:
                log(f"WARNING: {self.count} compile(s) inside the measured window")


def require_devices(chips: int, *, rehearse: bool):
    """The devices the cell runs on. A measurement needs TPUs, as many as the
    cell asks for; there is no CPU fallback. ``--rehearse`` takes the CPU."""
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if rehearse:
        if platform != "cpu":
            sys.exit(f"perfbench: --rehearse runs on the CPU, JAX gives {platform!r}")
    elif platform != "tpu":
        sys.exit(
            f"perfbench: JAX found no TPU (platform={platform!r}); a cell is measured "
            "on the chip or not at all"
        )
    if len(devices) < chips:
        sys.exit(f"perfbench: the cell needs {chips} chip(s), JAX reports {len(devices)}")
    return devices[:chips]


def device_block(devices) -> dict:
    peaks = [
        (d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices
    ]
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": int(max(peaks)),
    }


class Tracer:
    """One short ``jax.profiler`` trace inside the window. The trace
    directory is inside the checkout and is removed once reduced."""

    def __init__(self, name: str) -> None:
        self.dir = CACHE_DIR / "traces" / name
        self.started_at: float | None = None
        self.stopped_at: float | None = None

    def start(self, host_events: bool = True) -> None:
        import jax

        shutil.rmtree(self.dir, ignore_errors=True)
        self.dir.mkdir(parents=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0  # Python frames cost more than they tell
        options.host_tracer_level = 2 if host_events else 0
        jax.profiler.start_trace(str(self.dir), profiler_options=options)
        self.started_at = time.monotonic()

    def stop(self) -> None:
        import jax

        jax.profiler.stop_trace()
        self.stopped_at = time.monotonic()

    @property
    def active(self) -> bool:
        return self.started_at is not None and self.stopped_at is None

    def xplane(self) -> Path:
        found = sorted(self.dir.glob("plugins/profile/*/*.xplane.pb"))
        if not found:
            raise FileNotFoundError(f"the profiler wrote no .xplane.pb under {self.dir}")
        return found[-1]

    def discard(self) -> None:
        shutil.rmtree(self.dir, ignore_errors=True)


def annotate(name: str):
    """A host span on the profiler's own clock (free when no trace runs)."""
    import jax

    return jax.profiler.TraceAnnotation(name)


def keep_trace_for_reading(planes, cell_name: str, host_spans: tuple[str, ...] = ()) -> None:
    """Under ``chiprun_out/`` (git-ignored): the inventory of the trace, for
    reading by hand, and a cut of 40 ms of it in the recorded form the tests
    reduce. Nothing the result depends on."""
    from perfbench import trace_reduce

    out = CHECKOUT / "chiprun_out"
    try:
        out.mkdir(exist_ok=True)
        (out / f"trace_inventory.{cell_name}.txt").write_text(trace_reduce.inventory(planes))
        window = trace_reduce.slice_window(planes)
        if window is not None:
            lo = window[0] + (window[1] - window[0]) // 2
            kept = trace_reduce.record(
                planes, out / f"trace_cut.{cell_name}.json", start_ns=lo, end_ns=lo + 40_000_000,
                keep=lambda plane, line, name: (
                    line.name == trace_reduce.OP_LINE
                    if trace_reduce.DEVICE_PLANE.match(plane.name)
                    else name in host_spans
                ),
            )
            log(f"trace inventory and a cut of {kept} events written under {out}")
    except OSError as e:  # a read-only checkout must not fail the run
        log(f"trace inventory not written: {e}")
