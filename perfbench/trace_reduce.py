"""From a profiler trace to numbers. The only place this arithmetic lives.

A trace is a list of planes; a plane has lines; a line has events
``(name, start_ns, duration_ns)``. ``load_xplane`` reads the ``.xplane.pb``
that ``jax.profiler`` writes; ``load_recorded`` reads the small JSON cut kept
under ``testdata/`` that the tests reduce (``record`` writes such a cut).

On a TPU each chip is a plane ``/device:TPU:<n>`` whose line ``XLA Ops`` holds
one event per executed operation; host threads are lines of ``/host:CPU``,
and a ``jax.profiler.TraceAnnotation`` is an event on its thread's line. Both
are on one clock.

- busy: the union of the operation intervals of a chip, clipped to the slice;
  idle share = 1 - busy / slice. With several chips, busy is their mean.
- a kernel's time: the summed durations of the events whose instruction name
  matches its pattern. A kernel that was asked for and has no event is an error.
- collective time: the same, for the collective operations of the first chip.
- gaps: every idle interval of the first chip, shared out among the harness's
  host spans that cover it; what no span covers is ``unattributed``.
"""

from __future__ import annotations

import json
import re
from dataclasses import dataclass
from pathlib import Path

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OP_LINE = "XLA Ops"
SLICE_SPAN = "perfbench.slice"
COLLECTIVES = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
)
_INSTRUCTION = re.compile(r"^%?([^\s=]+)")
_SUFFIX = re.compile(r"(\.clone|\.remat\d*|[.\d])+$")

Event = tuple[str, int, int]  # name, start_ns, duration_ns


@dataclass
class Line:
    name: str
    events: list[Event]


@dataclass
class Plane:
    name: str
    lines: list[Line]

    def line(self, name: str) -> Line | None:
        return next((l for l in self.lines if l.name == name), None)


def load_xplane(path: str | Path) -> list[Plane]:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    return [
        Plane(
            plane.name,
            [
                Line(
                    line.name,
                    [(e.name, int(e.start_ns), int(e.duration_ns)) for e in line.events],
                )
                for line in plane.lines
            ],
        )
        for plane in data.planes
    ]


def load_recorded(path: str | Path) -> list[Plane]:
    raw = json.loads(Path(path).read_text())
    return [
        Plane(p["name"], [Line(l["name"], [tuple(e) for e in l["events"]]) for l in p["lines"]])
        for p in raw["planes"]
    ]


def record(planes: list[Plane], path: str | Path, *, start_ns: int, end_ns: int, keep) -> int:
    """Write the events that start inside [start_ns, end_ns) and that
    ``keep(plane, line, name)`` admits as a recorded cut, times made
    relative. Returns the number of events kept."""
    kept = 0
    out = []
    for plane in planes:
        lines = []
        for line in plane.lines:
            events = [
                [n, s - start_ns, d]
                for n, s, d in line.events
                if start_ns <= s < end_ns and keep(plane, line, n)
            ]
            if events:
                lines.append({"name": line.name, "events": events})
                kept += len(events)
        if lines:
            out.append({"name": plane.name, "lines": lines})
    Path(path).write_text(json.dumps({"planes": out}, separators=(",", ":")))
    return kept


def inventory(planes: list[Plane], top: int = 25) -> str:
    """What a trace holds, for reading by hand: planes, lines, and the event
    names that take most time on each line."""
    rows = []
    for plane in planes:
        rows.append(f"plane {plane.name!r}: {len(plane.lines)} lines")
        for line in plane.lines:
            total: dict[str, list] = {}
            for name, _s, d in line.events:
                t = total.setdefault(name, [0, 0])
                t[0] += d
                t[1] += 1
            rows.append(f"  line {line.name!r}: {len(line.events)} events, {len(total)} names")
            for name, (d, n) in sorted(total.items(), key=lambda kv: -kv[1][0])[:top]:
                rows.append(f"    {d / 1e6:10.3f} ms  x{n:<6d} {name[:140]}")
    return "\n".join(rows)


def instruction(name: str) -> str:
    """The instruction's own name. On a TPU an operation's event is named by
    its whole HLO line, ``%fusion.12 = bf16[...] fusion(...)``: what stands
    before `` = `` is the name, and a Pallas kernel's custom call carries the
    name of the jitted function that wraps its ``pallas_call``."""
    return _INSTRUCTION.match(name).group(1) if name else name


def category(name: str) -> str:
    """``%fusion.123 = ...`` -> ``fusion``: unrolled layers number their copies."""
    name = instruction(name)
    return _SUFFIX.sub("", name) or name


def _arrays(events: list[Event], lo: int, hi: int):
    """Start and end of every event, clipped to [lo, hi); events outside go."""
    if not events:
        return np.zeros(0, np.int64), np.zeros(0, np.int64), np.zeros(0, np.int64)
    start = np.fromiter((e[1] for e in events), np.int64, len(events))
    end = start + np.fromiter((e[2] for e in events), np.int64, len(events))
    start, end = np.clip(start, lo, hi), np.clip(end, lo, hi)
    keep = np.nonzero(end > start)[0]
    return start[keep], end[keep], keep


def _union(start: np.ndarray, end: np.ndarray):
    """Merged intervals of (start, end): two arrays, sorted and disjoint."""
    if start.size == 0:
        return start, end
    order = np.argsort(start, kind="stable")
    start, end = start[order], end[order]
    reach = np.maximum.accumulate(end)
    new = np.concatenate([[True], start[1:] > reach[:-1]])
    first = np.nonzero(new)[0]
    last = np.concatenate([first[1:] - 1, [start.size - 1]])
    return start[first], reach[last]


def _overlap_with(span_start, span_end, gap_start, gap_end) -> float:
    """Total length of gaps ∩ spans, both sorted and disjoint (nanoseconds)."""
    total, i, j = 0, 0, 0
    while i < span_start.size and j < gap_start.size:
        lo = max(span_start[i], gap_start[j])
        hi = min(span_end[i], gap_end[j])
        if hi > lo:
            total += hi - lo
        if span_end[i] < gap_end[j]:
            i += 1
        else:
            j += 1
    return float(total)


@dataclass
class TraceSummary:
    window_s: float
    chips: int
    busy_s: float  # mean over the chips
    busy_s_by_chip: list[float]
    op_s: dict[str, float]  # first chip, by category, kernels under their key
    kernel_s: dict[str, float]  # first chip, by kernel key
    kernel_calls: dict[str, int]
    collective_s: float  # first chip
    gap_s: dict[str, float]  # first chip's idle time by host span
    events: int = 0

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def breakdown(self, n: int = 10) -> dict:
        def top(d):
            return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:n] if v > 0]

        return {"device_ops": top(self.op_s), "idle_gaps": top(self.gap_s)}


def slice_window(planes: list[Plane]) -> tuple[int, int] | None:
    for plane in planes:
        if DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            for name, start, dur in line.events:
                if name == SLICE_SPAN:
                    return start, start + dur
    return None


def reduce(
    planes: list[Plane],
    *,
    kernels: dict[str, str],
    host_spans: tuple[str, ...] = (),
    chips: int | None = None,
    window_s: float | None = None,
) -> TraceSummary | None:
    """The summary of one traced slice; None where the trace holds no TPU
    plane (a rehearsal on the CPU: no device number is made up). The slice is
    the ``perfbench.slice`` span; where the trace holds no host events, every
    operation counts and ``window_s``, the traced time on the host's clock,
    is the slice's length."""
    devices = sorted(
        (int(m.group(1)), p) for p in planes if (m := DEVICE_PLANE.match(p.name))
    )
    if chips is not None:
        devices = devices[:chips]
    if not devices:
        return None
    window = slice_window(planes)
    if window is None:
        starts = [e[1] for _, p in devices for e in (p.line(OP_LINE) or Line("", [])).events]
        ends = [e[1] + e[2] for _, p in devices for e in (p.line(OP_LINE) or Line("", [])).events]
        if not starts:
            raise ValueError("the trace holds TPU planes and not one operation on them")
        window = (min(starts), max(ends))
    else:
        window_s = None
    lo, hi = window
    busy_by_chip = []
    first = None
    for _, plane in devices:
        line = plane.line(OP_LINE)
        if line is None:
            raise ValueError(f"plane {plane.name} has no line {OP_LINE!r}: {[l.name for l in plane.lines]}")
        start, end, keep = _arrays(line.events, lo, hi)
        u_start, u_end = _union(start, end)
        busy_by_chip.append(float((u_end - u_start).sum()) / 1e9)
        if first is None:
            first = (line, start, end, keep, u_start, u_end)
    line, start, end, keep, u_start, u_end = first
    names = [line.events[i][0] for i in keep]
    dur = (end - start).astype(np.float64) / 1e9
    patterns = {key: re.compile(rx) for key, rx in kernels.items()}
    op_s: dict[str, float] = {}
    kernel_s = {key: 0.0 for key in kernels}
    kernel_calls = {key: 0 for key in kernels}
    collective_s = 0.0
    cache: dict[str, tuple[str, str | None, bool]] = {}
    for name, d in zip(names, dur):
        hit = cache.get(name)
        if hit is None:
            op = instruction(name)
            key = next((k for k, rx in patterns.items() if rx.search(op)), None)
            hit = (key or category(name), key, bool(COLLECTIVES.match(op)))
            cache[name] = hit
        cat, key, is_coll = hit
        op_s[cat] = op_s.get(cat, 0.0) + d
        if key is not None:
            kernel_s[key] += d
            kernel_calls[key] += 1
        if is_coll:
            collective_s += d
    missing = [k for k, n in kernel_calls.items() if n == 0]
    if missing:
        seen = sorted({c for c in op_s})[:40]
        raise LookupError(
            f"the trace holds no event for kernel(s) {missing} (patterns "
            f"{[kernels[k] for k in missing]}); categories seen: {seen}"
        )
    # idle intervals of the first chip, then their share per host span
    gap_start = np.concatenate([[lo], u_end])
    gap_end = np.concatenate([u_start, [hi]])
    ok = gap_end > gap_start
    gap_start, gap_end = gap_start[ok], gap_end[ok]
    gap_total = float((gap_end - gap_start).sum())
    gap_s: dict[str, float] = {}
    covered = 0.0
    for span in host_spans:
        ev = [
            e
            for p in planes
            if not DEVICE_PLANE.match(p.name)
            for l in p.lines
            for e in l.events
            if e[0] == span
        ]
        s, e, _ = _arrays(ev, lo, hi)
        s, e = _union(s, e)
        got = _overlap_with(s, e, gap_start, gap_end)
        gap_s[span] = got / 1e9
        covered += got
    gap_s["unattributed"] = max(0.0, gap_total - covered) / 1e9
    return TraceSummary(
        window_s=(hi - lo) / 1e9 if window_s is None else window_s,
        chips=len(devices),
        busy_s=float(np.mean(busy_by_chip)),
        busy_s_by_chip=busy_by_chip,
        op_s=op_s,
        kernel_s=kernel_s,
        kernel_calls=kernel_calls,
        collective_s=collective_s,
        gap_s=gap_s,
        events=int(keep.size),
    )
