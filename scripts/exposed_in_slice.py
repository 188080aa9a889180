"""One traced run of a benchmark cell, with the engine's device-queue clock read
at the edges of the traced slice.

Runs ``perfbench.run`` unchanged (same arguments, same result line last) and
puts one more line before it: the seconds the caption engine's phases under
``step()`` ran with its device queue provably empty INSIDE the traced slice
(``step_exposed_s``, and the part of it the dispatch phases booked themselves),
from ``phase_seconds`` alone. Read it against the
driver's own ``traced slice ... busy ... by chip`` line: the exposed seconds,
less the dispatch phases' own, are a lower bound of the first chip's idle
seconds (slice less busy) and must not pass them (PERF.md section 3).

    chiprun -- python scripts/exposed_in_slice.py --workload <cell> --seed <n> --seconds 40 --trace 1

Nothing of the benchmark is edited: the two edges are ``measure.Tracer.start``
(after the profiler is up) and ``.stop`` (before it is torn down), wrapped here.
"""

from __future__ import annotations

import os
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from perfbench import measure  # noqa: E402  (first: its import is the start of setup_s)
from perfbench import run as bench_run  # noqa: E402

DISPATCH = ("prefill_dispatch_exposed_s", "decode_dispatch_exposed_s")


def main(argv=None) -> int:
    from cosmos_curate_tpu.models.vlm.engine import CaptionEngine

    engines: list = []
    setup, start, stop = CaptionEngine.setup, measure.Tracer.start, measure.Tracer.stop
    edge: dict = {}

    def spy_setup(self, *a, **kw):
        engines.append(self)
        return setup(self, *a, **kw)

    def spy_start(self, *a, **kw):
        start(self, *a, **kw)
        edge.update({id(e): e.phase_seconds for e in engines})

    def spy_stop(self):
        for e in engines:
            ph0, ph1 = edge[id(e)], e.phase_seconds
            if ph1["step_n"] == ph0["step_n"]:
                continue  # an engine of the set-up's comparisons: it did not step in the slice
            d = {k: ph1[k] - ph0[k] for k in ph1}
            own = sum(d[k] for k in DISPATCH)
            measure.log(
                f"exposed in the traced slice ({d['step_n']} steps, {d['decode_dispatch_n']} decode + "
                f"{d['prefill_dispatch_n']} prefill programs): step_exposed_s {d['step_exposed_s']:.4f} of "
                f"step_s {d['step_s']:.4f}, the dispatch phases' own {own:.4f} (decode "
                f"{d[DISPATCH[1]]:.4f}), so a lower bound of the device's idle seconds of "
                f"{d['step_exposed_s'] - own:.4f}; decode reads {d['decode_sample_n']}, fresh "
                f"{d['decode_wait_fresh']}, of them ready {d['decode_wait_ready']}"
            )
        stop(self)

    CaptionEngine.setup, measure.Tracer.start, measure.Tracer.stop = spy_setup, spy_start, spy_stop
    return bench_run.main(argv)


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    os._exit(code)
