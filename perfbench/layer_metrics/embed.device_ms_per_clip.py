"""Device-busy milliseconds per clip embedded, in the traced slice: the union
of the chip's operation intervals / clips whose frames the embed pipeline
dispatched in the slice (its ``rows`` counter / frames per clip)."""

UNIT, LAYER, MOVES, SOURCE = "ms/clip", "device stages", "clips_per_s", "device_trace"


def read(run):
    trace, piece = run.get("trace"), run.get("slice") or {}
    if trace is None or not piece.get("clips_embedded"):
        return None
    return 1e3 * trace.busy_s / piece["clips_embedded"]
