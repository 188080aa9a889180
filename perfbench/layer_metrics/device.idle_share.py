"""1 - (union of the device's operation intervals / traced slice), mean over
the chips used: what the ``device`` block's busy_s and window_s give."""

UNIT, LAYER, MOVES, SOURCE = "%", "device", "output_tok_per_s", "device_trace"


def read(run):
    trace = run.get("trace")
    return None if trace is None else 100.0 * trace.idle_share
