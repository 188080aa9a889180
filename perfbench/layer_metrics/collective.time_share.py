"""Device time of the collective operations (all-reduce, all-gather, ...)
/ device-busy time, first chip, traced slice. Only where a mesh is."""

UNIT, LAYER, MOVES, SOURCE = "%", "sharding", "output_tok_per_s", "device_trace"


def read(run):
    trace = run.get("trace")
    if trace is None or trace.chips < 2:
        return None
    return 100.0 * trace.collective_s / trace.busy_s_by_chip[0]
