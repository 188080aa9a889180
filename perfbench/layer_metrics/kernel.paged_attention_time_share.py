"""Device time of the two paged-attention kernels (decode and chunked
prefill) / device-busy time, first chip, traced slice."""

UNIT, LAYER, MOVES, SOURCE = "%", "kernels", "output_tok_per_s", "device_trace"


def read(run):
    trace = run.get("trace")
    if trace is None:
        return None
    return 100.0 * sum(trace.kernel_s.values()) / trace.busy_s_by_chip[0]
