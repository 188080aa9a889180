"""Device time of the held experts' grouped matrix products (``gmm``) /
device-busy time, first chip, traced slice. The sort, the gathers around it,
the router and the shared expert are plain XLA and carry no name a trace can be
split by. Nothing to read in a program without such a kernel."""

UNIT, LAYER, MOVES, SOURCE = "%", "kernels", "output_tok_per_s", "device_trace"


def read(run):
    trace, experts = run.get("trace"), run.get("expert_trace")
    if trace is None or experts is None:
        return None
    return 100.0 * sum(experts["kernel_s"].values()) / trace.busy_s_by_chip[0]
