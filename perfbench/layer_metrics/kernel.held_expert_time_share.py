"""Device time of the grouped matrix products (``gmm``) of the experts held on
this chip / device-busy time, first chip, traced slice, where window and full
attention layers are mixed (``kernel.expert_time_share`` counts the same for a
latent flavor; its cell's roofline assumes every held expert touched a pass,
which 20 assignments over 32 experts are not). The sort, the gathers around it,
the router and the shared expert are plain XLA and carry no name a trace can be
split by. Nothing to read where the driver records no such layers."""

UNIT, LAYER, MOVES, SOURCE = "%", "kernels", "output_tok_per_s", "device_trace"


def read(run):
    trace, experts = run.get("trace"), run.get("expert_trace")
    if trace is None or not experts or "windowed" not in run:
        return None
    return 100.0 * sum(experts["kernel_s"].values()) / trace.busy_s_by_chip[0]
