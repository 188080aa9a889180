"""Device time of the grouped matrix products (``gmm``) of the experts held on
this chip / device-busy time, first chip, traced slice, in the cell that holds a
recurrent store beside sorted experts (``kernel.expert_time_share`` and
``kernel.held_expert_time_share`` count the same for the latent and the
window/full flavors). The sort, the gathers around it, the router and the shared
expert are plain XLA and carry no name a trace can be split by. Nothing to read
where the driver records no such layers."""

UNIT, LAYER, MOVES, SOURCE = "%", "kernels", "output_tok_per_s", "device_trace"


def read(run):
    trace, experts = run.get("trace"), run.get("expert_trace")
    if trace is None or not experts or "kda" not in run:
        return None
    return 100.0 * sum(experts["kernel_s"].values()) / trace.busy_s_by_chip[0]
