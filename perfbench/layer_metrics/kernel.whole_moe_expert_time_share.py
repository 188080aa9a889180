"""Device time of the grouped matrix products (``gmm``) of an expert layer whose
tables are ALL on this chip / device-busy time, first chip, traced slice
(``kernel.expert_time_share``, ``kernel.held_expert_time_share`` and
``kernel.kda_cell_expert_time_share`` count the same for the chips that hold a
share). The sort, the gathers around it and the router are plain XLA and carry
no name a trace can be split by. Nothing to read where the driver records no
such layers."""

UNIT, LAYER, MOVES, SOURCE = "%", "kernels", "output_tok_per_s", "device_trace"


def read(run):
    trace, experts = run.get("trace"), run.get("expert_trace")
    if trace is None or not experts or "conv" not in run:
        return None
    return 100.0 * sum(experts["kernel_s"].values()) / trace.busy_s_by_chip[0]
