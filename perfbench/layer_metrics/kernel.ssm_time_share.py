"""Device time of the state-space custom calls the trace names (``_ssm_decode``;
``_ssd_prefill`` once the prefill scan is a kernel too) / device-busy time,
first chip, traced slice. The prefill scan is plain XLA today: its fusions
carry no name a trace can be split by, and its time shows in
``engine.prefill_share``. Nothing to read in a program without such kernels."""

UNIT, LAYER, MOVES, SOURCE = "%", "kernels", "output_tok_per_s", "device_trace"


def read(run):
    trace, ssm = run.get("trace"), run.get("ssm_trace")
    if trace is None or ssm is None:
        return None
    return 100.0 * sum(ssm["kernel_s"].values()) / trace.busy_s_by_chip[0]
