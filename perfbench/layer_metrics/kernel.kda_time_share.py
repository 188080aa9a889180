"""Device time of Kimi Delta Attention's recurrence / device-busy time, first
chip, traced slice: the custom calls the trace names (``_delta_decode``;
``_delta_prefill`` once the prefill scan is a kernel) plus the instructions
under the scopes ``delta.prefill_scan`` and ``delta.conv`` (plain XLA: told from
the compiled programs' text; the mixer shares the gated delta rule's code and
scopes). Read it beside ``kernel.kda_cell_expert_time_share``: the two
mechanisms this cell holds in one program. Nothing to read where the driver
records no such layers."""

UNIT, LAYER, MOVES, SOURCE = "%", "kernels", "output_tok_per_s", "device_trace"

SCOPES = ("delta.prefill_scan", "delta.conv")


def read(run):
    trace, delta = run.get("trace"), run.get("delta_trace")
    if trace is None or delta is None or "kda" not in run:
        return None
    scoped = sum(s for (_kind, scope), s in (run.get("scope_s") or {}).items() if scope in SCOPES)
    return 100.0 * (sum(delta["kernel_s"].values()) + scoped) / trace.busy_s_by_chip[0]
