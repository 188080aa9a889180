"""Device time of the gated delta rule / device-busy time, first chip, traced
slice: the custom calls the trace names (``_delta_decode``; ``_delta_prefill``
once the prefill scan is a kernel) plus the instructions under the scopes
``delta.prefill_scan`` and ``delta.conv`` (plain XLA: told from the compiled
programs' text). Read it beside ``kernel.paged_attention_time_share``: the two
kinds of layer of one decoder. Nothing to read in a program without such layers."""

UNIT, LAYER, MOVES, SOURCE = "%", "kernels", "output_tok_per_s", "device_trace"

SCOPES = ("delta.prefill_scan", "delta.conv")


def read(run):
    trace, delta = run.get("trace"), run.get("delta_trace")
    if trace is None or delta is None:
        return None
    scoped = sum(s for (_kind, scope), s in (run.get("scope_s") or {}).items() if scope in SCOPES)
    return 100.0 * (sum(delta["kernel_s"].values()) + scoped) / trace.busy_s_by_chip[0]
