"""Device time of the gated short convolutions / device-busy time, first chip,
traced slice: the instructions that the warmed programs' compiled text puts
under the scope ``mixer.short_conv`` (models/vlm/short_conv.py: both
projections, the gates, the three taps and the tails' hand-over; plain XLA, so
told by scope as ``attn.select`` is in the indexed cell), decode and prefill
programs alike. Read it beside ``kernel.whole_moe_expert_time_share``: the two
mechanisms of this cell. Nothing to read where the driver records no such
scope."""

UNIT, LAYER, MOVES, SOURCE = "%", "kernels", "output_tok_per_s", "device_trace"

SCOPE = "mixer.short_conv"


def read(run):
    trace, scopes = run.get("trace"), run.get("scope_s")
    spent = sum(s for (_kind, scope), s in (scopes or {}).items() if scope == SCOPE)
    if trace is None or not spent:
        return None
    return 100.0 * spent / trace.busy_s_by_chip[0]
