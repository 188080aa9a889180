"""Device time of the choice alone (the scope ``attn.select`` of the decode and
the prefill programs: a chunk's threshold kernel and the mask made of it, a
decode step's ``top_k``) / device-busy time, first chip, traced slice. Nothing
to read where the driver records no such scope."""

UNIT, LAYER, MOVES, SOURCE = "%", "kernels", "output_tok_per_s", "device_trace"


def read(run):
    trace, scopes = run.get("trace"), run.get("scope_s")
    if trace is None or not scopes:
        return None
    return 100.0 * sum(s for (_kind, scope), s in scopes.items() if scope == "attn.select") / trace.busy_s_by_chip[0]
