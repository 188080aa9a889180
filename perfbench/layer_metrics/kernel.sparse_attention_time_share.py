"""Device time of the index scores, the choice and the chosen-set attention
(``jax.named_scope``s ``attn.index_score``, ``attn.select``, ``attn.sparse`` of the
decode and the prefill programs: Pallas kernels and XLA operations alike, found
by ``drivers/caption_engine_sparse.py::scope_seconds``) / device-busy time,
first chip, traced slice. What the learned choice costs beside the experts and
the parameters. Nothing to read where the driver records no such scopes."""

UNIT, LAYER, MOVES, SOURCE = "%", "kernels", "output_tok_per_s", "device_trace"
SCOPES = ("attn.index_score", "attn.select", "attn.sparse")


def read(run):
    trace, scopes = run.get("trace"), run.get("scope_s")
    if trace is None or not scopes:
        return None
    return 100.0 * sum(s for (_kind, scope), s in scopes.items() if scope in SCOPES) / trace.busy_s_by_chip[0]
