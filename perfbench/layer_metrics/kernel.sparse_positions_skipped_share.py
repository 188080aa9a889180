"""Share of the positions the decode steps' rows could see whose K/V they did
not read: 1 - ``sparse_decode_positions_chosen`` / ``sparse_decode_positions_live``
of ``stats()``, over the window, layer by layer (a row reads ``min(context,
top_k)`` positions). A property of the mix; it falls if a change widens what a
step reads. Nothing to read where the engine has no indexer."""

UNIT, LAYER, MOVES, SOURCE = "%", "kernels", "output_tok_per_s", "program_counter"


def read(run):
    counters = run.get("sparse")
    if not counters or not counters.get("sparse_decode_positions_live"):
        return None
    return 100.0 * (1.0 - counters["sparse_decode_positions_chosen"] / counters["sparse_decode_positions_live"])
