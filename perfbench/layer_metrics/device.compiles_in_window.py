"""Backend compiles between window start and window end (``jax.monitoring``
duration events). Expected 0: a compile inside the window is time the
end-to-end metric should not hold."""

UNIT, LAYER, MOVES, SOURCE = "count", "device", "output_tok_per_s", "program_counter"


def read(run):
    return float(run["compiles_in_window"])
