"""GiB of the engine's window pool on one chip (``stats()``'s
``window_pool_bytes_per_chip``): the K/V of the sliding-attention layers, a ring
of ``ceil((window + chunk) / block) + 1`` blocks a row whatever the row's lane.
Memory that bounds the batch; it does not grow with the contexts served.
Nothing to read where the engine keeps one pool."""

UNIT, LAYER, MOVES, SOURCE = "GiB", "caption engine", "output_tok_per_s", "program_counter"


def read(run):
    counters = run.get("windowed")
    if not counters or not counters.get("window_pool_bytes_per_chip"):
        return None
    return counters["window_pool_bytes_per_chip"] / 2**30
