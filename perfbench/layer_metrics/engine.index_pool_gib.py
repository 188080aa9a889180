"""GiB of the engine's index-key array on one chip (``stats()``'s
``index_pool_bytes_per_chip``): one key a position a layer AS STORED, its
padding counted (64 values in a 128-lane row: 256 B where the key is 128).
Memory that bounds the batch beside the K/V pool. Nothing to read where the
engine has no indexer."""

UNIT, LAYER, MOVES, SOURCE = "GiB", "caption engine", "output_tok_per_s", "program_counter"


def read(run):
    counters = run.get("sparse")
    if not counters or not counters.get("index_pool_bytes_per_chip"):
        return None
    return counters["index_pool_bytes_per_chip"] / 2**30
