"""GiB of the engine's latent pool on one chip (``stats()``'s
``latent_pool_bytes_per_chip``): one row of 640 lanes a position a layer, K and V
in one array. What a position costs a latent-attention flavor; memory that
bounds the batch. Nothing to read where the engine keeps no such pool."""

UNIT, LAYER, MOVES, SOURCE = "GiB", "caption engine", "output_tok_per_s", "program_counter"


def read(run):
    counters = run.get("latent")
    if not counters or not counters.get("latent_pool_bytes_per_chip"):
        return None
    return counters["latent_pool_bytes_per_chip"] / 2**30
