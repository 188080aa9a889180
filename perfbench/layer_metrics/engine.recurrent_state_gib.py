"""GiB of the engine's recurrent store on one chip (``stats()``'s
``recurrent_state_bytes_per_chip``): the float32 Mamba-2 states and the
convolutions' tails, a row a slot and the garbage row. What a row costs a
hybrid, as KV blocks are what it costs the others; memory that bounds the
batch. Nothing to read where the engine keeps no such store."""

UNIT, LAYER, MOVES, SOURCE = "GiB", "caption engine", "output_tok_per_s", "program_counter"


def read(run):
    counters = run.get("recurrent")
    if not counters or not counters.get("recurrent_state_bytes_per_chip"):
        return None
    return counters["recurrent_state_bytes_per_chip"] / 2**30
