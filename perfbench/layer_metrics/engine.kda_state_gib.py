"""GiB of the engine's recurrent store on one chip where it holds Kimi Delta
Attention's states (``stats()``'s ``recurrent_state_bytes_per_chip`` of an
engine whose decode programs also count held experts): the float32 matrix
states, 64 heads of ``[128, 128]`` side by side a row, and the three
convolutions' tails, a row a slot and the garbage row. What a row costs this
hybrid whatever its context; memory that bounds the batch. Nothing to read
where the engine keeps no such store."""

UNIT, LAYER, MOVES, SOURCE = "GiB", "caption engine", "output_tok_per_s", "program_counter"


def read(run):
    counters = run.get("kda") or {}
    if not counters.get("recurrent_state_bytes_per_chip"):
        return None
    return counters["recurrent_state_bytes_per_chip"] / 2**30
