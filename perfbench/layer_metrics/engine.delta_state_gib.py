"""GiB of the engine's recurrent store on one chip where it holds gated-delta-
rule states (``stats()``'s ``recurrent_state_bytes_per_chip`` beside a
``delta_decode_calls`` that counts): the float32 matrix states, 30 heads side
by side a row, and the three convolutions' tails, a row a slot and the garbage
row. What a row costs this hybrid whatever its context; memory that bounds the
batch. Nothing to read where the engine keeps no such store."""

UNIT, LAYER, MOVES, SOURCE = "GiB", "caption engine", "output_tok_per_s", "program_counter"


def read(run):
    counters = run.get("recurrent") or {}
    if not counters.get("recurrent_state_bytes_per_chip") or "delta_decode_calls" not in counters:
        return None
    return counters["recurrent_state_bytes_per_chip"] / 2**30
