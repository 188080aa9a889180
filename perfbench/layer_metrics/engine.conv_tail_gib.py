"""GiB of the engine's recurrent store on one chip where it is a short
convolution's TAILS ALONE (``stats()``'s ``conv_tail_bytes_per_chip`` of an
engine whose recurrent layers are gated short convolutions): ``z``'s last two
values a channel a conv layer, bfloat16, a row a slot and the garbage row (LFM2:
8 layers x 2 x 2,048 x 2 B = 64 KiB a row, 0.016 GiB for 265 rows). What a row
costs this hybrid whatever its context: a thousandth of a delta-rule row.
Nothing to read where the engine keeps no such store."""

UNIT, LAYER, MOVES, SOURCE = "GiB", "caption engine", "output_tok_per_s", "program_counter"


def read(run):
    counters = run.get("conv") or {}
    if not counters.get("conv_tail_bytes_per_chip"):
        return None
    return counters["conv_tail_bytes_per_chip"] / 2**30
