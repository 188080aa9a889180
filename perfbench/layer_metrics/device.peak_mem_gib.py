"""``memory_stats()['peak_bytes_in_use']`` of the fullest chip, in GiB: a
guard, since memory that is reserved and unused limits the batch."""

UNIT, LAYER, MOVES, SOURCE = "GiB", "device", "output_tok_per_s", "program_counter"


def read(run):
    peak = run["device"]["memory_peak_bytes"]
    return peak / 2**30 if peak else None
