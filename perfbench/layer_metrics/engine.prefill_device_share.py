"""Share of the programs' device seconds that the prefill programs took, first
chip, traced slice: the DEVICE's view of what ``engine.prefill_share`` reads on
the host's clock (there a prefill program's device time is met by whichever
sync comes next, often a decode program's ``decode_wait``: with long prompts
beside decode the host's view reads a fifth of the device's). From the trace's
line of programs, one event a run of a jitted function, as the driver summed
them by kind (``program_s``). Nothing to read where the driver records none."""

UNIT, LAYER, MOVES, SOURCE = "%", "caption engine", "output_tok_per_s", "device_trace"


def read(run):
    programs = run.get("program_s")
    total = sum(seconds for seconds, _runs in programs.values()) if programs else 0.0
    if not total:
        return None
    return 100.0 * programs["prefill"][0] / total
