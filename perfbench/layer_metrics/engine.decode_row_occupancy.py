"""Share of the rows of the decode programs dispatched that held a request:
100 x delta ``decode_dispatch_live`` / delta ``decode_dispatch_rows`` (a
program runs its lane's every row, live or not). None from a program that does
not count them, or from a window without a decode program."""

UNIT, LAYER, MOVES, SOURCE = "%", "caption engine", "output_tok_per_s", "program_span"


def read(run):
    d = run.get("phase_delta") or {}
    if "decode_dispatch_live" not in d or not d.get("decode_dispatch_rows"):
        return None
    return 100.0 * d["decode_dispatch_live"] / d["decode_dispatch_rows"]
