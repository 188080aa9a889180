"""Milliseconds of decode programs (the engine's host clock around each
program and its host sync) per token they produced, over the window:
delta ``decode_s`` / delta ``decode_tokens``."""

UNIT, LAYER, MOVES, SOURCE = "ms/token", "caption engine", "output_tok_per_s", "program_span"


def read(run):
    d = run.get("stats_delta")
    if not d or not d["decode_tokens"]:
        return None
    return 1e3 * d["decode_s"] / d["decode_tokens"]
