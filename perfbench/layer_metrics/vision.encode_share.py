"""Seconds of vision-tower encodes (host clock of the background prep, which
overlaps decode) per second of window: delta ``vision_encode_s`` / window.
0 where no request carries frames."""

UNIT, LAYER, MOVES, SOURCE = "%", "model", "output_tok_per_s", "program_span"


def read(run):
    d = run.get("phase_delta")
    return None if not d else 100.0 * d["vision_encode_s"] / run["window_s"]
