"""Requests a round of the prep thread carried: delta ``prep_requests`` / delta
``prep_n`` (a round is one hold of the engine's lock to take waiting requests,
one embedding call and one device-to-host read for their text, one hold to hand
them on; a request with frames is a round of its own). 1.0 where the queue never
holds more than one; a lane of many short rows needs more than a request a round
to stay full. None from a program that does not count them, or from a window
without a round."""

UNIT, LAYER, MOVES, SOURCE = "count", "caption engine", "output_tok_per_s", "program_span"


def read(run):
    d = run.get("phase_delta") or {}
    if "prep_requests" not in d or not d.get("prep_n"):
        return None
    return d["prep_requests"] / d["prep_n"]
