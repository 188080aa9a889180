"""A prepared request's mean wait for a row: from ``_ready`` to the admission that gave it
a slot and its K/V blocks (``_admit`` past ``_claim_kv``; a head pushed back keeps
waiting). 1000 x delta ``request_row_wait_s`` / delta ``request_admitted_n``
(``CaptionEngine._stamp``). What the hold for a row of its own lane (``admit_held``), a
full lane, an exhausted pool and the admission linger cost a request. None from a
program that keeps no such stamps, or from a window in which the count is 0."""

UNIT, LAYER, MOVES, SOURCE = "ms", "caption engine", "output_tok_per_s", "program_span"


def read(run):
    d = run.get("phase_delta") or {}
    if "request_row_wait_s" not in d or not d.get("request_admitted_n"):
        return None
    return 1000.0 * d["request_row_wait_s"] / d["request_admitted_n"]
