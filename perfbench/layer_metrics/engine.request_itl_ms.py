"""The mean gap between two tokens of one request: the finished requests' time from first
token to end over their tokens after the first. 1000 x delta ``request_decode_s`` /
delta ``request_decode_gaps`` (``CaptionEngine._stamp``, booked in ``_maybe_finish``).
About a step of the engine: every lane's decode program and whatever prefill chunks ran
between two of them. None from a program that keeps no such stamps, or from a window in
which the count is 0."""

UNIT, LAYER, MOVES, SOURCE = "ms", "caption engine", "output_tok_per_s", "program_span"


def read(run):
    d = run.get("phase_delta") or {}
    if "request_decode_s" not in d or not d.get("request_decode_gaps"):
        return None
    return 1000.0 * d["request_decode_s"] / d["request_decode_gaps"]
