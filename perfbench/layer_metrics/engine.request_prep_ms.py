"""A request's mean time in its round of preparation: from the hold of the lock that took
it to the hold that handed the round to ``_ready`` (inline: until ``_safe_prepare``
returned). 1000 x delta ``request_prep_s`` / delta ``request_ready_n``
(``CaptionEngine._stamp``). Its round-mates' embedding, the device round trip behind the
program in flight and two waits for the engine's lock are in it: what a request pays for
sharing a round (``engine.prep_requests_per_round``). None from a program that keeps no
such stamps, or from a window in which the count is 0."""

UNIT, LAYER, MOVES, SOURCE = "ms", "caption engine", "output_tok_per_s", "program_span"


def read(run):
    d = run.get("phase_delta") or {}
    if "request_prep_s" not in d or not d.get("request_ready_n"):
        return None
    return 1000.0 * d["request_prep_s"] / d["request_ready_n"]
