"""A request's mean wait between arriving (``add_request``, or a refinement follow-up's
queueing) and being taken out of ``waiting``: by the prep thread's round
(``_take_round``), or inline by ``_admit``'s turn. 1000 x delta ``request_queue_s`` /
delta ``request_taken_n`` (``CaptionEngine._stamp``: booked where the interval closes,
so a window reads the requests taken inside it). It waited for the prep thread: a deep
backlog, or a round ahead of it that embeds a long prompt. None from a program that
keeps no such stamps, or from a window in which the count is 0."""

UNIT, LAYER, MOVES, SOURCE = "ms", "caption engine", "output_tok_per_s", "program_span"


def read(run):
    d = run.get("phase_delta") or {}
    if "request_queue_s" not in d or not d.get("request_taken_n"):
        return None
    return 1000.0 * d["request_queue_s"] / d["request_taken_n"]
