"""A request's mean time from its admission to its first token (``_start_slot``): its
prompt's chunks, each beside everyone's decode program, or its place in a whole-prompt
group. 1000 x delta ``request_prefill_s`` / delta ``request_first_n``
(``CaptionEngine._stamp``). With the queue, the round and the row wait before it: the
mean time to first token. None from a program that keeps no such stamps, or from a
window in which the count is 0."""

UNIT, LAYER, MOVES, SOURCE = "ms", "caption engine", "output_tok_per_s", "program_span"


def read(run):
    d = run.get("phase_delta") or {}
    if "request_prefill_s" not in d or not d.get("request_first_n"):
        return None
    return 1000.0 * d["request_prefill_s"] / d["request_first_n"]
