"""Output tokens a program bought: delta (``decode_sample_tokens``, the tokens
decode programs emitted, + ``prefill_sample_first``, the first tokens of the
prompts a prefill program finished) / delta (``decode_dispatch_n`` +
``prefill_dispatch_n``). What one read of every parameter gives; a lane's rows
are its ceiling. None from a program that does not count them."""

UNIT, LAYER, MOVES, SOURCE = "count", "caption engine", "output_tok_per_s", "program_span"

KEYS = ("decode_sample_tokens", "prefill_sample_first", "decode_dispatch_n", "prefill_dispatch_n")


def read(run):
    d = run.get("phase_delta") or {}
    if any(k not in d for k in KEYS):
        return None
    programs = d["decode_dispatch_n"] + d["prefill_dispatch_n"]
    if not programs:
        return None
    return (d["decode_sample_tokens"] + d["prefill_sample_first"]) / programs
