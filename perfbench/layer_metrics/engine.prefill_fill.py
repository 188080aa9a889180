"""Share of the positions the prefill programs had room for that were prompt:
100 x delta ``prefill_dispatch_tokens`` / delta ``prefill_dispatch_room``, room
being padded rows x T of each program that ran. None from a program that does
not count them, or from a window without a prefill program."""

UNIT, LAYER, MOVES, SOURCE = "%", "caption engine", "output_tok_per_s", "program_span"


def read(run):
    d = run.get("phase_delta") or {}
    if "prefill_dispatch_tokens" not in d or not d.get("prefill_dispatch_room"):
        return None
    return 100.0 * d["prefill_dispatch_tokens"] / d["prefill_dispatch_room"]
