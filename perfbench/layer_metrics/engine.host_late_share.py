"""Of the decode programs whose token vector no earlier sync had passed when
the host came to read it (``decode_wait_fresh``: a finished prefill chunk's
read passes the decode program dispatched before it, and such a read waits for
nothing whichever side is slower), the share whose vector had already landed
(``decode_wait_ready``; ``is_ready()`` asked where ``decode_wait`` opens):
100 x delta ``decode_wait_ready`` / delta ``decode_wait_fresh``. High: the host
is the slower side and the device waits for it; low: the host waits for the
device. The reads left out are delta ``decode_sample_n`` less the fresh ones.
None from a program that does not ask, or from a window without a fresh read."""

UNIT, LAYER, MOVES, SOURCE = "%", "caption engine", "output_tok_per_s", "program_span"


def read(run):
    d = run.get("phase_delta") or {}
    if "decode_wait_ready" not in d or not d.get("decode_wait_fresh"):
        return None
    return 100.0 * d["decode_wait_ready"] / d["decode_wait_fresh"]
