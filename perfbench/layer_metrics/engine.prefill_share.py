"""Share of the window spent in prefill programs (the engine's host clock
around them): delta ``prefill_s`` / window."""

UNIT, LAYER, MOVES, SOURCE = "%", "caption engine", "output_tok_per_s", "program_span"


def read(run):
    d = run.get("phase_delta")
    return None if not d else 100.0 * d["prefill_s"] / run["window_s"]
