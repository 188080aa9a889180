"""Share of the window the engine spent outside ``step()``, in its caller's
loop (feeding, collecting, waiting for work): 100 x (1 - delta ``step_s`` /
window). Host work inside ``step()`` is not a stall and is not counted here.
None from a program that does not time ``step()``."""

UNIT, LAYER, MOVES, SOURCE = "%", "caption engine", "output_tok_per_s", "program_span"


def read(run):
    d = run.get("phase_delta") or {}
    if "step_s" not in d or not run["window_s"]:
        return None
    return 100.0 * (1.0 - d["step_s"] / run["window_s"])
