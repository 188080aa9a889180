"""Programs the engine handed the device per ``step()``: delta
(``decode_dispatch_n`` + ``prefill_dispatch_n``) / delta ``step_n``, entries of
the engine's own dispatch phases (``CaptionEngine._phase``). Each program reads
every parameter, so a step that runs three where two would do pays for a third
read. None from a program that does not count its phases' entries."""

UNIT, LAYER, MOVES, SOURCE = "count", "caption engine", "output_tok_per_s", "program_span"

KEYS = ("decode_dispatch_n", "prefill_dispatch_n", "step_n")


def read(run):
    d = run.get("phase_delta") or {}
    if any(k not in d for k in KEYS) or not d["step_n"]:
        return None
    return (d["decode_dispatch_n"] + d["prefill_dispatch_n"]) / d["step_n"]
