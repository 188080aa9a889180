"""Share of the window the engine's stepping thread spent blocked on a device
result (the host sync after each program, timed inside ``step()``): delta
(``prefill_wait_s`` + ``decode_wait_s``) / window. The engine syncs after
every program, so what is left of the window is where the host can leave the
chip idle. None from a program that does not time these phases."""

UNIT, LAYER, MOVES, SOURCE = "%", "caption engine", "output_tok_per_s", "program_span"


def read(run):
    d = run.get("phase_delta") or {}
    if "prefill_wait_s" not in d or "decode_wait_s" not in d or not run["window_s"]:
        return None
    return 100.0 * (d["prefill_wait_s"] + d["decode_wait_s"]) / run["window_s"]
