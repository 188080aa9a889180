"""Share of the time inside ``step()`` during which the device held NOTHING of
the engine's: every program a thread handed over had been shown done by a host
sync of that thread (the engine's device-queue clock). 100 x delta
``step_exposed_s`` / delta ``step_s``. Host work the device waited for, on the
host's clock: a lower bound of the idle it causes (idle behind a program still
unread is not in it), over by at most the dispatch phases' own part
(``decode_dispatch_exposed_s`` + ``prefill_dispatch_exposed_s``). None from a
program that keeps no such clock."""

UNIT, LAYER, MOVES, SOURCE = "%", "caption engine", "output_tok_per_s", "program_span"


def read(run):
    d = run.get("phase_delta") or {}
    if "step_exposed_s" not in d or not d.get("step_s"):
        return None
    return 100.0 * d["step_exposed_s"] / d["step_s"]
