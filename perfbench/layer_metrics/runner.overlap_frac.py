"""Share of the summed stage work that ran hidden behind other stages:
``PipelinedRunner.overlap_frac`` = 1 - wall / sum of stage busy seconds, of
the measured pass. 0 is lockstep."""

UNIT, LAYER, MOVES, SOURCE = "%", "runners", "clips_per_s", "program_span"


def read(run):
    value = run.get("overlap_frac")
    return None if value is None else 100.0 * value
