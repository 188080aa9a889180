"""Seconds the frame-extraction stage's workers were busy, per clip written:
``stage_flow_summaries()['ClipFrameExtractionStage']['busy_s']`` / clips."""

UNIT, LAYER, MOVES, SOURCE = "s/clip", "host stages", "clips_per_s", "program_span"
STAGE = "ClipFrameExtractionStage"


def read(run):
    flow = (run.get("stage_flow") or {}).get(STAGE)
    if not flow or not run.get("clips"):
        return None
    return flow["busy_s"] / run["clips"]
