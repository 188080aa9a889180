"""Share of the programs' device seconds that the prefill programs took, first
chip, traced slice, in the cell whose every expert layer is WHOLE on the chip
(LFM2: a prefill program reads the 9.66 GB of expert tables again, beside the
decode program's read: the cell's second bottleneck): the DEVICE's view of what
``engine.prefill_share`` reads on the host's clock, from the trace's line of
programs as the driver summed them by kind (``program_s``:
``engine.prefill_device_share``'s arithmetic on this driver's record, as
``engine.kda_cell_prefill_device_share`` is on the Solar driver's). Nothing to
read where the driver records no such cell."""

UNIT, LAYER, MOVES, SOURCE = "%", "caption engine", "output_tok_per_s", "device_trace"


def read(run):
    programs = run.get("program_s")
    total = sum(seconds for seconds, _runs in programs.values()) if programs else 0.0
    if not total or "conv" not in run:
        return None
    return 100.0 * programs["prefill"][0] / total
