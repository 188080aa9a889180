"""The six per-layer metrics read off the counts and the device-queue clock of
the engine's phases (``CaptionEngine._phase``), each against a hand-made run
record. No JAX here."""

import pytest

from perfbench import catalog

# 100 steps: 200 decode programs of 8 rows (1,150 live) and 50 prefill programs
PHASES = {
    "step_n": 100, "step_s": 8.0, "step_exposed_s": 0.5,
    "decode_dispatch_n": 200, "decode_dispatch_rows": 1600, "decode_dispatch_live": 1200,
    "decode_wait_fresh": 120, "decode_wait_ready": 30,  # 80 reads behind a chunk's sync
    "decode_sample_n": 200, "decode_sample_tokens": 1150, "prefill_sample_first": 100,
    "prefill_dispatch_n": 50, "prefill_dispatch_tokens": 9600, "prefill_dispatch_room": 12800,
}
RUN = {"window_s": 10.0, "phase_delta": PHASES, "stats_delta": {"paged_kernel_steps": 200}}
WANT = {
    "engine.programs_per_step": 2.5,  # (200 + 50) / 100
    "engine.tokens_per_program": 5.0,  # (1150 + 100) / 250
    "engine.decode_row_occupancy": 75.0,  # 1200 / 1600
    "engine.prefill_fill": 75.0,  # 9600 / 12800
    "engine.exposed_host_share": 6.25,  # 0.5 / 8
    "engine.host_late_share": 25.0,  # 30 / 120: of the reads no earlier sync had passed
}
# what the parent's program reports: seconds under the old names, no count, no exposed second
PARENT_PHASES = {
    "step_s": 8.0, "decode_wait_s": 6.0, "decode_dispatch_s": 0.4, "prefill_s": 1.0,
    "decode_s": 6.4, "prep_s": 1.0, "vision_encode_s": 0.5,
}
ZERO_DENOMINATOR = {
    "engine.programs_per_step": {"step_n": 0},
    "engine.tokens_per_program": {"decode_dispatch_n": 0, "prefill_dispatch_n": 0},
    "engine.decode_row_occupancy": {"decode_dispatch_rows": 0, "decode_dispatch_live": 0},
    "engine.prefill_fill": {"prefill_dispatch_room": 0, "prefill_dispatch_tokens": 0},
    "engine.exposed_host_share": {"step_s": 0.0},
    "engine.host_late_share": {"decode_wait_fresh": 0, "decode_wait_ready": 0},
}


def _reader(name):
    return catalog.load_module("layer_metrics", name)


@pytest.mark.parametrize("name", sorted(WANT))
def test_value(name):
    assert _reader(name).read(RUN) == pytest.approx(WANT[name])


@pytest.mark.parametrize("name", sorted(WANT))
@pytest.mark.parametrize(
    "run",
    [
        {"window_s": 10.0},
        {"window_s": 10.0, "phase_delta": None, "stats_delta": None},
        dict(RUN, phase_delta=PARENT_PHASES),
    ],
    ids=["no_deltas", "deltas_none", "parent_program"],
)
def test_nothing_to_read(name, run):
    assert _reader(name).read(run) is None


@pytest.mark.parametrize("name", sorted(WANT))
def test_zero_denominator(name):
    """A window without a step, a program, a prefill program or a fresh read."""
    run = dict(RUN, phase_delta=dict(PHASES, **ZERO_DENOMINATOR[name]))
    assert _reader(name).read(run) is None


@pytest.mark.parametrize("name", sorted(WANT))
def test_constants_are_the_benchmarks_entry(name):
    (entry,) = [e for e in catalog.benchmark()["per_layer"] if e["name"] == name]
    reader = _reader(name)
    assert (reader.UNIT, reader.LAYER, reader.MOVES, reader.SOURCE) == (
        entry["unit"], entry["layer"], entry["moves"], entry["source"]
    )
    assert entry["source"] == "program_span" and "workloads" not in entry  # every cell, no rehearsal line


def test_the_cells_report_them_all():
    for cell in (w["name"] for w in catalog.benchmark()["workloads"]):
        assert set(WANT) <= set(catalog.load_cell(cell).per_layer), cell


def test_a_window_of_decode_alone_still_counts_its_programs():
    quiet = dict(PHASES, prefill_dispatch_n=0, prefill_dispatch_tokens=0, prefill_dispatch_room=0, prefill_sample_first=0)
    run = dict(RUN, phase_delta=quiet)
    assert _reader("engine.programs_per_step").read(run) == pytest.approx(2.0)
    assert _reader("engine.tokens_per_program").read(run) == pytest.approx(5.75)
    assert _reader("engine.prefill_fill").read(run) is None
