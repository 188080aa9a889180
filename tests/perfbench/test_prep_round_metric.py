"""``engine.prep_requests_per_round``: the reader against hand-made run records,
and its entry in ``BENCHMARK.json``. No JAX here."""

import pytest

from perfbench import catalog

NAME = "engine.prep_requests_per_round"
# what the parent's program reports of its prep thread: seconds, no count
PARENT_PHASES = {"step_s": 8.0, "step_n": 100, "prep_s": 1.0, "prep_other_s": 1.0}


def _read(run):
    return catalog.load_module("layer_metrics", NAME).read(run)


@pytest.mark.parametrize(
    "rounds, requests, want", [(400, 400, 1.0), (520, 936, 1.8), (1, 9, 9.0)],
    ids=["a_request_a_round", "a_full_short_lane", "one_deep_queue"],
)
def test_value(rounds, requests, want):
    run = {"window_s": 40.0, "phase_delta": {"prep_n": rounds, "prep_requests": requests, "prep_s": 9.0}}
    assert _read(run) == pytest.approx(want)


@pytest.mark.parametrize(
    "run",
    [
        {"window_s": 40.0},
        {"window_s": 40.0, "phase_delta": None},
        {"window_s": 40.0, "phase_delta": PARENT_PHASES},
        {"window_s": 40.0, "phase_delta": {"prep_n": 0, "prep_requests": 0}},
    ],
    ids=["no_delta", "delta_none", "parent_program", "no_round"],
)
def test_nothing_to_read(run):
    assert _read(run) is None


def test_the_entry_is_the_readers_and_every_cell_reports_it():
    bench = catalog.benchmark()
    assert len(bench["per_layer"]) == 54 and bench["per_layer"][-1]["name"] == NAME
    entry = bench["per_layer"][-1]
    reader = catalog.load_module("layer_metrics", NAME)
    assert entry == {
        "name": NAME, "unit": reader.UNIT, "better": "higher", "source": reader.SOURCE,
        "layer": reader.LAYER, "moves": reader.MOVES,
    }
    assert (reader.UNIT, reader.LAYER, reader.SOURCE) == ("count", "caption engine", "program_span")
    # every cell has a prep thread: no `workloads` list
    for cell in (w["name"] for w in bench["workloads"]):
        assert NAME in catalog.load_cell(cell).per_layer, cell
