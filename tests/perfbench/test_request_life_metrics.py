"""The five ``engine.request_*`` readers against hand-made run records, and what
their entries in ``BENCHMARK.json`` will say (found by name, once listed). No JAX here."""

import pytest

from perfbench import catalog

# metric -> the sum of seconds and the count it divides by (CaptionEngine._stamp's keys)
READS = {
    "engine.request_queue_ms": ("request_queue_s", "request_taken_n"),
    "engine.request_prep_ms": ("request_prep_s", "request_ready_n"),
    "engine.request_row_wait_ms": ("request_row_wait_s", "request_admitted_n"),
    "engine.request_prefill_ms": ("request_prefill_s", "request_first_n"),
    "engine.request_itl_ms": ("request_decode_s", "request_decode_gaps"),
}
# a window of 40 s at 15.8 requests/s of 192 tokens: what the engine's account hands a driver
WINDOW = {
    "step_s": 39.1, "step_n": 470,
    "request_queue_s": 301.6, "request_taken_n": 632,
    "request_prep_s": 75.84, "request_ready_n": 632, "request_dropped_n": 0,
    "request_row_wait_s": 94.65, "request_admitted_n": 631,
    "request_prefill_s": 189.9, "request_first_n": 633,
    "request_decode_s": 10060.0, "request_finished_n": 630, "request_decode_gaps": 630 * 191,
}
# what the parent's program reports: phases and their counts, no request's life
PARENT_PHASES = {"step_s": 8.0, "step_n": 100, "prep_s": 1.0, "prep_n": 40, "prep_requests": 70}


def _read(name, run):
    return catalog.load_module("layer_metrics", name).read(run)


@pytest.mark.parametrize("name", READS)
def test_a_reader_divides_its_sum_by_its_count_or_finds_nothing(name):
    seconds, n = READS[name]
    assert _read(name, {"window_s": 40.0, "phase_delta": WINDOW}) == pytest.approx(
        1000.0 * WINDOW[seconds] / WINDOW[n]
    )
    one = {"window_s": 40.0, "phase_delta": {seconds: 0.75, n: 3}}
    assert _read(name, one) == pytest.approx(250.0)
    for nothing in (
        {"window_s": 40.0},  # no delta
        {"window_s": 40.0, "phase_delta": None},
        {"window_s": 40.0, "phase_delta": PARENT_PHASES},  # the parent's program
        {"window_s": 40.0, "phase_delta": {**WINDOW, n: 0}},  # a window that closed no such interval
        {"window_s": 40.0, "phase_delta": {n: 5}},  # a count without its seconds
    ):
        assert _read(name, nothing) is None, nothing


def test_the_means_of_a_window_add_up_to_the_loops_size_over_its_rate():
    """Little's law, as PERF.md section 5 checks it on the chip: 268 requests in
    the loop at 630 finished in 40 s stay 17.0 s each."""
    run = {"window_s": 40.0, "phase_delta": WINDOW}
    ms = {name: _read(name, run) for name in READS}
    sojourn_s = (sum(ms.values()) - ms["engine.request_itl_ms"] + 191 * ms["engine.request_itl_ms"]) / 1000.0
    assert sojourn_s == pytest.approx(268 / (630 / 40.0), rel=0.002)


@pytest.mark.parametrize("name", READS)
def test_a_reader_carries_what_its_entry_will_say_and_every_cell_could_report_it(name):
    """``BENCHMARK.json`` does not list the five yet (PERF.md section 7: a ``benchmark``
    PR's to append); the reader holds the entry's ``unit / layer / moves / source``."""
    bench = catalog.benchmark()
    reader = catalog.load_module("layer_metrics", name)
    assert (reader.UNIT, reader.LAYER, reader.MOVES, reader.SOURCE) == (
        "ms", "caption engine", "output_tok_per_s", "program_span"
    )
    assert reader.LAYER in {e["layer"] for e in bench["per_layer"]}  # the accepted layer's name
    # every cell runs the engine and reports what the metric moves: no `workloads` list
    for cell in (w["name"] for w in bench["workloads"]):
        assert reader.MOVES in catalog.load_cell(cell).end_to_end, cell
    for entry in (e for e in bench["per_layer"] if e["name"] == name):  # once it is listed
        assert entry == {
            "name": name, "unit": reader.UNIT, "better": "lower", "source": reader.SOURCE,
            "layer": reader.LAYER, "moves": reader.MOVES,
        }
