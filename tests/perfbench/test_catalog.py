"""The benchmark's files load, name each other correctly and stay inside the
contract's limits. No JAX here."""

import json
import re

import pytest

from perfbench import catalog

BENCH = catalog.benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
CELLS = [w["name"] for w in BENCH["workloads"]]
CONFIGS = [c["name"] for c in BENCH["configs"]]
PER_LAYER = [m["name"] for m in BENCH["per_layer"]]
END_TO_END = [m["name"] for m in BENCH["end_to_end"]]
WIDTH_KEYS = re.compile(r"(hidden|intermediate|latent|state|projection|_dim$|_rank$|head_dim|expansion|experts_per)")


def test_top_level_keys_and_limits():
    assert set(BENCH) == {
        "command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"
    }
    assert 1 <= BENCH["run_seconds"] <= 51 and isinstance(BENCH["run_seconds"], int)
    assert len((catalog.CHECKOUT / "BENCHMARK.json").read_bytes()) <= 64 * 1024
    assert 2 <= len(CELLS) <= 24 and 1 <= len(CONFIGS) <= 24
    assert 1 <= len(END_TO_END) <= 16 and 1 <= len(PER_LAYER) <= 128
    for word in BENCH["command"]:
        assert 1 <= len(word) <= 200 and not word.startswith("/") and ".." not in word
    four = [w for w in BENCH["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(CELLS) // 4)
    assert all(w["chips"] in (1, 4) for w in BENCH["workloads"])


def test_names_are_unique():
    for names in (CELLS, CONFIGS, PER_LAYER + END_TO_END):
        assert len(names) == len(set(names))
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))


@pytest.mark.parametrize("entry", BENCH["end_to_end"] + BENCH["per_layer"], ids=lambda e: e["name"])
def test_metric_entry(entry):
    allowed = {"name", "unit", "better", "source", "workloads"}
    allowed |= {"bound"} if entry["name"] in END_TO_END else {"layer", "moves"}
    assert set(entry) <= allowed
    assert NAME.match(entry["name"]) and UNIT.match(entry["unit"])
    assert entry["better"] in ("lower", "higher")
    assert set(entry.get("workloads", CELLS)) <= set(CELLS)
    if entry["name"] in END_TO_END:
        assert entry["source"] in ("host_clock", "device_trace")
        assert 0.01 <= entry["bound"] <= 0.1
    else:
        assert entry["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
        assert entry["moves"] in END_TO_END
        assert "\n" not in entry["layer"] and 1 <= len(entry["layer"]) <= 200
        moved = next(m for m in BENCH["end_to_end"] if m["name"] == entry["moves"])
        # a per-layer metric is reported only where the metric it moves is
        assert set(entry.get("workloads", CELLS)) <= set(moved.get("workloads", CELLS))


@pytest.mark.parametrize("name", PER_LAYER)
def test_layer_metric_reader(name):
    entry = next(m for m in BENCH["per_layer"] if m["name"] == name)
    reader = catalog.load_module("layer_metrics", name)
    assert (reader.UNIT, reader.LAYER, reader.MOVES, reader.SOURCE) == (
        entry["unit"], entry["layer"], entry["moves"], entry["source"]
    )
    assert callable(reader.read) and reader.__doc__


READERS = sorted({p.stem for p in (catalog.HERE / "layer_metrics").glob("*.py")} - {"__init__"})


def test_every_metric_has_a_reader_file():
    assert set(PER_LAYER) <= set(READERS)


@pytest.mark.parametrize("name", READERS)
def test_reader_file_loads_and_returns_nothing_where_there_is_nothing(name):
    # files of a cell that is not admitted yet (split) are kept loadable
    reader = catalog.load_module("layer_metrics", name)
    assert UNIT.match(reader.UNIT) and reader.MOVES and reader.LAYER
    assert reader.SOURCE in ("device_trace", "program_span", "program_counter", "host_clock")
    empty = {"trace": None, "window_s": 1.0, "compiles_in_window": 0,
             "device": {"memory_peak_bytes": 0, "kind": "TPU v5 lite"}}
    assert reader.read(empty) in (None, 0.0)


@pytest.mark.parametrize("cell_name", CELLS)
def test_cell_loads_and_cross_references(cell_name):
    cell = catalog.load_cell(cell_name)
    entry = next(w for w in BENCH["workloads"] if w["name"] == cell_name)
    assert NAME.match(cell_name) and NAME.match(entry["traffic"]) and NAME.match(entry["config"])
    assert cell_name == f"{entry['config']}.{entry['traffic']}"
    assert 1 <= len(entry["why"]) <= 200 and set(entry) == {"name", "config", "traffic", "chips", "why"}
    assert cell.chips == cell.config["chips"] == entry["chips"]
    assert "setup_s" in cell.end_to_end and len(cell.end_to_end) >= 2 and cell.per_layer
    assert (catalog.HERE / "drivers" / f"{cell.config['driver']}.py").is_file()
    assert (catalog.HERE / "traffic" / f"{cell.traffic['generator']}.py").is_file()
    assert set(cell.traffic.get("rehearse", {})) <= set(cell.traffic["params"])
    assert cell.traffic_params(True).keys() == cell.traffic_params(False).keys()


@pytest.mark.parametrize("config_name", CONFIGS)
def test_config_file(config_name):
    entry = next(c for c in BENCH["configs"] if c["name"] == config_name)
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert entry["file"].startswith(tuple(p + "/" for p in BENCH["paths"]))
    conf = json.loads((catalog.CHECKOUT / entry["file"]).read_text())
    assert conf["name"] == config_name and conf["source"] == entry["source"]
    assert conf["reduced"] == entry["reduced"] and len(entry["reduced"]) <= 16
    assert set(conf["reduced_why"]) == set(conf["reduced"])
    for key in entry["reduced"]:
        assert NAME.match(key) and not WIDTH_KEYS.search(key), f"{key} names a width"
    assert any(w["config"] == config_name for w in BENCH["workloads"])
    assert conf["assumed"] and conf["deployment"] and "check" in conf


def test_every_data_file_is_named_in_the_benchmark():
    # a superset: the split cell's files wait for a configuration that fills
    # a quarter of the chip's memory (PERF.md, open questions)
    assert {p.stem for p in (catalog.HERE / "configs").glob("*.json")} >= set(CONFIGS)
    assert {p.stem for p in (catalog.HERE / "workloads").glob("*.json")} >= set(CELLS)
    assert {p.stem for p in (catalog.HERE / "traffic").glob("*.json")} >= {
        w["traffic"] for w in BENCH["workloads"]
    }
    for p in (catalog.HERE / "workloads").glob("*.json"):
        cell = json.loads(p.read_text())
        assert (catalog.HERE / "configs" / f"{cell['config']}.json").is_file()
        assert (catalog.HERE / "traffic" / f"{cell['traffic']}.json").is_file()
        assert p.stem == f"{cell['config']}.{cell['traffic']}"


def test_paths_hold_only_allowed_file_names():
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for root in BENCH["paths"]:
        assert re.match(r"^[A-Za-z0-9_.\-/]{1,200}$", root)
        for p in (catalog.CHECKOUT / root).rglob("*"):
            if "__pycache__" in p.parts:
                continue
            assert ok.match(str(p.relative_to(catalog.CHECKOUT))), p


def test_peaks_table_refuses_an_unknown_device():
    assert catalog.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="not in roofline/peaks.json"):
        catalog.peaks("TPU v9 imaginary")


def test_the_tp4_cell_reuses_the_2b_cells_traffic_file_unchanged():
    a = catalog.load_cell("qwen2vl-2b.windows-32f")
    b = catalog.load_cell("qwen25vl-7b-tp4.windows-32f")
    assert a.traffic == b.traffic and a.traffic_name == b.traffic_name
