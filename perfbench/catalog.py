"""Finds the benchmark's files by the names ``BENCHMARK.json`` gives them.

No registry: a configuration, a cell, a traffic mix, a driver or a metric
reader exists because its file does. Nothing here imports JAX.
"""

from __future__ import annotations

import importlib
import importlib.util
import json
from dataclasses import dataclass, field
from pathlib import Path
from types import ModuleType

HERE = Path(__file__).resolve().parent
CHECKOUT = HERE.parent


def _read_json(path: Path) -> dict:
    if not path.is_file():
        raise FileNotFoundError(f"perfbench: no file {path.relative_to(CHECKOUT)}")
    return json.loads(path.read_text())


def benchmark() -> dict:
    return _read_json(CHECKOUT / "BENCHMARK.json")


@dataclass(frozen=True)
class Cell:
    """One entry of ``workloads``: a configuration under a traffic mix."""

    name: str
    chips: int
    config_name: str
    config: dict
    traffic_name: str
    traffic: dict  # {"generator": ..., "params": {...}, "rehearse": {...}}
    end_to_end: tuple[str, ...]  # metric names this cell reports with --trace 0
    per_layer: tuple[str, ...]  # metric names this cell reports with --trace 1
    harness: dict = field(default_factory=dict)  # the cell file's own "harness" values

    def traffic_params(self, rehearse: bool) -> dict:
        """The mix's parameters; ``harness`` values of the cell's own file
        (how long to trace, never what is sent) take the place of the mix's."""
        params = dict(self.traffic["params"], **self.harness)
        if rehearse:
            params.update(self.traffic.get("rehearse", {}))
        return params


def _metrics_of(entries: list[dict], cell: str) -> tuple[str, ...]:
    return tuple(m["name"] for m in entries if cell in m.get("workloads", [cell]))


def load_cell(name: str) -> Cell:
    bench = benchmark()
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        return _unlisted_cell(name, [w["name"] for w in bench["workloads"]])
    cell_file = _read_json(HERE / "workloads" / f"{name}.json")
    for key in ("config", "traffic"):
        if cell_file[key] != entry[key]:
            raise ValueError(
                f"perfbench: workloads/{name}.json says {key}={cell_file[key]!r}, "
                f"BENCHMARK.json says {entry[key]!r}"
            )
    cfg_entry = next(c for c in bench["configs"] if c["name"] == entry["config"])
    return Cell(
        name=name,
        chips=int(entry["chips"]),
        config_name=entry["config"],
        config=_read_json(CHECKOUT / cfg_entry["file"]),
        traffic_name=entry["traffic"],
        traffic=_read_json(HERE / "traffic" / f"{entry['traffic']}.json"),
        end_to_end=_metrics_of(bench["end_to_end"], name),
        per_layer=_metrics_of(bench["per_layer"], name),
        harness=cell_file.get("harness", {}),
    )


def _unlisted_cell(name: str, listed: list[str]) -> Cell:
    """A cell whose files are here and which ``BENCHMARK.json`` does not list
    (not admitted yet): it can be run, and reports no metric on its line."""
    path = HERE / "workloads" / f"{name}.json"
    if not path.is_file():
        raise KeyError(f"perfbench: unknown workload {name!r}; BENCHMARK.json has {listed}")
    cell_file = _read_json(path)
    config = _read_json(HERE / "configs" / f"{cell_file['config']}.json")
    return Cell(
        name=name, chips=int(config["chips"]), config_name=cell_file["config"], config=config,
        traffic_name=cell_file["traffic"],
        traffic=_read_json(HERE / "traffic" / f"{cell_file['traffic']}.json"),
        end_to_end=(), per_layer=(), harness=cell_file.get("harness", {}),
    )


def load_module(kind: str, name: str) -> ModuleType:
    """``perfbench/<kind>/<name>.py``. Metric names hold dots, so the file is
    loaded by path where its name is no Python identifier."""
    path = HERE / kind / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"perfbench: no {kind} file {path.relative_to(CHECKOUT)}")
    if name.isidentifier():
        return importlib.import_module(f"perfbench.{kind}.{name}")
    spec = importlib.util.spec_from_file_location(
        f"perfbench.{kind}.{name.replace('.', '__').replace('-', '_')}", path
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def peaks(device_kind: str) -> dict:
    table = _read_json(HERE / "roofline" / "peaks.json")
    if device_kind not in table:
        raise KeyError(
            f"perfbench: device_kind {device_kind!r} is not in roofline/peaks.json "
            f"({sorted(k for k in table if not k.startswith('_'))}); add it with its source"
        )
    return table[device_kind]
