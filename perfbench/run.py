"""One process, one cell, once.

    python3 -m perfbench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Loads, warms only the cell's own shapes, measures for ``--seconds`` and prints
one JSON object as the last line of standard output: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` and, with ``--trace 1``,
``breakdown``. With ``--trace 0`` the metrics are the cell's end-to-end
metrics, with ``--trace 1`` its per-layer metrics, each read by its own file
under ``layer_metrics/``. Everything else worth reading goes on earlier lines.

It fails, with no result line, when JAX finds no TPU or fewer chips than the
cell asks for. ``--rehearse`` runs the same control flow at the tiny presets on
the CPU (four virtual devices for a four-chip cell): its line carries the CPU's
``device`` block and no device metric.
"""

from __future__ import annotations

from perfbench import measure  # first: its import is the start of setup_s

import argparse
import json
import os
import sys

from perfbench import catalog
from perfbench.measure import log

def parse(argv):
    p = argparse.ArgumentParser(prog="perfbench.run", description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--seconds", type=float, default=None)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--rehearse", action="store_true", help="tiny presets on the CPU; no device numbers")
    return p.parse_args(argv)


def result_line(cell, record: dict, *, trace: bool, rehearse: bool) -> dict:
    bench = catalog.benchmark()
    device = record["device"] = measure.device_block(record["devices"])  # readers read it too
    metrics: dict[str, dict] = {}

    def keep(name, value, entry):
        if rehearse and entry["source"] != "program_counter":
            # a time, rate or share from the CPU is never written under the
            # name of a device metric: it goes on an earlier line only
            log(f"rehearsal on the CPU, not a device number: {name} = {value:.6g} {entry['unit']}")
        else:
            metrics[name] = {"value": float(value), "unit": entry["unit"]}

    if not cell.end_to_end:
        log(f"{cell.name} is not listed in BENCHMARK.json: its numbers go on these lines only")
        for name, value in record["end_to_end"].items():
            log(f"unlisted cell: {name} = {value:.6g}")
        names = sorted(p.stem for p in (catalog.HERE / "layer_metrics").glob("*.*.py")) if trace else []
        for name in names:
            reader = catalog.load_module("layer_metrics", name)
            if reader.MOVES in record["end_to_end"]:
                log(f"unlisted cell: {name} = {reader.read(record)} {reader.UNIT}")
    elif not trace:
        entries = {e["name"]: e for e in bench["end_to_end"]}
        for name in cell.end_to_end:
            keep(name, record["end_to_end"][name], entries[name])
    else:
        entries = {e["name"]: e for e in bench["per_layer"]}
        for name in cell.per_layer:
            entry = entries[name]
            reader = catalog.load_module("layer_metrics", name)
            for key in ("unit", "layer", "moves", "source"):
                if getattr(reader, key.upper()) != entry[key]:
                    raise ValueError(
                        f"layer_metrics/{name}.py says {key}={getattr(reader, key.upper())!r}, "
                        f"BENCHMARK.json says {entry[key]!r}"
                    )
            value = reader.read(record)
            if value is None:
                log(f"metric {name}: nothing to read, left out")
                continue
            keep(name, value, entry)
    line = {
        "correct": bool(record["correct"]),
        "attempted": int(record["attempted"]),
        "failed": int(record["failed"]),
        "metrics": metrics,
        "device": device,
    }
    summary = record.get("trace")
    if trace and summary is not None:
        line["device"]["busy_s"] = summary.busy_s
        line["device"]["window_s"] = summary.window_s
        line["breakdown"] = summary.breakdown()
    return line


def main(argv=None) -> int:
    args = parse(argv)
    cell = catalog.load_cell(args.workload)
    seconds = args.seconds if args.seconds is not None else float(catalog.benchmark()["run_seconds"])
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        if cell.chips > 1:
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "") + f" --xla_force_host_platform_device_count={cell.chips}"
            ).strip()
    devices = measure.require_devices(cell.chips, rehearse=args.rehearse)
    log(
        f"cell {cell.name}: config {cell.config_name}, traffic {cell.traffic_name}, "
        f"{len(devices)} x {devices[0].device_kind!r}, seed {args.seed}, {seconds:g} s, "
        f"trace {args.trace}" + (", REHEARSAL on the CPU: no number here is a device number" if args.rehearse else "")
    )
    if not args.rehearse:
        catalog.peaks(devices[0].device_kind)  # an unknown device is an error, not a default
    driver = catalog.load_module("drivers", cell.config["driver"])
    record = driver.run(
        cell, seed=args.seed, seconds=seconds, trace=bool(args.trace), rehearse=args.rehearse,
        devices=devices, clock=measure.SetupClock(),
    )
    line = result_line(cell, record, trace=bool(args.trace), rehearse=args.rehearse)
    sys.stdout.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    # background threads of the program (prep, runner pools) are daemons or
    # done; leave without waiting for device work nobody will read
    os._exit(code)
