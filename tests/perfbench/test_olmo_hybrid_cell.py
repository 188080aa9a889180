"""The Olmo-Hybrid cell's own pieces of the yardstick: its operation and byte
counts against hand counts, its four readers on a hand-made record (and None
where there is nothing to read), this flavor's scopes found in a compiled text,
its configuration file against the catalog row and the flavor, the benchmark's
entries, and a rehearsal of the control flow."""

import dataclasses
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from perfbench import catalog
from perfbench.roofline import delta_bytes

CELL = "olmo-hybrid-7b-pp2.text-rewrite"
CONFIG = "olmo-hybrid-7b-pp2"
NEW = [
    "kernel.delta_decode_hbm_share", "kernel.delta_prefill_roofline_share", "kernel.delta_time_share",
    "engine.delta_state_gib",
]
SHAPE = dict(n_layers=12, n_heads=30, key_dim=96, value_dim=192)
STATE = 30 * 96 * 192 * 4  # a row's state in one layer: 2,211,840 B


def _reader(name):
    return catalog.load_module("layer_metrics", name)


class _Trace:
    busy_s_by_chip = [4.0]
    kernel_s = {"paged_decode": 0.3, "paged_prefill": 0.1}


def _record():
    return {
        "trace": _Trace(), "device": {"kind": "TPU v5 lite", "memory_peak_bytes": 1},
        "delta_trace": {"kernel_s": {"delta_decode": 0.5}, "kernel_calls": {"delta_decode": 1200}},
        "slice": {
            "decode_lengths": [[300, 500, 700]] * 100,  # three live rows a step, a hundred steps
            "prefill_valid": [[256], [256, 100], [1]],  # 4 + 4 + 2 + 1 chunks that held a token
            "delta_shape": SHAPE,
        },
        "scope_s": {
            ("prefill", "delta.prefill_scan"): 0.2, ("prefill", "delta.conv"): 0.05, ("decode", "delta.conv"): 0.05,
            ("decode", "delta.gate_norm"): 0.4, ("decode", "attn.full"): 0.3,
        },
        "recurrent": {
            "recurrent_state_bytes_per_chip": 5 * 2**28, "recurrent_rows_total": 44, "recurrent_rows_used_peak": 44,
            "prefix_state_snapshots": 9, "delta_decode_calls": 1200, "delta_prefill_chunks": 132,
        },
    }


def test_bytes_and_operations_against_a_hand_count():
    # the issue's numbers: 26.5 MB of state a row over the twelve layers, 53 MB read and written a decode step
    assert STATE == 2_211_840 and 12 * STATE == 26_542_080
    small = (2 * 30 * 96 + 2 * 30 * 192 + 2 * 30) * 4  # q, k, v in and o out, g and beta
    assert delta_bytes.delta_decode_bytes(1, **SHAPE) == 12 * (2 * STATE + small) == 53_916_480
    assert delta_bytes.delta_decode_bytes(44, **SHAPE) == 44 * 53_916_480
    assert delta_bytes.delta_decode_flops(1, **SHAPE) == 12 * 7 * 30 * 96 * 192
    # a state element: 8 bytes against 7 operations, so memory bounds the step by far
    assert delta_bytes.delta_decode_bytes(1, **SHAPE) / 819e9 > 100 * delta_bytes.delta_decode_flops(1, **SHAPE) / 197e12
    # a 64-token chunk a head: K K^T, Q K^T, the inverse, its two products, the three with the state, tril(QK^T) V'
    a_chunk = 2 * 64 * 64 * 96 + 64**3 // 3 + 64 * 64 * (96 + 192) + 3 * 64 * 96 * 192 + 64 * 64 * 192
    assert delta_bytes.delta_prefill_flops(1, n_layers=1, n_heads=1, key_dim=96, value_dim=192) == 2 * a_chunk
    assert delta_bytes.delta_prefill_flops(11, **SHAPE) == 11 * 12 * 30 * 2 * a_chunk
    assert delta_bytes.delta_prefill_bytes(1, n_layers=1, n_heads=1, key_dim=96, value_dim=192) == (64 * 576 + 128) * 4
    assert delta_bytes.CHUNK == 64


def test_the_four_readers_on_a_hand_made_record():
    run = _record()
    moved = 300 * delta_bytes.delta_decode_bytes(1, **SHAPE)
    got = _reader("kernel.delta_decode_hbm_share").read(run)
    assert got == pytest.approx(100 * moved / 819e9 / 0.5) and 0 < got < 100
    chunks = 4 + 4 + 2 + 1
    least = max(
        delta_bytes.delta_prefill_flops(chunks, **SHAPE) / 197e12, delta_bytes.delta_prefill_bytes(chunks, **SHAPE) / 819e9
    )
    got = _reader("kernel.delta_prefill_roofline_share").read(run)
    assert got == pytest.approx(100 * least / 0.2) and 0 < got < 100
    # the kernel, the scan and the convolutions; neither the gate's norm nor the attention layers
    assert _reader("kernel.delta_time_share").read(run) == pytest.approx(100 * (0.5 + 0.2 + 0.05 + 0.05) / 4.0)
    assert _reader("engine.delta_state_gib").read(run) == 1.25
    # the paged kernels' own share is the trace's, untouched by the second reduction
    assert _reader("kernel.paged_attention_time_share").read(run) == pytest.approx(100 * 0.4 / 4.0)
    # a scan that is a kernel one day is read by its name, beside whatever stays under the scope
    run["delta_trace"]["kernel_s"]["delta_prefill"] = 0.1
    assert _reader("kernel.delta_prefill_roofline_share").read(run) == pytest.approx(100 * least / 0.3)
    run["delta_trace"]["kernel_s"]["delta_decode"] = 1e-6  # faster than the memory could be: not this metric's bound
    assert _reader("kernel.delta_decode_hbm_share").read(run) > 100


@pytest.mark.parametrize("name", NEW)
def test_a_reader_finds_nothing_in_a_program_without_the_mechanism(name):
    """What the parent commit's runs and the other cells' records look like: a
    trace of the paged kernels, Granite's store and state-space kernel, no
    delta-rule kernel, no scopes."""
    run = {
        "trace": _Trace(), "device": {"kind": "TPU v5 lite", "memory_peak_bytes": 1},
        "slice": {"decode_lengths": [[100]], "kv_shape": {}, "ssm_shape": {}},
        "ssm_trace": {"kernel_s": {"ssm_decode": 0.4}, "kernel_calls": {"ssm_decode": 36}},
        "recurrent": {"recurrent_state_bytes_per_chip": 2**32, "ssm_decode_calls": 36},
        "stats_delta": {"paged_kernel_steps": 10},
    }
    assert _reader(name).read(run) is None
    assert _reader(name).read(dict(run, recurrent={}, scope_s=None, trace=None, delta_trace=None)) is None
    assert _reader(name).read({"device": {"kind": "TPU v5 lite"}}) is None
    empty = dict(run, delta_trace={"kernel_s": {}, "kernel_calls": {}}, scope_s={}, slice={"decode_lengths": [], "delta_shape": SHAPE})
    if name != "kernel.delta_time_share":  # (0% of the device's time is a reading)
        assert _reader(name).read(empty) is None


def test_the_cell_reports_the_new_metrics_and_the_old_cells_do_not():
    cell = catalog.load_cell(CELL)
    assert set(NEW) <= set(cell.per_layer)
    for old in ("qwen2vl-2b.text-rewrite", "granite-4.0-h-micro.text-rewrite", "deepseek-v2-ep8.text-rewrite"):
        other = catalog.load_cell(old)
        assert not set(NEW) & set(other.per_layer)
        assert other.traffic == cell.traffic  # the fourth architecture on ONE traffic file
    assert "engine.recurrent_state_gib" not in cell.per_layer  # Granite's own, by its `workloads`
    assert cell.chips == 1 and cell.end_to_end == ("output_tok_per_s", "setup_s")
    # every metric without a `workloads` list is this cell's too
    everywhere = [m["name"] for m in catalog.benchmark()["per_layer"] if "workloads" not in m]
    assert set(everywhere) <= set(cell.per_layer) and "kernel.paged_attention_time_share" in everywhere
    # the issue's traffic, letter for letter
    p = cell.traffic["params"]
    assert (p["frames"], p["prefix_tokens"], p["output_tokens"], p["backlog"], p["trace_seconds"]) == (0, 64, 192, 4, 8.0)
    assert p["prompt_tokens"] == {"min": 144, "max": 592, "step": 64} and cell.traffic["generator"] == "caption_requests"
    assert cell.traffic_params(False)["warm_rows"] == 8 and cell.harness == {"warm_rows": 8}


def test_benchmark_gained_entries_and_lost_none():
    """Written so that the NEXT cell does not break it: what the benchmark had is
    all there in its order, and this PR's entries come after it."""
    bench = catalog.benchmark()
    configs = [c["name"] for c in bench["configs"]]
    cells = [w["name"] for w in bench["workloads"]]
    metrics = [m["name"] for m in bench["per_layer"]]
    had = ["qwen2vl-2b", "qwen25vl-7b-tp4", "granite-4.0-h-micro", "deepseek-v2-ep8", "trinity-large-ep8", "keye-vl2-a3b-ep8"]
    assert configs[:6] == had and configs[6] == CONFIG
    assert cells[6] == "keye-vl2-a3b-ep8.digest-2k-30k" and cells[7] == CELL
    at = metrics.index("engine.index_pool_gib")  # the last the benchmark had
    assert metrics[at + 1 : at + 5] == NEW
    for m in bench["per_layer"][at + 1 : at + 5]:
        assert m["workloads"] == [CELL] and m["moves"] == "output_tok_per_s"
        reader = _reader(m["name"])
        assert (reader.UNIT, reader.LAYER, reader.MOVES, reader.SOURCE) == (m["unit"], m["layer"], m["moves"], m["source"])
        assert set(m) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
    assert sum(w["chips"] == 4 for w in bench["workloads"][:8]) == 1 and bench["run_seconds"] == 40
    entry = bench["workloads"][7]
    assert set(entry) == {"name", "config", "traffic", "chips", "why"} and len(entry["why"]) <= 200
    assert (entry["config"], entry["traffic"], entry["chips"]) == (CONFIG, "text-rewrite", 1)
    # what test_catalog.py::test_config_file asserts, with widths told from depth
    # (its pattern takes the word "hidden" in num_hidden_layers for a width)
    entry = bench["configs"][6]
    conf = json.loads((catalog.CHECKOUT / entry["file"]).read_text())
    assert set(entry) == {"name", "source", "file", "reduced", "why"} and entry["file"].startswith("perfbench/")
    assert len(entry["reduced"]) <= 16 and any(w["config"] == entry["name"] for w in bench["workloads"])
    widths = ("hidden_size", "intermediate", "latent", "state", "projection", "_dim", "_rank", "expansion", "experts_per")
    assert not [k for k in conf["reduced"] if any(w in k for w in widths)]
    assert conf["assumed"] and conf["deployment"] and "check" in conf and len(entry["why"]) <= 200


def test_config_file_is_the_catalog_row_and_the_flavor():
    from cosmos_curate_tpu.models.vlm.model import GatedDeltaConfig, vlm_flavor
    from perfbench.drivers.caption_engine_delta import check_config_file

    conf = catalog.load_cell(CELL).config
    flavor = vlm_flavor(conf["flavor"])
    check_config_file(conf, flavor.cfg, flavor.kv_lanes)  # raises where they disagree
    with pytest.raises(ValueError, match="linear_value_head_dim"):
        delta = dataclasses.replace(flavor.cfg.gated_delta, value_dim=128)
        check_config_file(conf, dataclasses.replace(flavor.cfg, gated_delta=delta), flavor.kv_lanes)
    with pytest.raises(ValueError, match="assumed.block"):
        check_config_file(conf, dataclasses.replace(flavor.cfg, pre_norm=True), flavor.kv_lanes)
    with pytest.raises(ValueError, match="kv_lanes"):
        check_config_file(conf, flavor.cfg, ((1024, 8),))
    assert isinstance(flavor.cfg.gated_delta, GatedDeltaConfig) and flavor.prefill_rows is None
    # the published widths, uncut
    for key, value in dict(
        hidden_size=3840, intermediate_size=11008, num_attention_heads=30, num_key_value_heads=30, vocab_size=100352,
        linear_num_key_heads=30, linear_num_value_heads=30, linear_key_head_dim=96, linear_value_head_dim=192,
        linear_conv_kernel_dim=4, linear_allow_neg_eigval=True, rms_norm_eps=1e-6, tie_word_embeddings=False,
    ).items():
        assert conf[key] == value, key
    assert conf["assumed"]["head_dim"] == 128 == 3840 // 30
    assert conf["reduced"] == ["num_hidden_layers", "layer_types", "max_position_embeddings"]
    assert conf["num_hidden_layers"] == 16 and conf["layer_types"] == (["linear_attention"] * 3 + ["full_attention"]) * 4
    assert conf["max_position_embeddings"] == 4096 == flavor.cfg.max_seq
    assert conf["published"] == {"num_hidden_layers": 32, "max_position_embeddings": 65536}
    assert conf["serving"]["kv_lanes"] == [[1024, 40], [4096, 4]] and conf["serving"]["block_size"] == 16
    assert sum(n for _, n in conf["serving"]["kv_lanes"]) >= 32  # the issue's floor of rows
    assert "ONE CHIP OF TWO" in conf["deployment"] and "pipeline" in conf["deployment"]
    for point in ("linear_layer", "block", "position_embedding", "decay_init", "branch_norm_init", "ssm_state_dtype", "weights"):
        assert conf["assumed"][point], point
    for limit in ("reference_rel_tol", "state_rms_tol", "decode_rel_tol", "xla_path_rel_tol"):
        assert 0 < conf["check"][limit] < 1 and len(conf["check"][limit + "_why"]) > 100, limit
    assert conf["check"]["text_tokens"] == [200, 700] and conf["check"]["decode_steps"] == 8
    assert conf["check"]["lower_precision_readings"]
    entry = next(c for c in catalog.benchmark()["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == conf["reduced"] and entry["source"] == conf["source"]
    assert set(conf["reduced_why"]) == set(conf["reduced"]) and conf["name"] == entry["name"]
    catalog_file = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog_file):  # every number of the catalog row, or listed as reduced
        rows = [json.loads(line) for line in open(catalog_file) if line.strip()]
        row = next(r for r in rows if r["name"] == "Olmo-Hybrid-7B")
        assert conf["source"] == row["source_url"]
        assert {k for k, v in row["config"].items() if conf.get(k) != v} == set(conf["reduced"])
        assert conf["layer_types"] == row["config"]["layer_types"][:16]


def test_this_flavors_scopes_are_found_in_a_compiled_text():
    """``caption_engine_sparse.scope_maps`` told the delta rule's scopes: the
    instructions under ``delta.prefill_scan`` and ``delta.conv`` of a compiled
    program, which is how the plain-XLA scan's device time is read."""
    import jax
    import jax.numpy as jnp

    from perfbench.drivers import caption_engine_sparse as scoped
    from perfbench.drivers.caption_engine_delta import DELTA_KERNELS, DELTA_SCOPES, scope_maps, store_layout

    assert DELTA_SCOPES.search("jit(f)/layer_0/mixer/delta.prefill_scan/dot_general").group(0) == "delta.prefill_scan"
    assert DELTA_SCOPES.search("jit(f)/layer_3/attn.full/paged").group(0) == "attn.full"

    def program(x, y):
        with jax.named_scope("delta.conv"):
            x = jnp.tanh(x) * 2.0
        with jax.named_scope("delta.prefill_scan"):
            out = jnp.cumsum(x @ y, axis=0)
        return out + 1.0

    shapes = (jax.ShapeDtypeStruct((8, 64), jnp.float32), jax.ShapeDtypeStruct((64, 16), jnp.float32))
    maps = scope_maps({"prefill": [(jax.jit(program), shapes)]})
    assert "delta.prefill_scan" in set(maps["prefill"].values())
    assert scoped.SCOPES.pattern.startswith("attn")  # the other cell's pattern is as it was
    import re

    assert re.search(DELTA_KERNELS["delta_decode"], "_delta_decode") and re.search(DELTA_KERNELS["delta_decode"], "delta_decode.3")
    # the reference's [heads, dk, dv] as a row of the store holds it
    state = np.arange(2 * 3 * 4, dtype=np.float32).reshape(2, 3, 4)
    assert store_layout(state).shape == (3, 8) and store_layout(state)[1, 4:].tolist() == state[1, 1].tolist()


def test_the_xla_engine_is_handed_the_kernel_engines_first_token():
    """Two engines that round differently may choose differently between two
    nearly equal logits (seed 1036307914 on the chip, PR 44): the XLA engine
    keeps its own first logits for the comparison and decodes from the kernel
    engine's token."""
    from types import SimpleNamespace

    from perfbench.drivers.caption_engine_delta import hand_first_logits

    sampled_from, kept = {}, {}
    engine = SimpleNamespace(_start_slot=lambda lane, i, req, t, rope, row: sampled_from.__setitem__(req.request_id, row))
    theirs, own = np.array([0.0, 1.0, 0.99]), np.array([0.0, 0.99, 1.0])
    hand_first_logits(engine, "check-xla", theirs)
    inner = engine._start_slot

    def spy(lane, i, req, t, rope, row):  # as `_Private` wraps whatever is there
        kept[req.request_id] = row
        return inner(lane, i, req, t, rope, row)

    engine._start_slot = spy
    for name in ("check-xla", "hold-check-xla"):
        engine._start_slot(None, 0, SimpleNamespace(request_id=name), 5, 5, own)
    assert kept["check-xla"] is own and sampled_from["check-xla"] is theirs
    assert sampled_from["hold-check-xla"] is own


def test_cell_rehearses_on_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    out = subprocess.run(
        [sys.executable, "-m", "perfbench.run", "--workload", CELL, "--seed", "4400000002",
         "--seconds", "4", "--trace", "1", "--rehearse"],
        capture_output=True, text=True, env=env, cwd=str(catalog.CHECKOUT), timeout=600,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    # on the CPU only the program's counters are written under a metric's name
    assert set(line["metrics"]) == {"device.compiles_in_window", "engine.delta_state_gib"}
    assert line["metrics"]["device.compiles_in_window"]["value"] == 0
    assert "first linear-attention layer's state in the store" in out.stdout
    assert "both from the kernel engine's first token" in out.stdout and "FAILED" not in out.stdout
