"""The Solar-Open2 cell's own pieces of the yardstick: its operation and byte
counts against hand counts, its ten readers on a hand-made record (and None
where there is nothing to read), its configuration file against the catalog row
and the flavor, the benchmark's entries (all it had, unchanged, the new ones
after), the bounded ramp, the two stated precisions held where nothing has rounded the
inputs (and a lower-precision control that comes out not correct), and a
rehearsal of the control flow."""

import dataclasses
import json
import os
import subprocess
import sys
from types import SimpleNamespace

import pytest

from perfbench import catalog
from perfbench.roofline import kda_bytes

CELL = "solar-open2-ep8.text-rewrite"
CONFIG = "solar-open2-ep8"
NEW = [
    "kernel.kda_decode_hbm_share", "kernel.kda_prefill_roofline_share", "kernel.kda_time_share",
    "kernel.kda_cell_expert_time_share", "engine.kda_state_gib", "engine.kda_cell_assignments_per_program",
    "kernel.kda_cell_expert_matmul_roofline_share", "kernel.kda_cell_paged_decode_hbm_share",
    "engine.kda_cell_prefill_device_share", "engine.kda_cell_live_assignments_per_program",
]
SHAPE = dict(n_layers=3, n_heads=64, key_dim=128, value_dim=128)
STATE = 64 * 128 * 128 * 4  # a row's state in one layer: 4 MiB


def _reader(name):
    return catalog.load_module("layer_metrics", name)


class _Trace:
    busy_s_by_chip = [8.0]
    kernel_s = {"paged_decode": 0.3, "paged_prefill": 0.1}


def _record():
    return {
        "trace": _Trace(), "device": {"kind": "TPU v5 lite", "memory_peak_bytes": 1},
        "delta_trace": {"kernel_s": {"delta_decode": 2.0}, "kernel_calls": {"delta_decode": 600}},
        "expert_trace": {"kernel_s": {"expert_matmul": 1.6}, "kernel_calls": {"expert_matmul": 4000}},
        "slice": {
            "decode_lengths": [[300, 500, 700, 400]] * 200,  # four live rows a step, two hundred steps
            "prefill_valid": [[256], [256, 100], [1]],  # 4 + 4 + 2 + 1 chunks that held a token
            "kda_shape": SHAPE,
            "kv_shape": dict(n_layers=1, n_kv_heads=8, head_dim=128, block_size=16, dtype_bytes=2),
            "attention_shape": dict(n_layers=1, n_heads=64, head_dim=128),
            "expert_shape": dict(dim=4096, width=1280, held=40, dtype_bytes=2, sparse_layers=4, router_outputs=320, top_k=8),
        },
        "program_s": {"prefill": [3.0, 50], "decode": [4.5, 200], "other": [0.5, 9]},
        "scope_s": {
            ("prefill", "delta.prefill_scan"): 0.8, ("prefill", "delta.conv"): 0.1, ("decode", "delta.conv"): 0.1,
            ("decode", "delta.gate_norm"): 0.4, ("decode", "attn.full"): 0.3,
        },
        "stats_delta": {"paged_kernel_steps": 200},
        "kda": {
            "recurrent_state_bytes_per_chip": 13 * 2**28, "recurrent_rows_total": 264, "recurrent_rows_used_peak": 264,
            "prefix_state_snapshots": 9, "delta_decode_calls": 600, "delta_prefill_chunks": 33,
            "expert_assignments_held": 200 * 1000, "expert_assignments_held_live": 200 * 600,
        },
    }


def test_bytes_and_operations_against_a_hand_count():
    # the issue's numbers: 4 MiB a row a layer, 12 MiB of state a row
    assert STATE == 4 * 2**20 and 3 * STATE == 12 * 2**20
    small = (3 * 64 * 128 + 2 * 64 * 128 + 64) * 4  # q, k and the decay column; v in and o out; beta
    assert kda_bytes.kda_decode_bytes(1, **SHAPE) == 3 * (2 * STATE + small) == 25_658_112
    assert kda_bytes.kda_decode_bytes(256, **SHAPE) == 256 * 25_658_112  # 6.6 GB a full decode program
    assert kda_bytes.kda_decode_flops(1, **SHAPE) == 3 * 7 * 64 * 128 * 128
    # a state element: 8 bytes against 7 operations, so memory bounds the step by far
    assert kda_bytes.kda_decode_bytes(1, **SHAPE) / 819e9 > 100 * kda_bytes.kda_decode_flops(1, **SHAPE) / 197e12
    # a 64-token chunk a head: A and QK with the decay a term, the inverse, its two products, the three with the state, tril(QK) V'
    a_chunk = 4 * 64 * 64 * 128 + 64**3 // 3 + 64 * 64 * 256 + 3 * 64 * 128 * 128 + 64 * 64 * 128
    assert kda_bytes.kda_prefill_flops(1, n_layers=1, n_heads=1, key_dim=128, value_dim=128) == 2 * a_chunk
    assert kda_bytes.kda_prefill_flops(11, **SHAPE) == 11 * 3 * 64 * 2 * a_chunk
    assert kda_bytes.kda_prefill_bytes(1, n_layers=1, n_heads=1, key_dim=128, value_dim=128) == (64 * 640 + 64) * 4
    assert kda_bytes.CHUNK == 64


def test_the_ten_readers_on_a_hand_made_record():
    run = _record()
    moved = 800 * kda_bytes.kda_decode_bytes(1, **SHAPE)
    got = _reader("kernel.kda_decode_hbm_share").read(run)
    assert got == pytest.approx(100 * moved / 819e9 / 2.0) and 0 < got < 100
    chunks = 4 + 4 + 2 + 1
    least = max(kda_bytes.kda_prefill_flops(chunks, **SHAPE) / 197e12, kda_bytes.kda_prefill_bytes(chunks, **SHAPE) / 819e9)
    got = _reader("kernel.kda_prefill_roofline_share").read(run)
    assert got == pytest.approx(100 * least / 0.8) and 0 < got < 100
    # the kernel, the scan and the convolutions; neither the gate's norm nor the attention layer
    assert _reader("kernel.kda_time_share").read(run) == pytest.approx(100 * (2.0 + 0.8 + 0.1 + 0.1) / 8.0)
    assert _reader("kernel.kda_cell_expert_time_share").read(run) == pytest.approx(100 * 1.6 / 8.0)
    assert _reader("engine.kda_state_gib").read(run) == 3.25
    assert _reader("engine.kda_cell_assignments_per_program").read(run) == 1000.0
    assert _reader("engine.kda_cell_live_assignments_per_program").read(run) == 600.0
    assert _reader("engine.kda_cell_prefill_device_share").read(run) == pytest.approx(100 * 3.0 / 8.0)
    # the one attention layer's K/V: 8 heads x 128 x 2 B, K and V, whole pages of 16 positions (300 -> 304)
    kv = 200 * (304 + 512 + 704 + 400) * 2 * 8 * 128 * 2
    assert _reader("kernel.kda_cell_paged_decode_hbm_share").read(run) == pytest.approx(100 * kv / 819e9 / 0.3)
    # 2,000 passes of 40 experts' three 4096 x 1280 tables, as far as a program's tokens touch them (200
    # decode programs of 4 live rows, prefill programs of 256, 356 and 1 valid tokens: a token makes 8
    # choices among 320), and the decode programs' 200,000 assignments
    touched = (200 * (1 - (319 / 320) ** 32) + sum(1 - (319 / 320) ** (8 * n) for n in (256, 356, 1))) / 203
    assert 0.09 < touched < 0.11  # four live rows touch a tenth of the experts
    tables, rows = 2000 * 40 * 3 * 4096 * 1280 * 2, 200_000 * (2 * 4096 + 3 * 1280) * 2
    got = _reader("kernel.kda_cell_expert_matmul_roofline_share").read(run)
    assert got == pytest.approx(100 * (touched * tables + rows) / 819e9 / 1.6)
    full = _reader("kernel.kda_cell_expert_matmul_roofline_share").touched_share(256, router_outputs=320, top_k=8)
    assert 0.998 < full < 1 and _reader("kernel.kda_cell_expert_matmul_roofline_share").touched_share(0, router_outputs=320, top_k=8) == 0
    # the paged kernels' own share is the trace's, untouched by the other reductions
    assert _reader("kernel.paged_attention_time_share").read(run) == pytest.approx(100 * 0.4 / 8.0)
    # a scan that is a kernel one day is read by its name, beside whatever stays under the scope
    run["delta_trace"]["kernel_s"]["delta_prefill"] = 0.2
    assert _reader("kernel.kda_prefill_roofline_share").read(run) == pytest.approx(100 * least / 1.0)
    run["delta_trace"]["kernel_s"]["delta_decode"] = 1e-6  # faster than the memory could be: not this metric's bound
    assert _reader("kernel.kda_decode_hbm_share").read(run) > 100


@pytest.mark.parametrize("name", NEW)
def test_a_reader_finds_nothing_in_a_program_without_the_mechanism(name):
    """What the parent commit's runs and the other cells' records look like:
    Olmo's delta-rule kernel, scopes and store under ITS keys, DeepSeek's
    grouped matmul, no ``kda`` block and no ``kda_shape``."""
    run = {
        "trace": _Trace(), "device": {"kind": "TPU v5 lite", "memory_peak_bytes": 1},
        "slice": {"decode_lengths": [[100]], "prefill_valid": [[256]], "kv_shape": {}, "delta_shape": {}},
        "delta_trace": {"kernel_s": {"delta_decode": 0.4}, "kernel_calls": {"delta_decode": 36}},
        "expert_trace": {"kernel_s": {"expert_matmul": 0.4}, "kernel_calls": {"expert_matmul": 36}},
        "scope_s": {("prefill", "delta.prefill_scan"): 0.2},
        "recurrent": {"recurrent_state_bytes_per_chip": 2**32, "delta_decode_calls": 36},
        "latent": {"expert_assignments_held": 99}, "windowed": {"expert_assignments_held": 99},
        "stats_delta": {"paged_kernel_steps": 10},
    }
    assert _reader(name).read(run) is None
    assert _reader(name).read(dict(run, scope_s=None, trace=None, delta_trace=None, expert_trace=None)) is None
    assert _reader(name).read({"device": {"kind": "TPU v5 lite"}}) is None


def test_the_cell_reports_the_new_metrics_and_the_old_cells_do_not():
    cell = catalog.load_cell(CELL)
    assert set(NEW) <= set(cell.per_layer)
    for old in ("qwen2vl-2b.text-rewrite", "granite-4.0-h-micro.text-rewrite", "deepseek-v2-ep8.text-rewrite",
                "olmo-hybrid-7b-pp2.text-rewrite"):
        other = catalog.load_cell(old)
        assert not set(NEW) & set(other.per_layer)
        assert other.traffic == cell.traffic  # the fifth architecture on ONE traffic file
    for theirs in ("engine.recurrent_state_gib", "engine.delta_state_gib", "kernel.delta_decode_hbm_share",
                   "kernel.expert_time_share", "kernel.held_expert_time_share"):
        assert theirs not in cell.per_layer  # the other cells' own, by their `workloads`
    assert cell.chips == 1 and cell.end_to_end == ("output_tok_per_s", "setup_s")
    # every metric without a `workloads` list is this cell's too
    everywhere = [m["name"] for m in catalog.benchmark()["per_layer"] if "workloads" not in m]
    assert set(everywhere) <= set(cell.per_layer) and "kernel.paged_attention_time_share" in everywhere
    # the issue's traffic, letter for letter
    p = cell.traffic["params"]
    assert (p["frames"], p["prefix_tokens"], p["output_tokens"], p["backlog"], p["trace_seconds"]) == (0, 64, 192, 4, 8.0)
    assert p["prompt_tokens"] == {"min": 144, "max": 592, "step": 64} and cell.traffic["generator"] == "caption_requests"
    # the cell's own file says how many rows to warm and how long to trace, never what is sent
    assert cell.harness == {"warm_rows": 8, "trace_seconds": 4.0}
    assert {k: v for k, v in cell.traffic_params(False).items() if k not in cell.harness} == {
        k: v for k, v in p.items() if k not in cell.harness}


def test_benchmark_gained_entries_and_lost_none():
    """Written so that the NEXT cell does not break it: what the benchmark had
    (the parent commit's seven configurations, eight cells and 43 per-layer
    metrics, by name and in order) is all there, and this PR's come after it."""
    bench = catalog.benchmark()
    configs = [c["name"] for c in bench["configs"]]
    cells = [w["name"] for w in bench["workloads"]]
    metrics = [m["name"] for m in bench["per_layer"]]
    had = ["qwen2vl-2b", "qwen25vl-7b-tp4", "granite-4.0-h-micro", "deepseek-v2-ep8", "trinity-large-ep8",
           "keye-vl2-a3b-ep8", "olmo-hybrid-7b-pp2"]
    assert configs[:7] == had and configs[7] == CONFIG
    assert cells[7] == "olmo-hybrid-7b-pp2.text-rewrite" and cells[8] == CELL and len(cells[:8]) == 8
    at = metrics.index("engine.delta_state_gib")  # the last the benchmark had
    assert at == 42 and metrics[at + 1 : at + 11] == NEW
    for m in bench["per_layer"][at + 1 : at + 11]:
        assert m["workloads"] == [CELL] and m["moves"] == "output_tok_per_s"
        reader = _reader(m["name"])
        assert (reader.UNIT, reader.LAYER, reader.MOVES, reader.SOURCE) == (m["unit"], m["layer"], m["moves"], m["source"])
        assert set(m) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert m["layer"] in ("kernels", "caption engine") and m["better"] in ("lower", "higher")
    # no older metric lists the new cell, and no end-to-end entry moved
    assert not [m["name"] for m in bench["per_layer"][: at + 1] if CELL in m.get("workloads", [])]
    assert [(e["name"], e["bound"]) for e in bench["end_to_end"]] == [("output_tok_per_s", 0.08), ("setup_s", 0.1)]
    assert sum(w["chips"] == 4 for w in bench["workloads"][:9]) == 1 and bench["run_seconds"] == 40
    entry = bench["workloads"][8]
    assert set(entry) == {"name", "config", "traffic", "chips", "why"} and len(entry["why"]) <= 200
    assert (entry["config"], entry["traffic"], entry["chips"]) == (CONFIG, "text-rewrite", 1)
    # what test_catalog.py::test_config_file asserts, with widths told from depth
    # (its pattern takes the word "hidden" in num_hidden_layers for a width)
    entry = bench["configs"][7]
    conf = json.loads((catalog.CHECKOUT / entry["file"]).read_text())
    assert set(entry) == {"name", "source", "file", "reduced", "why"} and entry["file"].startswith("perfbench/")
    assert conf["name"] == CONFIG and conf["source"] == entry["source"] and conf["reduced"] == entry["reduced"]
    assert set(conf["reduced_why"]) == set(conf["reduced"]) and len(entry["reduced"]) <= 16
    assert any(w["config"] == entry["name"] for w in bench["workloads"])
    widths = ("hidden_size", "intermediate", "latent", "state", "projection", "_dim", "_rank", "expansion", "experts_per")
    assert not [k for k in conf["reduced"] if any(w in k for w in widths)]
    assert conf["assumed"] and conf["deployment"] and "check" in conf and len(entry["why"]) <= 200
    assert len(json.dumps(bench)) < 64 * 1024


def test_config_file_is_the_catalog_row_and_the_flavor():
    from cosmos_curate_tpu.models.vlm.model import vlm_flavor
    from perfbench.drivers.caption_engine_kda import check_config_file

    conf = catalog.load_cell(CELL).config
    flavor = vlm_flavor(conf["flavor"])
    check_config_file(conf, flavor.cfg, flavor.kv_lanes, flavor.prefill_rows)  # raises where they disagree
    with pytest.raises(ValueError, match="linear_attn_config"):
        delta = dataclasses.replace(flavor.cfg.gated_delta, key_dim=96)
        check_config_file(conf, dataclasses.replace(flavor.cfg, gated_delta=delta), flavor.kv_lanes, 8)
    with pytest.raises(ValueError, match="assumed.kda_decay_rank"):
        delta = dataclasses.replace(flavor.cfg.gated_delta, decay_rank=64)
        check_config_file(conf, dataclasses.replace(flavor.cfg, gated_delta=delta), flavor.kv_lanes, 8)
    with pytest.raises(ValueError, match="assumed.block"):
        check_config_file(conf, dataclasses.replace(flavor.cfg, qk_norm=True), flavor.kv_lanes, 8)
    with pytest.raises(ValueError, match="published_counts"):
        moe = dataclasses.replace(flavor.cfg.moe, held=(40, 40))
        check_config_file(conf, dataclasses.replace(flavor.cfg, moe=moe), flavor.kv_lanes, 8)
    with pytest.raises(ValueError, match="kv_lanes"):
        check_config_file(conf, flavor.cfg, ((1024, 8),), 8)
    with pytest.raises(ValueError, match="prefill_rows"):
        check_config_file(conf, flavor.cfg, flavor.kv_lanes, None)
    # the published widths, uncut
    for key, value in dict(
        hidden_size=4096, num_attention_heads=64, num_key_value_heads=8, head_dim=128, moe_intermediate_size=1280,
        intermediate_size=10240, num_experts_per_tok=8, n_shared_experts=1, norm_topk_prob=True,
        routed_scaling_factor=1, first_k_dense_replace=0, use_rope=False, use_gqa_gate=True,
        kda_use_full_proj=False, kda_allow_neg_eigval=True, rms_norm_eps=1e-5, tie_word_embeddings=False,
    ).items():
        assert conf[key] == value, key
    assert conf["linear_attn_config"] == {"short_conv_kernel_size": 4, "head_dim": 128, "num_heads": 64, "num_kv_heads": None}
    assert conf["published_counts"] == {"router_outputs": 320, "held_experts": [0, 40]}
    assert conf["reduced"] == ["num_hidden_layers", "gqa_layers", "n_routed_experts", "vocab_size", "max_position_embeddings"]
    assert (conf["num_hidden_layers"], conf["gqa_layers"], conf["n_routed_experts"], conf["vocab_size"]) == (4, [0], 40, 24576)
    assert conf["max_position_embeddings"] == 4096 == flavor.cfg.max_seq and 8 * conf["vocab_size"] == 196608
    assert conf["published"] == {
        "num_hidden_layers": 48, "gqa_layers": list(range(0, 48, 4)), "n_routed_experts": 320, "vocab_size": 196608,
        "max_position_embeddings": 1048576,
    }
    assert conf["serving"]["kv_lanes"] == [[1024, 256], [4096, 8]] and conf["serving"]["block_size"] == 16
    assert (conf["serving"]["prefill_chunk"], conf["serving"]["prefill_rows"], conf["serving"]["async_prep"]) == (256, 8, True)
    assert conf["serving"]["paged_attention"] == "auto"
    assert "ONE CHIP OF EIGHT" in conf["deployment"] and "TWELVE" in conf["deployment"] and "WITHOUT its exchange" in conf["deployment"]
    for point in ("linear_layer", "kda_low_rank_why", "attention_gate", "position_embedding", "router_why", "block",
                  "intermediate_size_unused", "decay_init", "ssm_state_dtype", "conv_state_dtype", "kv_cache_dtype", "weights"):
        assert conf["assumed"][point], point
    for limit in ("reference_rel_tol", "state_rms_tol", "decode_rel_tol", "xla_path_rel_tol", "router_weight_tol",
                  "state_steps_rms_tol"):
        assert 0 < conf["check"][limit] < 1 and len(conf["check"][limit + "_why"]) > 100, limit
    assert conf["assumed"]["router_precision"] == flavor.cfg.moe.router_precision == "highest"
    assert conf["assumed"]["router_precision_why"] and conf["check"]["state_steps"] == 192  # a request's whole output
    assert conf["check"]["text_tokens"] == [200, 700] and conf["check"]["decode_steps"] == 8
    assert conf["check"]["lower_precision_readings"] and conf["check"]["routing_margin_why"]
    catalog_file = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog_file):  # every number of the catalog row, or listed as reduced
        rows = [json.loads(line) for line in open(catalog_file) if line.strip()]
        row = next(r for r in rows if r["name"] == "Solar-Open2-250B")
        assert conf["source"] == row["source_url"]
        assert {k for k, v in row["config"].items() if conf.get(k) != v} == set(conf["reduced"])


def test_the_ramp_is_one_turnover_of_the_slots():
    """The ramp this driver takes (the indexed driver's ``DigestLoop.ramp``): the
    first request, a warmer a prompt length, then the whole target at once and
    as many requests finished as there are slots: no wait for a lull."""
    from perfbench.drivers import caption_engine_kda

    loop = caption_engine_kda.scoped.DigestLoop.__new__(caption_engine_kda.scoped.DigestLoop)
    loop.engine = SimpleNamespace(slots={}, add_request=lambda r: warmers.append(r))
    loop.traffic = SimpleNamespace(
        grid=[144, 208], request=lambda i, **kw: SimpleNamespace(request_id=kw["name"], **kw),
    )
    loop._request = lambda spec: spec
    loop.reachable_slots, loop.full_target, loop.target, loop.results, loop.warm_done = 6, 10, 1, [], 0
    warmers, targets = [], []

    def turn():
        targets.append(loop.target)
        loop.engine.slots[0] = "decoding"
        loop.warm_done = len(warmers)
        if loop.target == loop.full_target:
            loop.results.append("done")

    loop.turn = turn
    loop.ramp(timeout_s=5.0)
    assert [w.request_id for w in warmers] == ["warm144", "warm208"] and all(w.max_new_tokens == 1 for w in warmers)
    assert len(loop.results) == 6 and loop.target == 10
    assert targets.count(10) == 6 and set(targets) == {1, 10}  # never a slot at a time
    warmers.clear()
    loop.results, loop.turn = [], lambda: setattr(loop, "warm_done", len(warmers))
    with pytest.raises(TimeoutError, match="one turnover"):
        loop.ramp(timeout_s=0.05)


def _tiny():
    from cosmos_curate_tpu.models.vlm import model as vlm_model
    from perfbench.drivers import caption_engine_kda as driver

    cell = catalog.load_cell(CELL)
    cfg = vlm_model.VLM_SOLAR_OPEN2_TINY_TEST
    check = dict(cell.config["check"], **cell.config["rehearse"]["check"])
    traffic = catalog.load_module("traffic", cell.traffic["generator"]).CaptionTraffic(
        cell.traffic_params(True), 7, vocab=cfg.vocab, image_size=cfg.vision.image_size
    )
    return driver, cfg, driver.make_params(cfg, 7), traffic, check


@pytest.mark.parametrize("low, holds", [
    (None, True),  # the program itself: float32 at both sites
    ({"state_mantissa_bits": 7}, False), ({"router_mantissa_bits": 7}, False),
    ({"activation_mantissa_bits": 7}, True),  # what the file states: neither site is an activation
])
def test_the_stated_precisions_are_held_where_nothing_has_rounded_the_inputs(low, holds):
    import jax.numpy as jnp

    driver, cfg, params, traffic, check = _tiny()
    assert driver.check_stated_precisions(cfg, params, jnp.float32, traffic, check, 7, low=low) is holds


def test_a_store_or_a_router_in_fewer_bits_is_not_correct(monkeypatch):
    """The PROGRAM in the nearest precision below the stated one, at either site."""
    import jax
    import jax.numpy as jnp

    driver, cfg, params, traffic, check = _tiny()
    assert not driver.check_stated_precisions(cfg, params, jnp.bfloat16, traffic, check, 7)
    from cosmos_curate_tpu.models.vlm import model as vlm_model

    route = vlm_model.route
    monkeypatch.setattr(vlm_model, "route", lambda moe, logits, bias=None: route(
        moe, jax.lax.reduce_precision(logits, exponent_bits=8, mantissa_bits=7), bias))
    assert not driver.check_stated_precisions(cfg, params, jnp.float32, traffic, check, 7)


def test_a_control_below_the_stated_precision_comes_out_not_correct():
    from perfbench.drivers import caption_engine_kda as driver

    assert driver.lower_precision(4900000003, ["state"], rehearse=True) == {"state": False}
    assert set(driver.CONTROLS) == {"state", "router", "activations", "stated"}


def test_cell_rehearses_on_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    out = subprocess.run(
        [sys.executable, "-m", "perfbench.run", "--workload", CELL, "--seed", "4900000002",
         "--seconds", "4", "--trace", "1", "--rehearse"],
        capture_output=True, text=True, env=env, cwd=str(catalog.CHECKOUT), timeout=900,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    # on the CPU only the program's counters are written under a metric's name
    assert set(line["metrics"]) == {
        "device.compiles_in_window", "engine.kda_state_gib", "engine.kda_cell_assignments_per_program",
        "engine.kda_cell_live_assignments_per_program",
    }
    held, live = (line["metrics"][f"engine.kda_cell_{k}assignments_per_program"]["value"] for k in ("", "live_"))
    assert 0 < live <= held
    # (its value is the chip run's to hold at 0: this loop of six slots has lulls in which the
    # engine prefills a prompt whole, in a bucket no warmer made; 264 slots have none)
    assert line["metrics"]["engine.kda_cell_assignments_per_program"]["value"] > 0
    assert "first linear-attention layer's state in the store" in out.stdout
    assert "both from the kernel engine's first token" in out.stdout and "one turnover of" in out.stdout
    assert "the program's router vs the float32 reference's" in out.stdout
    assert "the program's decode recurrence on a float32 store" in out.stdout
    assert "FAILED" not in out.stdout and "still waiting for" not in out.stdout
