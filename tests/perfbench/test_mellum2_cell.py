"""The Mellum2 cell's own pieces of the yardstick: its configuration file against
the catalog row and the flavor, its traffic file's contexts against the lanes, the
benchmark's entries (all it had, unchanged, the new configuration and cell after),
its two readers on made-up runs (and None where there is nothing to read; neither
is a `BENCHMARK.json` entry yet), a request's choice put together from the
prefix's build, its chunks and its steps, lower-precision controls that come out
not correct, and a rehearsal of the whole cell on the CPU."""

import dataclasses
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from perfbench import catalog

CELL = "mellum2-12b-a2.5b-pp4.digest-2k-30k-rubric-2k"
CONFIG = "mellum2-12b-a2.5b-pp4"
TRAFFIC = "digest-2k-30k-rubric-2k"
# this PR's readers: files under layer_metrics/ that no `BENCHMARK.json` entry names yet (PERF.md section 7:
# tests/perfbench/test_prep_round_metric.py pins the benchmark's length; a `benchmark` PR lists them)
NEW = ["engine.prefix_reuse_share", "engine.prefix_tail_blocks_per_hit"]


def _reader(name):
    return catalog.load_module("layer_metrics", name)


def test_config_file_is_the_catalog_row_and_the_flavor():
    from cosmos_curate_tpu.models.vlm.model import YarnConfig, vlm_flavor
    from perfbench.drivers.caption_engine_mellum import check_config_file

    conf = catalog.load_cell(CELL).config
    flavor = vlm_flavor(conf["flavor"])
    check_config_file(conf, flavor.cfg, flavor.kv_lanes, flavor.prefill_rows)  # raises where they disagree
    with pytest.raises(ValueError, match="sliding_window"):
        check_config_file(conf, dataclasses.replace(flavor.cfg, sliding_window=4096), flavor.kv_lanes, 4)
    with pytest.raises(ValueError, match="rope_parameters"):
        yarn = dataclasses.replace(flavor.cfg.full_attention_yarn, factor=8.0)
        check_config_file(conf, dataclasses.replace(flavor.cfg, full_attention_yarn=yarn), flavor.kv_lanes, 4)
    with pytest.raises(ValueError, match="rope_parameters"):  # the published attention_factor, not the formula's float
        yarn = dataclasses.replace(flavor.cfg.full_attention_yarn, attention_factor=1.27)
        check_config_file(conf, dataclasses.replace(flavor.cfg, full_attention_yarn=yarn), flavor.kv_lanes, 4)
    with pytest.raises(ValueError, match="assumed.block"):
        check_config_file(conf, dataclasses.replace(flavor.cfg, qk_norm=False), flavor.kv_lanes, 4)
    with pytest.raises(ValueError, match="assumed.router_precision"):
        moe = dataclasses.replace(flavor.cfg.moe, router_precision=None)
        check_config_file(conf, dataclasses.replace(flavor.cfg, moe=moe), flavor.kv_lanes, 4)
    with pytest.raises(ValueError, match="published_counts"):  # a share held: no longer the layer whole
        moe = dataclasses.replace(flavor.cfg.moe, held=(0, 8))
        check_config_file(conf, dataclasses.replace(flavor.cfg, moe=moe), flavor.kv_lanes, 4)
    with pytest.raises(ValueError, match="kv_lanes"):
        check_config_file(conf, flavor.cfg, ((8192, 4), (32768, 12)), 4)
    with pytest.raises(ValueError, match="prefill_rows"):
        check_config_file(conf, flavor.cfg, flavor.kv_lanes, None)
    # the published widths, uncut; all 64 experts and the whole vocabulary; both rope sections
    for key, value in dict(
        hidden_size=2304, intermediate_size=7168, moe_intermediate_size=896, num_attention_heads=32,
        num_key_value_heads=4, head_dim=128, num_experts=64, num_experts_per_tok=8, vocab_size=98304,
        rms_norm_eps=1e-6, norm_topk_prob=True, sliding_window=1024, tie_word_embeddings=False, model_type="mellum",
        attention_bias=False, hidden_act="silu", use_sliding_window=True, max_window_layers=0,
    ).items():
        assert conf[key] == value, key
    full = conf["rope_parameters"]["full_attention"]
    assert full == {"rope_type": "yarn", "rope_theta": 500000, "factor": 16, "original_max_position_embeddings": 8192,
                    "beta_fast": 32, "beta_slow": 1, "attention_factor": 1.2772588722239782}
    assert conf["rope_parameters"]["sliding_attention"] == {"rope_type": "default", "rope_theta": 500000}
    assert flavor.cfg.full_attention_yarn == YarnConfig(16.0, 8192, 32.0, 1.0, 1.2772588722239782)
    assert conf["published_counts"] == {"router_outputs": 64, "held_experts": [0, 64]}
    assert conf["reduced"] == ["num_hidden_layers", "layer_types", "mlp_layer_types", "max_position_embeddings"]
    assert set(conf["reduced_why"]) == set(conf["reduced"]) == set(conf["published"])
    assert conf["num_hidden_layers"] == 8 and conf["max_position_embeddings"] == 32768 == flavor.cfg.max_seq
    assert conf["layer_types"] == conf["published"]["layer_types"][:8] == (["sliding_attention"] * 3 + ["full_attention"]) * 2
    assert conf["mlp_layer_types"] == ["sparse"] * 8 and conf["published"]["mlp_layer_types"] == ["sparse"] * 28
    assert (conf["published"]["num_hidden_layers"], conf["published"]["max_position_embeddings"]) == (28, 131072)
    assert conf["serving"]["kv_lanes"] == [[8192, 4], [32768, 24]] and conf["serving"]["block_size"] == 128
    assert (conf["serving"]["prefill_chunk"], conf["serving"]["prefill_rows"], conf["serving"]["async_prep"]) == (256, 4, True)
    assert "FIRST OF FOUR" in conf["deployment"] and "WHOLE" in conf["deployment"] and "3.5 TIMES" in conf["deployment"]
    assert "8 + 8 + 8 + 4" in conf["deployment"] and "not run" in conf["deployment"]
    for point in ("qk_norm", "window_edge", "block", "intermediate_size", "expert_block", "router_precision_why",
                  "hand_out_choice_why", "mtp_head", "head_place", "kv_cache_dtype", "weights", "tokenizer"):
        assert conf["assumed"][point], point
    for limit in ("reference_rel_tol", "decode_rel_tol", "rows_rms_tol", "router_weight_tol", "routing_margin", "routing_flip_share"):
        assert 0 < conf["check"][limit] <= 0.07 and len(conf["check"][limit + "_why"]) > 100, limit
        assert "First reading" in conf["check"][limit + "_why"] and "NOT correct" in conf["check"][limit + "_why"], limit
    assert conf["check"]["prompt_tokens"] == [1536, 17920] and conf["check"]["decode_steps"] == 8
    assert flavor.cfg.moe.hand_out_choice and conf["assumed"]["router_precision"] == flavor.cfg.moe.router_precision == "highest"
    # the arithmetic of the deployment: a layer, the stage, the two pools
    layer = 2 * 2304 * 32 * 128 + 2 * 2304 * 4 * 128 + 2304 * 64 + 64 * 3 * 2304 * 896
    assert round(layer / 1e6, 1) == 417.7 and round(28 * layer * 2 / 1e9, 1) == 23.4
    stage = 8 * layer + 2 * 98304 * 2304
    assert round(stage / 1e9, 2) == 3.79 and round((stage * 2 + 98304 * 2304 * 2) / 1e9, 2) == 8.04
    assert (4 * 8192 + 24 * 32768) // 128 == 6400 and round(6400 * 128 * 2 * 2048 / 2**30, 1) == 3.1
    catalog_file = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog_file):  # every number of the catalog row, or listed as reduced
        rows = [json.loads(line) for line in open(catalog_file) if line.strip()]
        row = next(r for r in rows if r["name"] == "Mellum2-12B-A2.5B-Instruct")
        assert conf["source"] == row["source_url"]
        assert {k for k, v in row["config"].items() if conf.get(k) != v} == set(conf["reduced"])


def test_the_traffic_files_contexts_fit_the_lanes_and_the_prefix_outlives_the_ring():
    cell = catalog.load_cell(CELL)
    p = cell.traffic["params"]
    # the issue's traffic, letter for letter
    assert (p["frames"], p["prefix_tokens"], p["output_tokens"], p["backlog"], p["trace_seconds"], p["warm_rows"]) == (0, 2048, 256, 4, 8.0, 4)
    assert p["prompt_tokens"] == {"min": 1536, "max": 30208, "step": 4096} and cell.traffic["generator"] == "caption_requests"
    keye = catalog.load_cell("keye-vl2-a3b-ep8.digest-2k-30k").traffic["params"]
    assert p["prompt_tokens"] == keye["prompt_tokens"] and p["output_tokens"] == keye["output_tokens"]  # Keye's grid
    grid = list(range(1536, 30208 + 1, 4096))
    assert len(grid) == 8
    lanes = [length for length, _ in cell.config["serving"]["kv_lanes"]]
    need = [p["prefix_tokens"] + n + p["output_tokens"] + 1 for n in grid]
    assert max(need) == 32513 <= lanes[-1] and sum(n <= lanes[0] for n in need) == 2
    assert (min(need) - 257, max(need) - 1) == (3584, 32512)  # contexts 3.6k (the shortest prompt) to 32.5k
    # the ring of a row: ceil((1024 + 256) / 128) + 1 = 11 blocks = 1,408 positions; the prefix is 16 blocks
    window, chunk, bs = cell.config["sliding_window"], cell.config["serving"]["prefill_chunk"], cell.config["serving"]["block_size"]
    ring = -(-(window + chunk) // bs) + 1
    assert ring == 11 and p["prefix_tokens"] // bs == 16 > ring and round(16 / ring, 2) == 1.45
    assert -(-p["prefix_tokens"] // bs) - (p["prefix_tokens"] - window) // bs == 8  # the tail an entry keeps, a hit copies
    assert round(100 * p["prefix_tokens"] / (p["prefix_tokens"] + sum(grid) / 8), 1) == 11.4  # the share the cache saves
    assert all(n + p["prefix_tokens"] > window for n in grid)  # every length passes the window
    # the rehearsal's prefix outlives the tiny ring too (24 positions), and its contexts fit the tiny lanes
    r = cell.traffic["rehearse"]
    tiny = cell.config["rehearse"]
    tiny_ring = (-(-(10 + tiny["prefill_chunk"]) // tiny["block_size"]) + 1) * tiny["block_size"]
    assert r["prefix_tokens"] == 37 > tiny_ring == 24
    assert r["prefix_tokens"] + r["prompt_tokens"]["max"] + r["output_tokens"] + 1 <= tiny["kv_lanes"][-1][0]
    assert cell.harness == {"warm_rows": 4}


def test_benchmark_gained_two_entries_and_lost_none():
    """Written so that the NEXT cell does not break it: what the benchmark had
    (the parent commit's nine configurations, ten cells and 54 per-layer
    metrics, by name and in order) is all there, and this PR's configuration
    and cell come after it. No per-layer entry is added."""
    bench = catalog.benchmark()
    configs = [c["name"] for c in bench["configs"]]
    cells = [w["name"] for w in bench["workloads"]]
    metrics = [m["name"] for m in bench["per_layer"]]
    had = ["qwen2vl-2b", "qwen25vl-7b-tp4", "granite-4.0-h-micro", "deepseek-v2-ep8", "trinity-large-ep8",
           "keye-vl2-a3b-ep8", "olmo-hybrid-7b-pp2", "solar-open2-ep8", "lfm2-24b-a2b-pp5"]
    assert configs[:9] == had and configs[9] == CONFIG
    assert cells[9] == "lfm2-24b-a2b-pp5.text-rewrite" and cells[10] == CELL and len(set(cells)) == len(cells)
    assert metrics.index("engine.prep_requests_per_round") == 53 and len(metrics) >= 54
    assert not [m["name"] for m in bench["per_layer"][:54] if CELL in m.get("workloads", [])]
    assert not set(NEW) & set(metrics)  # the two readers wait for a `benchmark` PR
    assert [(e["name"], e["bound"]) for e in bench["end_to_end"]] == [("output_tok_per_s", 0.08), ("setup_s", 0.1)]
    assert sum(w["chips"] == 4 for w in bench["workloads"][:11]) == 1 and bench["run_seconds"] == 40
    entry = bench["workloads"][10]
    assert set(entry) == {"name", "config", "traffic", "chips", "why"} and len(entry["why"]) <= 200
    assert (entry["config"], entry["traffic"], entry["chips"]) == (CONFIG, TRAFFIC, 1)
    entry = bench["configs"][9]
    conf = json.loads((catalog.CHECKOUT / entry["file"]).read_text())
    assert set(entry) == {"name", "source", "file", "reduced", "why"} and entry["file"] == f"perfbench/configs/{CONFIG}.json"
    assert conf["name"] == CONFIG and conf["source"] == entry["source"] and conf["reduced"] == entry["reduced"]
    widths = ("hidden_size", "intermediate", "latent", "state", "projection", "_dim", "_rank", "expansion", "experts_per")
    assert not [k for k in conf["reduced"] if any(w in k for w in widths)] and len(entry["why"]) <= 200
    assert conf["assumed"] and conf["deployment"] and "check" in conf and len(json.dumps(bench)) < 64 * 1024
    # the cell reports both end-to-end metrics and every per-layer metric without a list: seventeen of them
    cell = catalog.load_cell(CELL)
    everywhere = [m["name"] for m in bench["per_layer"] if "workloads" not in m]
    assert cell.chips == 1 and cell.end_to_end == ("output_tok_per_s", "setup_s")
    assert len(everywhere) == 17 and set(everywhere) == set(cell.per_layer)


def test_the_two_readers_on_made_up_runs():
    run = {
        "stats_delta": {"prefill_tokens": 15872 * 60},
        "prefix": {"prefix_cache_hits": 60, "prefix_tokens_saved": 2048 * 60, "prefix_tail_blocks_copied": 480, "window_blocks_held": 8},
        "phase_delta": {"prefix_tail_copy_n": 60, "prefix_tail_copy_blocks": 480, "prefix_tail_copy_s": 0.05},
    }
    assert _reader("engine.prefix_reuse_share").read(run) == pytest.approx(100 * 2048 / 17920)  # the issue's 11.4%
    assert _reader("engine.prefix_tail_blocks_per_hit").read(run) == pytest.approx(8.0)
    # a prefix re-prefilled by every request (the parent's engine on this traffic): nothing saved, nothing copied
    parent = {"stats_delta": {"prefill_tokens": 17920 * 60}, "phase_delta": {"step_s": 8.0, "admit_s": 0.1}}
    for name in NEW:
        reader = _reader(name)
        assert reader.read(parent) is None and reader.read({}) is None and reader.read({"phase_delta": None, "stats_delta": None}) is None
        assert (reader.UNIT in ("%", "count") and reader.LAYER == "caption engine" and reader.MOVES == "output_tok_per_s"
                and reader.SOURCE == "program_counter")
    # an entry that saved nothing in a window that prefilled: 0, a reading; no admission copied: nothing to read
    idle = dict(run, prefix=dict(run["prefix"], prefix_tokens_saved=0), phase_delta={"prefix_tail_copy_n": 0, "prefix_tail_copy_blocks": 0})
    assert _reader("engine.prefix_reuse_share").read(idle) == 0.0 and _reader("engine.prefix_tail_blocks_per_hit").read(idle) is None


def test_a_requests_choice_is_put_together_from_the_prefix_its_chunks_and_its_steps():
    from perfbench.drivers.caption_engine_mellum import _MellumPrivate

    spy = _MellumPrivate.__new__(_MellumPrivate)  # the bookkeeping alone: no engine
    chunk = lambda at, n, t=8: (at, n, np.full((4, t, 2), at, np.int32))  # noqa: E731
    spy.prefix_choice = np.full((4, 64, 2), 100, np.int32)  # the build's padded bucket
    spy.prompt_choice = {"check-hit": [chunk(37, 8), chunk(45, 8), chunk(50, 8)], "check-text": [chunk(0, 8)]}
    spy.step_choice = {"check-hit": [np.full((4, 2), 7, np.int32)]}
    got = spy.choice_of("check-hit", 37, 58, steps=1)
    assert got.shape == (4, 59, 2)
    assert got[0, :, 0].tolist() == [100] * 37 + [37] * 8 + [45] * 5 + [50] * 8 + [7]  # the shifted last chunk wins
    assert spy.choice_of("check-text", 0, 8).shape == (4, 8, 2) and spy.choice_of("check-text", 0, 9) is None
    spy.prefix_choice = None
    assert spy.choice_of("check-hit", 37, 58) is None  # the prefix's build was not read


def test_controls_below_the_stated_precision_come_out_not_correct():
    from perfbench.drivers import caption_engine_mellum as driver

    verdicts = driver.lower_precision(5700000003, ["router", "activations", "stated"], rehearse=True)
    assert verdicts == {"router": False, "activations": False, "stated": True}
    assert set(driver.CONTROLS) == {"router", "activations", "stated"}


def test_rehearsal_of_the_cell_on_the_cpu():
    """The whole control flow at the tiny preset: the prefix past the ring, both
    lanes, `correct` with hits and text at both lengths, the window, a traced
    slice, the readers' line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "-m", "perfbench.run", "--workload", CELL, "--rehearse", "--seed", "5700000004", "--seconds", "4",
         "--trace", "1"],
        cwd=catalog.CHECKOUT, env=env, capture_output=True, text=True, timeout=900,
    )
    assert out.returncode == 0, out.stderr[-3000:] + out.stdout[-3000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 10
    log = out.stderr + out.stdout
    assert "the two prefix requests were hits (2), each copied the entry's window tail of 4 blocks" in log
    own = json.loads(next(l for l in log.splitlines() if "the cell's own readers: " in l).split("readers: ", 1)[1])
    assert own["engine.prefix_tail_blocks_per_hit"] == 4.0 and 20 < own["engine.prefix_reuse_share"] < 70
    assert own["kernel.window_pages_skipped_share"] > 50 and own["engine.window_pool_gib"] > 0
    assert "FAILED" not in log
