"""The LFM2 cell's own pieces of the yardstick: its operation and byte counts
against hand counts, its eight readers on a hand-made record (and None where
there is nothing to read; none of them is a `BENCHMARK.json` entry yet), its
configuration file against the catalog row and the flavor, the benchmark's
entries (all it had, unchanged, the new configuration and cell after), the
reference that FOLLOWS a program's choice of experts and the judges that hold
every layer through it, a router in fewer bits that is not correct, a
lower-precision control and planted faults that come out not correct, and a
rehearsal of the control flow."""

import dataclasses
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from perfbench import catalog
from perfbench.roofline import expert_bytes, lfm2_bytes

CELL = "lfm2-24b-a2b-pp5.text-rewrite"
CONFIG = "lfm2-24b-a2b-pp5"
# the cell's own readers: files under layer_metrics/ that no `BENCHMARK.json` entry names yet (PERF.md section 7:
# tests/perfbench/test_prep_round_metric.py pins the benchmark's length; a `benchmark` PR lists them)
NEW = [
    "kernel.short_conv_time_share", "kernel.short_conv_hbm_share", "kernel.whole_moe_expert_matmul_roofline_share",
    "kernel.whole_moe_expert_time_share", "engine.conv_tail_gib", "engine.whole_moe_assignments_per_program",
    "kernel.whole_moe_cell_paged_decode_hbm_share", "engine.whole_moe_cell_prefill_device_share",
]
CONV = dict(n_layers=8, dim=2048, taps=3, dtype_bytes=2)
EXPERTS = dict(dim=2048, width=1536, held=64, dtype_bytes=2, sparse_layers=8, router_outputs=64, top_k=4)


def _reader(name):
    return catalog.load_module("layer_metrics", name)


class _Trace:
    busy_s_by_chip = [8.0]
    kernel_s = {"paged_decode": 0.3, "paged_prefill": 0.1}


def _record():
    return {
        "trace": _Trace(), "device": {"kind": "TPU v5 lite", "memory_peak_bytes": 1},
        "expert_trace": {"kernel_s": {"expert_matmul": 4.0}, "kernel_calls": {"expert_matmul": 16 * 210}},
        "slice": {
            "decode_lengths": [[300] * 250] * 200,  # 250 live rows a step, two hundred steps
            "prefill_valid": [[256] * 8] * 9 + [[16]],  # nine full programs and a last chunk of 16 tokens
            "conv_shape": CONV, "expert_shape": EXPERTS,
            "kv_shape": dict(n_layers=2, n_kv_heads=8, head_dim=64, block_size=16, dtype_bytes=2),
            "attention_shape": dict(n_layers=2, n_heads=32, head_dim=64),
        },
        "program_s": {"prefill": [3.0, 10], "decode": [4.5, 200], "other": [0.5, 9]},
        "scope_s": {
            ("decode", "mixer.short_conv"): 0.1, ("prefill", "mixer.short_conv"): 0.3, ("decode", "moe.experts"): 3.0,
            ("decode", "moe.route"): 0.2,
        },
        "stats_delta": {"paged_kernel_steps": 200},
        "conv": {
            "conv_tail_bytes_per_chip": 265 * 8 * 4096 * 2, "recurrent_rows_total": 264, "recurrent_rows_used_peak": 264,
            "prefix_state_snapshots": 9, "expert_assignments_held": 200 * 8192, "expert_assignments_held_live": 200 * 8000,
        },
    }


def test_bytes_and_operations_against_a_hand_count():
    # the issue's arithmetic: a conv mixer is 16.78 M parameters, of them 4 x 2,048^2 in the two projections
    assert 4 * 2048 * 2048 + 3 * 2048 == 16_783_360
    layer = 4 * 2048 * 2048 * 2 + 3 * 2048 * 4  # bfloat16 projections, float32 taps: 33.58 MB
    assert lfm2_bytes.short_conv_weight_bytes(1, **CONV) == 8 * layer == 268_632_064
    assert lfm2_bytes.short_conv_weight_bytes(200, **CONV) == 200 * 268_632_064
    # a token: its input in and its output out; a row: two tails read and two written; bfloat16; eight layers
    assert lfm2_bytes.short_conv_token_bytes(1, 0, **CONV) == 8 * 2 * 2048 * 2 == 65_536
    assert lfm2_bytes.short_conv_token_bytes(0, 1, **CONV) == 8 * 2 * 2 * 2048 * 2 == 131_072  # the 64 KiB a row, both ways
    assert lfm2_bytes.short_conv_token_bytes(256, 256, **CONV) == 256 * (65_536 + 131_072)
    assert lfm2_bytes.short_conv_flops(1, **CONV) == 8 * (2 * 4 * 2048 * 2048 + 8 * 2048)
    # a decode program: 268.6 MB of weights and 50 MB of rows are 0.389 ms at 819 GB/s; 256 rows sit at the chip's
    # ridge (240 operations a byte of weights), so the operations are 0.349 ms: the bytes bound it, by a tenth
    moved = lfm2_bytes.short_conv_weight_bytes(1, **CONV) + lfm2_bytes.short_conv_token_bytes(256, 256, **CONV)
    assert 0.388e-3 < moved / 819e9 < 0.390e-3 and 0.348e-3 < lfm2_bytes.short_conv_flops(256, **CONV) / 197e12 < 0.350e-3
    # an expert: 3 x 2,048 x 1,536 = 9.44 M parameters; a layer's 64: 604.0 M, 1.208 GB (the issue's 1.21)
    assert 3 * 2048 * 1536 == 9_437_184 and 64 * 9_437_184 == 603_979_776
    assert lfm2_bytes.whole_expert_table_bytes(1, **EXPERTS) == 2 * 603_979_776 == 1_207_959_552
    assert lfm2_bytes.whole_expert_table_bytes(8, **EXPERTS) == 9_663_676_416  # the 9.66 GB a decode step reads
    with pytest.raises(ValueError, match="not a whole layer"):
        lfm2_bytes.whole_expert_table_bytes(1, **dict(EXPERTS, held=8))
    # 256 rows x top 4 x 8 sparse layers: 8,192 assignments a decode program, 16 an expert a layer
    assert lfm2_bytes.whole_assignments(256, **EXPERTS) == 8192 and 256 * 4 // 64 == 16
    assert expert_bytes.expert_flops(1, **EXPERTS) == 6 * 2048 * 1536
    # a decode step's experts: 9.66 GB at 819 GB/s is 11.8 ms; its operations 0.8 ms: the tables' read bounds it
    assert 11.7e-3 < 9_663_676_416 / 819e9 < 11.9e-3 and expert_bytes.expert_flops(8192, **EXPERTS) / 197e12 < 1e-3


def test_the_eight_readers_on_a_hand_made_record():
    run = _record()
    assert _reader("kernel.short_conv_time_share").read(run) == pytest.approx(100 * 0.4 / 8.0)
    moved = 200 * 268_632_064 + 200 * 250 * (65_536 + 131_072)
    assert _reader("kernel.short_conv_hbm_share").read(run) == pytest.approx(100 * moved / 0.1 / 819e9)
    assert _reader("kernel.whole_moe_expert_time_share").read(run) == pytest.approx(50.0)
    assert _reader("engine.conv_tail_gib").read(run) == pytest.approx(265 * 65536 / 2**30)
    assert _reader("engine.whole_moe_assignments_per_program").read(run) == pytest.approx(8000.0)
    # the experts' roofline by hand: 210 programs; a decode program's 1,000 assignments touch every expert, the
    # last chunk's 16 tokens 63.5% of them
    share = _reader("kernel.whole_moe_expert_matmul_roofline_share")
    assert share.touched_share(250, **EXPERTS) == pytest.approx(1.0, abs=1e-6)
    assert share.touched_share(16, **EXPERTS) == pytest.approx(1 - (63 / 64) ** 64)
    tokens = 200 * 250 + 9 * 2048 + 16
    touched = (209 + 1 - (63 / 64) ** 64) / 210
    passes = 16 * 210 // 2
    moved = touched * passes * 1_207_959_552 + tokens * 4 * 8 * (2 * 2048 + 3 * 1536) * 2
    flops = tokens * 4 * 8 * 6 * 2048 * 1536
    want = 100 * max(moved / 819e9, flops / 197e12) / 4.0
    assert share.read(run) == pytest.approx(want, rel=1e-4) and 50 < want < 100
    # the two the Solar cell has copies of too: the prefill programs' 3.0 of the programs' 8.0 device seconds; the
    # K/V of 250 rows of 300 positions a step: 19 pages of 16, 4 KiB a position (2 layers x K and V x 8 heads x 64 x 2 B)
    assert _reader("engine.whole_moe_cell_prefill_device_share").read(run) == pytest.approx(37.5)
    kv = 200 * 250 * 19 * 16 * 4096
    assert _reader("kernel.whole_moe_cell_paged_decode_hbm_share").read(run) == pytest.approx(100 * kv / 0.3 / 819e9)
    # a share of a roofline cannot pass 100%: the fastest the chip could read those tables is 2.5 s
    assert share.read(dict(run, expert_trace={"kernel_s": {"expert_matmul": 2.6}, "kernel_calls": {"expert_matmul": 3360}})) < 100


@pytest.mark.parametrize("name", NEW)
def test_a_reader_finds_nothing_in_a_program_without_the_mechanism(name):
    """What the parent commit's runs and the other cells' records look like:
    Solar's grouped matmul and scopes under ITS keys, no ``conv`` block, no
    ``conv_shape`` and an expert shape that holds a share."""
    run = {
        "trace": _Trace(), "device": {"kind": "TPU v5 lite", "memory_peak_bytes": 1},
        "slice": {"decode_lengths": [[100]], "prefill_valid": [[256]], "kv_shape": {}, "kda_shape": {},
                  "expert_shape": dict(EXPERTS, held=40, router_outputs=320)},
        "expert_trace": {"kernel_s": {"expert_matmul": 0.4}, "kernel_calls": {"expert_matmul": 36}},
        "scope_s": {("prefill", "delta.prefill_scan"): 0.2, ("decode", "delta.conv"): 0.1},
        "program_s": {"prefill": [1.0, 3], "decode": [2.0, 9]},
        "kda": {"recurrent_state_bytes_per_chip": 2**32, "expert_assignments_held": 99, "expert_assignments_held_live": 9},
        "latent": {"expert_assignments_held": 99}, "stats_delta": {"paged_kernel_steps": 10},
    }
    assert _reader(name).read(run) is None
    assert _reader(name).read(dict(run, scope_s=None, trace=None, expert_trace=None)) is None
    assert _reader(name).read({"device": {"kind": "TPU v5 lite"}}) is None


def test_the_cell_reports_every_metric_without_a_list_and_its_own_readers_wait():
    cell = catalog.load_cell(CELL)
    listed = {m["name"] for m in catalog.benchmark()["per_layer"]}
    for name in NEW:  # files the harness does not read yet: each is a reader all the same
        reader = _reader(name)
        assert name not in listed and name not in cell.per_layer
        assert (reader.UNIT in ("%", "GiB", "count") and reader.LAYER in ("kernels", "caption engine")
                and reader.MOVES == "output_tok_per_s" and reader.SOURCE in ("device_trace", "program_counter"))
    for old in ("qwen2vl-2b.text-rewrite", "granite-4.0-h-micro.text-rewrite", "deepseek-v2-ep8.text-rewrite",
                "olmo-hybrid-7b-pp2.text-rewrite", "solar-open2-ep8.text-rewrite"):
        other = catalog.load_cell(old)
        assert other.traffic == cell.traffic  # the sixth architecture on ONE traffic file
    for theirs in ("engine.recurrent_state_gib", "engine.kda_state_gib", "kernel.kda_cell_expert_time_share",
                   "kernel.expert_time_share", "kernel.paged_decode_hbm_share"):
        assert theirs not in cell.per_layer  # the other cells' own, by their `workloads`
    assert cell.chips == 1 and cell.end_to_end == ("output_tok_per_s", "setup_s")
    # every metric without a `workloads` list is this cell's too: seventeen of them
    everywhere = [m["name"] for m in catalog.benchmark()["per_layer"] if "workloads" not in m]
    assert len(everywhere) == 17 and set(everywhere) == set(cell.per_layer)
    # the issue's traffic, letter for letter: the mix's file is the five other cells'
    p = cell.traffic["params"]
    assert (p["frames"], p["prefix_tokens"], p["output_tokens"], p["backlog"], p["trace_seconds"]) == (0, 64, 192, 4, 8.0)
    assert p["prompt_tokens"] == {"min": 144, "max": 592, "step": 64} and cell.traffic["generator"] == "caption_requests"
    # the cell's own file says how many rows to warm and how long to trace, never what is sent
    assert cell.harness == {"warm_rows": 8, "trace_seconds": 4.0}
    assert {k: v for k, v in cell.traffic_params(False).items() if k not in cell.harness} == {
        k: v for k, v in p.items() if k not in cell.harness}


def test_benchmark_gained_entries_and_lost_none():
    """Written so that the NEXT cell does not break it: what the benchmark had
    (the parent commit's eight configurations, nine cells and 54 per-layer
    metrics, by name and in order) is all there, and this PR's configuration
    and cell come after it. No per-layer entry is added: the benchmark's own
    tests/perfbench/test_prep_round_metric.py says PR 50's is the last of 54."""
    bench = catalog.benchmark()
    configs = [c["name"] for c in bench["configs"]]
    cells = [w["name"] for w in bench["workloads"]]
    metrics = [m["name"] for m in bench["per_layer"]]
    had = ["qwen2vl-2b", "qwen25vl-7b-tp4", "granite-4.0-h-micro", "deepseek-v2-ep8", "trinity-large-ep8",
           "keye-vl2-a3b-ep8", "olmo-hybrid-7b-pp2", "solar-open2-ep8"]
    assert configs[:8] == had and configs[8] == CONFIG
    assert cells[8] == "solar-open2-ep8.text-rewrite" and cells[9] == CELL
    at = metrics.index("engine.prep_requests_per_round")  # the last the benchmark had, and has
    assert at == 53 and len(metrics) >= 54
    # PR 50's entry is there, unchanged, equal to its reader's, and every cell reports it, the new one too
    entry, reader = bench["per_layer"][at], _reader("engine.prep_requests_per_round")
    assert entry == {"name": "engine.prep_requests_per_round", "unit": reader.UNIT, "better": "higher",
                     "source": reader.SOURCE, "layer": reader.LAYER, "moves": reader.MOVES}
    assert all("engine.prep_requests_per_round" in catalog.load_cell(c).per_layer for c in cells)
    # no older metric lists the new cell, and no end-to-end entry moved
    assert not [m["name"] for m in bench["per_layer"][: at + 1] if CELL in m.get("workloads", [])]
    assert [(e["name"], e["bound"]) for e in bench["end_to_end"]] == [("output_tok_per_s", 0.08), ("setup_s", 0.1)]
    assert sum(w["chips"] == 4 for w in bench["workloads"][:10]) == 1 and bench["run_seconds"] == 40
    entry = bench["workloads"][9]
    assert set(entry) == {"name", "config", "traffic", "chips", "why"} and len(entry["why"]) <= 200
    assert (entry["config"], entry["traffic"], entry["chips"]) == (CONFIG, "text-rewrite", 1)
    # what test_catalog.py::test_config_file asserts, with widths told from depth
    # (its pattern takes the word "hidden" in num_hidden_layers for a width)
    entry = bench["configs"][8]
    conf = json.loads((catalog.CHECKOUT / entry["file"]).read_text())
    assert set(entry) == {"name", "source", "file", "reduced", "why"} and entry["file"].startswith("perfbench/")
    assert conf["name"] == CONFIG and conf["source"] == entry["source"] and conf["reduced"] == entry["reduced"]
    assert set(conf["reduced_why"]) == set(conf["reduced"]) == set(conf["published"]) and len(entry["reduced"]) <= 16
    assert any(w["config"] == entry["name"] for w in bench["workloads"])
    widths = ("hidden_size", "intermediate", "latent", "state", "projection", "_dim", "_rank", "expansion", "experts_per")
    assert not [k for k in conf["reduced"] if any(w in k for w in widths)]
    assert conf["assumed"] and conf["deployment"] and "check" in conf and len(entry["why"]) <= 200
    assert len(json.dumps(bench)) < 64 * 1024


def test_config_file_is_the_catalog_row_and_the_flavor():
    from cosmos_curate_tpu.models.vlm.model import vlm_flavor
    from perfbench.drivers.caption_engine_conv import check_config_file

    conf = catalog.load_cell(CELL).config
    flavor = vlm_flavor(conf["flavor"])
    check_config_file(conf, flavor.cfg, flavor.kv_lanes, flavor.prefill_rows)  # raises where they disagree
    with pytest.raises(ValueError, match="conv_L_cache"):
        conv = dataclasses.replace(flavor.cfg.short_conv, l_cache=4)
        check_config_file(conf, dataclasses.replace(flavor.cfg, short_conv=conv), flavor.kv_lanes, 8)
    with pytest.raises(ValueError, match="assumed.norm_topk_eps"):
        moe = dataclasses.replace(flavor.cfg.moe, norm_topk_eps=1e-20)
        check_config_file(conf, dataclasses.replace(flavor.cfg, moe=moe), flavor.kv_lanes, 8)
    with pytest.raises(ValueError, match="assumed.block"):
        check_config_file(conf, dataclasses.replace(flavor.cfg, qk_norm=False), flavor.kv_lanes, 8)
    with pytest.raises(ValueError, match="published_counts"):  # a share held: no longer the layer whole
        moe = dataclasses.replace(flavor.cfg.moe, held=(0, 8))
        check_config_file(conf, dataclasses.replace(flavor.cfg, moe=moe), flavor.kv_lanes, 8)
    with pytest.raises(ValueError, match="kv_lanes"):
        check_config_file(conf, flavor.cfg, ((1024, 8),), 8)
    with pytest.raises(ValueError, match="prefill_rows"):
        check_config_file(conf, flavor.cfg, flavor.kv_lanes, None)
    # the published widths, uncut; all 64 experts and the whole vocabulary
    for key, value in dict(
        hidden_size=2048, intermediate_size=11776, moe_intermediate_size=1536, num_attention_heads=32,
        num_key_value_heads=8, head_dim=64, num_experts=64, num_experts_per_tok=4, conv_L_cache=3, conv_bias=False,
        vocab_size=65536, norm_eps=1e-5, routed_scaling_factor=1, norm_topk_prob=True, use_expert_bias=True,
        num_dense_layers=2, model_type="lfm2_moe",
    ).items():
        assert conf[key] == value, key
    assert conf["rope_parameters"] == {"rope_theta": 1000000, "rope_type": "default"}
    assert conf["published_counts"] == {"router_outputs": 64, "held_experts": [0, 64]}
    assert conf["reduced"] == ["num_hidden_layers", "layer_types", "max_position_embeddings"]
    assert conf["num_hidden_layers"] == 10 and conf["max_position_embeddings"] == 4096 == flavor.cfg.max_seq
    assert conf["layer_types"] == conf["published"]["layer_types"][:10] and len(conf["published"]["layer_types"]) == 40
    assert conf["published"]["layer_types"].count("conv") == 30 and conf["published"]["layer_types"].count("full_attention") == 10
    assert (conf["published"]["num_hidden_layers"], conf["published"]["max_position_embeddings"]) == (40, 128000)
    assert conf["serving"]["kv_lanes"] == [[1024, 256], [4096, 8]] and conf["serving"]["block_size"] == 16
    assert (conf["serving"]["prefill_chunk"], conf["serving"]["prefill_rows"], conf["serving"]["async_prep"]) == (256, 8, True)
    assert conf["serving"]["paged_attention"] == "auto"
    assert "FIRST OF FIVE" in conf["deployment"] and "WHOLE" in conf["deployment"] and "FOUR TIMES" in conf["deployment"]
    for point in ("tie_word_embeddings_why", "dense_width", "expert_block", "router_precision_why", "selection_bias_seed",
                  "block", "conv_state_dtype", "kv_cache_dtype", "weights", "tokenizer"):
        assert conf["assumed"][point], point
    limits = ("reference_rel_tol", "tail_rms_tol", "tails_rms_tol", "decode_rel_tol", "xla_path_rel_tol", "router_weight_tol",
              "routing_margin", "routing_flip_share")
    for limit in limits:
        assert 0 < conf["check"][limit] <= 0.07 and len(conf["check"][limit + "_why"]) > 100, limit
        assert "First reading" in conf["check"][limit + "_why"] and "NOT correct" in conf["check"][limit + "_why"], limit
    # every limit tells a precision: none is the kind a flipped expert passes (REVIEW.md, PR 54)
    assert conf["check"]["tail_rms_tol"] < conf["check"]["tails_rms_tol"] <= 0.06 and conf["check"]["router_weight_tol"] < 1e-4
    assert "expert_tail_rms_tol" not in conf["check"] and "decode_routing_margin" not in conf["check"]
    assert flavor.cfg.moe.hand_out_choice and conf["check"]["planted_faults"]
    assert conf["assumed"]["router_precision"] == flavor.cfg.moe.router_precision == "highest"
    assert conf["check"]["text_tokens"] == [[150, 256], [600, 767]] and conf["check"]["decode_steps"] == 8
    assert conf["check"]["decode_prompt_tokens"] + conf["check"]["decode_steps"] == 257  # the short and the prefix groups' shape
    assert conf["check"]["lower_precision_readings"] and conf["check"]["routing_margin_why"] and conf["check"]["budget"]
    catalog_file = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog_file):  # every number of the catalog row, or listed as reduced
        rows = [json.loads(line) for line in open(catalog_file) if line.strip()]
        row = next(r for r in rows if r["name"] == "LFM2-24B-A2B")
        assert conf["source"] == row["source_url"]
        assert {k for k, v in row["config"].items() if conf.get(k) != v} == set(conf["reduced"])


def _tiny():
    from cosmos_curate_tpu.models.vlm import model as vlm_model
    from perfbench.drivers import caption_engine_conv as driver

    cell = catalog.load_cell(CELL)
    cfg = vlm_model.VLM_LFM2_MOE_TINY_TEST
    check = dict(cell.config["check"], **cell.config["rehearse"]["check"])
    traffic = catalog.load_module("traffic", cell.traffic["generator"]).CaptionTraffic(
        cell.traffic_params(True), 7, vocab=cfg.vocab, image_size=cfg.vision.image_size
    )
    ref = catalog.load_module("reference", driver.REFERENCE)
    return driver, ref, cfg, driver.make_params(cfg, 7), traffic, check


def test_a_groups_prompts_are_prefixes_of_one_sequence_and_the_reference_follows_a_choice():
    """A group compiles ONE shape: its prompts are prefixes of one seeded
    sequence, a decode step's token takes the next place; what the reference
    says of a prefix while it follows a choice given for the prefix alone is
    what a forward over the prefix alone says."""
    import jax.numpy as jnp

    driver, ref, cfg, params, traffic, check = _tiny()
    sizes = ref.model_kwargs(cfg)
    groups = driver.plan(traffic, check)
    assert set(groups) == {"short", "long", "prefix"}
    for key, group in groups.items():
        lo, hi = check["text_tokens"][0 if key == "short" else 1] if key != "prefix" else [8 + n for n in check["prefix_prompt_tokens"]]
        assert len(group.ends) == check["prompts"] and group.ends[0] + 1 == lo and group.ends[-1] + 1 == hi
        assert len(group.ids) == hi + 1  # one more id than the longest prompt: the place of a decode step's token
        name, prompt, prefix = group.requests()[0]
        assert prefix + prompt == group.ids[: group.ends[0] + 1] and name.startswith("check-")
        assert (len(prefix) == 8) == (key == "prefix")
    group = groups["long"]
    own = driver.follow(ref, params, sizes, group.ids)
    sparse = cfg.n_layers - cfg.moe.first_dense
    assert own.own.shape == (sparse, len(group.ids), cfg.moe.top_k) and own.margins.shape == (sparse, len(group.ids))
    end = group.ends[1]
    # another choice for the first end + 1 positions: every expert's neighbour
    other = (own.own[:, : end + 1] + 1) % cfg.moe.n_experts
    followed = driver.follow(ref, params, sizes, group.ids, other)
    logits, tails = driver.answers_at(ref, params, sizes, followed, [end])
    np.testing.assert_array_equal(followed.own[:, end + 1 :].shape, own.own[:, end + 1 :].shape)
    z = []
    h, _ = ref.forward(params, jnp.asarray(group.ids[: end + 1], jnp.int32), z=z, follow=jnp.asarray(other), **sizes)
    alone = np.asarray(ref.logits_of(params, h[-1:], **sizes))[0]
    np.testing.assert_allclose(alone, logits[0], atol=2e-5 * float(np.abs(alone).max()))
    np.testing.assert_allclose(np.asarray(ref.tails_after(z, end + 1)), tails[0], atol=1e-5)
    assert driver._rel(logits[0], driver.answers_at(ref, params, sizes, own, [end])[0][0]) > 1e-3  # and it is another answer
    # the choice is judged apart: the reference's own where its margin is wide, counted where it is not
    assert driver.judge_choice("its own", [(own.own, own.own, own.margins)], check)
    assert not driver.judge_choice("every expert's neighbour", [(other, own.own[:, : end + 1], own.margins[:, : end + 1])], check)
    narrow = np.where(own.margins[..., None] < check["routing_margin"], (own.own + 1) % cfg.moe.n_experts, own.own)
    assert driver.judge_choice("another expert where the margin is narrow", [(narrow, own.own, own.margins)], check)
    assert not driver.judge_choice("nothing read", [], check)
    # a margin's noise has a tail: ONE flip where the margin is wide passes the cell's share (0.003 of some
    # thousands) and not the tiny preset's (none), and a lost bias's one in a hundred passes neither
    one = own.own.copy()
    layer, at = np.argwhere(own.margins >= check["routing_margin"])[0]
    one[layer, at] = (one[layer, at] + 1) % cfg.moe.n_experts
    many = [(one, own.own, own.margins)] + [(own.own, own.own, own.margins)] * 4  # 1 of ~1,500 wide ones
    full = dict(check, routing_flip_share=catalog.load_cell(CELL).config["check"]["routing_flip_share"])
    assert full["routing_flip_share"] == 0.003 and check["routing_flip_share"] == 0.0
    assert driver.judge_choice("one flip at a wide margin, the cell's share", many, full)
    assert not driver.judge_choice("one flip at a wide margin, none allowed", many, check)
    assert not driver.judge_choice("one in a hundred", [(one, own.own, own.margins)] * 1 + [(other, own.own[:, : end + 1], own.margins[:, : end + 1])], full)


def test_a_requests_choice_is_put_together_from_the_prefix_its_chunks_and_its_steps():
    from perfbench.drivers.caption_engine_conv import _ConvPrivate

    spy = _ConvPrivate.__new__(_ConvPrivate)  # the bookkeeping alone: no engine
    chunk = lambda at, n, t=8: (at, n, np.full((3, t, 2), at, np.int32))  # noqa: E731
    spy.prefix_choice = np.full((3, 8, 2), 100, np.int32)
    spy.prompt_choice = {"check-a": [chunk(4, 8), chunk(12, 3)], "check-b": [chunk(0, 8)], "check-c": []}
    spy.step_choice = {"check-a": [np.full((3, 2), 7, np.int32), np.full((3, 2), 9, np.int32)]}
    got = spy.choice_of("check-a", 4, 15, steps=2)
    assert got.shape == (3, 17, 2)
    assert got[0, :, 0].tolist() == [100] * 4 + [4] * 8 + [12] * 3 + [7, 9]
    assert spy.choice_of("check-b", 0, 8).shape == (3, 8, 2)
    assert spy.choice_of("check-b", 0, 9) is None  # a position no program that was read covers
    assert spy.choice_of("check-a", 4, 15, steps=3) is None and spy.choice_of("check-c", 0, 4) is None
    spy.prefix_choice = None
    assert spy.choice_of("check-a", 4, 15) is None  # the prefix's build was not read


@pytest.mark.parametrize("low, holds", [
    (None, True),  # the program itself: a float32 router at `highest`
    ({"router_mantissa_bits": 7}, False),
    ({"activation_mantissa_bits": 7, "tail_mantissa_bits": 7}, True),  # what the file states: no activation enters
])
def test_the_routers_precision_is_held_where_nothing_has_rounded_the_inputs(low, holds):
    driver, ref, cfg, params, traffic, check = _tiny()
    assert driver.check_router(ref, cfg, params, ref.model_kwargs(cfg), traffic, check, low=low) is holds


def test_a_router_in_fewer_bits_is_not_correct(monkeypatch):
    """The PROGRAM in the nearest precision below the stated one."""
    import jax

    from cosmos_curate_tpu.models.vlm import model as vlm_model

    driver, ref, cfg, params, traffic, check = _tiny()
    route = vlm_model.route
    monkeypatch.setattr(vlm_model, "route", lambda moe, logits, bias=None: route(
        moe, jax.lax.reduce_precision(logits, exponent_bits=8, mantissa_bits=7), bias))
    assert not driver.check_router(ref, cfg, params, ref.model_kwargs(cfg), traffic, check)


def test_tails_in_fewer_bits_are_not_correct():
    """``judge_tails`` on the reference's own tails rounded as a store of the
    nearest type below bfloat16 would hold them (3 bits of mantissa), and as
    the engine's bfloat16 does; in ONE deep layer alone too: every layer's
    tails are held for every request."""
    import jax

    driver, ref, cfg, params, traffic, check = _tiny()
    group = driver.plan(traffic, check)["short"]
    f = driver.follow(ref, params, ref.model_kwargs(cfg), group.ids)
    want = list(driver.answers_at(ref, params, ref.model_kwargs(cfg), f, group.ends)[1])
    rounded = lambda bits: [np.asarray(jax.lax.reduce_precision(t, exponent_bits=8, mantissa_bits=bits)) for t in want]  # noqa: E731
    assert driver.judge_tails("bfloat16 tails", rounded(7), want, check)
    assert not driver.judge_tails("8-bit-float tails", rounded(3), want, check)
    shifted = [np.roll(t, cfg.dim, axis=-1) for t in want]  # z_{t-1} where z_{t-2} belongs
    assert not driver.judge_tails("tails one position off", shifted, want, check)
    for layer in (0, 3):
        only = [t.copy() for t in want]
        only[-1][layer] = np.roll(only[-1][layer], 1)  # ONE request's tails of one layer, a channel off
        assert not driver.judge_tails(f"one request's layer {layer} a channel off", only, want, check)


def test_a_control_below_the_stated_precision_comes_out_not_correct():
    from perfbench.drivers import caption_engine_conv as driver

    assert driver.lower_precision(5400000003, ["tails", "stated"], rehearse=True) == {"tails": False, "stated": True}
    assert set(driver.CONTROLS) == {"router", "activations", "tails", "stated"}


def test_faults_planted_above_the_first_expert_layer_come_out_not_correct():
    """The engine's parameters carry the fault, the reference's do not. At the
    tiny preset (one attention layer: no second one's keys to mis-project)."""
    from cosmos_curate_tpu.models.vlm.model import VLM_LFM2_24B_A2B_PP5
    from perfbench.drivers import caption_engine_conv as driver

    assert set(driver.faults(VLM_LFM2_24B_A2B_PP5)) == {"tables-swapped", "one-table", "kv-projection", "bias-lost", "final-norm"}
    what = {k: v[0] for k, v in driver.faults(VLM_LFM2_24B_A2B_PP5).items()}
    assert "layers 8 and 9" in what["tables-swapped"] and "(6)" in what["kv-projection"] and "(2)" in what["kv-projection"]
    # (the tiny preset's seeded tables of width 32 add little to the stream: swapped ones read under its limits)
    assert driver.planted(5400000004, ["final-norm"], rehearse=True) == {"final-norm": False}


def test_cell_rehearses_on_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    out = subprocess.run(
        [sys.executable, "-m", "perfbench.run", "--workload", CELL, "--seed", "5400000002",
         "--seconds", "4", "--trace", "1", "--rehearse"],
        capture_output=True, text=True, env=env, cwd=str(catalog.CHECKOUT), timeout=900,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    # on the CPU only the program's counters are written under a metric's name (the cell's own readers are no
    # `BENCHMARK.json` entries yet: the traced run puts them on a line of its own)
    assert set(line["metrics"]) == {"device.compiles_in_window"}
    own = json.loads(next(l for l in out.stdout.splitlines() if "the cell's own readers" in l).split("readers: ", 1)[1])
    assert set(own) == set(NEW)
    # the tiny preset: 4 conv layers x 2 x 64 values x 2 B a row, 7 rows
    assert own["engine.conv_tail_gib"] == pytest.approx(7 * 4 * 128 * 2 / 2**30)
    # top 2 x 4 sparse layers a live row, at most the lanes' 6 rows a program
    assert 0 < own["engine.whole_moe_assignments_per_program"] <= 6 * 2 * 4
    assert "the first conv layer's tails vs float32 reference" in out.stdout
    assert "every conv layer's tails vs float32 reference" in out.stdout
    assert "vs the float32 reference that follows the program's choice" in out.stdout
    assert "the program's experts vs the reference's own on the followed path" in out.stdout
    assert "the prefix requests on the engine's XLA path" in out.stdout and "one turnover of" in out.stdout
    assert "the program's router vs the float32 reference's" in out.stdout
    assert "three prefill chunks, padding in the last" in out.stdout and "shared 8-token prefix" in out.stdout
    assert "FAILED" not in out.stdout and "still waiting for" not in out.stdout
