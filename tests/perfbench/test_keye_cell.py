"""The Keye cell's own pieces of the yardstick: its operation and byte counts
against hand counts, its six readers on a hand-made record (and None where there
is nothing to read), device seconds by named scope from a compiled text and a
hand-made trace, its configuration file against the catalog row and the flavor,
the reference's eight shares summing to the uncut layer, the choice's overlap
statistic, and a rehearsal of the control flow."""

import dataclasses
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from perfbench import catalog, trace_reduce
from perfbench.roofline import sparse_bytes

CELL = "keye-vl2-a3b-ep8.digest-2k-30k"
NEW = [
    "kernel.sparse_attention_time_share", "kernel.index_score_roofline_share", "kernel.sparse_decode_hbm_share",
    "kernel.topk_select_time_share", "kernel.sparse_positions_skipped_share", "engine.index_pool_gib",
]
SHAPE = dict(n_layers=8, top_k=2048, index_heads=16, index_dim=64, n_kv_heads=4, head_dim=128, dtype_bytes=2)
POSITION = 4 * 128 * 2 * 2  # K and V of one position in one layer: 2,048 B, the issue's number


def _reader(name):
    return catalog.load_module("layer_metrics", name)


class _Trace:
    busy_s_by_chip = [4.0]
    kernel_s = {"decode:attn.sparse": 0.5, "prefill:attn.sparse": 1.0}


def _record():
    return {
        "trace": _Trace(), "device": {"kind": "TPU v5 lite", "memory_peak_bytes": 1},
        "slice": {
            "decode_lengths": [[30000, 1000, 0, 0]] * 10,  # two live rows a step, ten steps
            "prefill_rows": [[(25600, 256)], [(0, 256), (1024, 200)]],
            "sparse_shape": SHAPE,
        },
        "scope_s": {
            ("decode", "attn.index_score"): 0.1, ("prefill", "attn.index_score"): 0.3,
            ("decode", "attn.select"): 0.2, ("prefill", "attn.select"): 0.2,
            ("decode", "attn.sparse"): 0.5, ("prefill", "attn.sparse"): 1.0,
            ("decode", "moe.experts"): 0.7, ("prefill", "attn.index"): 0.05,
        },
        "sparse": {
            "index_pool_bytes_per_chip": 7 * 2**27, "sparse_decode_calls": 80,
            "sparse_decode_positions_live": 8 * 31000 * 10, "sparse_decode_positions_chosen": 8 * 3048 * 10,
        },
    }


def test_bytes_and_operations_against_a_hand_count():
    # a decode step of two rows: min(context, 2048) positions a row a layer, K and V
    assert sparse_bytes.chosen_decode_kv_bytes(
        [30000, 1000, 0], n_layers=8, top_k=2048, n_kv_heads=4, head_dim=128
    ) == 8 * (2048 + 1000) * POSITION
    # the issue's numbers at 30k: 3.9 MB of index keys and 4.2 MB of chosen K/V a row a layer
    assert sparse_bytes.index_score_bytes(30528, n_layers=1, index_dim=64) == 30528 * 128 == 3_907_584
    assert sparse_bytes.chosen_decode_kv_bytes([30528], n_layers=1, top_k=2048, n_kv_heads=4, head_dim=128) == 4_194_304
    assert sparse_bytes.index_score_flops(1000, n_layers=8, index_heads=16, index_dim=64) == 8 * 1000 * 2 * 16 * 64
    # a chunk of 3 queries written at 10: they see 11, 12 and 13 positions
    assert sparse_bytes.prefill_pairs(10, 3) == 11 + 12 + 13
    assert sparse_bytes.prefill_pairs(0, 256) == 256 * 257 // 2


def test_the_six_readers_on_a_hand_made_record():
    run = _record()
    assert _reader("kernel.sparse_attention_time_share").read(run) == pytest.approx(100 * 2.3 / 4.0)
    assert _reader("kernel.topk_select_time_share").read(run) == pytest.approx(10.0)
    moved = 10 * 8 * (2048 + 1000) * POSITION
    assert _reader("kernel.sparse_decode_hbm_share").read(run) == pytest.approx(100 * moved / 819e9 / 0.5)
    least = 10 * max(8 * 31000 * 2 * 16 * 64 / 197e12, 8 * 31000 * 128 / 819e9)
    for rows in run["slice"]["prefill_rows"]:
        pairs = sum(sparse_bytes.prefill_pairs(w, v) for w, v in rows)
        live = sum(w + v for w, v in rows)
        least += max(8 * pairs * 2 * 16 * 64 / 197e12, 8 * live * 128 / 819e9)
    got = _reader("kernel.index_score_roofline_share").read(run)
    assert got == pytest.approx(100 * least / 0.4) and 0 < got < 100
    assert _reader("kernel.sparse_positions_skipped_share").read(run) == pytest.approx(100 * (1 - 3048 / 31000))
    assert _reader("engine.index_pool_gib").read(run) == 0.875
    # the scopes' seconds are this cell's attention kernels, all of them
    assert _reader("kernel.paged_attention_time_share").read(run) == pytest.approx(100 * 1.5 / 4.0)


@pytest.mark.parametrize("name", NEW)
def test_a_reader_finds_nothing_in_a_program_without_the_mechanism(name):
    """What the parent commit's runs and the other cells' records look like: a
    trace of the paged kernels, no scopes, no index keys."""
    run = {
        "trace": _Trace(), "device": {"kind": "TPU v5 lite", "memory_peak_bytes": 1},
        "slice": {"decode_lengths": [[100]], "kv_shape": {}},
        "stats_delta": {"paged_kernel_steps": 10},
    }
    assert _reader(name).read(run) is None
    assert _reader(name).read(dict(run, sparse={}, scope_s=None, trace=None)) is None
    assert _reader(name).read(dict(run, sparse={"index_pool_bytes_per_chip": 0, "sparse_decode_positions_live": 0}, scope_s={})) is None


def test_the_cell_reports_the_new_metrics_and_the_old_cells_do_not():
    cell = catalog.load_cell(CELL)
    assert set(NEW) <= set(cell.per_layer)
    for old in ("qwen2vl-2b.text-rewrite", "deepseek-v2-ep8.text-rewrite", "trinity-large-ep8.digest-1k-12k"):
        assert not set(NEW) & set(catalog.load_cell(old).per_layer)
    assert cell.chips == 1 and cell.end_to_end == ("output_tok_per_s", "setup_s")
    # the issue's traffic, letter for letter
    grid = list(range(1536, 30209, 4096))
    assert grid == [1536, 5632, 9728, 13824, 17920, 22016, 26112, 30208]
    p = cell.traffic["params"]
    assert (p["frames"], p["prefix_tokens"], p["output_tokens"], p["backlog"], p["trace_seconds"]) == (0, 64, 256, 4, 8.0)
    assert p["prompt_tokens"] == {"min": 1536, "max": 30208, "step": 4096}
    assert cell.traffic["generator"] == "caption_requests"
    contexts = [64 + n + 256 for n in grid]
    assert (contexts[0], contexts[-1]) == (1856, 30528) and sum(c <= 2048 for c in contexts) == 1  # one never chooses
    assert cell.traffic_params(False)["warm_rows"] == 4


def test_config_file_is_the_catalog_row_and_the_flavor():
    from cosmos_curate_tpu.models.vlm.engine import default_block_size
    from cosmos_curate_tpu.models.vlm.model import IndexerConfig, vlm_flavor
    from perfbench.drivers.caption_engine_sparse import check_config_file

    conf = catalog.load_cell(CELL).config
    flavor = vlm_flavor(conf["flavor"])
    serving = (flavor.kv_lanes, flavor.prefill_rows)
    check_config_file(conf, flavor.cfg, *serving)  # raises where they disagree
    with pytest.raises(ValueError, match="sa_config"):
        check_config_file(conf, dataclasses.replace(flavor.cfg, indexer=IndexerConfig(top_k=1024)), *serving)
    with pytest.raises(ValueError, match="assumed"):
        check_config_file(conf, dataclasses.replace(flavor.cfg, qk_norm=False), *serving)
    with pytest.raises(ValueError, match="prefill_rows"):
        check_config_file(conf, flavor.cfg, flavor.kv_lanes, 8)
    assert conf["serving"]["block_size"] == 128 == default_block_size(flavor.kv_lanes)
    assert conf["serving"]["kv_lanes"] == [[8192, 4], [32768, 12]]
    # the check's own instruction fills whole blocks of the pool; the mix's does not
    assert conf["check"]["prefix_tokens"] // 128 == 2 > catalog.load_cell(CELL).traffic["params"]["prefix_tokens"] // 128
    assert conf["check"]["long_tokens"] > 26000
    # the published widths, uncut
    for key, value in dict(
        hidden_size=2048, num_attention_heads=32, num_key_value_heads=4, head_dim=128, moe_intermediate_size=768,
        intermediate_size=6144, num_experts_per_tok=8, norm_topk_prob=True, rope_theta=10000000, rms_norm_eps=1e-6,
    ).items():
        assert conf[key] == value, key
    assert conf["sa_config"] == {
        "indexer_head_dim": 64, "indexer_num_heads": 16, "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
        "q_chunk_size": 512, "topk": 2048,
    }
    assert conf["published_counts"]["router_outputs"] == 128 == flavor.cfg.moe.n_experts
    reduced = ["num_hidden_layers", "num_experts", "num_local_experts", "vocab_size", "max_position_embeddings"]
    assert conf["reduced"] == reduced
    assert (conf["num_hidden_layers"], conf["num_experts"], conf["num_local_experts"], conf["vocab_size"]) == (8, 16, 16, 18992)
    assert conf["max_position_embeddings"] == 32768 == flavor.cfg.max_seq
    assert len([k for k in conf["assumed"] if k[0].isdigit()]) == 5  # the issue's five points
    assert "EIGHT" in conf["deployment"] and "NOT modelled" in conf["deployment"]
    entry = next(c for c in catalog.benchmark()["configs"] if c["name"] == "keye-vl2-a3b-ep8")
    assert entry["reduced"] == conf["reduced"] and entry["source"] == conf["source"]
    assert set(conf["reduced_why"]) == set(conf["reduced"]) and conf["name"] == entry["name"]
    catalog_file = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog_file):  # every number of the catalog row, or listed as reduced
        rows = [json.loads(line) for line in open(catalog_file) if line.strip()]
        row = next(r for r in rows if r["name"] == "Keye-VL-2.0-30B-A3B")
        assert conf["source"] == row["source_url"]
        assert {k for k, v in row["config"].items() if conf.get(k) != v} == set(conf["reduced"])


def test_benchmark_gained_entries_and_lost_none():
    """Written so that the NEXT cell does not break it: what the benchmark had is
    all there in its order, and this PR's entries come after it."""
    bench = catalog.benchmark()
    configs = [c["name"] for c in bench["configs"]]
    cells = [w["name"] for w in bench["workloads"]]
    metrics = [m["name"] for m in bench["per_layer"]]
    had_configs = ["qwen2vl-2b", "qwen25vl-7b-tp4", "granite-4.0-h-micro", "deepseek-v2-ep8", "trinity-large-ep8"]
    assert configs[:5] == had_configs and configs[5] == "keye-vl2-a3b-ep8"
    assert cells[:6][-1] == "trinity-large-ep8.digest-1k-12k" and cells[6] == CELL
    at = metrics.index("engine.prefill_device_share")  # the last the benchmark had
    assert metrics[at + 1 : at + 7] == NEW and at == len(metrics[: at + 1]) - 1
    for m in bench["per_layer"][at + 1 : at + 7]:
        assert m["workloads"] == [CELL] and m["moves"] == "output_tok_per_s"
        reader = _reader(m["name"])
        assert (reader.UNIT, reader.LAYER, reader.MOVES, reader.SOURCE) == (m["unit"], m["layer"], m["moves"], m["source"])
    assert sum(w["chips"] == 4 for w in bench["workloads"][:7]) == 1 and bench["run_seconds"] == 40
    # what test_catalog.py::test_config_file asserts, with widths told from depth
    # (its pattern takes the word "hidden" in num_hidden_layers for a width)
    entry = bench["configs"][5]
    conf = json.loads((catalog.CHECKOUT / entry["file"]).read_text())
    assert set(entry) == {"name", "source", "file", "reduced", "why"} and entry["file"].startswith("perfbench/")
    assert len(entry["reduced"]) <= 16 and any(w["config"] == entry["name"] for w in bench["workloads"])
    widths = ("hidden_size", "intermediate", "latent", "state", "projection", "_dim", "_rank", "expansion", "experts_per")
    assert not [k for k in conf["reduced"] if any(w in k for w in widths)]
    assert conf["assumed"] and conf["deployment"] and "check" in conf and len(entry["why"]) <= 200


def test_the_references_eight_shares_sum_to_the_uncut_layer():
    """Every share of the experts, given to the reference as an argument, adds
    its part of the routed sum; with attention counted once the eight parts are
    the layer the uncut router and all sixteen experts give."""
    import flax.linen as nn
    import jax.numpy as jnp

    from cosmos_curate_tpu.models.vlm.engine import _init_params
    from cosmos_curate_tpu.models.vlm.model import VLM, VLM_KEYE_TINY_TEST, MoEFFN
    from perfbench.reference import keye_vl2 as ref

    cfg = dataclasses.replace(VLM_KEYE_TINY_TEST, moe=dataclasses.replace(VLM_KEYE_TINY_TEST.moe, held=None))
    tree = nn.unbox(_init_params(VLM(cfg), 0))
    sizes = ref.model_kwargs(cfg)
    lp = dict(tree["params"]["layer_1"])
    mp = dict(lp["moe"], router={"kernel": lp["moe"]["router"]["kernel"] * 20})  # probabilities that spread
    rng = np.random.default_rng(0)
    n = jnp.asarray(rng.normal(size=(48, cfg.dim)), jnp.float32)
    uncut, _ = ref.experts(n, mp, moe=sizes["moe"])
    parts = []
    for chip in range(8):  # eight chips, two consecutive experts each
        share = dict(mp, gate_up=mp["gate_up"][2 * chip : 2 * chip + 2], down=mp["down"][2 * chip : 2 * chip + 2])
        parts.append(ref.experts(n, share, moe=dict(sizes["moe"], held=(2 * chip, 2)))[0])
    np.testing.assert_allclose(np.asarray(sum(parts)), np.asarray(uncut), atol=1e-5)
    assert min(float(jnp.abs(p).max()) for p in parts) > 1e-4  # every chip's experts were chosen by some token
    # the whole layer: every chip's output is the stream after attention (replicated:
    # the same on all eight) plus ITS part, so the eight outputs less seven streams
    # are the uncut layer's output
    h = jnp.asarray(rng.normal(size=(48, cfg.dim)), jnp.float32)
    at = jnp.asarray([47])
    kw = {k: sizes[k] for k in ("attn", "indexer", "rms_eps")}
    whole, _, whole_sets, _ = ref.layer(h, dict(lp, moe=mp), at, moe=sizes["moe"], **kw)
    outputs = []
    for chip in range(8):
        share = dict(mp, gate_up=mp["gate_up"][2 * chip : 2 * chip + 2], down=mp["down"][2 * chip : 2 * chip + 2])
        out, _, sets, _ = ref.layer(h, dict(lp, moe=share), at, moe=dict(sizes["moe"], held=(2 * chip, 2)), **kw)
        np.testing.assert_array_equal(np.asarray(sets), np.asarray(whole_sets))  # the choice is no chip's own
        outputs.append(out)
    nothing = dict(mp, gate_up=jnp.zeros_like(mp["gate_up"][:2]), down=jnp.zeros_like(mp["down"][:2]))
    stream, _, _, _ = ref.layer(h, dict(lp, moe=nothing), at, moe=dict(sizes["moe"], held=(0, 2)), **kw)
    np.testing.assert_allclose(np.asarray(sum(outputs) - 7 * stream), np.asarray(whole), atol=3e-5)
    # and the program's own layer, told the same share, gives the same part
    held = dataclasses.replace(cfg, moe=dataclasses.replace(cfg.moe, held=(2, 2)))
    share = dict(mp, gate_up=mp["gate_up"][2:4], down=mp["down"][2:4])
    got = MoEFFN(held, dtype=jnp.float32).apply({"params": share}, n[None])[0]
    want, _ = ref.experts(n, share, moe=dict(sizes["moe"], held=(2, 2)))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


def test_overlap_and_the_judgement_of_a_choice(capsys):
    from perfbench.drivers.caption_engine_sparse import judge_choice, overlap, unpack_choice

    a = np.zeros(100, bool)
    a[:20] = True
    b = np.zeros(100, bool)
    b[5:25] = True
    assert overlap(a, b) == 15 / 25 and overlap(a, a) == 1.0
    words = np.array([[0b1011, 1]], np.uint32)
    assert unpack_choice(words, 34).nonzero()[1].tolist() == [0, 1, 3, 32]
    sets = np.stack([a, a])
    assert judge_choice("same", [(sets, sets)], 20, 0.7)
    assert not judge_choice("shifted", [(np.stack([b, b]), sets)], 20, 0.7)  # 0.6 of the set shared
    full = np.ones((2, 100), bool)
    assert not judge_choice("the choice left out", [(full, sets)], 20, 0.1)  # the wrong size fails whatever the overlap
    assert judge_choice("under the top-k", [(full, full)], 2048, 0.7)  # every position while there are no more than k
    assert "FAILED" in capsys.readouterr().out


def test_device_seconds_by_scope_from_a_compiled_text_and_a_trace():
    """``scope_maps`` reads which instructions a ``jax.named_scope`` covers out of
    a compiled program's text; ``scope_seconds`` gives each operation's event to
    the program whose run holds it and to that instruction's scope."""
    import jax
    import jax.numpy as jnp

    from perfbench.drivers.caption_engine_sparse import SCOPES, scope_maps, scope_seconds

    assert SCOPES.search("jit(f)/layer_0/attn.index_score/dot").group(0) == "attn.index_score"
    assert SCOPES.search("jit(f)/layer_0/attn.index/dense").group(0) == "attn.index"

    def program(x, y):
        with jax.named_scope("attn.select"):
            top, _ = jax.lax.top_k(x, 4)
        with jax.named_scope("attn.sparse"):
            out = jnp.tanh(top) @ y
        return out + 1.0

    f = jax.jit(program)
    shapes = (jax.ShapeDtypeStruct((8, 64), jnp.float32), jax.ShapeDtypeStruct((4, 16), jnp.float32))
    maps = scope_maps({"decode": [(f, shapes)]})
    assert set(maps["decode"].values()) == {"attn.select", "attn.sparse"}
    select = next(n for n, s in maps["decode"].items() if s == "attn.select")
    attend = next(n for n, s in maps["decode"].items() if s == "attn.sparse")
    ms = 1_000_000
    planes = [
        trace_reduce.Plane("/host:CPU", [trace_reduce.Line("python", [(trace_reduce.SLICE_SPAN, 0, 100 * ms)])]),
        trace_reduce.Plane("/device:TPU:0", [
            trace_reduce.Line("XLA Modules", [
                ("jit_decode_step_indexed(1)", 10 * ms, 20 * ms), ("jit_prefill_batch_indexed(2)", 40 * ms, 20 * ms),
                ("jit_decode_step_indexed(1)", 95 * ms, 20 * ms),
            ]),
            trace_reduce.Line("XLA Ops", [
                (f"%{select} = f32[8,4] sort(...)", 11 * ms, 2 * ms),
                (f"%{attend} = f32[8,16] fusion(...)", 14 * ms, 3 * ms),
                ("%other.7 = f32[8] fusion(...)", 18 * ms, 5 * ms),
                (f"%{select} = f32[8,4] sort(...)", 41 * ms, 9 * ms),  # the same name inside ANOTHER program: not this map's
                (f"%{attend} = f32[8,16] fusion(...)", 96 * ms, 10 * ms),  # 4 ms of it inside the slice
            ]),
        ]),
    ]
    got = scope_seconds(planes, maps)
    assert got == {("decode", "attn.select"): pytest.approx(0.002), ("decode", "attn.sparse"): pytest.approx(0.007)}
    assert scope_seconds(planes[:1], maps) is None


@pytest.mark.parametrize("seed", ["4000000002", "4000000301"])
def test_cell_rehearses_on_the_cpu(seed):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    out = subprocess.run(
        [sys.executable, "-m", "perfbench.run", "--workload", CELL, "--seed", seed,
         "--seconds", "4", "--trace", "1", "--rehearse"],
        capture_output=True, text=True, env=env, cwd=str(catalog.CHECKOUT), timeout=600,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    # on the CPU only the program's counters are written under a metric's name
    assert set(line["metrics"]) == {
        "device.compiles_in_window", "kernel.sparse_positions_skipped_share", "engine.index_pool_gib",
    }
    assert 10 < line["metrics"]["kernel.sparse_positions_skipped_share"]["value"] < 100
    assert "chosen sets (query x layer)" in out.stdout


def test_lengths_are_drawn_without_replacement_in_pairs_of_one_sum():
    """The issue's traffic: the existing generator's grid, uniform, every length
    once a run of eight in an order from (seed, run). This cell's orders are the
    run's four pairs of one sum (31,744 tokens) in a drawn order, each pair in a
    drawn order: any stretch of the queue between two pairs' edges holds the same
    prompt tokens, whatever the seed."""
    from perfbench.drivers.caption_engine_sparse import lengths_in_pairs
    from perfbench.traffic.caption_requests import CaptionTraffic

    cell = catalog.load_cell(CELL)
    seen = {}
    for seed in (4000000011, 4000000012, 7):
        traffic = CaptionTraffic(cell.traffic_params(False), seed, vocab=18992, image_size=32)
        plain = [len(traffic.request(i, prompt_len=None).prompt_ids) for i in range(8)]
        lengths_in_pairs(traffic)
        drawn = [len(traffic.request(i).prompt_ids) for i in range(32)]
        for run in range(4):
            part = drawn[8 * run : 8 * run + 8]
            assert sorted(part) == traffic.grid  # every length once a run of eight
            assert all(part[j] + part[j + 1] == 1536 + 30208 for j in range(0, 8, 2))  # in pairs of one sum
        assert drawn[:8] != drawn[8:16] or drawn[8:16] != drawn[16:24]  # in an order of the run's own
        assert drawn == [len(traffic.request(i).prompt_ids) for i in range(32)]  # a pure function of (seed, index)
        assert len(traffic.request(3, prompt_len=77).prompt_ids) == 77  # a length the caller fixes stays fixed
        seen[seed] = (drawn, plain)
    assert seen[4000000011][0] != seen[4000000012][0]  # the seed draws the order
    # over many runs every length stands at every place of a run about as often
    traffic = CaptionTraffic(cell.traffic_params(False), 5, vocab=18992, image_size=32)
    lengths_in_pairs(traffic)
    first = [len(traffic.request(8 * run).prompt_ids) for run in range(400)]
    counts = [first.count(n) for n in traffic.grid]
    assert min(counts) > 25 and max(counts) < 80  # 50 expected
