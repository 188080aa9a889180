"""The Trinity cell's own pieces of the yardstick: its operation and byte counts
against hand counts, its seven readers on a hand-made record (and None where
there is nothing to read), its configuration file against the catalog row and
the flavor, the reference's eight shares summing to the uncut layer, what the
reference reads when computed in fewer bits, and a rehearsal of the control flow."""

import dataclasses
import json
import os
import subprocess
import sys

import pytest

from perfbench import catalog
from perfbench.roofline import window_bytes

CELL = "trinity-large-ep8.digest-1k-12k"
NEW = {
    "kernel.window_decode_hbm_share", "kernel.window_prefill_roofline_share",
    "kernel.window_pages_skipped_share", "engine.window_pool_gib",
    "kernel.held_expert_time_share", "engine.held_assignments_per_program", "engine.prefill_device_share",
}
SHAPE = dict(n_full=1, n_window=4, window=4096, n_heads=48, n_kv_heads=8, head_dim=128, dtype_bytes=2)
KV = {k: SHAPE[k] for k in ("n_full", "n_window", "window", "n_kv_heads", "head_dim", "dtype_bytes")}
OPS = {k: SHAPE[k] for k in ("n_full", "n_window", "window", "n_heads", "head_dim")}
POSITION = 8 * 128 * 2 * 2  # K and V of one position in one layer: 4 KiB, the issue's number


def _reader(name):
    return catalog.load_module("layer_metrics", name)


def test_decode_bytes_and_flops_against_a_hand_count():
    assert POSITION == 4096
    # under the window every layer reads the row's positions: 100 x 5 layers
    assert window_bytes.window_decode_kv_bytes([100], **KV) == 100 * 5 * POSITION
    # past it: the full layer 9,000 positions, a window layer the last 4,096; an idle row (0) reads nothing
    assert window_bytes.window_decode_kv_bytes([9000, 0], **KV) == (9000 + 4 * 4096) * POSITION
    # the window's bytes a row a layer do not grow with the context: 16 MiB
    for n in (5000, 9000, 12000):
        assert window_bytes.window_decode_kv_bytes([n], **dict(KV, n_full=0, n_window=1)) == 2**24
    # counted by position: the count has no block size to move with
    assert "block_size" not in window_bytes.window_decode_kv_bytes.__kwdefaults__
    assert window_bytes.window_decode_flops([100, 9000], **OPS) == (5 * 100 + 9000 + 4 * 4096) * 48 * 128 * 4


def test_prefill_pairs_bytes_and_flops_against_a_hand_count():
    pairs = window_bytes.window_prefill_pairs
    assert pairs(0, 4, None) == 1 + 2 + 3 + 4 and pairs(0, 4, 3) == 1 + 2 + 3 + 3
    assert pairs(10, 4, 3) == 12 and pairs(1, 3, 3) == 2 + 3 + 3
    for write, valid, window in ((0, 256, 4096), (4000, 256, 4096), (8000, 256, 4096), (3, 7, 5)):
        brute = sum(min(write + t + 1, window) for t in range(valid))
        assert pairs(write, valid, window) == brute
    # a 256-token chunk at 8,000: the full layer sees 8,000 + (1..256) keys a query, a window layer 4,096
    full, win = 256 * 8000 + 256 * 257 // 2, 256 * 4096
    assert window_bytes.window_prefill_flops([(8000, 256)], **OPS) == (full + 4 * win) * 48 * 128 * 4
    # its bytes: positions 0..8,255 in the full layer; 3,905..8,255 in a window layer (4,351);
    # queries in and outputs out, 256 x 48 x 128 x 2 B each, in every layer
    moved = (8256 + 4 * 4351) * POSITION + 5 * 256 * 48 * 128 * 2 * 2
    assert window_bytes.window_prefill_bytes([(8000, 256)], **SHAPE) == moved


class _Trace:
    busy_s_by_chip = [4.0]
    kernel_s = {"paged_decode": 0.5, "paged_prefill": 1.5}


def _record():
    return {
        "trace": _Trace(), "device": {"kind": "TPU v5 lite", "memory_peak_bytes": 13 * 2**30},
        "slice": {
            "decode_lengths": [[9000] * 24, [100] * 16], "prefill_rows": [[(8000, 256)], [(0, 256), (4000, 256)]],
            "window_shape": SHAPE,
        },
        "stats_delta": {"paged_kernel_steps": 2},
        "windowed": {
            "window_pool_bytes_per_chip": 21 * 2**27, "full_pool_bytes_per_chip": 11 * 2**27,
            "paged_decode_pages_walked": 250, "paged_decode_pages_spanned": 1000, "expert_assignments_held": 150,
        },
        "expert_trace": {"kernel_s": {"expert_matmul": 0.8}, "kernel_calls": {"expert_matmul": 32}},
        "program_s": {"prefill": [2.4, 3], "decode": [1.2, 2], "other": [0.4, 5]},
    }


def test_the_seven_readers_on_a_hand_made_record():
    run = _record()
    moved = 24 * (9000 + 4 * 4096) * POSITION + 16 * 100 * 5 * POSITION
    assert _reader("kernel.window_decode_hbm_share").read(run) == pytest.approx(100 * moved / 819e9 / 0.5)
    least = 0.0
    for rows in run["slice"]["prefill_rows"]:
        least += max(
            window_bytes.window_prefill_flops(rows, **OPS) / 197e12,
            window_bytes.window_prefill_bytes(rows, **SHAPE) / 819e9,
        )
    assert _reader("kernel.window_prefill_roofline_share").read(run) == pytest.approx(100 * least / 1.5)
    assert 0 < _reader("kernel.window_prefill_roofline_share").read(run) < 100
    assert _reader("kernel.window_pages_skipped_share").read(run) == pytest.approx(75.0)
    assert _reader("engine.window_pool_gib").read(run) == 2.625
    assert _reader("kernel.held_expert_time_share").read(run) == pytest.approx(20.0)
    assert _reader("engine.held_assignments_per_program").read(run) == 75.0
    assert _reader("engine.prefill_device_share").read(run) == pytest.approx(60.0)
    # the same two kernels are this cell's attention kernels
    assert _reader("kernel.paged_attention_time_share").read(run) == pytest.approx(50.0)


@pytest.mark.parametrize("name", sorted(NEW))
def test_a_reader_finds_nothing_in_a_program_without_the_mechanism(name):
    """What the parent commit's runs and the other cells' records look like: a
    trace of the paged kernels, a slice without window layers, one pool."""
    run = {
        "trace": _Trace(), "device": {"kind": "TPU v5 lite", "memory_peak_bytes": 1},
        "slice": {"decode_lengths": [[100]], "kv_shape": {}},
        "stats_delta": {"paged_kernel_steps": 10},
    }
    assert _reader(name).read(run) is None
    assert _reader(name).read(dict(run, windowed={}, trace=None)) is None
    assert _reader(name).read(dict(run, windowed={"window_pool_bytes_per_chip": 0, "paged_decode_pages_spanned": 0})) is None


def test_the_cell_reports_the_new_metrics_and_the_old_cells_do_not():
    cell = catalog.load_cell(CELL)
    assert NEW <= set(cell.per_layer)
    # it counts every layer's whole context: over 100% here; and the DeepSeek cell's expert
    # metrics assume every held expert touched a pass
    assert not {"kernel.paged_decode_hbm_share", "kernel.expert_matmul_roofline_share"} & set(cell.per_layer)
    for old in ("qwen2vl-2b.text-rewrite", "deepseek-v2-ep8.text-rewrite"):
        assert not NEW & set(catalog.load_cell(old).per_layer)
    assert cell.chips == 1 and cell.end_to_end == ("output_tok_per_s", "setup_s")
    grid = list(range(448, 11201, 1536))
    assert len(grid) == 8 and grid[-1] == 11200
    p = cell.traffic["params"]
    assert (p["frames"], p["prefix_tokens"], p["output_tokens"], p["backlog"]) == (0, 64, 192, 4)
    assert p["prompt_tokens"] == {"min": 448, "max": 11200, "step": 1536}
    contexts = [64 + n + 192 + 1 for n in grid]
    assert (contexts[0], contexts[-1]) == (705, 11457) and sum(c <= 4096 for c in contexts) == 3


def test_config_file_is_the_catalog_row_and_the_flavor():
    from cosmos_curate_tpu.models.vlm.model import vlm_flavor
    from perfbench.drivers.caption_engine_windowed import check_config_file

    conf = catalog.load_cell(CELL).config
    flavor = vlm_flavor(conf["flavor"])
    serving = (flavor.kv_lanes, flavor.prefill_rows)
    check_config_file(conf, flavor.cfg, *serving)  # raises where they disagree
    with pytest.raises(ValueError, match="sliding_window"):
        check_config_file(conf, dataclasses.replace(flavor.cfg, sliding_window=2048), *serving)
    with pytest.raises(ValueError, match="assumed"):
        check_config_file(conf, dataclasses.replace(flavor.cfg, attention_gate=False), *serving)
    with pytest.raises(ValueError, match="prefill_rows"):
        check_config_file(conf, flavor.cfg, flavor.kv_lanes, 8)
    # the engine's own block size for these lanes, as SharedCaptionEngine.get builds it: nobody names one
    from cosmos_curate_tpu.models.vlm.engine import default_block_size

    assert conf["serving"]["block_size"] == 128 == default_block_size(flavor.kv_lanes) and not hasattr(flavor, "kv_block")
    with pytest.raises(ValueError, match="block_size"):
        check_config_file(dict(conf, serving=dict(conf["serving"], block_size=16)), flavor.cfg, *serving)
    # the check's own instruction fills whole blocks of both pools; the mix's does not
    assert conf["check"]["prefix_tokens"] // 128 == 2 > catalog.load_cell(CELL).traffic["params"]["prefix_tokens"] // 128
    # the published widths, uncut
    for key, value in dict(
        hidden_size=3072, num_attention_heads=48, num_key_value_heads=8, head_dim=128, moe_intermediate_size=3072,
        num_shared_experts=1, intermediate_size=12288, num_experts_per_tok=4, sliding_window=4096, route_scale=2.448,
        score_func="sigmoid", rope_theta=10000, rms_norm_eps=1e-5,
    ).items():
        assert conf[key] == value, key
    assert conf["published_counts"]["router_outputs"] == 256 == flavor.cfg.moe.n_experts
    reduced = ["num_hidden_layers", "num_dense_layers", "layer_types", "num_experts", "vocab_size", "max_position_embeddings"]
    assert sorted(conf["reduced"]) == sorted(reduced)
    assert (conf["num_hidden_layers"], conf["num_dense_layers"], conf["num_experts"], conf["vocab_size"]) == (5, 1, 32, 25024)
    assert conf["layer_types"] == ["sliding_attention"] * 3 + ["full_attention", "sliding_attention"]
    assert conf["max_position_embeddings"] == 12288 == flavor.cfg.max_seq
    for point in ("OUTPUT GATE", "BEFORE rope", "sliding_attention layers ONLY", "BRANCH"):
        assert point in conf["assumed"]["not_keys_of_the_config"]
    assert "8" in conf["deployment"] and "EIGHT" in conf["deployment"]
    entry = next(c for c in catalog.benchmark()["configs"] if c["name"] == "trinity-large-ep8")
    assert entry["reduced"] == conf["reduced"] and entry["source"] == conf["source"]
    # what test_catalog.py::test_config_file asserts, with widths told from depth
    # (its pattern takes the word "hidden" in num_hidden_layers for a width)
    assert set(entry) == {"name", "source", "file", "reduced", "why"} and entry["file"].startswith("perfbench/")
    assert set(conf["reduced_why"]) == set(conf["reduced"]) and conf["name"] == entry["name"]
    assert len(entry["reduced"]) <= 16 and any(w["config"] == entry["name"] for w in catalog.benchmark()["workloads"])
    widths = ("hidden_size", "intermediate", "latent", "state", "projection", "_dim", "_rank", "expansion", "experts_per")
    assert not [k for k in conf["reduced"] if any(w in k for w in widths)]
    assert conf["assumed"] and conf["deployment"] and "check" in conf
    catalog_file = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog_file):  # every number of the catalog row, or listed as reduced
        rows = [json.loads(line) for line in open(catalog_file) if line.strip()]
        row = next(r for r in rows if r["name"] == "Trinity-Large-Preview")
        assert conf["source"] == row["source_url"]
        assert {k for k, v in row["config"].items() if conf.get(k) != v} == set(conf["reduced"])
        assert conf["layer_types"] == row["config"]["layer_types"][:5]


def test_benchmark_gained_entries_and_lost_none():
    bench = catalog.benchmark()
    assert [c["name"] for c in bench["configs"]][-1] == "trinity-large-ep8"
    assert [w["name"] for w in bench["workloads"]][-1] == CELL and len(bench["workloads"]) == 6
    assert [m["name"] for m in bench["per_layer"]][-7:] == [
        "kernel.window_decode_hbm_share", "kernel.window_prefill_roofline_share",
        "kernel.window_pages_skipped_share", "engine.window_pool_gib",
        "kernel.held_expert_time_share", "engine.held_assignments_per_program", "engine.prefill_device_share",
    ]
    for m in bench["per_layer"][-7:]:
        assert m["workloads"] == [CELL] and m["moves"] == "output_tok_per_s"
        reader = _reader(m["name"])
        assert (reader.UNIT, reader.LAYER, reader.MOVES, reader.SOURCE) == (m["unit"], m["layer"], m["moves"], m["source"])
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1


def _tiny_tree(seed=0):
    import flax.linen as nn

    from cosmos_curate_tpu.models.vlm.engine import _init_params
    from cosmos_curate_tpu.models.vlm.model import VLM, VLM_TRINITY_TINY_TEST

    whole = dataclasses.replace(
        VLM_TRINITY_TINY_TEST, moe=dataclasses.replace(VLM_TRINITY_TINY_TEST.moe, held=None)
    )
    return whole, nn.unbox(_init_params(VLM(whole), seed))


def test_the_references_eight_shares_sum_to_the_uncut_layer():
    """Every share of the experts, given to the reference as an argument, adds
    its part of the routed sum; with the shared expert counted once the parts
    are the layer the uncut router and all eight experts give."""
    import jax.numpy as jnp
    import numpy as np

    from perfbench.reference import trinity_afmoe as ref

    cfg, tree = _tiny_tree()
    sizes = ref.model_kwargs(cfg)
    mp = dict(tree["params"]["layer_1"]["moe"])
    rng = np.random.default_rng(0)
    mp["router"] = {"kernel": mp["router"]["kernel"] * 20}  # scores spread over (0, 1)
    mp["router_bias"] = jnp.asarray(0.05 * rng.standard_normal(8), jnp.float32)
    n = jnp.asarray(rng.normal(size=(24, cfg.dim)), jnp.float32)
    uncut, _ = ref.experts(n, mp, moe=sizes["moe"])
    parts = []
    for first in range(8):  # eight chips, an expert each
        share = dict(mp, gate_up=mp["gate_up"][first : first + 1], down=mp["down"][first : first + 1])
        y, _ = ref.experts(n, share, moe=dict(sizes["moe"], held=(first, 1)), with_shared=first == 0)
        parts.append(y)
    np.testing.assert_allclose(np.asarray(sum(parts)), np.asarray(uncut), atol=1e-5)
    assert min(float(jnp.abs(p).max()) for p in parts[1:]) > 1e-4  # every chip's experts were chosen by some token
    # and the program's own layer, told the same share, gives the same part
    from cosmos_curate_tpu.models.vlm.model import MoEFFN

    held = dataclasses.replace(cfg, moe=dataclasses.replace(cfg.moe, held=(2, 4)))
    share = dict(mp, gate_up=mp["gate_up"][2:6], down=mp["down"][2:6])
    got = MoEFFN(held, dtype=jnp.float32).apply({"params": share}, n[None])[0]
    want, _ = ref.experts(n, share, moe=dict(sizes["moe"], held=(2, 4)))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


def test_computing_in_fewer_bits_moves_the_reference():
    """The second readings of the config file's limits, at test size: an 8-bit
    float's activations move the logits by more than bfloat16's do, and what
    the configuration keeps in float32 (router, norms, head) in bfloat16 moves
    them too, less."""
    import jax.numpy as jnp
    import numpy as np

    from cosmos_curate_tpu.models.vlm.model import VLM_TRINITY_TINY_TEST as cfg
    from perfbench.reference import trinity_afmoe as ref

    _, tree = _tiny_tree()
    held = cfg.moe.held_experts
    mp = {k: dict(v) for k, v in tree["params"].items()}
    for i in range(1, cfg.n_layers):
        moe = dict(mp[f"layer_{i}"]["moe"])
        moe["gate_up"], moe["down"] = moe["gate_up"][held[0] : sum(held)], moe["down"][held[0] : sum(held)]
        mp[f"layer_{i}"] = dict(mp[f"layer_{i}"], moe=moe)
    tree, sizes = {"params": mp}, ref.model_kwargs(cfg)
    ids = jnp.asarray(np.random.default_rng(5).integers(256, 512, 48), jnp.int32)
    positions = list(range(48))
    want, _ = ref.logits_at(tree, ids, positions, **sizes)

    def logits_err(**low):
        got, _ = ref.logits_at(tree, ids, positions, **sizes, **low)
        e = np.abs(np.asarray(got) - np.asarray(want)).max(axis=-1) / np.abs(np.asarray(want)).max()
        return float(np.median(e))

    assert 0 < logits_err(activation_mantissa_bits=7) < 0.03 < logits_err(activation_mantissa_bits=3)
    assert 0 < logits_err(router_mantissa_bits=7, norm_mantissa_bits=7, head_mantissa_bits=7) < 0.03


@pytest.mark.parametrize(
    "seed,met_eos",
    [pytest.param("3800000002", False, id="every-decode-step"), pytest.param("3800000301", True, id="eos-after-five-steps")],
)
def test_cell_rehearses_on_the_cpu(seed, met_eos):
    """The second seed's ``check-decode`` request meets the end-of-sequence id
    after five of its six greedy steps: the steps made are compared, and
    ``correct`` is not failed for the draw."""
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
    assert ("check-decode ended on EOS" in out.stderr + out.stdout) is met_eos
    # on the CPU only the program's counters are written under a metric's name
    assert set(line["metrics"]) == {
        "device.compiles_in_window", "kernel.window_pages_skipped_share", "engine.window_pool_gib",
        "engine.held_assignments_per_program",
    }
    assert 0 < line["metrics"]["kernel.window_pages_skipped_share"]["value"] < 100


def test_prompt_lengths_are_drawn_without_replacement_by_the_seed():
    """The issue's traffic: the existing generator's grid, uniform. The cell's
    loop is the hybrid cell's ``SpreadLoop`` as imported; request ``i``'s length
    is a pure function of (seed, ``i``), every run of eight requests holds each
    length once, and another seed gives another order: the driver's seeds
    sample the traffic, and no order is written down anywhere."""
    from perfbench.drivers import caption_engine_windowed as driver
    from perfbench.drivers.caption_engine_hybrid import SpreadLoop
    from perfbench.traffic.caption_requests import CaptionTraffic

    cell = catalog.load_cell(CELL)
    assert "length_order" not in cell.traffic["params"] and cell.traffic["generator"] == "caption_requests"
    assert driver.SpreadLoop is SpreadLoop and not hasattr(driver, "PermutedBlocksLoop")
    assert "ramp" not in vars(driver) and not [n for n in vars(driver) if n.endswith("Loop") and n != "SpreadLoop"]
    grid = list(range(448, 11201, 1536))

    def traffic_of(seed):
        traffic = CaptionTraffic(cell.traffic_params(False), seed, vocab=25024, image_size=28)
        driver.lengths_in_blocks(traffic)
        assert traffic.grid == grid
        return traffic

    def lengths(seed, n=64):
        traffic = traffic_of(seed)
        return [len(traffic.request(i).prompt_ids) for i in range(n)]

    a, b = lengths(3800000071), lengths(3800000072)
    assert a == lengths(3800000071) and a != b
    for drawn in (a, b):
        runs = [drawn[i : i + 8] for i in range(0, 64, 8)]
        assert all(sorted(run) == grid for run in runs) and len({tuple(run) for run in runs}) > 4
    # over seeds a place in a run sees every length: nothing is pinned to a place
    assert {lengths(seed, 1)[0] for seed in range(3800000100, 3800000140)} == set(grid)
    # a length the caller fixes stays fixed, and the ids are the generator's own for (seed, index)
    traffic = traffic_of(3800000071)
    assert len(traffic.request(5, prompt_len=448).prompt_ids) == 448
    plain = CaptionTraffic(cell.traffic_params(False), 3800000071, vocab=25024, image_size=28)
    assert traffic.request(5).prompt_ids == plain.request(5, prompt_len=a[5]).prompt_ids
    assert traffic.request(5).prefix_ids == plain.prefix_ids and len(plain.prefix_ids) == 64


@pytest.mark.parametrize(
    "lanes,block",
    [
        pytest.param(((4096, 4),), 16, id="the-2b-lane"),
        pytest.param(((1024, 256), (4096, 8)), 16, id="the-latent-flavors-lanes"),
        pytest.param(((4096, 16), (12288, 24)), 128, id="a-lane-past-4096"),
    ],
)
def test_the_engine_derives_its_block_size_from_its_lanes(lanes, block):
    """Only a lane past 4,096 positions moves the engine off blocks of 16: every
    older flavor's programs stay as they are, and a caller's own size still holds."""
    from cosmos_curate_tpu.models.vlm import CaptionEngine
    from cosmos_curate_tpu.models.vlm.engine import default_block_size
    from cosmos_curate_tpu.models.vlm.model import VLM_TINY_TEST

    assert default_block_size(lanes) == block
    cfg = dataclasses.replace(VLM_TINY_TEST, max_seq=max(length for length, _ in lanes))
    assert CaptionEngine(cfg, kv_lanes=lanes).block_size == block
    assert CaptionEngine(cfg, kv_lanes=lanes, block_size=32).block_size == 32


def _planes(events):
    from perfbench import trace_reduce as tr

    host = tr.Plane("/host:CPU", [tr.Line("main", [(tr.SLICE_SPAN, 1_000, 9_000)])])
    chip = tr.Plane("/device:TPU:0", [tr.Line("XLA Ops", []), tr.Line("XLA Modules", events)])
    return [host, chip]


def test_program_seconds_by_kind_inside_the_slice():
    from perfbench.drivers.caption_engine_windowed import program_seconds

    events = [
        ("jit_prefill_batch_paged(123)", 0, 2_000),  # half of it before the slice opens at 1,000
        ("jit_decode_step_counted(456)", 2_000, 500), ("jit_prefill_batch_paged(123)", 3_000, 2_000),
        ("jit__copy_blocks(7)", 6_000, 100), ("jit_decode_step_counted(456)", 9_500, 1_000),  # cut at 10,000
        ("jit_decode_step_counted(456)", 20_000, 1_000),  # after the slice
    ]
    got = program_seconds(_planes(events))
    assert got == {"prefill": [pytest.approx(3e-6), 2], "decode": [pytest.approx(1e-6), 2], "other": [pytest.approx(1e-7), 1]}
    assert _reader("engine.prefill_device_share").read({"program_s": got}) == pytest.approx(100 * 3.0 / 4.1)
    # a trace without the line of programs, or without a device: nothing to read, nothing raised
    planes = _planes(events)
    planes[1].lines.pop()
    assert program_seconds(planes) is None and program_seconds(planes[:1]) is None
    assert _reader("engine.prefill_device_share").read({"program_s": None}) is None


def test_the_prefix_blocks_must_be_referenced_by_the_short_rows_and_copied_by_the_long():
    import types

    from perfbench.drivers.caption_engine_windowed import _prefix_blocks_shared

    def found(*names):
        return [(types.SimpleNamespace(request_id=n), None, 1.0) for n in names]

    groups = {"prefix-short": found("s0", "s1"), "prefix-long": found("l0", "l1")}
    heads = {"s0": (5, 6, 7, 8), "s1": (5, 6, 7, 8), "l0": (40, 41, 42, 43), "l1": (40, 41, 42, 43)}
    assert _prefix_blocks_shared(types.SimpleNamespace(window_heads=heads), groups, 4)
    # a short row with blocks of its own, a long row that kept a shared block, a request never seen
    assert not _prefix_blocks_shared(types.SimpleNamespace(window_heads=dict(heads, s1=(9, 10, 11, 12))), groups, 4)
    assert not _prefix_blocks_shared(types.SimpleNamespace(window_heads=dict(heads, l1=(40, 6, 42, 43))), groups, 4)
    assert not _prefix_blocks_shared(types.SimpleNamespace(window_heads={k: v for k, v in heads.items() if k != "l0"}), groups, 4)
    # a prefix shorter than a block: nothing is referenced, and the check says so instead of passing unseen
    empty = {k: () for k in heads}
    assert not _prefix_blocks_shared(types.SimpleNamespace(window_heads=empty), groups, 0)
