"""The DeepSeek-V2 cell's own pieces of the yardstick: its operation and byte
counts against hand counts, its five readers on a hand-made record (and None
where there is nothing to read), its configuration file against the catalog row
and the flavor, the reference's shares summing to the uncut layer, what the
reference reads when computed in fewer bits, and a rehearsal of the control flow."""

import dataclasses
import json
import os
import subprocess
import sys

import pytest

from perfbench import catalog
from perfbench.roofline import expert_bytes, mla_bytes

CELL = "deepseek-v2-ep8.text-rewrite"
NEW = {
    "kernel.mla_decode_roofline_share", "kernel.expert_matmul_roofline_share",
    "kernel.expert_time_share", "engine.expert_assignments_per_step", "engine.latent_pool_gib",
}
MLA = dict(n_layers=7, n_heads=128, key_width=576, value_width=512, block_size=16, dtype_bytes=2)
EXPERTS = dict(dim=5120, width=1536, held=20, dtype_bytes=2, sparse_layers=6)


def _reader(name):
    return catalog.load_module("layer_metrics", name)


def test_mla_bytes_and_flops_against_the_issues_hand_count():
    # one position, one layer: 512 + 64 values of 2 B; 128 heads x (576 + 512) x 2
    one = dict(MLA, n_layers=1, block_size=1)
    assert mla_bytes.mla_decode_bytes([1], **one) == 1152
    assert mla_bytes.mla_decode_flops([1], **one) == 278_528
    # whole pages: 17 positions are two pages of 16; an idle row (0) reads nothing
    assert mla_bytes.mla_decode_bytes([17, 0, 16], **MLA) == (32 + 16) * 1152 * 7
    assert mla_bytes.mla_decode_flops([17, 0, 16], **MLA) == (32 + 16) * 278_528 * 7
    # 242 operations a byte against the v5e's ridge of 240.5
    peaks = catalog.peaks("TPU v5 lite")
    assert 278_528 / 1152 == pytest.approx(241.8, abs=0.1)
    assert peaks["flops_bf16"] / peaks["hbm_bytes_per_s"] == pytest.approx(240.5, abs=0.1)


def test_expert_bytes_and_flops_against_the_issues_hand_count():
    assert expert_bytes.expert_flops(1, **EXPERTS) == 6 * 5120 * 1536 == 47_185_920
    # one pass through a sparse layer reads 20 experts of 23.6 M values: 944 MB
    assert expert_bytes.expert_table_bytes(1, **EXPERTS) == 20 * 3 * 5120 * 1536 * 2 == 943_718_400
    assert expert_bytes.expert_activation_bytes(192, **EXPERTS) == 192 * (2 * 5120 + 3 * 1536) * 2


class _Trace:
    busy_s_by_chip = [4.0]
    kernel_s = {"mla_decode": 0.5, "mla_prefill": 0.3}


def _record():
    steps = [[500] * 256, [900] * 8]
    return {
        "trace": _Trace(), "device": {"kind": "TPU v5 lite", "memory_peak_bytes": 12 * 2**30},
        "expert_trace": {"kernel_s": {"expert_matmul": 1.6}, "kernel_calls": {"expert_matmul": 48}},
        "slice": {"decode_lengths": steps, "mla_shape": MLA, "expert_shape": EXPERTS},
        "stats_delta": {"paged_kernel_steps": 4},
        "latent": {"latent_pool_bytes_per_chip": 5 * 2**29, "expert_assignments_held": 4608},
    }


def test_the_five_readers_on_a_hand_made_record():
    run = _record()
    positions = 256 * 512 + 8 * 912  # whole pages of 16
    t_mem, t_ops = positions * 1152 * 7 / 819e9, positions * 278_528 * 7 / 197e12
    assert _reader("kernel.mla_decode_roofline_share").read(run) == pytest.approx(100 * max(t_mem, t_ops) / 0.5)
    # 24 passes read 24 x 944 MB; the window's 4,608 assignments, half of them the slice's (2 of 4 programs)
    moved = 24 * 943_718_400 + 2304 * (2 * 5120 + 3 * 1536) * 2
    assert _reader("kernel.expert_matmul_roofline_share").read(run) == pytest.approx(100 * moved / 819e9 / 1.6)
    assert _reader("kernel.expert_time_share").read(run) == pytest.approx(40.0)
    assert _reader("engine.expert_assignments_per_step").read(run) == 1152.0
    assert _reader("engine.latent_pool_gib").read(run) == 2.5
    # the two latent kernels are this cell's attention kernels
    assert _reader("kernel.paged_attention_time_share").read(run) == pytest.approx(20.0)


@pytest.mark.parametrize("name", sorted(NEW))
def test_a_reader_finds_nothing_in_a_program_without_the_mechanism(name):
    """What the parent commit's runs and the other cells' records look like: a
    trace of the paged kernels, a slice, no latent or expert summary."""

    class Trace:
        busy_s_by_chip = [4.0]
        kernel_s = {"paged_decode": 0.5, "paged_prefill": 0.3}

    run = {
        "trace": Trace(), "device": {"kind": "TPU v5 lite", "memory_peak_bytes": 1},
        "slice": {"decode_lengths": [[100]], "kv_shape": {}},
        "stats_delta": {"paged_kernel_steps": 10},
    }
    assert _reader(name).read(run) is None
    assert _reader(name).read(dict(run, expert_trace=None, latent={})) is None


def test_the_cell_reports_the_new_metrics_and_the_old_cells_do_not():
    cell = catalog.load_cell(CELL)
    assert NEW <= set(cell.per_layer)
    assert "kernel.paged_decode_hbm_share" not in cell.per_layer  # it counts K and V twice
    for old in ("qwen2vl-2b.text-rewrite", "granite-4.0-h-micro.text-rewrite"):
        assert not NEW & set(catalog.load_cell(old).per_layer)
        assert "kernel.paged_decode_hbm_share" in catalog.load_cell(old).per_layer
    # three architectures under one traffic file
    assert cell.traffic == catalog.load_cell("qwen2vl-2b.text-rewrite").traffic
    assert cell.chips == 1 and cell.end_to_end == ("output_tok_per_s", "setup_s")


def test_config_file_is_the_catalog_row_and_the_flavor():
    from cosmos_curate_tpu.models.vlm.model import vlm_flavor
    from perfbench.drivers.caption_engine_latent import check_config_file

    conf = catalog.load_cell(CELL).config
    flavor = vlm_flavor(conf["flavor"])
    check_config_file(conf, flavor.cfg, flavor.kv_lanes, flavor.prefill_rows)  # raises where they disagree
    with pytest.raises(ValueError, match="kv_lora_rank"):
        cut = dataclasses.replace(flavor.cfg, mla=dataclasses.replace(flavor.cfg.mla, kv_lora_rank=256))
        check_config_file(conf, cut, flavor.kv_lanes, flavor.prefill_rows)
    # the published widths, uncut
    for key, value in dict(
        hidden_size=5120, num_attention_heads=128, q_lora_rank=1536, kv_lora_rank=512, qk_nope_head_dim=128,
        qk_rope_head_dim=64, v_head_dim=128, moe_intermediate_size=1536, n_shared_experts=2,
        intermediate_size=12288, num_experts_per_tok=6, n_group=8, topk_group=3,
    ).items():
        assert conf[key] == value, key
    assert conf["published_counts"]["router_outputs"] == 160 == flavor.cfg.moe.n_experts
    assert sorted(conf["reduced"]) == sorted(
        ["num_hidden_layers", "n_routed_experts", "vocab_size", "max_position_embeddings"]
    )
    assert (conf["num_hidden_layers"], conf["n_routed_experts"], conf["vocab_size"]) == (7, 20, 12800)
    entry = next(c for c in catalog.benchmark()["configs"] if c["name"] == "deepseek-v2-ep8")
    assert entry["reduced"] == conf["reduced"] and entry["source"] == conf["source"]
    # what test_catalog.py::test_config_file asserts, with widths told from depth
    # (its pattern takes the word "hidden" in num_hidden_layers for a width)
    assert set(entry) == {"name", "source", "file", "reduced", "why"} and entry["file"].startswith("perfbench/")
    assert set(conf["reduced_why"]) == set(conf["reduced"]) and conf["name"] == entry["name"]
    widths = ("hidden_size", "intermediate", "latent", "state", "projection", "_dim", "_rank", "expansion", "experts_per")
    assert not [k for k in conf["reduced"] if any(w in k for w in widths)]
    assert conf["assumed"] and conf["deployment"] and "check" in conf
    catalog_file = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog_file):  # every number of the catalog row, or listed as reduced
        rows = [json.loads(line) for line in open(catalog_file) if line.strip()]
        row = next(r for r in rows if r["name"] == "DeepSeek-V2")
        assert conf["source"] == row["source_url"]
        assert {k for k, v in row["config"].items() if conf.get(k) != v} == set(conf["reduced"])


def _tiny_tree(seed=0):
    import flax.linen as nn

    from cosmos_curate_tpu.models.vlm.engine import _init_params
    from cosmos_curate_tpu.models.vlm.model import VLM, VLM_DEEPSEEK_V2_TINY_TEST

    cfg = VLM_DEEPSEEK_V2_TINY_TEST
    whole = dataclasses.replace(cfg, moe=dataclasses.replace(cfg.moe, held=None))
    return whole, nn.unbox(_init_params(VLM(whole), seed))


def test_the_references_shares_sum_to_the_uncut_layer():
    """Every share of the experts, given to the reference as an argument, adds
    its part of the routed sum; with the shared expert counted once the parts
    are the layer the uncut router and all sixteen experts give."""
    import jax.numpy as jnp
    import numpy as np

    from perfbench.reference import deepseek_v2 as ref

    cfg, tree = _tiny_tree()
    sizes = ref.model_kwargs(cfg)
    mp = tree["params"]["layer_1"]["moe"]
    n = jnp.asarray(np.random.default_rng(0).normal(size=(24, cfg.dim)), jnp.float32)
    uncut, _ = ref.experts(n, mp, moe=sizes["moe"])
    parts = []
    for first in range(0, 16, 4):
        share = dict(mp, gate_up=mp["gate_up"][first : first + 4], down=mp["down"][first : first + 4])
        y, _ = ref.experts(n, share, moe=dict(sizes["moe"], held=(first, 4)), with_shared=first == 0)
        parts.append(y)
    np.testing.assert_allclose(np.asarray(sum(parts)), np.asarray(uncut), atol=1e-5)
    assert float(jnp.abs(parts[1]).max()) > 1e-3


def test_computing_in_fewer_bits_or_dropping_assignments_moves_the_reference():
    """The second readings of the config file's limits, at test size: an 8-bit
    float's activations move the logits by more than bfloat16's do; every other
    assignment dropped is next to nothing in the last position's logits
    and plain in the last layer's latent rows, which carry every token's
    experts (``late_rows_tol``)."""
    import jax.numpy as jnp
    import numpy as np

    from cosmos_curate_tpu.models.vlm.model import VLM_DEEPSEEK_V2_TINY_TEST as cfg
    from perfbench.drivers.caption_engine_latent import late_row_errors
    from perfbench.reference import deepseek_v2 as ref

    _, tree = _tiny_tree()
    held = cfg.moe.held_experts
    mp = {k: dict(v) for k, v in tree["params"].items()}
    for i in range(1, cfg.n_layers):
        moe = dict(mp[f"layer_{i}"]["moe"])
        # (tables drawn at 0.02 make a routed part of nothing at a width of 64: times 8)
        moe["gate_up"], moe["down"] = 8 * moe["gate_up"][held[0] : sum(held)], 8 * moe["down"][held[0] : sum(held)]
        mp[f"layer_{i}"] = dict(mp[f"layer_{i}"], moe=moe)
    tree, sizes = {"params": mp}, ref.model_kwargs(cfg)
    ids = jnp.asarray(np.random.default_rng(5).integers(256, 512, 48), jnp.int32)
    positions = list(range(48))
    want, margin = ref.logits_at(tree, ids, positions, **sizes)
    wide = np.asarray(margin) > 0.1
    assert 5 < wide.sum() < 48

    def logits_err(**low):
        got, _ = ref.logits_at(tree, ids, positions, **sizes, **low)
        e = np.abs(np.asarray(got) - np.asarray(want)).max(axis=-1) / np.abs(np.asarray(want)).max()
        return float(np.median(e[wide]))

    assert 0 < logits_err(activation_mantissa_bits=7) < 0.03 < logits_err(activation_mantissa_bits=3)
    last = cfg.n_layers - 1
    rows, row_margin = ref.cache_rows(tree, ids, last, **sizes)

    def rows_err(**low):
        got, _ = ref.cache_rows(tree, ids, last, **sizes, **low)
        return float(late_row_errors(got, rows, row_margin, 0.1).max())

    assert rows_err(drop_every=2) > 3 * rows_err(activation_mantissa_bits=7) > 0


def test_cell_rehearses_on_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    out = subprocess.run(
        [sys.executable, "-m", "perfbench.run", "--workload", CELL, "--seed", "3000000001",
         "--seconds", "4", "--trace", "1", "--rehearse"],
        capture_output=True, text=True, env=env, cwd=str(catalog.CHECKOUT), timeout=600,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    # on the CPU only the program's counters are written under a metric's name
    assert set(line["metrics"]) == {
        "device.compiles_in_window", "engine.expert_assignments_per_step", "engine.latent_pool_gib",
    }
    assert line["metrics"]["engine.expert_assignments_per_step"]["value"] > 0
