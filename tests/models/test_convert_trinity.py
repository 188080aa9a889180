"""models/convert_trinity.py on a SYNTHETIC state dict at test size: built from a
seeded tree of ours under the converter's own (unverified) HF names, with all
eight experts, a vocabulary of 512 and rope as interleaved pairs, and converted
back: the share held, the vocabulary slice, the fused ``gate_up`` and the rope
permutation are its arguments. No ``transformers`` module for afmoe exists here,
so this proves the map's shapes, transposes, slices and permutation, not the names."""

import dataclasses

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cosmos_curate_tpu.models.convert_trinity import NAMES, convert_trinity_lm, trinity_config
from cosmos_curate_tpu.models.vlm.engine import _init_params
from cosmos_curate_tpu.models.vlm.model import VLM, VLM_TRINITY_TINY_TEST
from perfbench.reference import trinity_afmoe as ref

WHOLE = dataclasses.replace(VLM_TRINITY_TINY_TEST, moe=dataclasses.replace(VLM_TRINITY_TINY_TEST.moe, held=None))


def _pairs(d):  # halves -> interleaved pairs: the inverse of the converter's permutation
    return np.argsort(np.concatenate([np.arange(0, d, 2), np.arange(1, d, 2)]))


@pytest.fixture(scope="module")
def tree():
    tree = nn.unbox(_init_params(VLM(WHOLE), 0))
    rng = np.random.default_rng(3)
    return jax.tree_util.tree_map_with_path(
        lambda path, x: jnp.asarray(1 + 0.3 * rng.standard_normal(x.shape), x.dtype)
        if jax.tree_util.keystr(path).endswith("['scale']") else x, tree,
    )


def _state_dict(tree, interleaved):
    """Our tree under HF's names, ``[out, in]``, every expert apart."""
    p, cfg, d = tree["params"], WHOLE, WHOLE.head_dim
    perm = _pairs(d) if interleaved else np.arange(d)
    sd = {
        "model.embed_tokens.weight": np.asarray(p["embed"]["embedding"]),
        "model.norm.weight": np.asarray(p["ln_f"]["scale"]),
        "lm_head.weight": np.asarray(p["lm_head"]["kernel"]).T,
    }
    for i in range(cfg.n_layers):
        lp, pre = p[f"layer_{i}"], f"model.layers.{i}."
        for ours in ("ln1", "post_attn_norm", "ln2", "post_mlp_norm"):
            sd[pre + NAMES[ours] + ".weight"] = np.asarray(lp[ours]["scale"])
        for ours, heads in (("q", cfg.n_heads), ("k", cfg.n_kv_heads)):
            w = np.asarray(lp[ours]["kernel"]).reshape(-1, heads, d)[..., perm]
            sd[pre + NAMES[ours] + ".weight"] = w.reshape(w.shape[0], -1).T
            sd[pre + NAMES[ours + "_norm"] + ".weight"] = np.asarray(lp[ours + "_norm"]["scale"])[perm]
        for ours in ("v", "o", "g"):
            sd[pre + NAMES[ours] + ".weight"] = np.asarray(lp[ours]["kernel"]).T
        if i < cfg.moe.first_dense:
            for a, b in (("gate", "gate_proj"), ("up", "up_proj"), ("down", "down_proj")):
                sd[f"{pre}mlp.{b}.weight"] = np.asarray(lp[a]["kernel"]).T
            continue
        moe, width = lp["moe"], cfg.moe.hidden
        sd[pre + NAMES["router"] + ".weight"] = np.asarray(moe["router"]["kernel"]).T
        sd[pre + NAMES["router_bias"]] = 0.01 * np.arange(cfg.moe.n_experts, dtype=np.float32)
        for e in range(cfg.moe.n_experts):
            sd[f"{pre}mlp.experts.{e}.gate_proj.weight"] = np.asarray(moe["gate_up"][e, :, :width]).T
            sd[f"{pre}mlp.experts.{e}.up_proj.weight"] = np.asarray(moe["gate_up"][e, :, width:]).T
            sd[f"{pre}mlp.experts.{e}.down_proj.weight"] = np.asarray(moe["down"][e]).T
        for a, b in (("shared_gate", "gate_proj"), ("shared_up", "up_proj"), ("shared_down", "down_proj")):
            sd[f"{pre}mlp.shared_experts.{b}.weight"] = np.asarray(moe[a]["kernel"]).T
    return sd


@pytest.mark.parametrize("interleaved", [False, True], ids=["halves", "interleaved-pairs"])
def test_round_trip_gives_back_the_tree(tree, interleaved):
    got = convert_trinity_lm(_state_dict(tree, interleaved), WHOLE, interleaved_rope=interleaved)["params"]
    want = {k: v for k, v in tree["params"].items() if k in got}
    for i in range(WHOLE.moe.first_dense, WHOLE.n_layers):
        want[f"layer_{i}"] = dict(want[f"layer_{i}"], moe=dict(
            want[f"layer_{i}"]["moe"], router_bias=0.01 * np.arange(8, dtype=np.float32)
        ))
    flat_got, flat_want = jax.tree_util.tree_leaves_with_path(got), dict(jax.tree_util.tree_leaves_with_path(want))
    assert {jax.tree_util.keystr(k) for k, _ in flat_got} == {jax.tree_util.keystr(k) for k in flat_want}
    for path, leaf in flat_got:
        np.testing.assert_array_equal(np.asarray(leaf), np.asarray(flat_want[path]), err_msg=jax.tree_util.keystr(path))


def test_held_experts_and_vocabulary_slice(tree):
    cfg = dataclasses.replace(VLM_TRINITY_TINY_TEST, vocab=128)  # held (2, 4), rows 256..383
    got = convert_trinity_lm(_state_dict(tree, True), cfg, vocab_first=256, interleaved_rope=True)["params"]
    p = tree["params"]
    np.testing.assert_array_equal(got["embed"]["embedding"], np.asarray(p["embed"]["embedding"])[256:384])
    np.testing.assert_array_equal(got["lm_head"]["kernel"], np.asarray(p["lm_head"]["kernel"])[:, 256:384])
    moe = got["layer_1"]["moe"]
    assert moe["gate_up"].shape == (4, 64, 64) and moe["down"].shape == (4, 32, 64)
    np.testing.assert_array_equal(moe["gate_up"], np.asarray(p["layer_1"]["moe"]["gate_up"])[2:6])
    assert moe["router"]["kernel"].shape == (64, 8) and moe["router_bias"].shape == (8,)  # the router whole
    # the converted share serves: the reference on it is the reference's own share of the whole tree
    ids = jnp.asarray(np.random.default_rng(1).integers(0, 128, 20), jnp.int32)
    sizes = ref.model_kwargs(cfg)
    logits, _ = ref.logits_at(jax.tree.map(jnp.asarray, {"params": got}), ids, [19], **sizes)
    assert np.isfinite(np.asarray(logits)).all()


def test_a_missing_name_says_which(tree):
    sd = _state_dict(tree, False)
    del sd["model.layers.1.mlp.expert_bias"]
    with pytest.raises(KeyError, match="expert_bias.*unverified"):
        convert_trinity_lm(sd, WHOLE)


def test_config_from_the_published_keys():
    import json
    import os

    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog here")
    row = next(json.loads(l) for l in open(path) if '"Trinity-Large-Preview"' in l)
    cfg = trinity_config(row["config"], held=(0, 32))
    assert (cfg.dim, cfg.n_layers, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim) == (3072, 60, 48, 8, 128)
    assert len(cfg.window_layers) == 45 and len(cfg.full_layers) == 15 and cfg.sliding_window == 4096
    assert cfg.moe.n_experts == 256 and cfg.moe.top_k == 4 and cfg.moe.first_dense == 6
    assert cfg.moe.score_func == "sigmoid" and cfg.moe.routed_scaling_factor == 2.448
    assert cfg.embedding_multiplier == 3072**0.5 and not cfg.full_attention_rope
    with pytest.raises(ValueError, match="rope_scaling"):
        trinity_config(dict(row["config"], rope_scaling={"type": "yarn"}))
