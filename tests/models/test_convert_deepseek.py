"""The plain reference (perfbench/reference/deepseek_v2.py) and our
latent-attention ``VLM`` against ``transformers``' ``DeepseekV2ForCausalLM`` at
test size, through the name map of models/convert_deepseek.py: the rope
permutation, the share held and the vocabulary slice are its arguments.

``transformers``' port leaves YaRN's ``mscale(all_dim) ** 2`` out of the softmax
scale, which the published ``modeling_deepseek.py`` (and this repo, and ISSUE
33's equations) apply; and it takes ``mscale`` for 1 where ``mscale_all_dim`` is
0. The fixture therefore runs at ``mscale`` 1 and ``mscale_all_dim`` 0, where
all three agree, with the factor on cos/sin (1.139) still in play; the scale's
``m ** 2`` is checked by hand in tests/models/test_deepseek_v2.py."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

torch = pytest.importorskip("torch")
transformers = pytest.importorskip("transformers")
deepseek = pytest.importorskip("transformers.models.deepseek_v2")

from cosmos_curate_tpu.models.convert_deepseek import (  # noqa: E402
    convert_deepseek_v2_lm,
    deepseek_v2_config,
)
from cosmos_curate_tpu.models.vlm.model import VLM, init_cache  # noqa: E402
from perfbench.reference import deepseek_v2 as ref  # noqa: E402

VOCAB = 320


@pytest.fixture(scope="module")
def hf():
    config = deepseek.DeepseekV2Config(
        vocab_size=VOCAB, hidden_size=64, intermediate_size=128, moe_intermediate_size=32,
        num_hidden_layers=3, num_attention_heads=4, num_key_value_heads=4, n_shared_experts=2,
        n_routed_experts=16, routed_scaling_factor=4.0, kv_lora_rank=32, q_lora_rank=24,
        qk_rope_head_dim=8, v_head_dim=16, qk_nope_head_dim=16, n_group=4, topk_group=2,
        num_experts_per_tok=3, first_k_dense_replace=1, norm_topk_prob=False,
        topk_method="group_limited_greedy", max_position_embeddings=128, rope_theta=10000.0,
        rms_norm_eps=1e-6, attention_bias=False, tie_word_embeddings=False,
        rope_scaling={
            "type": "yarn", "rope_type": "yarn", "factor": 4.0, "original_max_position_embeddings": 32,
            "beta_fast": 4.0, "beta_slow": 1.0, "mscale": 1.0, "mscale_all_dim": 0.0,
        },
    )
    torch.manual_seed(0)
    model = deepseek.DeepseekV2ForCausalLM(config).eval().float()
    with torch.no_grad():  # away from HF's constant init, where a swapped name would not show
        for name, p in model.named_parameters():
            if name.endswith("layernorm.weight") or name.endswith("norm.weight"):
                p.add_(0.3 * torch.randn_like(p))
    return model


def _hf_logits(hf, ids):
    with torch.no_grad():
        return hf(torch.tensor(np.asarray(ids))[None]).logits[0].numpy()


def test_config_maps_every_size(hf):
    cfg = deepseek_v2_config(hf.config, held=(4, 4))
    assert (cfg.dim, cfg.n_layers, cfg.n_heads, cfg.vocab) == (64, 3, 4, VOCAB) and not cfg.tied_embeddings
    assert (cfg.mla.q_lora_rank, cfg.mla.kv_lora_rank, cfg.mla.qk_rope_head_dim, cfg.mla.cache_width) == (24, 32, 8, 128)
    assert (cfg.mla.yarn_factor, cfg.mla.yarn_original_max) == (4.0, 32)
    m = cfg.moe
    assert (m.n_experts, m.top_k, m.hidden, m.shared_hidden, m.first_dense) == (16, 3, 32, 64, 1)
    assert (m.n_group, m.topk_group, m.norm_topk_prob, m.routed_scaling_factor) == (4, 2, False, 4.0)
    assert m.dispatch == "sorted" and m.held_experts == (4, 4)
    with pytest.raises(ValueError, match="attention_bias"):
        deepseek_v2_config(deepseek.DeepseekV2Config(**{**hf.config.to_dict(), "attention_bias": True}))


@pytest.mark.parametrize("n_tokens", [5, 40])
def test_reference_agrees_with_transformers(hf, n_tokens):
    """40 positions are past the ramp's original context of 32: YaRN's
    interpolated frequencies and the permuted rotary columns both matter."""
    cfg = deepseek_v2_config(hf.config)
    params = convert_deepseek_v2_lm(hf.state_dict(), cfg)
    ids = np.random.default_rng(n_tokens).integers(0, VOCAB, n_tokens)
    got, _ = ref.logits_at(params, jnp.asarray(ids), list(range(n_tokens)), **ref.model_kwargs(cfg))
    np.testing.assert_allclose(np.asarray(got), _hf_logits(hf, ids), atol=2e-5)


def test_our_model_agrees_with_transformers(hf):
    cfg = deepseek_v2_config(hf.config)
    params = jax.tree.map(jnp.asarray, convert_deepseek_v2_lm(hf.state_dict(), cfg))
    ids = np.random.default_rng(3).integers(0, VOCAB, 40)
    model = VLM(cfg, dtype=jnp.float32)
    embeds = model.apply(params, jnp.asarray(ids)[None], method=model.embed_tokens)
    ck, cv = init_cache(cfg, 1, dtype=jnp.float32, length=40)
    with jax.default_matmul_precision("highest"):
        logits, _, _ = model.apply(
            params, embeds, ck, cv, jnp.arange(40)[None], jnp.zeros(1, jnp.int32), jnp.full((1,), 40, jnp.int32)
        )
    np.testing.assert_allclose(np.asarray(logits[0]), _hf_logits(hf, ids), atol=3e-5)


def test_the_rope_permutation_is_an_argument_and_matters(hf):
    cfg = deepseek_v2_config(hf.config)
    sd = hf.state_dict()
    halves = convert_deepseek_v2_lm(sd, cfg)["params"]["layer_0"]
    as_stored = convert_deepseek_v2_lm(sd, cfg, interleaved_rope=False)["params"]["layer_0"]
    raw = sd["model.layers.0.self_attn.kv_a_proj_with_mqa.weight"].numpy().T  # [D, C | rope]
    np.testing.assert_array_equal(as_stored["kv_a"]["kernel"], raw)
    np.testing.assert_array_equal(halves["kv_a"]["kernel"][:, :32], raw[:, :32])
    np.testing.assert_array_equal(halves["kv_a"]["kernel"][:, 32:], raw[:, 32:][:, [0, 2, 4, 6, 1, 3, 5, 7]])
    q_raw = sd["model.layers.0.self_attn.q_b_proj.weight"].numpy().T.reshape(24, 4, 24)
    q = halves["q_b"]["kernel"].reshape(24, 4, 24)
    np.testing.assert_array_equal(q[..., :16], q_raw[..., :16])
    np.testing.assert_array_equal(q[..., 16:], q_raw[..., 16:][..., [0, 2, 4, 6, 1, 3, 5, 7]])
    # left as stored, the model rotates the wrong pairs: not HF's logits any more
    ids = np.random.default_rng(1).integers(0, VOCAB, 12)
    wrong = convert_deepseek_v2_lm(sd, cfg, interleaved_rope=False)
    got, _ = ref.logits_at(wrong, jnp.asarray(ids), [11], **ref.model_kwargs(cfg))
    assert np.abs(np.asarray(got[0]) - _hf_logits(hf, ids)[11]).max() > 1e-3


def test_the_share_held_and_the_vocabulary_slice_are_arguments(hf):
    """One chip of four: experts 4-7 and vocabulary rows 80-159. Its tables are
    the checkpoint's for those experts, its logits the slice's columns once the
    other shares' parts are added (the reference sums them)."""
    whole = deepseek_v2_config(hf.config)
    sd = hf.state_dict()
    share = dataclasses.replace(whole, vocab=80, moe=dataclasses.replace(whole.moe, held=(4, 4)))
    part = convert_deepseek_v2_lm(sd, share, vocab_first=80)["params"]
    full = convert_deepseek_v2_lm(sd, whole)["params"]
    moe = part["layer_1"]["moe"]
    assert moe["gate_up"].shape == (4, 64, 64) and moe["down"].shape == (4, 32, 64)
    assert moe["router"]["kernel"].shape == (64, 16)  # the router keeps every output
    np.testing.assert_array_equal(moe["gate_up"], full["layer_1"]["moe"]["gate_up"][4:8])
    np.testing.assert_array_equal(moe["gate_up"][1][:, :32], sd["model.layers.1.mlp.experts.5.gate_proj.weight"].numpy().T)
    np.testing.assert_array_equal(part["embed"]["embedding"], full["embed"]["embedding"][80:160])
    np.testing.assert_array_equal(part["lm_head"]["kernel"], full["lm_head"]["kernel"][:, 80:160])
    assert part["layer_0"]["up"]["kernel"].shape == (64, 128) and "moe" not in part["layer_0"]
    # an explicit held= overrides the flavor's
    other = convert_deepseek_v2_lm(sd, share, held=(8, 2))["params"]["layer_2"]["moe"]
    np.testing.assert_array_equal(other["down"], full["layer_2"]["moe"]["down"][8:10])
