"""The plain reference (perfbench/reference/granite_hybrid.py) and our hybrid
``VLM`` against ``transformers``' ``GraniteMoeHybridForCausalLM`` at test size,
through the name map of models/convert_granite.py."""

import jax.numpy as jnp
import numpy as np
import pytest

torch = pytest.importorskip("torch")
transformers = pytest.importorskip("transformers")
hybrid = pytest.importorskip("transformers.models.granitemoehybrid")

from cosmos_curate_tpu.models.convert_granite import (  # noqa: E402
    convert_granite_hybrid_lm,
    granite_hybrid_config,
)
from perfbench.reference import granite_hybrid as ref  # noqa: E402

PERIOD = ["mamba"] * 5 + ["attention"] + ["mamba"] * 4


@pytest.fixture(scope="module")
def hf():
    config = hybrid.GraniteMoeHybridConfig(
        vocab_size=320, hidden_size=64, intermediate_size=128, shared_intermediate_size=128,
        num_hidden_layers=10, num_attention_heads=4, num_key_value_heads=2,
        layer_types=PERIOD, num_local_experts=0, num_experts_per_tok=0,
        mamba_n_heads=8, mamba_d_head=16, mamba_d_state=16, mamba_d_conv=4, mamba_expand=2,
        mamba_n_groups=1, mamba_chunk_size=8, mamba_conv_bias=True, mamba_proj_bias=False,
        position_embedding_type="nope", attention_multiplier=1 / 16, embedding_multiplier=12.0,
        residual_multiplier=0.22, logits_scaling=8.0, rms_norm_eps=1e-5, tie_word_embeddings=True,
        max_position_embeddings=128, attention_bias=False, attention_dropout=0.0,
    )
    torch.manual_seed(0)
    model = hybrid.GraniteMoeHybridForCausalLM(config).eval().float()
    with torch.no_grad():  # away from HF's constant init, where a swapped name would not show
        for name, p in model.named_parameters():
            if name.endswith(("dt_bias", "A_log", ".D", "norm.weight", "layernorm.weight", "conv1d.bias")):
                p.add_(0.3 * torch.randn_like(p))
    return model


def test_config_maps_every_size(hf):
    cfg = granite_hybrid_config(hf.config)
    assert cfg.layer_types == tuple(PERIOD) and cfg.kv_layers == (5,) and len(cfg.ssm_layers) == 9
    assert (cfg.mamba.d_inner, cfg.mamba.conv_dim) == (128, 160)
    assert not cfg.use_rope and cfg.attention_multiplier == 1 / 16 and cfg.logits_scaling == 8.0


def test_granite_config_is_refused_with_routed_experts(hf):
    config = hybrid.GraniteMoeHybridConfig(**{**hf.config.to_dict(), "num_local_experts": 4})
    with pytest.raises(ValueError, match="num_local_experts"):
        granite_hybrid_config(config)


@pytest.mark.parametrize("n_tokens", [5, 27])
def test_reference_agrees_with_transformers(hf, n_tokens):
    cfg = granite_hybrid_config(hf.config)
    params = convert_granite_hybrid_lm(hf.state_dict(), cfg)
    ids = np.random.default_rng(n_tokens).integers(0, 320, n_tokens)
    with torch.no_grad():
        want = hf(torch.as_tensor(ids)[None]).logits[0].numpy()
    got = np.asarray(
        ref.logits_at(params, jnp.asarray(ids, jnp.int32), list(range(n_tokens)), **ref.model_kwargs(cfg))
    )
    np.testing.assert_allclose(got, want, rtol=0, atol=2e-5 * np.abs(want).max())


def test_our_model_agrees_with_transformers(hf):
    import flax.linen as nn

    from cosmos_curate_tpu.models.convert_qwen import merge_lm_params
    from cosmos_curate_tpu.models.vlm.engine import _init_params
    from cosmos_curate_tpu.models.vlm.model import VLM, init_cache

    cfg = granite_hybrid_config(hf.config)
    model = VLM(cfg, dtype=jnp.float32)
    params = merge_lm_params(nn.unbox(_init_params(model)), convert_granite_hybrid_lm(hf.state_dict(), cfg))
    ids = np.random.default_rng(3).integers(0, 320, 19)
    with torch.no_grad():
        want = hf(torch.as_tensor(ids)[None]).logits[0].numpy()
    embeds = model.apply(params, jnp.asarray(ids)[None], method=model.embed_tokens)
    logits, *_ = model.apply(
        params, embeds, *init_cache(cfg, 1, dtype=jnp.float32, length=32), jnp.arange(19)[None],
        jnp.zeros(1, jnp.int32), jnp.full(1, 19, jnp.int32),
    )
    np.testing.assert_allclose(np.asarray(logits[0]), want, rtol=0, atol=2e-5 * np.abs(want).max())
