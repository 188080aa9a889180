"""HF ``granitemoehybrid`` checkpoints (Granite-4.0-H) → our hybrid ``VLM``.

The name map of the hybrid decoder (models/vlm/model.py ``layer_types``,
models/vlm/mamba2.py), in the style of ``convert_qwen.convert_qwen2_lm``:
torch ``Linear`` weights ``[out, in]`` become flax kernels ``[in, out]``, the
depthwise ``Conv1d`` weight ``[C, 1, K]`` becomes ``[K, C]``, and the fused
``shared_mlp.input_linear`` (``gate | up``) is split. Only the shape the
engine serves is taken: no routed experts (``num_local_experts`` 0), one
B/C group, no position embedding. Parity with ``transformers``' module is
proven at test size (tests/models/test_convert_granite.py).
"""

from __future__ import annotations

import numpy as np


def _t(w) -> np.ndarray:
    return np.asarray(w.detach().cpu().float().numpy() if hasattr(w, "detach") else w, np.float32)


def granite_hybrid_config(hf_config, **overrides):
    """``VLMConfig`` of an HF ``GraniteMoeHybridConfig``; refuses what the
    decoder here does not compute."""
    from cosmos_curate_tpu.models.vit import VIT_TINY_TEST
    from cosmos_curate_tpu.models.vlm.model import Mamba2Config, VLMConfig

    c = hf_config
    unsupported = {
        "num_local_experts": c.num_local_experts != 0,
        "mamba_n_groups": c.mamba_n_groups != 1,
        "position_embedding_type": c.position_embedding_type != "nope",
        "attention_bias": bool(c.attention_bias),
        "mamba_proj_bias": bool(c.mamba_proj_bias),
        "mamba_conv_bias": not c.mamba_conv_bias,
        "mamba_expand": c.mamba_expand * c.hidden_size != c.mamba_n_heads * c.mamba_d_head,
        "shared_intermediate_size": c.shared_intermediate_size != c.intermediate_size,
    }
    if any(unsupported.values()):
        raise ValueError(f"unsupported granitemoehybrid settings: {[k for k, v in unsupported.items() if v]}")
    fields = dict(
        vocab=c.vocab_size,
        dim=c.hidden_size,
        n_layers=c.num_hidden_layers,
        n_heads=c.num_attention_heads,
        n_kv_heads=c.num_key_value_heads,
        head_dim=c.hidden_size // c.num_attention_heads,
        hidden_mult=c.shared_intermediate_size / c.hidden_size,
        max_seq=min(c.max_position_embeddings, 4096),
        rms_eps=c.rms_norm_eps,
        tied_embeddings=bool(c.tie_word_embeddings),
        vision=VIT_TINY_TEST,
        vision_tokens=8,
        layer_types=tuple(c.layers_block_type),
        mamba=Mamba2Config(
            n_heads=c.mamba_n_heads, head_dim=c.mamba_d_head, d_state=c.mamba_d_state,
            d_conv=c.mamba_d_conv, chunk=c.mamba_chunk_size,
        ),
        use_rope=False,
        attention_multiplier=float(c.attention_multiplier),
        embedding_multiplier=float(c.embedding_multiplier),
        residual_multiplier=float(c.residual_multiplier),
        logits_scaling=float(c.logits_scaling),
    )
    fields.update(overrides)
    return VLMConfig(**fields)


def convert_granite_hybrid_lm(state_dict, cfg) -> dict:
    """``GraniteMoeHybridForCausalLM.state_dict()`` → the LM side of our
    params tree (``{"params": {embed, layer_<i>, ln_f}}``), float32 numpy;
    merge into an init tree with ``convert_qwen.merge_lm_params``."""
    sd = {k.removeprefix("model."): _t(v) for k, v in state_dict.items()}
    hidden = int(round(cfg.dim * cfg.hidden_mult))

    def kernel(name):
        return {"kernel": sd[name].T}

    lm: dict = {
        "embed": {"embedding": sd["embed_tokens.weight"]},
        "ln_f": {"scale": sd["norm.weight"]},
    }
    for i, kind in enumerate(cfg.layer_types):
        p = f"layers.{i}."
        fused = sd[p + "shared_mlp.input_linear.weight"]  # [2 * hidden, dim]: gate | up
        layer = {
            "ln1": {"scale": sd[p + "input_layernorm.weight"]},
            "ln2": {"scale": sd[p + "post_attention_layernorm.weight"]},
            "gate": {"kernel": fused[:hidden].T},
            "up": {"kernel": fused[hidden:].T},
            "down": kernel(p + "shared_mlp.output_linear.weight"),
        }
        if kind == "mamba":
            m = p + "mamba."
            layer["mixer"] = {
                "in_proj": kernel(m + "in_proj.weight"),
                "conv_kernel": sd[m + "conv1d.weight"][:, 0, :].T,  # [C, 1, K] -> [K, C]
                "conv_bias": sd[m + "conv1d.bias"],
                "dt_bias": sd[m + "dt_bias"],
                "A_log": sd[m + "A_log"],
                "D": sd[m + "D"],
                "norm_scale": sd[m + "norm.weight"],
                "out_proj": kernel(m + "out_proj.weight"),
            }
        else:
            a = p + "self_attn."
            for ours, theirs in (("q", "q_proj"), ("k", "k_proj"), ("v", "v_proj"), ("o", "o_proj")):
                layer[ours] = kernel(a + theirs + ".weight")
        lm[f"layer_{i}"] = layer
    if not cfg.tied_embeddings:
        lm["lm_head"] = {"kernel": _t(state_dict["lm_head.weight"]).T}
    return {"params": lm}
