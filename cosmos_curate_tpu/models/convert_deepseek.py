"""HF ``deepseek_v2`` checkpoints (DeepSeek-V2) → our latent-attention ``VLM``.

The name map of ``LatentAttentionLayer`` and the sorted-dispatch ``MoEFFN``
(models/vlm/model.py), in the style of ``convert_granite``: torch ``Linear``
weights ``[out, in]`` become flax kernels ``[in, out]``. What is particular:

- **the share held.** An expert-parallel deployment's chip stores a run of
  the routed experts, ``held = (first, count)`` (default: the flavor's own,
  ``cfg.moe.held``; all of them where that is None): only those experts'
  tables are read from the checkpoint and stacked, gate and up side by side,
  ``gate_up [count, D, 2 * width]``, ``down [count, width, D]``. The router
  keeps all its rows. ``vocab_first`` picks the chip's slice of
  ``cfg.vocab`` rows of the embedding and the head the same way;
- **the rope permutation.** HF keeps the rotary dims of ``q_b_proj`` (every
  head's last ``qk_rope_head_dim`` outputs) and of ``kv_a_proj_with_mqa``
  (its last ``qk_rope_head_dim`` outputs) as interleaved pairs and
  de-interleaves inside ``apply_rotary_pos_emb``; our layers rotate halves.
  ``interleaved_rope=True`` applies that de-interleave to the weights' output
  columns once, here (pair ``(2j, 2j + 1)`` -> ``(j, j + d / 2)``). A score is
  a dot product over those dims, so it is unchanged. Pass False for a
  checkpoint already stored in halves;
- ``kv_b_proj`` is ONE table here too (``kv_b [C, H * (nope + v)]``): the
  layer takes its two halves as ``W_UK`` and ``W_UV`` at trace time.
"""

from __future__ import annotations

import numpy as np


def _t(w) -> np.ndarray:
    return np.asarray(w.detach().cpu().float().numpy() if hasattr(w, "detach") else w, np.float32)


def deepseek_v2_config(hf_config, *, held: tuple[int, int] | None = None, **overrides):
    """``VLMConfig`` of an HF ``DeepseekV2Config``; refuses what the decoder
    here does not compute. ``held``: the run of experts this program stores."""
    from cosmos_curate_tpu.models.vit import VIT_TINY_TEST
    from cosmos_curate_tpu.models.vlm.model import MLAConfig, MoEConfig, VLMConfig

    c = hf_config
    scaling = getattr(c, "rope_scaling", None) or {}
    unsupported = {
        "q_lora_rank": not c.q_lora_rank,
        "attention_bias": bool(c.attention_bias),
        "scoring_func": getattr(c, "scoring_func", "softmax") != "softmax",
        "topk_method": getattr(c, "topk_method", "greedy") not in ("greedy", "group_limited_greedy"),
        "moe_layer_freq": getattr(c, "moe_layer_freq", 1) != 1,
        "rope_scaling": bool(scaling) and scaling.get("type", scaling.get("rope_type")) != "yarn",
        "tie_word_embeddings": bool(c.tie_word_embeddings),
    }
    if any(unsupported.values()):
        raise ValueError(f"unsupported deepseek_v2 settings: {[k for k, v in unsupported.items() if v]}")
    grouped = getattr(c, "topk_method", "greedy") == "group_limited_greedy"
    fields = dict(
        vocab=c.vocab_size,
        dim=c.hidden_size,
        n_layers=c.num_hidden_layers,
        n_heads=c.num_attention_heads,
        n_kv_heads=c.num_attention_heads,
        head_dim=c.qk_nope_head_dim,
        hidden_mult=c.intermediate_size / c.hidden_size,
        max_seq=min(c.max_position_embeddings, 4096),
        rope_theta=float(c.rope_theta),
        rms_eps=c.rms_norm_eps,
        tied_embeddings=False,
        vision=VIT_TINY_TEST,
        vision_tokens=8,
        mla=MLAConfig(
            q_lora_rank=c.q_lora_rank, kv_lora_rank=c.kv_lora_rank,
            qk_nope_head_dim=c.qk_nope_head_dim, qk_rope_head_dim=c.qk_rope_head_dim,
            v_head_dim=c.v_head_dim,
            yarn_factor=float(scaling.get("factor", 1.0)),
            yarn_original_max=int(scaling.get("original_max_position_embeddings", 4096)),
            yarn_beta_fast=float(scaling.get("beta_fast", 32)),
            yarn_beta_slow=float(scaling.get("beta_slow", 1)),
            yarn_mscale=float(scaling.get("mscale", 1.0)),
            yarn_mscale_all_dim=float(scaling.get("mscale_all_dim", 0.0)),
        ),
        moe=MoEConfig(
            n_experts=c.n_routed_experts, top_k=c.num_experts_per_tok, hidden=c.moe_intermediate_size,
            shared_hidden=(c.n_shared_experts or 0) * c.moe_intermediate_size,
            first_dense=c.first_k_dense_replace,
            n_group=c.n_group if grouped else 1, topk_group=c.topk_group if grouped else 1,
            norm_topk_prob=bool(c.norm_topk_prob), routed_scaling_factor=float(c.routed_scaling_factor),
            dispatch="sorted", held=held,
        ),
    )
    return VLMConfig(**{**fields, **overrides})


def _halves(d: int) -> np.ndarray:
    """Where each rotary dim comes from: pairs ``(2j, 2j + 1)`` of HF's layout
    become ``(j, j + d / 2)``."""
    return np.concatenate([np.arange(0, d, 2), np.arange(1, d, 2)])


def convert_deepseek_v2_lm(
    state_dict, cfg, *, held: tuple[int, int] | None = None, vocab_first: int = 0,
    interleaved_rope: bool = True,
) -> dict:
    """``DeepseekV2ForCausalLM.state_dict()`` → the LM side of our params tree
    (``{"params": {embed, layer_<i>, ln_f, lm_head}}``), float32 numpy: the
    first ``cfg.n_layers`` layers, the experts ``held`` and ``cfg.vocab``
    vocabulary rows from ``vocab_first``; merge into an init tree with
    ``convert_qwen.merge_lm_params``."""
    sd = {k.removeprefix("model."): v for k, v in state_dict.items()}
    mla, moe = cfg.mla, cfg.moe
    first, count = held if held is not None else moe.held_experts
    h, dn, dr, c = cfg.n_heads, mla.qk_nope_head_dim, mla.qk_rope_head_dim, mla.kv_lora_rank
    rope = _halves(dr) if interleaved_rope else np.arange(dr)
    rows = slice(vocab_first, vocab_first + cfg.vocab)

    def kernel(name):
        return {"kernel": _t(sd[name]).T}

    lm: dict = {
        "embed": {"embedding": _t(sd["embed_tokens.weight"])[rows]},
        "ln_f": {"scale": _t(sd["norm.weight"])},
        "lm_head": {"kernel": _t(sd["lm_head.weight"])[rows].T},
    }
    for i in range(cfg.n_layers):
        p, a = f"layers.{i}.", f"layers.{i}.self_attn."
        q_b = _t(sd[a + "q_b_proj.weight"]).T.reshape(-1, h, dn + dr)  # [q_rank, H, nope | rope]
        q_b = np.concatenate([q_b[..., :dn], q_b[..., dn:][..., rope]], axis=-1)
        kv_a = _t(sd[a + "kv_a_proj_with_mqa.weight"]).T  # [D, C | rope]
        layer = {
            "ln1": {"scale": _t(sd[p + "input_layernorm.weight"])},
            "ln2": {"scale": _t(sd[p + "post_attention_layernorm.weight"])},
            "q_a": kernel(a + "q_a_proj.weight"),
            "q_a_norm": {"scale": _t(sd[a + "q_a_layernorm.weight"])},
            "q_b": {"kernel": q_b.reshape(q_b.shape[0], h * (dn + dr))},
            "kv_a": {"kernel": np.concatenate([kv_a[:, :c], kv_a[:, c:][:, rope]], axis=1)},
            "kv_a_norm": {"scale": _t(sd[a + "kv_a_layernorm.weight"])},
            "kv_b": _t(sd[a + "kv_b_proj.weight"]).T,
            "o": kernel(a + "o_proj.weight"),
        }
        m = p + "mlp."
        if i < moe.first_dense:
            for ours, theirs in (("gate", "gate_proj"), ("up", "up_proj"), ("down", "down_proj")):
                layer[ours] = kernel(m + theirs + ".weight")
        else:
            experts = range(first, first + count)
            layer["moe"] = {
                "router": kernel(m + "gate.weight"),
                "gate_up": np.stack([
                    np.concatenate([
                        _t(sd[f"{m}experts.{e}.gate_proj.weight"]).T, _t(sd[f"{m}experts.{e}.up_proj.weight"]).T,
                    ], axis=1)
                    for e in experts
                ]),
                "down": np.stack([_t(sd[f"{m}experts.{e}.down_proj.weight"]).T for e in experts]),
            }
            if moe.shared_hidden:
                for ours, theirs in (("shared_gate", "gate_proj"), ("shared_up", "up_proj"), ("shared_down", "down_proj")):
                    layer["moe"][ours] = kernel(f"{m}shared_experts.{theirs}.weight")
        lm[f"layer_{i}"] = layer
    return {"params": lm}
