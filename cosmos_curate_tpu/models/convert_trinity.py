"""HF ``afmoe`` checkpoints (Trinity) → our ``VLM`` with window and full
attention layers mixed, in the style of ``convert_deepseek``: torch ``Linear``
weights ``[out, in]`` become flax kernels ``[in, out]``. What is particular:

- **the share held.** ``held = (first, count)`` (default: the flavor's own,
  ``cfg.moe.held``): only those experts' tables are read and stacked, gate and
  up side by side, ``gate_up [count, D, 2 * width]``, ``down [count, width,
  D]``; the router and its selection bias keep all their rows. ``vocab_first``
  picks the chip's slice of ``cfg.vocab`` rows of the embedding and the head;
- **rope.** Our layers rotate halves. A checkpoint stored as interleaved pairs
  (``interleaved_rope=True``) has the output columns of ``q_proj`` and
  ``k_proj`` permuted head by head here, pair ``(2j, 2j + 1)`` -> ``(j, j + d /
  2)``, and the q/k norm scales with them (the norm is over ``head_dim``, before
  rope): a score is a dot product over those dims, so it is unchanged. The
  full-attention layers carry no rope, and the same permutation there is as
  harmless. The default is False: the family rotates halves itself.

**UNVERIFIED NAMES.** This installation's ``transformers`` (4.57) has no
``afmoe`` and there is no network here, so the tensor names below follow
``modeling_afmoe.py`` from memory and are NOT checked against a checkpoint:
``self_attn.{q,k,v,o}_proj``, ``self_attn.gate_proj`` (the output gate),
``self_attn.{q,k}_norm``, the four norms ``input_layernorm`` /
``post_attention_layernorm`` (on the attention BRANCH) / ``pre_mlp_layernorm``
/ ``post_mlp_layernorm`` (on the FFN branch), ``mlp.router.gate``,
``mlp.expert_bias``, ``mlp.experts.<e>.{gate,up,down}_proj``,
``mlp.shared_experts.{gate,up,down}_proj``, and a dense layer's
``mlp.{gate,up,down}_proj``. ``NAMES`` holds them in one place; a missing key
raises ``KeyError`` with the name tried. The CPU test round-trips a synthetic
state dict under exactly these names, which proves the map's shapes,
transposes, slices and permutation, not the names.
"""

from __future__ import annotations

import numpy as np

from cosmos_curate_tpu.models.convert_deepseek import _halves, _t

# ours -> HF, relative to ``model.layers.<i>.``
NAMES = {
    "ln1": "input_layernorm", "post_attn_norm": "post_attention_layernorm",
    "ln2": "pre_mlp_layernorm", "post_mlp_norm": "post_mlp_layernorm",
    "q": "self_attn.q_proj", "k": "self_attn.k_proj", "v": "self_attn.v_proj", "o": "self_attn.o_proj",
    "g": "self_attn.gate_proj", "q_norm": "self_attn.q_norm", "k_norm": "self_attn.k_norm",
    "router": "mlp.router.gate", "router_bias": "mlp.expert_bias", "experts": "mlp.experts",
    "shared": "mlp.shared_experts", "dense": "mlp",
}
_FFN = (("gate", "gate_proj"), ("up", "up_proj"), ("down", "down_proj"))


def trinity_config(hf_config, *, held: tuple[int, int] | None = None, **overrides):
    """``VLMConfig`` of an HF ``AfmoeConfig`` (or its dict); refuses what the
    decoder here does not compute. ``held``: the run of experts this program stores."""
    from cosmos_curate_tpu.models.vit import VIT_TINY_TEST
    from cosmos_curate_tpu.models.vlm.model import MoEConfig, VLMConfig

    c = hf_config if isinstance(hf_config, dict) else hf_config.to_dict()
    unsupported = {
        "rope_scaling": bool(c.get("rope_scaling")),
        "n_group": c.get("n_group", 1) != 1 or c.get("topk_group", 1) != 1,
        "score_func": c.get("score_func") not in ("sigmoid", "softmax"),
        "tie_word_embeddings": bool(c.get("tie_word_embeddings")),
        "layer_types": set(c["layer_types"]) - {"sliding_attention", "full_attention"},
    }
    if any(unsupported.values()):
        raise ValueError(f"unsupported afmoe settings: {[k for k, v in unsupported.items() if v]}")
    fields = dict(
        vocab=c["vocab_size"], dim=c["hidden_size"], n_layers=c["num_hidden_layers"],
        n_heads=c["num_attention_heads"], n_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        hidden_mult=c["intermediate_size"] / c["hidden_size"], max_seq=c["max_position_embeddings"],
        rope_theta=float(c["rope_theta"]), rms_eps=c["rms_norm_eps"], tied_embeddings=False,
        vision=VIT_TINY_TEST, vision_tokens=8, qk_norm=True, layer_types=tuple(c["layer_types"]),
        sliding_window=c["sliding_window"], full_attention_rope=False, attention_gate=True,
        sandwich_norm=True, embedding_multiplier=c["hidden_size"] ** 0.5 if c.get("mup_enabled") else 1.0,
        moe=MoEConfig(
            n_experts=c["num_experts"], top_k=c["num_experts_per_tok"], hidden=c["moe_intermediate_size"],
            shared_hidden=c.get("num_shared_experts", 0) * c["moe_intermediate_size"],
            first_dense=c["num_dense_layers"], norm_topk_prob=bool(c.get("route_norm", True)),
            routed_scaling_factor=float(c.get("route_scale", 1.0)), dispatch="sorted", held=held,
            score_func=c["score_func"], selection_bias=True,
        ),
    )
    return VLMConfig(**{**fields, **overrides})


def convert_trinity_lm(
    state_dict, cfg, *, held: tuple[int, int] | None = None, vocab_first: int = 0,
    interleaved_rope: bool = False,
) -> dict:
    """``AfmoeForCausalLM.state_dict()`` → the LM side of our params tree
    (``{"params": {embed, layer_<i>, ln_f, lm_head}}``), float32 numpy: the first
    ``cfg.n_layers`` layers, the experts ``held`` and ``cfg.vocab`` vocabulary
    rows from ``vocab_first``; merge into an init tree with
    ``convert_qwen.merge_lm_params``."""
    sd = {k.removeprefix("model."): v for k, v in state_dict.items()}
    moe, d = cfg.moe, cfg.head_dim
    first, count = held if held is not None else moe.held_experts
    rope = _halves(d) if interleaved_rope else np.arange(d)
    rows = slice(vocab_first, vocab_first + cfg.vocab)

    def weight(name):
        if name not in sd:
            raise KeyError(f"no tensor {name!r} in the state dict (the afmoe names are unverified: see NAMES)")
        return _t(sd[name])

    def kernel(name):
        return {"kernel": weight(name + ".weight").T}

    def by_head(name, heads):  # [D, heads * d], every head's rotary dims in halves
        w = weight(name + ".weight").T.reshape(-1, heads, d)[..., rope]
        return {"kernel": w.reshape(w.shape[0], heads * d)}

    def swiglu(prefix, ours=""):
        return {ours + a: kernel(f"{prefix}.{b}") for a, b in _FFN}

    lm: dict = {
        "embed": {"embedding": weight("embed_tokens.weight")[rows]},
        "ln_f": {"scale": weight("norm.weight")},
        "lm_head": {"kernel": weight("lm_head.weight")[rows].T},
    }
    for i in range(cfg.n_layers):
        p = f"layers.{i}."
        layer = {ours: {"scale": weight(p + NAMES[ours] + ".weight")} for ours in ("ln1", "post_attn_norm", "ln2", "post_mlp_norm")}
        layer["q"], layer["k"] = by_head(p + NAMES["q"], cfg.n_heads), by_head(p + NAMES["k"], cfg.n_kv_heads)
        for ours in ("v", "o", "g"):
            layer[ours] = kernel(p + NAMES[ours])
        for ours in ("q_norm", "k_norm"):
            layer[ours] = {"scale": weight(p + NAMES[ours] + ".weight")[rope]}
        if i < moe.first_dense:
            layer.update(swiglu(p + NAMES["dense"]))
        else:
            experts = [f"{p}{NAMES['experts']}.{e}" for e in range(first, first + count)]
            layer["moe"] = {
                "router": kernel(p + NAMES["router"]),
                "router_bias": weight(p + NAMES["router_bias"]).reshape(-1),
                "gate_up": np.stack([
                    np.concatenate([weight(e + ".gate_proj.weight").T, weight(e + ".up_proj.weight").T], axis=1)
                    for e in experts
                ]),
                "down": np.stack([weight(e + ".down_proj.weight").T for e in experts]),
            }
            if moe.shared_hidden:
                layer["moe"].update(swiglu(p + NAMES["shared"], "shared_"))
        lm[f"layer_{i}"] = layer
    return {"params": lm}
