"""Plain reference of the CLIP vision transformer (ViT-B/16 as published:
Radford et al. 2021; ``modeling_clip.py`` of transformers), float32,
``jax.numpy`` only, matmuls at ``highest`` precision.

    x = conv_patch(pixels) ; x = [cls ; x] + pos ; x = ln_pre(x)
    for every layer:  x = x + attn(ln1(x)) ; x = x + W2 quick_gelu(W1 ln2(x))
    e = proj(ln_post(x)[cls]) ; e = e / |e|

Pixels: uint8 frames, already 224x224, scaled to [0, 1] and normalised with
CLIP's mean and standard deviation. A clip's embedding is the mean of its
frames' unit embeddings, made unit again (what ``ClipEmbeddingStage`` with
``variant="clip"`` writes). Only the parameter names are the program's.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

MEAN = (0.48145466, 0.4578275, 0.40821073)
STD = (0.26862954, 0.26130258, 0.27577711)


def _ln(x, p, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def _linear(x, p):
    y = x @ p["kernel"].astype(jnp.float32)
    return y + p["bias"].astype(jnp.float32) if "bias" in p else y


def frame_embeddings(params, frames_u8, *, patch, layers, heads, ln_eps):
    """uint8 [N, S, S, 3] -> float32 [N, P], unit norm."""
    p = params["params"]
    with jax.default_matmul_precision("highest"):
        x = (frames_u8.astype(jnp.float32) / 255.0 - jnp.asarray(MEAN)) / jnp.asarray(STD)
        n, s, _, _ = x.shape
        g = s // patch
        kernel = p["patch_embed"]["kernel"].astype(jnp.float32)  # [patch, patch, 3, W]
        w = kernel.shape[-1]
        x = x.reshape(n, g, patch, g, patch, 3).transpose(0, 1, 3, 2, 4, 5)
        x = x.reshape(n, g * g, patch * patch * 3) @ kernel.reshape(patch * patch * 3, w)
        cls = jnp.broadcast_to(p["cls"].astype(jnp.float32), (n, 1, w))
        x = jnp.concatenate([cls, x], axis=1) + p["pos_embed"].astype(jnp.float32)
        x = _ln(x, p["ln_pre"], ln_eps)
        t, d = x.shape[1], w // heads
        for i in range(layers):
            bp = p[f"block_{i}"]
            a = _ln(x, bp["ln1"], ln_eps)
            q, k, v = (_linear(a, bp["attn"][m]).reshape(n, t, heads, d) for m in "qkv")
            sc = jnp.einsum("nqhd,nkhd->nhqk", q, k) * d**-0.5
            o = jnp.einsum("nhqk,nkhd->nqhd", jax.nn.softmax(sc, axis=-1), v)
            x = x + _linear(o.reshape(n, t, w), bp["attn"]["out"])
            m = _linear(_ln(x, bp["ln2"], ln_eps), bp["mlp"]["up"])
            x = x + _linear(m * jax.nn.sigmoid(1.702 * m), bp["mlp"]["down"])
        e = _linear(_ln(x, p["ln_post"], ln_eps)[:, 0], p["proj"])
        return e / jnp.linalg.norm(e, axis=-1, keepdims=True)


def clip_embedding(params, frames_u8, **sizes):
    """The mean of the frames' unit embeddings, unit again: float32 [P]."""
    mean = frame_embeddings(params, frames_u8, **sizes).mean(axis=0)
    return mean / (jnp.linalg.norm(mean) + 1e-8)
