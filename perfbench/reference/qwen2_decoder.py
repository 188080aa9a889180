"""Plain reference of the Qwen2 decoder-only language model (the text side of
Qwen2-VL and Qwen2.5-VL), after the published description (Qwen2 technical
report; ``modeling_qwen2.py`` of transformers): float32 throughout,
``jax.numpy`` only, matmuls at ``highest`` precision, no cache, no kernels, no
batching, no sharding.

    h_0 = E[ids]
    for every layer:
        a   = rmsnorm(h) ; q, k, v = a Wq + bq, a Wk + bk, a Wv + bv
        q, k rotated by rope (theta, rotate-half pairing (i, i + D/2))
        o   = softmax(q k^T / sqrt(D) + causal) v     (KV head j serves query
                                                       heads j*G .. j*G+G-1)
        h   = h + o Wo
        h   = h + (silu(n Wgate) * (n Wup)) Wdown,  n = rmsnorm(h)
    logits = rmsnorm(h_last) E^T   (tied)  or  rmsnorm(h_last) Whead (untied)

Rope is one-dimensional: for a text-only prompt the three m-rope components
(t, h, w) are equal, and m-rope then reduces to it (Qwen2-VL paper, sec. 2.1),
so this is the whole model for the requests it is compared on. No departure
from the published equations.

The parameter tree is the program's own (``params["params"]["layer_<i>"]``…);
only its names are shared with the program, none of its code.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def _rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """x: [T, H, D] float32, positions 0..T-1."""
    t, _, d = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]  # [T, D/2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2 :]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


def _linear(x, p):
    y = x @ p["kernel"].astype(jnp.float32)
    return y + p["bias"].astype(jnp.float32) if "bias" in p else y


def embed(table, ids):
    """h_0 = E[ids]: [T, dim] float32."""
    return table.astype(jnp.float32)[ids]


def layer(h, lp, *, n_heads, n_kv_heads, head_dim, rope_theta, rms_eps):
    """One decoder layer on the whole prompt, [T, dim] -> [T, dim]."""
    with jax.default_matmul_precision("highest"):
        t = h.shape[0]
        group = n_heads // n_kv_heads
        a = _rmsnorm(h, lp["ln1"]["scale"], rms_eps)
        q = _rope(_linear(a, lp["q"]).reshape(t, n_heads, head_dim), rope_theta)
        k = _rope(_linear(a, lp["k"]).reshape(t, n_kv_heads, head_dim), rope_theta)
        v = _linear(a, lp["v"]).reshape(t, n_kv_heads, head_dim)
        k = jnp.repeat(k, group, axis=1)  # KV head j -> query heads j*G..
        v = jnp.repeat(v, group, axis=1)
        s = jnp.einsum("qhd,khd->hqk", q, k) * head_dim**-0.5
        s = jnp.where(jnp.tril(jnp.ones((t, t), bool))[None], s, -jnp.inf)
        o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)
        h = h + _linear(o.reshape(t, n_heads * head_dim), lp["o"])
        n = _rmsnorm(h, lp["ln2"]["scale"], rms_eps)
        return h + _linear(jax.nn.silu(_linear(n, lp["gate"])) * _linear(n, lp["up"]), lp["down"])


def head(h_last, scale, matrix, *, rms_eps, tied_embeddings):
    """Logits [vocab] of one position: the tied table [vocab, dim] or the
    untied head [dim, vocab]."""
    with jax.default_matmul_precision("highest"):
        last = _rmsnorm(h_last, scale, rms_eps)
        matrix = matrix.astype(jnp.float32)
        return last @ (matrix.T if tied_embeddings else matrix)


def last_logits(params, ids, *, n_layers, n_heads, n_kv_heads, head_dim, rope_theta, rms_eps,
                tied_embeddings, place=lambda tree: tree):
    """Logits [vocab] at the last position of the prompt ``ids`` [T].

    One jitted program per piece, the layer's reused for every layer, and
    ``place`` applied to each piece's parameters just before use: with
    parameters split over a mesh, ``place`` gathers one layer at a time onto
    one chip, so the reference never asks the compiler to partition anything
    and no chip holds the model whole."""
    p = params["params"]
    sizes = dict(n_heads=n_heads, n_kv_heads=n_kv_heads, head_dim=head_dim,
                 rope_theta=rope_theta, rms_eps=rms_eps)
    run_layer = jax.jit(functools.partial(layer, **sizes))
    table = place(p["embed"]["embedding"])
    h = jax.jit(embed)(table, ids)
    if not tied_embeddings:
        del table
    for i in range(n_layers):
        h = run_layer(h, place(p[f"layer_{i}"]))
    matrix = table if tied_embeddings else place(p["lm_head"]["kernel"])
    return jax.jit(functools.partial(head, rms_eps=rms_eps, tied_embeddings=tied_embeddings))(
        h[-1], place(p["ln_f"]["scale"]), matrix
    )


def model_kwargs(cfg) -> dict:
    """The reference's sizes from the program's ``VLMConfig``."""
    return dict(
        n_layers=cfg.n_layers, n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
        head_dim=cfg.head_dim, rope_theta=cfg.rope_theta, rms_eps=cfg.rms_eps,
        tied_embeddings=cfg.tied_embeddings,
    )
