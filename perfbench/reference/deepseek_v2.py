"""Plain reference of the DeepSeek-V2 decoder (HF ``deepseek_v2``, as
deepseek-ai/DeepSeek-V2 publishes it), after ``modeling_deepseek.py`` of that
repository: float32 throughout, ``jax.numpy`` only, matmuls at ``highest``
precision, the NON-absorbed attention equations, the full softmax over all
routed experts, no cache, no kernels, no batching.

    h_0 = E[ids]
    for every layer i, a = rmsnorm(h):
        c_q = rmsnorm(a W_DQ) ;  [q_nope_j | q_rope_j] = (c_q W_UQ)_j ;  q_rope <- rope(q_rope)
        [c_kv | k_rope] = a W_DKV ;  c_kv <- rmsnorm(c_kv) ;  k_rope <- rope(k_rope)   (one for all heads)
        [k_nope_j | v_j] = (c_kv W_UKV)_j
        s_j(t, s) = (q_nope_j(t) . k_nope_j(s) + q_rope_j(t) . k_rope(s)) * scale,  causal
        h = h + concat_j(softmax_s(s_j) v_j) W_O
        n = rmsnorm(h)
        layer < first_dense:  h = h + (silu(n Wgate) * (n Wup)) Wdown
        else:  p = softmax(n W_r) over ALL experts (float32); the experts are n_group runs of
               consecutive experts; keep the topk_group groups whose best p is highest, zero the
               rest; top k of what is left; w_e = p_e * routed_scaling_factor (NOT renormalised)
               h = h + sum_{e in top k, e held} w_e E_e(n) + S(n)
    logits = rmsnorm(h_last) W_head

``scale = (nope + rope)^-0.5 * m^2``, ``m = 0.1 * mscale_all_dim * ln(factor) + 1``;
rope on the ``rope`` dims with YaRN's frequencies (``yarn_inv_freq`` below).

**The held share.** ``held = (first, count)`` says which experts the tree's
tables ``moe/gate_up [count, D, 2 * width]`` and ``moe/down`` are: the layer adds
the held experts' part of the routed sum (the router still scores all of them),
and the shared expert where ``with_shared``. Summed over the shares of a
deployment, the shared expert counted once, that is the uncut layer
(tests/perfbench/test_deepseek_cell.py).

Departures from the HF module, none of which changes a value: (1) the ROPE
LAYOUT. HF keeps the checkpoint's rotary dims as interleaved pairs and
de-interleaves q and k inside ``apply_rotary_pos_emb`` before ``rotate_half``;
this file takes them ALREADY de-interleaved (first half, second half), the
layout the program's converter produces (models/convert_deepseek.py); a score
is a dot product over those dims, so the same permutation on both sides leaves
it as it was. (2) float32 throughout, where HF computes in the checkpoint's
bfloat16 and only the router's softmax in float32. (3) HF's gate scores
``n W_r`` through ``F.linear`` in float32 too: the same. (4) the factor on
cos/sin, ``mscale / mscale_all_dim``, is applied as HF applies it (1 for the
published numbers).

The parameter tree is the program's own (``params["params"]["layer_<i>"]``…);
only its names are shared with the program, none of its code.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np


def _rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _f32(w):
    """A stored table in float32, AT ITS USE: a sparse layer's tables widened
    at once are 2.7 GB beside the serving engine's 12 (one expert's are 94 MB)."""
    return w.astype(jnp.float32)


def _linear(x, p):
    return x @ _f32(p["kernel"])


def _round(v, mantissa_bits):
    """``v`` rounded to a float with that many bits of mantissa (23: as it is).
    ``reduce_precision`` because XLA elides a convert pair."""
    if mantissa_bits >= 23:
        return v
    return jax.lax.reduce_precision(v, exponent_bits=8, mantissa_bits=mantissa_bits)


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(*, rope_dim, theta, factor, original_max, beta_fast, beta_slow, **_) -> np.ndarray:
    """``inv_freq_j = (f_j / factor) * ramp_j + f_j * (1 - ramp_j)``, ``f_j =
    theta^(-2j / rope_dim)``, the ramp linear from the dim that turns
    ``beta_fast`` times over the original context to the one that turns
    ``beta_slow`` times."""
    f = 1.0 / theta ** (np.arange(0, rope_dim, 2, dtype=np.float64) / rope_dim)
    if factor <= 1:
        return f.astype(np.float32)

    def dim_of(rotations):
        return rope_dim * math.log(original_max / (rotations * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(dim_of(beta_fast)), 0)
    high = min(math.ceil(dim_of(beta_slow)), rope_dim - 1)
    ramp = np.clip((np.arange(rope_dim // 2) - low) / max(high - low, 0.001), 0.0, 1.0)
    return (f / factor * ramp + f * (1 - ramp)).astype(np.float32)


def _rope(x, inv_freq, gain):
    """x: [T, heads, rope_dim], halves layout; position t is row t."""
    angles = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv_freq  # [T, rope_dim / 2]
    cos, sin = jnp.cos(angles)[:, None] * gain, jnp.sin(angles)[:, None] * gain
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


def latent_rows(a, lp, *, attn, rms_eps):
    """What the cache holds for every position: ``c_kv`` [T, kv_rank] after
    its norm and ``k_rope`` [T, rope_dim] after rope. ``a``: the normed input."""
    c, gain = attn["kv_rank"], yarn_mscale(attn["factor"], attn["mscale"]) / yarn_mscale(
        attn["factor"], attn["mscale_all_dim"]
    )
    kv = _linear(a, lp["kv_a"])
    c_kv = _rmsnorm(kv[:, :c], lp["kv_a_norm"]["scale"], rms_eps)
    k_rope = _rope(kv[:, None, c:], jnp.asarray(yarn_inv_freq(**attn)), gain)[:, 0]
    return c_kv, k_rope


def attention(a, lp, *, attn, rms_eps):
    t = a.shape[0]
    h, dn, dr, dv = attn["n_heads"], attn["nope_dim"], attn["rope_dim"], attn["v_dim"]
    gain = yarn_mscale(attn["factor"], attn["mscale"]) / yarn_mscale(attn["factor"], attn["mscale_all_dim"])
    m = yarn_mscale(attn["factor"], attn["mscale_all_dim"])
    scale = (dn + dr) ** -0.5 * m * m
    c_q = _rmsnorm(_linear(a, lp["q_a"]), lp["q_a_norm"]["scale"], rms_eps)
    q = _linear(c_q, lp["q_b"]).reshape(t, h, dn + dr)
    q_nope, q_rope = q[..., :dn], _rope(q[..., dn:], jnp.asarray(yarn_inv_freq(**attn)), gain)
    c_kv, k_rope = latent_rows(a, lp, attn=attn, rms_eps=rms_eps)
    kv = (c_kv @ _f32(lp["kv_b"])).reshape(t, h, dn + dv)
    k_nope, v = kv[..., :dn], kv[..., dn:]
    s = (jnp.einsum("qhd,khd->hqk", q_nope, k_nope) + jnp.einsum("qhr,kr->hqk", q_rope, k_rope)) * scale
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool))[None], s, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)
    return _linear(o.reshape(t, h * dv), lp["o"])


def route(n, router, *, moe, router_mantissa_bits=23):
    """(weights [T, k], experts [T, k], margin [T]) of the group-limited
    greedy router. ``margin`` is how far, as a share of the score, a token's
    choice of HELD experts is from changing: the gap between the last group
    kept and the first dropped, and between the last expert taken and the first
    left out where either is held (1 where neither is). A comparison with a
    program that computes in fewer bits means something only where this is
    wide: under it, another choice is rounding and not an error."""
    e, g, k = moe["n_experts"], moe["n_group"], moe["top_k"]
    first, count = moe["held"]
    p = jax.nn.softmax(_round(_linear(n, router), router_mantissa_bits), axis=-1)
    p = _round(p, router_mantissa_bits)
    t = p.shape[0]
    margin = jnp.ones((t,), jnp.float32)
    is_held = (jnp.arange(e) >= first) & (jnp.arange(e) < first + count)
    if g > 1:
        best = p.reshape(t, g, e // g).max(axis=-1)
        gv, gi = jax.lax.top_k(best, min(moe["topk_group"] + 1, g))
        kept = jnp.zeros((t, g), bool).at[jnp.arange(t)[:, None], gi[:, : moe["topk_group"]]].set(True)
        if moe["topk_group"] < g:
            # whichever groups swap, the held experts' rivals for the top k change
            margin = (gv[:, moe["topk_group"] - 1] - gv[:, moe["topk_group"]]) / gv[:, moe["topk_group"] - 1]
        p = jnp.where(jnp.repeat(kept, e // g, axis=1), p, 0.0)
    w, idx = jax.lax.top_k(p, k + 1)
    touches = is_held[idx[:, k - 1]] | (is_held[idx[:, k]] & (w[:, k] > 0))
    gap = (w[:, k - 1] - w[:, k]) / w[:, k - 1]
    margin = jnp.where(touches, jnp.minimum(margin, gap), margin)
    w, idx = w[:, :k], idx[:, :k]
    if moe["norm_topk_prob"]:
        w = w / w.sum(axis=-1, keepdims=True)
    return w * moe["routed_scaling_factor"], idx, margin


def _swiglu(n, gate, up, down):
    return (jax.nn.silu(n @ _f32(gate)) * (n @ _f32(up))) @ _f32(down)


def experts(n, mp, *, moe, with_shared=True, router_mantissa_bits=23, drop_every=0):
    """The held experts' part of the routed sum, plus the shared expert: [T, D],
    and the routing margin [T]. A loop over the held experts, every token
    through each, weighted by what the router gave it (zero where it was not
    chosen): the definition, at ``count`` times the needed work.
    ``drop_every`` > 0 forgets every that-many-th assignment (a reading of
    what a dispatch that drops costs; the benchmark's lower-precision run)."""
    first, count = moe["held"]
    w, idx, margin = route(n, mp["router"], moe=moe, router_mantissa_bits=router_mantissa_bits)
    if drop_every:
        order = jnp.arange(w.size).reshape(w.shape)
        w = jnp.where(order % drop_every == drop_every - 1, 0.0, w)
    width = mp["down"].shape[1]

    def one(acc, inp):
        e, gate_up, down = inp
        weight = jnp.sum(jnp.where(idx == first + e, w, 0.0), axis=-1, keepdims=True)  # [T, 1]
        return acc + weight * _swiglu(n, gate_up[:, :width], gate_up[:, width:], down), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(n), (jnp.arange(count), mp["gate_up"], mp["down"]))
    if with_shared and "shared_gate" in mp:
        y = y + _swiglu(n, mp["shared_gate"]["kernel"], mp["shared_up"]["kernel"], mp["shared_down"]["kernel"])
    return y, margin


def layer(h, lp, *, dense, attn, moe, rms_eps, with_shared=True, activation_mantissa_bits=23,
          router_mantissa_bits=23, drop_every=0):
    """One decoder layer on the whole prompt: ([T, dim], routing margin [T]).
    ``activation_mantissa_bits`` under 23 rounds what a serving engine keeps in
    its activation type (the normed inputs of both halves and both branches'
    outputs): 7 is bfloat16, 3 an 8-bit float. Only the benchmark's
    lower-precision readings pass these three."""
    act = functools.partial(_round, mantissa_bits=activation_mantissa_bits)
    with jax.default_matmul_precision("highest"):
        h = h + act(attention(act(_rmsnorm(h, lp["ln1"]["scale"], rms_eps)), lp, attn=attn, rms_eps=rms_eps))
        n = act(_rmsnorm(h, lp["ln2"]["scale"], rms_eps))
        if dense:
            y = _swiglu(n, lp["gate"]["kernel"], lp["up"]["kernel"], lp["down"]["kernel"])
            margin = jnp.ones((h.shape[0],), jnp.float32)
        else:
            y, margin = experts(
                n, lp["moe"], moe=moe, with_shared=with_shared,
                router_mantissa_bits=router_mantissa_bits, drop_every=drop_every,
            )
        return h + act(y), margin


def embed(table, ids):
    return table.astype(jnp.float32)[ids]


def head(h, scale, kernel, *, rms_eps):
    with jax.default_matmul_precision("highest"):
        return _rmsnorm(h, scale.astype(jnp.float32), rms_eps) @ kernel.astype(jnp.float32)


def forward(params, ids, *, n_layers, first_dense, rms_eps, attn, moe, place=lambda tree: tree, **low):
    """(hidden states [T, dim] after the last layer, routing margin [T]: the
    least over the sparse layers). One jitted program per kind of layer,
    reused for every layer of the kind; ``place`` applied to a layer's
    parameters just before use, so that a tree that lives elsewhere (or in a
    narrower type) is widened a layer at a time."""
    p = params["params"]
    run = {
        dense: jax.jit(functools.partial(layer, dense=dense, attn=attn, moe=moe, rms_eps=rms_eps, **low))
        for dense in (True, False)
    }
    h = jax.jit(embed)(place(p["embed"]["embedding"]), ids)
    margin = jnp.ones((ids.shape[0],), jnp.float32)
    for i in range(n_layers):
        h, m = run[i < first_dense](h, place(p[f"layer_{i}"]))
        margin = jnp.minimum(margin, m)
    return h, margin


def logits_at(params, ids, positions, *, rms_eps, place=lambda tree: tree, **sizes):
    """(logits [len(positions), vocab], routing margins [len(positions)]) of the
    prompt ``ids`` [T] at ``positions``: the full forward pass, no cache."""
    p = params["params"]
    h, margin = forward(params, ids, rms_eps=rms_eps, place=place, **sizes)
    at = jnp.asarray(positions)
    logits = jax.jit(functools.partial(head, rms_eps=rms_eps))(
        h[at], place(p["ln_f"]["scale"]), place(p["lm_head"]["kernel"])
    )
    return logits, margin[at]


def last_logits(params, ids, **sizes):
    """(logits [vocab] at the last position of ``ids`` [T], its routing margin)."""
    logits, margin = logits_at(params, ids, [ids.shape[0] - 1], **sizes)
    return logits[0], margin[0]


def cache_rows(params, ids, layer_index, *, first_dense, rms_eps, attn, moe, n_layers=None,
               place=lambda tree: tree, **low):
    """(``[c_kv | k_rope]`` [T, kv_rank + rope_dim] of layer ``layer_index`` for
    every position: what the engine's latent pool must hold there; the routing
    margin [T] of the layers before it). The last layer's rows carry every
    earlier layer's experts for EVERY token, where logits carry one position."""
    p = params["params"]
    h = embed(place(p["embed"]["embedding"]), ids)
    margin = jnp.ones((ids.shape[0],), jnp.float32)
    for i in range(layer_index):
        h, m = jax.jit(functools.partial(layer, dense=i < first_dense, attn=attn, moe=moe, rms_eps=rms_eps, **low))(
            h, place(p[f"layer_{i}"])
        )
        margin = jnp.minimum(margin, m)

    def rows(h, lp):
        with jax.default_matmul_precision("highest"):
            a = _rmsnorm(h, lp["ln1"]["scale"], rms_eps)
            return jnp.concatenate(latent_rows(a, lp, attn=attn, rms_eps=rms_eps), axis=-1)

    return jax.jit(rows)(h, place(p[f"layer_{layer_index}"])), margin


def model_kwargs(cfg) -> dict:
    """The reference's sizes from the program's ``VLMConfig``, as plain numbers."""
    a, m = cfg.mla, cfg.moe
    return dict(
        n_layers=cfg.n_layers,
        first_dense=m.first_dense,
        rms_eps=cfg.rms_eps,
        attn=dict(
            n_heads=cfg.n_heads, q_rank=a.q_lora_rank, kv_rank=a.kv_lora_rank,
            nope_dim=a.qk_nope_head_dim, rope_dim=a.qk_rope_head_dim, v_dim=a.v_head_dim,
            theta=cfg.rope_theta, factor=a.yarn_factor, original_max=a.yarn_original_max,
            beta_fast=a.yarn_beta_fast, beta_slow=a.yarn_beta_slow, mscale=a.yarn_mscale,
            mscale_all_dim=a.yarn_mscale_all_dim,
        ),
        moe=dict(
            n_experts=m.n_experts, top_k=m.top_k, n_group=m.n_group, topk_group=m.topk_group,
            norm_topk_prob=m.norm_topk_prob, routed_scaling_factor=m.routed_scaling_factor,
            held=tuple(m.held_experts),
        ),
    )
