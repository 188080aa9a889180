"""Plain reference of the LFM2-MoE decoder (HF ``lfm2_moe``, as
LiquidAI/LFM2-24B-A2B publishes it): float32 throughout, ``jax.numpy`` only,
matmuls at ``highest`` precision, the whole sequence at once, the convolution as
a sum of shifted copies, dense softmax attention, the experts by a plain loop
over ALL of a layer's experts (nothing is left out: the layer is uncut), no
chunks, no cache, no kernels, no batching.

For a layer's input ``x`` ``[T, dim]`` (eps 1e-5, HF ``norm_eps``):

    h = x + Op(rmsnorm_op(x)) ;  y = h + FFN(rmsnorm_ffn(h))              (pre-norm, both)
    logits = rmsnorm(y_last) E^T           (HF ``embedding_norm``, then the TIED head)

conv (``Lfm2ShortConv``; ``conv_L_cache`` 3, ``conv_bias`` false):

    [B | C | X] = u W_in          three slices of ``dim``, in that order
    z = B * X
    c_t = w[0] z_{t-2} + w[1] z_{t-1} + w[2] z_t          per channel; z is zero before the prompt
    Op = (C * c) W_out
  what a cache would carry to the next token is ``z_{t-1}, z_{t-2}``: :func:`forward`
  hands out every conv layer's ``z`` so that a program's tails can be held to it.

full_attention (``Lfm2Attention``): ``q = rmsnorm_64(u W_q)``, ``k = rmsnorm_64(u W_k)``
a head (a 64-wide scale each), ``v = u W_v``; rope on all 64 dims of q and k, theta
1e6, the half-split (``rotate_half``) layout; causal softmax at ``64^-0.5``; 32 query
heads over 8 KV heads; ``Op = o W_out``; no bias.

FFN of the ``num_dense_layers`` leading layers: ``(silu(u W_1) * u W_3) W_2``, width
``intermediate_size`` as the config gives it. FFN of the others: ``s = sigmoid(u
W_r)`` over all 64 experts in float32; the top 4 of ``s + b`` (``use_expert_bias``:
the bias chooses and does not weigh; ties to the lower index); the weights are ``s``
at the chosen, divided by (their sum + 1e-6) (``norm_topk_prob``), times
``routed_scaling_factor``; the sum of the four experts' SwiGLUs so weighted. No
shared expert.

DEPARTURES from the published description, each the configuration file's
``assumed``: the installed transformers (4.57.6) has ``lfm2`` and no ``lfm2_moe``,
so the expert block is written from the config's keys (``use_expert_bias``,
``norm_topk_prob``, ``routed_scaling_factor``) and the family's router; the head
is tied (the config has no key for it; the family ties); the dense width is
taken as given (``block_auto_adjust_ff_dim`` is no key of this config).

The parameter tree is the program's own (``params["params"]["layer_<i>"]``...);
only its names are shared with the program, none of its code. Three knobs serve
the benchmark's lower-precision readings alone (23 bits = float32 = off).
``follow`` makes a sparse layer take a GIVEN choice of experts in place of its
router's own (weighed by this router's float32 scores of them; everything else
stays the reference's): with all 64 experts held a program in bfloat16 takes
another expert at one (token, layer) in twelve, each such flip swaps a quarter
of a layer, and a comparison of the layers above it means something only along
the program's own choice. Whether that choice was right is asked apart: the
reference hands out its own (``choices``) and its margin (``margins``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def _rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _f32(w):
    return w.astype(jnp.float32)


def _linear(x, p):
    return x @ _f32(p["kernel"])


def _round(x, mantissa_bits):
    """``reduce_precision`` because XLA elides a convert pair; 23 = float32."""
    if mantissa_bits >= 23:
        return x
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=mantissa_bits)


def short_conv(u, mp, *, tail_mantissa_bits=23):
    """(Op [T, dim], z [T, dim]). ``tail_mantissa_bits``: what ``z`` is rounded
    to, as a cache that stores its tails in fewer bits would have it."""
    t = u.shape[0]
    gate_in, gate_out, x = jnp.split(_linear(u, mp["in_proj"]), 3, axis=-1)
    z = _round(gate_in * x, tail_mantissa_bits)
    w = _f32(mp["conv_kernel"])  # [taps, dim]: w[i] meets z_{t - (taps - 1) + i}
    taps = w.shape[0]
    padded = jnp.pad(z, ((taps - 1, 0), (0, 0)))
    c = sum(padded[i : i + t] * w[i] for i in range(taps))
    return _linear(gate_out * c, mp["out_proj"]), z


def _rope(x, theta):
    """x: [T, heads, d]; the half-split layout: pairs (i, i + d / 2)."""
    t, _, d = x.shape
    freqs = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angles = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs  # [T, d / 2]
    cos, sin = jnp.cos(angles)[:, None], jnp.sin(angles)[:, None]
    x1, x2 = x[..., : d // 2], x[..., d // 2 :]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


def attention(u, lp, *, n_heads, n_kv_heads, head_dim, rope_theta, rms_eps):
    t = u.shape[0]
    group = n_heads // n_kv_heads
    q = _rmsnorm(_linear(u, lp["q"]).reshape(t, n_heads, head_dim), _f32(lp["q_norm"]["scale"]), rms_eps)
    k = _rmsnorm(_linear(u, lp["k"]).reshape(t, n_kv_heads, head_dim), _f32(lp["k_norm"]["scale"]), rms_eps)
    q, k = _rope(q, rope_theta), jnp.repeat(_rope(k, rope_theta), group, axis=1)
    v = jnp.repeat(_linear(u, lp["v"]).reshape(t, n_kv_heads, head_dim), group, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k) * head_dim**-0.5
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool))[None], s, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v).reshape(t, n_heads * head_dim)
    return _linear(o, lp["o"])


def _swiglu(n, gate, up, down):
    return (jax.nn.silu(n @ _f32(gate)) * (n @ _f32(up))) @ _f32(down)


def route(n, mp, *, moe, router_mantissa_bits=23, follow=None):
    """(weights [T, k], experts [T, k], margin [T], the router's OWN choice [T,
    k]). ``margin`` is how far, as a share of the (biased) score, a token's
    choice is from changing: the gap between the last expert taken and the first
    left out. Every expert is held, so every near-tie counts: a program that
    computes in fewer bits takes another expert wherever this is narrower than
    its rounding. ``follow`` [T, k] int32: the experts a token takes INSTEAD of
    the router's own choice (a row of -1: its own), weighed by this router's
    scores of them. That is the only way the layers above a near-tie can be held
    to anything: the comparison follows the program's choice and asks whether
    everything else is right (and, apart, whether the choice was the router's
    own wherever the margin is wide)."""
    k = moe["top_k"]
    s = _round(jax.nn.sigmoid(_round(_linear(n, mp["router"]), router_mantissa_bits)), router_mantissa_bits)
    c, idx = jax.lax.top_k(s + _f32(mp["router_bias"]), k + 1)  # ties to the lower index
    margin = (c[:, k - 1] - c[:, k]) / jnp.abs(c[:, k - 1])
    own = idx = idx[:, :k]
    if follow is not None:
        idx = jnp.where(follow[:, :1] >= 0, follow, own)
    w = jnp.take_along_axis(s, idx, axis=-1)  # the bias chooses; it is no part of the weight
    if moe["norm_topk_prob"]:
        w = w / (w.sum(axis=-1, keepdims=True) + moe["norm_topk_eps"])
    return w * moe["routed_scaling_factor"], idx, margin, own


def experts(n, mp, *, moe, router_mantissa_bits=23, follow=None):
    """The routed sum [T, dim], the routing margin [T] and the router's own
    choice [T, k]. A loop over ALL the experts, every token through each,
    weighted by what the router gave it (zero where it was not chosen): the
    definition, at ``n_experts / top_k`` times the needed work."""
    w, idx, margin, own = route(n, mp, moe=moe, router_mantissa_bits=router_mantissa_bits, follow=follow)
    width = mp["down"].shape[1]

    def one(acc, inp):
        e, gate_up, down = inp
        weight = jnp.sum(jnp.where(idx == e, w, 0.0), axis=-1, keepdims=True)  # [T, 1]
        return acc + weight * _swiglu(n, gate_up[:, :width], gate_up[:, width:], down), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(n), (jnp.arange(moe["n_experts"]), mp["gate_up"], mp["down"]))
    return y, margin, own


def layer(h, lp, follow=None, *, kind, dense_ffn, rms_eps, attn, moe, router_only=False,
          activation_mantissa_bits=23, router_mantissa_bits=23, tail_mantissa_bits=23):
    """One decoder layer on the whole prompt: ([T, dim], routing margin [T] (1
    in a dense layer), ``z`` [T, dim] of a conv layer (zeros ``[0, dim]`` of an
    attention layer), the router's own choice [T, k] (``[T, 0]`` in a dense
    layer)); ``follow``: :func:`route`'s; with ``router_only`` what enters the
    layer's router and what leaves it: (n [T, dim], weights [T, k], experts [T,
    k], margin [T]).
    ``activation_mantissa_bits`` under 23 rounds what a serving engine keeps in
    its activation type (the normed inputs of both halves and both branches'
    outputs): 7 is bfloat16, as the engine computes; 3 an 8-bit float."""
    act = functools.partial(_round, mantissa_bits=activation_mantissa_bits)
    with jax.default_matmul_precision("highest"):
        n = act(_rmsnorm(h, _f32(lp["ln1"]["scale"]), rms_eps))
        if kind == "conv":
            m, z = short_conv(n, lp["mixer"], tail_mantissa_bits=tail_mantissa_bits)
        else:
            m, z = attention(n, lp, rms_eps=rms_eps, **attn), jnp.zeros((0, h.shape[1]), jnp.float32)
        h = h + act(m)
        n = act(_rmsnorm(h, _f32(lp["ln2"]["scale"]), rms_eps))
        if router_only:
            return (n, *route(n, lp["moe"], moe=moe, router_mantissa_bits=router_mantissa_bits)[:3])
        if dense_ffn:
            y, margin = _swiglu(n, lp["gate"]["kernel"], lp["up"]["kernel"], lp["down"]["kernel"]), jnp.ones(h.shape[0])
            own = jnp.zeros((h.shape[0], 0), jnp.int32)
        else:
            y, margin, own = experts(n, lp["moe"], moe=moe, router_mantissa_bits=router_mantissa_bits, follow=follow)
        return h + act(y), margin, z, own


def embed(table, ids):
    """h_0 = E[ids]: [T, dim] float32."""
    return _f32(table)[ids]


def head(h, scale, table, *, rms_eps):
    """Logits of the given positions, [..., vocab], from the TIED head."""
    with jax.default_matmul_precision("highest"):
        return _rmsnorm(h, _f32(scale), rms_eps) @ _f32(table).T


def forward(params, ids, *, layer_types, rms_eps, attn, moe, place=lambda tree: tree, upto=None, z=None,
            margins=None, choices=None, follow=None, **low):
    """(hidden states [T, dim] after layer ``upto`` - 1 (None: the last), the
    routing margin [T]: the least over those layers). IN BLOCKS: one jitted
    program per kind of layer, ``place`` applied to each layer's parameters just
    before use, so a layer is upcast to float32 when its turn comes and the
    reference fits beside the engine at the published widths. ``z``: a list that
    is given each conv layer's ``z`` [T, dim], in the layers' order; ``margins``:
    one that is given EVERY layer's routing margin [T] (1 in a dense layer);
    ``choices``: one that is given every SPARSE layer's own choice [T, k];
    ``follow`` [sparse layers, T, k] int32: the experts each sparse layer takes
    instead (:func:`route`; -1: its own)."""
    p = params["params"]
    kinds = {(kind, i < moe["first_dense"]) for i, kind in enumerate(layer_types)}
    run = {
        (kind, dense): jax.jit(functools.partial(
            layer, kind=kind, dense_ffn=dense, rms_eps=rms_eps, attn=attn, moe=moe, **low
        ))
        for kind, dense in kinds
    }
    h = jax.jit(embed)(place(p["embed"]["embedding"]), ids)
    margin = jnp.ones((ids.shape[0],), jnp.float32)
    none = jnp.full((ids.shape[0], moe["top_k"]), -1, jnp.int32)
    for i, kind in enumerate(layer_types[:upto]):
        dense = i < moe["first_dense"]
        taken = none if dense or follow is None else follow[i - moe["first_dense"]]
        h, m, z_i, own = run[(kind, dense)](h, place(p[f"layer_{i}"]), taken)
        margin = jnp.minimum(margin, m)
        if margins is not None:
            margins.append(m)
        if choices is not None and not dense:
            choices.append(own)
        if z is not None and kind == "conv":
            z.append(z_i)
    return h, margin


def logits_of(params, h, *, rms_eps, place=lambda tree: tree, **_):
    """The tied head on hidden states ``h`` [N, dim] (rows of :func:`forward`'s)."""
    p = params["params"]
    return jax.jit(functools.partial(head, rms_eps=rms_eps))(h, place(p["ln_f"]["scale"]), place(p["embed"]["embedding"]))


def logits_at(params, ids, positions, **sizes):
    """(logits [len(positions), vocab], routing margins [len(positions)]) of the
    prompt ``ids`` [T] at ``positions``: the full forward pass, no cache."""
    h, margin = forward(params, ids, **sizes)
    at = jnp.asarray(positions)
    return logits_of(params, h[at], **sizes), margin[at]


def last_logits(params, ids, **sizes):
    """(logits [vocab] at the last position of ``ids`` [T], its routing margin)."""
    logits, margin = logits_at(params, ids, [ids.shape[0] - 1], **sizes)
    return logits[0], margin[0]


def tails_after(z, n: int):
    """What a cache holds of every conv layer after the first ``n`` tokens, as a
    row of the engine's store lays it out: ``[Lc, 2 * dim]``, ``z_{n-2} |
    z_{n-1}`` (zeros before the prompt's first token). ``z``: :func:`forward`'s."""
    stacked = jnp.pad(jnp.stack(z), ((0, 0), (2, 0), (0, 0)))  # [Lc, 2 + T, dim]
    return stacked[:, n : n + 2].reshape(len(z), -1)


def first_router(params, ids, *, layer_types, rms_eps, attn, moe, place=lambda tree: tree, **low):
    """(n [T, dim], weights [T, k], experts [T, k], margin [T]) of the FIRST
    SPARSE layer's router over the prompt (the leading dense layers run whole
    before it): the normed hidden states that enter it, in float32, and what it
    makes of them. A program's router handed the same ``n`` must answer alike to
    the last bits of float32: no rounding stands between the two."""
    first = moe["first_dense"]
    h, _ = forward(params, ids, layer_types=layer_types, rms_eps=rms_eps, attn=attn, moe=moe, place=place, upto=first)
    return jax.jit(functools.partial(
        layer, kind=layer_types[first], dense_ffn=False, rms_eps=rms_eps, attn=attn, moe=moe, router_only=True, **low,
    ))(h, place(params["params"][f"layer_{first}"]))


def model_kwargs(cfg) -> dict:
    """The reference's sizes from the program's ``VLMConfig``."""
    m = cfg.moe
    return dict(
        layer_types=tuple(cfg.layer_types),
        rms_eps=cfg.rms_eps,
        attn=dict(n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads, head_dim=cfg.head_dim, rope_theta=cfg.rope_theta),
        moe=dict(
            n_experts=m.n_experts, top_k=m.top_k, first_dense=m.first_dense, norm_topk_prob=m.norm_topk_prob,
            norm_topk_eps=m.norm_topk_eps, routed_scaling_factor=m.routed_scaling_factor,
        ),
    )
