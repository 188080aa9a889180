"""Plain reference of the Solar-Open2 decoder (HF ``solar_open2``, as
upstage/Solar-Open2-250B publishes it) as ONE CHIP OF AN EXPERT-PARALLEL
DEPLOYMENT computes it: float32 throughout, ``jax.numpy`` only, matmuls at
``highest`` precision, the recurrence one token at a time (``lax.scan``), dense
softmax attention, the experts by a plain loop over the HELD experts (what the
absent experts would add is left out here as in the program: a layer returns
this chip's partial sum), no chunks, no cache, no kernels, no batching.

For a layer's input ``x`` ``[T, dim]``, both kinds of layer (eps 1e-5):

    x = x + mixer(rmsnorm(x)) ;  x = x + experts(rmsnorm(x))            (pre-norm)
    logits = rmsnorm(x_last) W_head                (a final norm, an UNTIED head)

linear_attention = Kimi Delta Attention (Kimi Linear, arXiv:2510.26692;
flash-linear-attention's ``KimiDeltaAttention``), ``H`` heads, ``dk = dv``
(64, 128 as published):

    q = silu(conv4(x Wq))   k = silu(conv4(x Wk))   v = silu(conv4(x Wv))
            three causal depthwise convolutions, no bias, zeros before the prompt
    q = q / sqrt(sum(q^2) + 1e-6) * dk^-0.5     k = k / sqrt(sum(k^2) + 1e-6)        (a head each)
    g = -exp(A_log[h]) * softplus((x Wf1) Wf2 + dt_bias)      in [H, dk]: a decay a CHANNEL
    beta = 2 sigmoid(x Wb)                                      (kda_allow_neg_eigval)
    S' = Diag(exp(g_t)) S_{t-1}       u = S'^T k_t       S_t = S' + k_t (x) beta_t (v_t - u)
    o_t = S_t^T q_t                                             S: [dk, dv] a head, from zeros
    m = (rmsnorm_dv(o) * w * sigmoid((x Wg1) Wg2)) Wo          (the norm BEFORE the gate)

full_attention: ``q = x Wq`` (64 heads x 128), ``k = x Wk``, ``v = x Wv`` (8 KV
heads), NO position embedding (``use_rope: false``: the linear layers carry the
order), ``o = softmax(q k^T / sqrt(128) + causal) v``, then ``m = (o *
sigmoid(x Wgate)) Wo`` (``use_gqa_gate``); no bias, no q / k norm.

experts: ``s = sigmoid(n W_r)`` over ALL 320 experts in float32; the top 8 of
``s + bias`` (a stored selection bias that chooses and does not weigh); the
weights are the chosen ``s``, renormalised (``+ 1e-20``), x 1; the layer adds the
HELD experts' SwiGLUs of 1280 so weighted, and one shared SwiGLU of 1280 that
every token visits.

ASSUMED, where the ``config.json`` is silent (the configuration's file says why,
point by point): the linear layer is flash-linear-attention's
``KimiDeltaAttention`` with ``kda_use_full_proj: false`` read as low-rank decay
and gate pairs of rank ``head_dim``; a sigmoid gate; the attention gate as wide
as ``q`` and no q / k norms; the router's score function and bias are the
family's (``glm4_moe``); pre-norm blocks, a final norm, an untied head.

The parameter tree is the program's own (``params["params"]["layer_<i>"]``…);
only its names are shared with the program, none of its code.
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


def attention(x, lp, *, n_heads, n_kv_heads, head_dim):
    t = x.shape[0]
    group = n_heads // n_kv_heads
    q = _linear(x, lp["q"]).reshape(t, n_heads, head_dim)
    k = jnp.repeat(_linear(x, lp["k"]).reshape(t, n_kv_heads, head_dim), group, axis=1)
    v = jnp.repeat(_linear(x, lp["v"]).reshape(t, n_kv_heads, head_dim), group, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k) * head_dim**-0.5
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool))[None], s, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v).reshape(t, n_heads * head_dim)
    return _linear(o * jax.nn.sigmoid(_linear(x, lp["g"])), lp["o"])


def _conv(x, kernel):
    """out[t] = sum_i w[i] in[t - (taps - 1) + i], zeros before the prompt."""
    taps, t = kernel.shape[0], x.shape[0]
    padded = jnp.pad(x, ((taps - 1, 0), (0, 0)))
    return sum(padded[i : i + t] * _f32(kernel[i]) for i in range(taps))


def delta_steps(s0, q, k, v, g, beta, *, state_mantissa_bits=23):
    """The recurrence itself, a token at a time from ``s0`` [H, dk, dv]: q, k, g
    [T, H, dk], v [T, H, dv], beta [T, H] -> (S after the last token, o [T, H,
    dv]). ``state_mantissa_bits``: what ``S`` is rounded to after every token
    (23: float32, never rounded; the lower-precision reading passes 7, bfloat16)."""

    def token(s, inp):
        qt, kt, vt, gt, bt = inp
        s = s * jnp.exp(gt)[:, :, None]
        u = jnp.einsum("hkv,hk->hv", s, kt)
        s = _round(s + kt[:, :, None] * (bt[:, None] * (vt - u))[:, None, :], state_mantissa_bits)
        return s, jnp.einsum("hkv,hk->hv", s, qt)

    with jax.default_matmul_precision("highest"):
        return jax.lax.scan(token, s0, (q, k, v, g, beta))


def kda(x, mp, *, heads, key_dim, value_dim, neg_eigval, rms_eps, state_mantissa_bits=23, return_state=False):
    """``state_mantissa_bits``: :func:`delta_steps`'s."""
    t = x.shape[0]
    q = jax.nn.silu(_conv(_linear(x, mp["q_proj"]), mp["q_conv"])).reshape(t, heads, key_dim)
    k = jax.nn.silu(_conv(_linear(x, mp["k_proj"]), mp["k_conv"])).reshape(t, heads, key_dim)
    v = jax.nn.silu(_conv(_linear(x, mp["v_proj"]), mp["v_conv"])).reshape(t, heads, value_dim)
    q = q * jax.lax.rsqrt(jnp.sum(q * q, axis=-1, keepdims=True) + 1e-6) * key_dim**-0.5
    k = k * jax.lax.rsqrt(jnp.sum(k * k, axis=-1, keepdims=True) + 1e-6)
    beta = jax.nn.sigmoid(_linear(x, mp["b_proj"])) * (2.0 if neg_eigval else 1.0)  # [T, H]
    f = _linear(_linear(x, mp["f_a_proj"]), mp["f_b_proj"]) + _f32(mp["dt_bias"])
    g = -jnp.exp(_f32(mp["A_log"]))[:, None] * jax.nn.softplus(f).reshape(t, heads, key_dim)  # [T, H, dk]

    s_last, o = delta_steps(
        jnp.zeros((heads, key_dim, value_dim), jnp.float32), q, k, v, g, beta, state_mantissa_bits=state_mantissa_bits
    )
    if return_state:
        return s_last
    gate = _linear(_linear(x, mp["g_a_proj"]), mp["g_b_proj"]).reshape(t, heads, value_dim)
    o = _rmsnorm(o, _f32(mp["o_norm_scale"]), rms_eps) * jax.nn.sigmoid(gate)
    return _linear(o.reshape(t, heads * value_dim), mp["o_proj"])


def route(n, mp, *, moe, router_mantissa_bits=23):
    """(weights [T, k], experts [T, k], margin [T]). ``margin`` is how far, as
    a share of the (biased) score, a token's choice of HELD experts is from
    changing: the gap between the last expert taken and the first left out
    where either is held (1 where neither is). A comparison with a program
    that computes in fewer bits means something only where this is wide."""
    e, k = moe["n_experts"], moe["top_k"]
    first, count = moe["held"]
    s = _round(jax.nn.sigmoid(_round(_linear(n, mp["router"]), router_mantissa_bits)), router_mantissa_bits)
    chosen_by = s + _f32(mp["router_bias"])
    c, idx = jax.lax.top_k(chosen_by, k + 1)  # ties to the lower index
    is_held = (jnp.arange(e) >= first) & (jnp.arange(e) < first + count)
    touches = is_held[idx[:, k - 1]] | is_held[idx[:, k]]
    margin = jnp.where(touches, (c[:, k - 1] - c[:, k]) / jnp.abs(c[:, k - 1]), 1.0)
    idx = idx[:, :k]
    w = jnp.take_along_axis(s, idx, axis=-1)  # the bias chooses; it is no part of the weight
    if moe["norm_topk_prob"]:
        w = w / (w.sum(axis=-1, keepdims=True) + 1e-20)
    return w * moe["routed_scaling_factor"], idx, margin


def _swiglu(n, gate, up, down):
    return (jax.nn.silu(n @ _f32(gate)) * (n @ _f32(up))) @ _f32(down)


def experts(n, mp, *, moe, with_shared=True, router_mantissa_bits=23):
    """The held experts' part of the routed sum, plus the shared expert: [T, D],
    the routing margin [T], and how many of each token's top-k experts are held
    here [T]. A loop over the held experts, every token through each, weighted
    by what the router gave it (zero where it was not chosen): the definition,
    at ``count`` times the needed work."""
    first, count = moe["held"]
    w, idx, margin = route(n, mp, moe=moe, router_mantissa_bits=router_mantissa_bits)
    width = mp["down"].shape[1]

    def one(acc, inp):
        e, gate_up, down = inp
        weight = jnp.sum(jnp.where(idx == first + e, w, 0.0), axis=-1, keepdims=True)  # [T, 1]
        return acc + weight * _swiglu(n, gate_up[:, :width], gate_up[:, width:], down), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(n), (jnp.arange(count), mp["gate_up"], mp["down"]))
    if with_shared:
        y = y + _swiglu(n, mp["shared_gate"]["kernel"], mp["shared_up"]["kernel"], mp["shared_down"]["kernel"])
    return y, margin, jnp.sum((idx >= first) & (idx < first + count), axis=-1)


def layer(h, lp, *, kind, rms_eps, attn, delta, moe, state_only=False, router_only=False,
          activation_mantissa_bits=23, state_mantissa_bits=23, router_mantissa_bits=23):
    """One decoder layer on the whole prompt: ([T, dim], routing margin [T],
    held assignments [T]); with ``state_only`` a linear-attention layer's ``S``
    after the last token instead; with ``router_only`` what enters the layer's
    router and what leaves it: (n [T, dim], weights [T, k], experts [T, k],
    margin [T]). ``activation_mantissa_bits`` under 23 rounds
    what a serving engine keeps in its activation type (the normed inputs of
    both halves and both branches' outputs): 7 is bfloat16, as the engine
    computes; 3 an 8-bit float. Only the benchmark's lower-precision readings
    pass these three."""
    act = functools.partial(_round, mantissa_bits=activation_mantissa_bits)
    with jax.default_matmul_precision("highest"):
        n = act(_rmsnorm(h, _f32(lp["ln1"]["scale"]), rms_eps))
        if kind == "linear_attention":
            m = kda(n, lp["mixer"], rms_eps=rms_eps, return_state=state_only,
                    state_mantissa_bits=state_mantissa_bits, **delta)
            if state_only:
                return m
        else:
            m = attention(n, lp, **attn)
        h = h + act(m)
        n = act(_rmsnorm(h, _f32(lp["ln2"]["scale"]), rms_eps))
        if router_only:
            return (n, *route(n, lp["moe"], moe=moe, router_mantissa_bits=router_mantissa_bits))
        y, margin, held = experts(n, lp["moe"], moe=moe, router_mantissa_bits=router_mantissa_bits)
        return h + act(y), margin, held


def embed(table, ids):
    """h_0 = E[ids]: [T, dim] float32."""
    return _f32(table)[ids]


def head(h, scale, kernel, *, rms_eps):
    """Logits of the given positions, [..., vocab], from the untied head."""
    with jax.default_matmul_precision("highest"):
        return _rmsnorm(h, _f32(scale), rms_eps) @ _f32(kernel)


def forward(params, ids, *, layer_types, rms_eps, attn, delta, moe, place=lambda tree: tree, upto=None,
            held=None, **low):
    """(hidden states [T, dim] after layer ``upto`` - 1 (None: the last), the
    routing margin [T]: the least over those layers). One jitted program per
    kind of layer, ``place`` applied to each layer's parameters just before
    use: a layer is upcast to float32 when its turn comes, so that the
    reference fits beside the engine. ``held``: a list that is given each
    layer's count of held assignments a position ([T])."""
    p = params["params"]
    run = {
        kind: jax.jit(functools.partial(layer, kind=kind, rms_eps=rms_eps, attn=attn, delta=delta, moe=moe, **low))
        for kind in set(layer_types)
    }
    h = jax.jit(embed)(place(p["embed"]["embedding"]), ids)
    margin = jnp.ones((ids.shape[0],), jnp.float32)
    for i, kind in enumerate(layer_types[:upto]):
        h, m, n_held = run[kind](h, place(p[f"layer_{i}"]))
        margin = jnp.minimum(margin, m)
        if held is not None:
            held.append(n_held)
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


def first_ssm_state(params, ids, *, layer_types, rms_eps, attn, delta, moe, place=lambda tree: tree, **low):
    """``S`` [heads, dk, dv] of the FIRST linear-attention layer after the whole
    prompt: what the engine's recurrent store must hold for the request (there
    side by side, ``[dk, heads * dv]``). The layers before it (the period opens
    with the attention layer) run whole, experts and all."""
    first = layer_types.index("linear_attention")
    h, _ = forward(
        params, ids, layer_types=layer_types, rms_eps=rms_eps, attn=attn, delta=delta, moe=moe,
        place=place, upto=first, **low,
    )
    return jax.jit(functools.partial(
        layer, kind="linear_attention", rms_eps=rms_eps, attn=attn, delta=delta, moe=moe, state_only=True, **low,
    ))(h, place(params["params"][f"layer_{first}"]))


def first_router(params, ids, *, layer_types, rms_eps, attn, delta, moe, place=lambda tree: tree, **low):
    """(n [T, dim], weights [T, k], experts [T, k], margin [T]) of the FIRST
    layer's router over the prompt: the normed hidden states that enter it, in
    float32, and what it makes of them. A program's router handed the same ``n``
    must answer alike to the last bits of float32: no earlier rounding stands
    between the two."""
    return jax.jit(functools.partial(
        layer, kind=layer_types[0], rms_eps=rms_eps, attn=attn, delta=delta, moe=moe, router_only=True, **low,
    ))(jax.jit(embed)(place(params["params"]["embed"]["embedding"]), ids), place(params["params"]["layer_0"]))


def model_kwargs(cfg) -> dict:
    """The reference's sizes from the program's ``VLMConfig``."""
    d, m = cfg.gated_delta, cfg.moe
    return dict(
        layer_types=tuple(cfg.layer_types),
        rms_eps=cfg.rms_eps,
        attn=dict(n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads, head_dim=cfg.head_dim),
        delta=dict(heads=d.n_heads, key_dim=d.key_dim, value_dim=d.value_dim, neg_eigval=d.allow_neg_eigval),
        moe=dict(
            n_experts=m.n_experts, top_k=m.top_k, held=tuple(m.held_experts),
            norm_topk_prob=m.norm_topk_prob, routed_scaling_factor=m.routed_scaling_factor,
        ),
    )
