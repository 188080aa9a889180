"""Plain reference of the Olmo-Hybrid decoder (HF ``olmo_hybrid``, as
allenai/Olmo-Hybrid-7B publishes it): float32 throughout, ``jax.numpy`` only,
matmuls at ``highest`` precision, the recurrence one token at a time
(``lax.scan``), no chunks, no cache, no kernels, no batching.

For a layer's input ``x`` ``[T, dim]``, both kinds of layer (eps 1e-6):

    x = x + rmsnorm(mixer(x)) ;  x = x + rmsnorm((silu(x Wgate) * (x Wup)) Wdown)
    logits = rmsnorm(x_last) W_head                (a final norm, an UNTIED head)

linear_attention (``H`` heads, ``dk``, ``dv``; 30, 96, 192 as published):

    q = silu(conv(x Wq))   k = silu(conv(x Wk))   v = silu(conv(x Wv))
            three causal depthwise convolutions of d_conv taps, no bias, zeros before the prompt
    q = q / sqrt(sum(q^2) + 1e-6) * dk^-0.5     k = k / sqrt(sum(k^2) + 1e-6)        (a head each)
    beta = 2 sigmoid(x Wb)             g = -exp(A_log) * softplus(x Wa + dt_bias)      a = exp(g)
    S_t = a_t S_{t-1} + k_t (x) (beta_t (v_t - (a_t S_{t-1})^T k_t))       S: [dk, dv] a head, from zeros
    o_t = S_t^T q_t
    m = (rmsnorm_dv(o) * w * silu(x Wg)) Wo                              (the norm BEFORE the gate)

full_attention: ``q = rmsnorm(x Wq)``, ``k = rmsnorm(x Wk)`` (each over the
WHOLE projection, all heads at once), ``v = x Wv``; no position embedding;
``o = softmax(q k^T * head_dim^-0.5 + causal) v``; ``m = o Wo``; no bias.

ASSUMED, where the ``config.json`` is silent (the configuration's file says
why, point by point): ``head_dim`` = hidden / heads; the linear layer is
flash-linear-attention's ``GatedDeltaNet`` as the ``linear_*`` keys configure
it (separate q / k / v convolutions, l2-normed q and k, the norm before the
gate; its recurrence is the one ``transformers``' ``qwen3_next`` module holds
as ``torch_recurrent_gated_delta_rule``, which tests/ops/test_delta_rule.py
compares with); the block's norm placement and the whole-width q / k norms
are the Olmo 2 / 3 family's; no rope on the full layers
(``rope_parameters.rope_theta`` null).

The parameter tree is the program's own (``params["params"]["layer_<i>"]``…);
only its names are shared with the program, none of its code.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def _rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _linear(x, p):
    return x @ p["kernel"].astype(jnp.float32)


def _round(x, mantissa_bits):
    """``reduce_precision`` because XLA elides a convert pair; 23 = float32."""
    if mantissa_bits >= 23:
        return x
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=mantissa_bits)


def attention(x, lp, *, n_heads, n_kv_heads, head_dim, rms_eps):
    t = x.shape[0]
    group = n_heads // n_kv_heads
    q = _rmsnorm(_linear(x, lp["q"]), lp["q_norm"]["scale"], rms_eps).reshape(t, n_heads, head_dim)
    k = _rmsnorm(_linear(x, lp["k"]), lp["k_norm"]["scale"], rms_eps).reshape(t, n_kv_heads, head_dim)
    k = jnp.repeat(k, group, axis=1)
    v = jnp.repeat(_linear(x, lp["v"]).reshape(t, n_kv_heads, head_dim), group, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k) * head_dim**-0.5
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool))[None], s, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)
    return _linear(o.reshape(t, n_heads * head_dim), lp["o"])


def _conv(x, kernel):
    """out[t] = sum_i w[i] in[t - (taps - 1) + i], zeros before the prompt."""
    taps, t = kernel.shape[0], x.shape[0]
    padded = jnp.pad(x, ((taps - 1, 0), (0, 0)))
    return sum(padded[i : i + t] * kernel[i] for i in range(taps))


def gated_delta(x, mp, *, heads, key_dim, value_dim, neg_eigval, rms_eps, state_mantissa_bits=23,
                return_state=False):
    """``state_mantissa_bits``: what ``S`` is rounded to after every token
    (23: float32, never rounded). Only the lower-precision reading of the
    benchmark's ``check`` passes 7 (bfloat16), to show what a bfloat16 state
    would cost."""
    t = x.shape[0]
    q = jax.nn.silu(_conv(_linear(x, mp["q_proj"]), mp["q_conv"])).reshape(t, heads, key_dim)
    k = jax.nn.silu(_conv(_linear(x, mp["k_proj"]), mp["k_conv"])).reshape(t, heads, key_dim)
    v = jax.nn.silu(_conv(_linear(x, mp["v_proj"]), mp["v_conv"])).reshape(t, heads, value_dim)
    q = q * jax.lax.rsqrt(jnp.sum(q * q, axis=-1, keepdims=True) + 1e-6) * key_dim**-0.5
    k = k * jax.lax.rsqrt(jnp.sum(k * k, axis=-1, keepdims=True) + 1e-6)
    beta = jax.nn.sigmoid(_linear(x, mp["b_proj"])) * (2.0 if neg_eigval else 1.0)  # [T, H]
    g = -jnp.exp(mp["A_log"]) * jax.nn.softplus(_linear(x, mp["a_proj"]) + mp["dt_bias"])

    def token(s, inp):
        qt, kt, vt, gt, bt = inp
        s = s * jnp.exp(gt)[:, None, None]
        u = jnp.einsum("hkv,hk->hv", s, kt)
        s = _round(s + kt[:, :, None] * (bt[:, None] * (vt - u))[:, None, :], state_mantissa_bits)
        return s, jnp.einsum("hkv,hk->hv", s, qt)

    s0 = jnp.zeros((heads, key_dim, value_dim), jnp.float32)
    s_last, o = jax.lax.scan(token, s0, (q, k, v, g, beta))
    if return_state:
        return s_last
    gate = _linear(x, mp["g_proj"]).reshape(t, heads, value_dim)
    o = _rmsnorm(o, mp["o_norm_scale"], rms_eps) * jax.nn.silu(gate)
    return _linear(o.reshape(t, heads * value_dim), mp["o_proj"])


def layer(h, lp, *, kind, rms_eps, attn, delta, state_only=False, activation_mantissa_bits=23):
    """One decoder layer on the whole prompt, [T, dim] -> [T, dim]; with
    ``state_only`` a linear-attention layer's ``S`` after the last token
    instead. ``activation_mantissa_bits`` under 23 rounds what a serving
    engine keeps in its activation type (the layer's input as both branches
    read it, both branches' outputs before and after their norm, the FFN's
    hidden product) to that many bits: 7 is bfloat16, as the engine computes;
    3 an 8-bit float. Only the lower-precision readings of the benchmark's
    ``check`` pass it."""
    act = functools.partial(_round, mantissa_bits=activation_mantissa_bits)
    with jax.default_matmul_precision("highest"):
        lp = jax.tree.map(lambda w: w.astype(jnp.float32), lp)
        if kind == "linear_attention":
            m = gated_delta(act(h), lp["mixer"], rms_eps=rms_eps, return_state=state_only, **delta)
            if state_only:
                return m
        else:
            m = attention(act(h), lp, rms_eps=rms_eps, **attn)
        h = h + act(_rmsnorm(act(m), lp["post_attn_norm"]["scale"], rms_eps))
        n = act(h)
        hidden = act(jax.nn.silu(_linear(n, lp["gate"])) * _linear(n, lp["up"]))
        return h + act(_rmsnorm(act(_linear(hidden, lp["down"])), lp["post_mlp_norm"]["scale"], rms_eps))


def embed(table, ids):
    """h_0 = E[ids]: [T, dim] float32."""
    return table[ids].astype(jnp.float32)


def head(h, scale, kernel, *, rms_eps):
    """Logits of the given positions, [..., vocab], from the untied head."""
    with jax.default_matmul_precision("highest"):
        return _rmsnorm(h, scale.astype(jnp.float32), rms_eps) @ kernel.astype(jnp.float32)


def hidden_states(params, ids, *, layer_types, rms_eps, attn, delta, place=lambda tree: tree,
                  activation_mantissa_bits=23, state_mantissa_bits=23):
    """[T, dim] after the last layer. One jitted program per kind of layer,
    reused for every layer of the kind, ``place`` applied to each layer's
    parameters just before use: a layer is upcast to float32 when its turn
    comes, so that the reference fits beside the engine."""
    p = params["params"]
    delta = dict(delta, state_mantissa_bits=state_mantissa_bits)
    run = {
        kind: jax.jit(functools.partial(
            layer, kind=kind, rms_eps=rms_eps, attn=attn, delta=delta,
            activation_mantissa_bits=activation_mantissa_bits,
        ))
        for kind in set(layer_types)
    }
    h = jax.jit(embed)(place(p["embed"]["embedding"]), ids)
    for i, kind in enumerate(layer_types):
        h = run[kind](h, place(p[f"layer_{i}"]))
    return h


def logits_at(params, ids, positions, *, rms_eps, place=lambda tree: tree, **sizes):
    """Logits [len(positions), vocab] of the prompt ``ids`` [T] at
    ``positions``: the full forward pass, no cache."""
    p = params["params"]
    h = hidden_states(params, ids, rms_eps=rms_eps, place=place, **sizes)
    return jax.jit(functools.partial(head, rms_eps=rms_eps))(
        h[jnp.asarray(positions)], place(p["ln_f"]["scale"]), place(p["lm_head"]["kernel"])
    )


def first_ssm_state(params, ids, *, layer_types, rms_eps, attn, delta, place=lambda tree: tree,
                    state_mantissa_bits=23, **_):
    """``S`` [heads, dk, dv] of the FIRST linear-attention layer after the
    whole prompt: what the engine's recurrent store must hold for the
    request (there side by side, ``[dk, heads * dv]``), and where a state
    kept in fewer bits shows first (nothing upstream of it but the embedding)."""
    if layer_types[0] != "linear_attention":
        raise ValueError("the first layer is not a linear-attention layer")
    p = params["params"]
    return jax.jit(functools.partial(
        layer, kind="linear_attention", rms_eps=rms_eps, attn=attn,
        delta=dict(delta, state_mantissa_bits=state_mantissa_bits), state_only=True,
    ))(embed(place(p["embed"]["embedding"]), ids), place(p["layer_0"]))


def last_logits(params, ids, **sizes):
    """Logits [vocab] at the last position of the prompt ``ids`` [T]."""
    return logits_at(params, ids, [ids.shape[0] - 1], **sizes)[0]


def model_kwargs(cfg) -> dict:
    """The reference's sizes from the program's ``VLMConfig``."""
    m = cfg.gated_delta
    return dict(
        layer_types=tuple(cfg.layer_types),
        rms_eps=cfg.rms_eps,
        attn=dict(n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads, head_dim=cfg.head_dim),
        delta=dict(heads=m.n_heads, key_dim=m.key_dim, value_dim=m.value_dim, neg_eigval=m.allow_neg_eigval),
    )
