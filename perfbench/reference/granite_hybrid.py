"""Plain reference of the Granite-4.0-H hybrid decoder (HF ``granitemoehybrid``
with ``num_local_experts`` 0, as ibm-granite/granite-4.0-h-micro publishes it),
after ``modeling_granitemoehybrid.py`` of transformers: float32 throughout,
``jax.numpy`` only, matmuls at ``highest`` precision, the recurrence one token
at a time, no chunks, no cache, no kernels, no batching.

    h_0 = E[ids] * embedding_multiplier
    for every layer i, by layer_types[i]:
        a = rmsnorm(h)
        attention:  q, k, v = a Wq, a Wk, a Wv             (no bias, NO rope)
                    o = softmax(q k^T * attention_multiplier + causal) v
                    m = o Wo                (KV head j serves query heads j*G..)
        mamba:      [z | xBC | dt] = a W_in
                    xBC = silu(conv(xBC) + b_conv)   causal, depthwise, d_conv taps
                    [x | B | C] = xBC ;  dt = softplus(dt + dt_bias) ;  A = -exp(A_log)
                    H_t = exp(dt_t A) H_{t-1} + dt_t x_t (x) B_t ;  y_t = H_t C_t + D x_t
                    m = (rmsnorm(y * silu(z)) * w) W_out      (gate before norm)
        h = h + residual_multiplier * m
        n = rmsnorm(h) ;  h = h + residual_multiplier * (silu(n Wgate) * (n Wup)) Wdown
    logits = rmsnorm(h_last) E^T / logits_scaling                        (tied)

Departures from the HF module, none of which changes a value: HF's
``torch_forward`` computes the recurrence in its chunked form (a
reassociation of the same sums; this file scans token by token, which is the
definition); HF fuses ``Wgate | Wup`` into one ``input_linear`` (split here by
the name map, models/convert_granite.py); HF clamps ``dt`` to ``(0, inf)``, a
no-op after softplus; the routed experts do not exist at ``num_local_experts``
0 and are left out, as HF leaves them out.

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


def embed(table, ids, *, embedding_multiplier):
    """h_0 = E[ids] * embedding_multiplier: [T, dim] float32."""
    return table.astype(jnp.float32)[ids] * embedding_multiplier


def attention(a, lp, *, n_heads, n_kv_heads, head_dim, attention_multiplier):
    t = a.shape[0]
    group = n_heads // n_kv_heads
    q = _linear(a, lp["q"]).reshape(t, n_heads, head_dim)
    k = jnp.repeat(_linear(a, lp["k"]).reshape(t, n_kv_heads, head_dim), group, axis=1)
    v = jnp.repeat(_linear(a, lp["v"]).reshape(t, n_kv_heads, head_dim), group, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k) * attention_multiplier
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool))[None], s, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)
    return _linear(o.reshape(t, n_heads * head_dim), lp["o"])


def mamba(a, mp, *, mamba_heads, mamba_head_dim, d_state, d_conv, rms_eps, state_mantissa_bits=23,
          return_state=False):
    """``state_mantissa_bits``: what ``H`` is rounded to after every token
    (23: float32, never rounded). Only the lower-precision reading of the
    benchmark's ``check`` passes 7 (bfloat16), to show what a bfloat16 state
    would cost; ``reduce_precision`` because XLA elides a convert pair."""
    t = a.shape[0]
    d_inner = mamba_heads * mamba_head_dim
    zxbcdt = _linear(a, mp["in_proj"])
    z, xbc, dt = jnp.split(zxbcdt, [d_inner, 2 * d_inner + 2 * d_state], axis=-1)
    # out[t] = b + sum_k w[k] in[t - (d_conv - 1) + k], zeros before the prompt
    padded = jnp.pad(xbc, ((d_conv - 1, 0), (0, 0)))
    xbc = mp["conv_bias"] + sum(padded[k : k + t] * mp["conv_kernel"][k] for k in range(d_conv))
    xbc = jax.nn.silu(xbc)
    x, b, c = jnp.split(xbc, [d_inner, d_inner + d_state], axis=-1)
    x = x.reshape(t, mamba_heads, mamba_head_dim)
    dt = jax.nn.softplus(dt + mp["dt_bias"])  # [T, heads]
    a_neg = -jnp.exp(mp["A_log"])

    def token(h, inp):
        xt, dtt, bt, ct = inp
        h = h * jnp.exp(dtt * a_neg)[:, None, None] + (dtt[:, None] * xt)[:, :, None] * bt
        if state_mantissa_bits < 23:
            h = jax.lax.reduce_precision(h, exponent_bits=8, mantissa_bits=state_mantissa_bits)
        return h, jnp.einsum("hpn,n->hp", h, ct) + mp["D"][:, None] * xt

    h0 = jnp.zeros((mamba_heads, mamba_head_dim, d_state), jnp.float32)
    h_last, y = jax.lax.scan(token, h0, (x, dt, b, c))
    if return_state:
        return h_last
    y = y.reshape(t, d_inner) * jax.nn.silu(z)
    return _linear(_rmsnorm(y, mp["norm_scale"], rms_eps), mp["out_proj"])


def layer(h, lp, *, kind, residual_multiplier, rms_eps, attn, ssm, state_only=False,
          activation_mantissa_bits=23):
    """One decoder layer on the whole prompt, [T, dim] -> [T, dim]; with
    ``state_only`` a state-space layer's ``H`` after the last token instead.
    ``activation_mantissa_bits`` under 23 rounds what a serving engine keeps
    in its activation type (the normed inputs of both halves, both branches'
    outputs, the FFN's hidden product) to that many bits: 7 is bfloat16, as
    the engine computes; 3 an 8-bit float. Only the lower-precision readings
    of the benchmark's ``check`` pass it."""

    def act(v):
        if activation_mantissa_bits >= 23:
            return v
        return jax.lax.reduce_precision(v, exponent_bits=8, mantissa_bits=activation_mantissa_bits)

    with jax.default_matmul_precision("highest"):
        lp = jax.tree.map(lambda w: w.astype(jnp.float32), lp)
        a = act(_rmsnorm(h, lp["ln1"]["scale"], rms_eps))
        if kind == "mamba":
            m = mamba(a, lp["mixer"], rms_eps=rms_eps, return_state=state_only, **ssm)
            if state_only:
                return m
        else:
            m = attention(a, lp, **attn)
        h = h + residual_multiplier * act(m)
        n = act(_rmsnorm(h, lp["ln2"]["scale"], rms_eps))
        hidden = act(jax.nn.silu(_linear(n, lp["gate"])) * _linear(n, lp["up"]))
        return h + residual_multiplier * act(_linear(hidden, lp["down"]))


def head(h, scale, table, *, rms_eps, logits_scaling):
    """Logits of the given positions, [..., vocab], from the tied table."""
    with jax.default_matmul_precision("highest"):
        return _rmsnorm(h, scale.astype(jnp.float32), rms_eps) @ table.astype(jnp.float32).T / logits_scaling


def hidden_states(params, ids, *, layer_types, embedding_multiplier, residual_multiplier, rms_eps,
                  attn, ssm, place=lambda tree: tree, activation_mantissa_bits=23, **_):
    """[T, dim] after the last layer. One jitted program per kind of layer,
    reused for every layer of the kind, ``place`` applied to each layer's
    parameters just before use (``qwen2_decoder.last_logits`` has the why)."""
    p = params["params"]
    run = {
        kind: jax.jit(functools.partial(
            layer, kind=kind, residual_multiplier=residual_multiplier, rms_eps=rms_eps,
            attn=attn, ssm=ssm, activation_mantissa_bits=activation_mantissa_bits,
        ))
        for kind in set(layer_types)
    }
    h = jax.jit(functools.partial(embed, embedding_multiplier=embedding_multiplier))(
        place(p["embed"]["embedding"]), ids
    )
    for i, kind in enumerate(layer_types):
        h = run[kind](h, place(p[f"layer_{i}"]))
    return h


def logits_at(params, ids, positions, *, rms_eps, logits_scaling, place=lambda tree: tree, **sizes):
    """Logits [len(positions), vocab] of the prompt ``ids`` [T] at
    ``positions``: the full forward pass, no cache."""
    p = params["params"]
    h = hidden_states(params, ids, rms_eps=rms_eps, place=place, **sizes)
    return jax.jit(functools.partial(head, rms_eps=rms_eps, logits_scaling=logits_scaling))(
        h[jnp.asarray(positions)], place(p["ln_f"]["scale"]), place(p["embed"]["embedding"])
    )


def first_ssm_state(params, ids, *, layer_types, embedding_multiplier, residual_multiplier, rms_eps,
                    attn, ssm, place=lambda tree: tree, **_):
    """``H`` [heads, head_dim, d_state] of the FIRST state-space layer after
    the whole prompt: what the engine's recurrent store must hold for the
    request, and where a state kept in fewer bits shows first (nothing
    upstream of it but the embedding)."""
    if layer_types[0] != "mamba":
        raise ValueError("the first layer is not a state-space layer")
    p = params["params"]
    h = embed(place(p["embed"]["embedding"]), ids, embedding_multiplier=embedding_multiplier)
    return jax.jit(functools.partial(
        layer, kind="mamba", residual_multiplier=residual_multiplier, rms_eps=rms_eps, attn=attn,
        ssm=ssm, state_only=True,
    ))(h, place(p["layer_0"]))


def last_logits(params, ids, **sizes):
    """Logits [vocab] at the last position of the prompt ``ids`` [T]."""
    return logits_at(params, ids, [ids.shape[0] - 1], **sizes)[0]


def model_kwargs(cfg) -> dict:
    """The reference's sizes from the program's ``VLMConfig``."""
    m = cfg.mamba
    return dict(
        layer_types=tuple(cfg.layer_types),
        embedding_multiplier=cfg.embedding_multiplier,
        residual_multiplier=cfg.residual_multiplier,
        logits_scaling=cfg.logits_scaling,
        rms_eps=cfg.rms_eps,
        attn=dict(
            n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads, head_dim=cfg.head_dim,
            attention_multiplier=cfg.attention_multiplier,
        ),
        ssm=dict(
            mamba_heads=m.n_heads, mamba_head_dim=m.head_dim, d_state=m.d_state, d_conv=m.d_conv,
        ),
    )
