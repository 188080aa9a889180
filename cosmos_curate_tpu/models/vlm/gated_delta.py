"""The gated-delta-rule mixer of a hybrid decoder layer (Olmo-Hybrid; with a
decay a channel, Solar-Open2's Kimi Delta Attention).

After flash-linear-attention's ``GatedDeltaNet`` as HF's ``linear_*`` keys
configure it (separate q / k / v convolutions, l2-normed q and k, the norm
BEFORE the gate), for one layer's input ``h``:

    q = silu(conv(W_q h))   k = silu(conv(W_k h))   v = silu(conv(W_v h))
            (three causal depthwise convolutions of d_conv taps, no bias)
    q = q / sqrt(sum(q^2) + 1e-6) * dk^-0.5     k = k / sqrt(sum(k^2) + 1e-6)     (a head each)
    beta = sigmoid(W_b h)  (* 2 with ``allow_neg_eigval``)
    g = -exp(A_log) * softplus(W_a h + dt_bias)                          (per head)
    S_t = exp(g_t) S_{t-1} + k_t (x) (beta_t (v_t - (exp(g_t) S_{t-1})^T k_t)) ; o_t = S_t^T q_t
    out = W_o (RMSNorm_dv(o) * w * silu(W_g h))

Kimi Delta Attention (``KimiDeltaAttention``; ``GatedDeltaConfig.decay_rank``
and ``gate_rank`` set) is the same mixer with a decay a CHANNEL and a low-rank
sigmoid gate; everything above holds but these two lines:

    g = -exp(A_log[h]) * softplus(W_f2 (W_f1 h) + dt_bias)     in [H, dk]: S' = Diag(exp(g_t)) S_{t-1}
    out = W_o (RMSNorm_dv(o) * w * sigmoid(W_g2 (W_g1 h)))

What a request carries from token to token is ``S`` (float32, ``[dk, dv]`` a
head) and the three convolutions' last ``d_conv - 1`` inputs; both live in
the engine's recurrent store (``VLM._forward`` reads and writes it), the
tails of all three in one row, ``q | k | v`` a tap. Padding must not advance
either: positions at or past a row's ``valid`` get ``beta = 0, g = 0``, and
the tails are taken at the last valid position. The recurrence itself is
ops/delta_rule.py's, which alone decides how it runs.

The projections compute in ``dtype`` like every matmul of the decoder; the
convolutions, the gates, the recurrence and the gated norm compute in float32
and their small parameters are stored in float32 (``VLM.param_dtype`` has the rule).
"""

from __future__ import annotations

from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from cosmos_curate_tpu.models.layers import dense
from cosmos_curate_tpu.models.vlm.mamba2 import _dt_bias_init, conv_with_tail
from cosmos_curate_tpu.ops import delta_rule as delta_ops


def _a_log_init(key, shape, dtype):  # the layer's own: A uniform in (0, 16)
    return jnp.log(jax.random.uniform(key, shape, dtype, minval=1e-3, maxval=16.0))


class GatedDeltaMixer(nn.Module):
    cfg: Any  # model.GatedDeltaConfig
    dim: int
    rms_eps: float
    dtype: jnp.dtype = jnp.bfloat16
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, h, ssm, tail, rows, valid, *, layer_index=0, use_kernel=None):
        """h: [B, T, D]; ssm: ``[Ll, R, dk, H * dv]`` float32, this layer's
        states at ``[layer_index, rows]``; tail: ``[B, (d_conv - 1) *
        conv_dim]``, the rows' last convolution inputs; rows: [B]; valid: [B]
        leading positions of the chunk that are tokens. Returns (out [B, T,
        D], ssm, the new tail)."""
        m = self.cfg
        b, t, _ = h.shape
        nh, dk, dv, taps = m.n_heads, m.key_dim, m.value_dim, m.d_conv
        f32 = jnp.float32
        small = self.param  # the float32 parameters, by name
        proj = lambda n, name: dense(  # noqa: E731
            n, None, name=name, use_bias=False, dtype=self.dtype, param_dtype=self.param_dtype
        )
        widths = (nh * dk, nh * dk, nh * dv)
        qkv = jnp.concatenate([proj(w, f"{n}_proj")(h) for n, w in zip("qkv", widths)], axis=-1)

        # the three causal depthwise convolutions as one over [tail | chunk]
        # (a channel meets its own taps only); the new tail is the d_conv - 1
        # inputs before position ``valid``
        with jax.named_scope("delta.conv"):
            w = jnp.concatenate(
                [small(f"{n}_conv", nn.initializers.normal(0.2), (taps, w), f32) for n, w in zip("qkv", widths)],
                axis=-1,
            )
            qkv, new_tail = conv_with_tail(qkv, tail, w, valid)
            qkv = nn.silu(qkv)
            q, k, v = jnp.split(qkv, [widths[0], widths[0] + widths[1]], axis=-1)
            q, k, v = q.reshape(b, t, nh, dk), k.reshape(b, t, nh, dk), v.reshape(b, t, nh, dv)
            q = q * jax.lax.rsqrt(jnp.sum(q * q, axis=-1, keepdims=True) + 1e-6) * dk**-0.5
            k = k * jax.lax.rsqrt(jnp.sum(k * k, axis=-1, keepdims=True) + 1e-6)

        token = jnp.arange(t)[None, :, None] < valid[:, None, None]
        beta = jax.nn.sigmoid(proj(nh, "b_proj")(h).astype(f32)) * (2.0 if m.allow_neg_eigval else 1.0)
        if m.decay_rank is None:
            dt_bias = small("dt_bias", _dt_bias_init, (nh,), f32)
            a = jnp.exp(small("A_log", _a_log_init, (nh,), f32))
            g = -a * jax.nn.softplus(proj(nh, "a_proj")(h).astype(f32) + dt_bias)
            beta, g = jnp.where(token, beta, 0.0), jnp.where(token, g, 0.0)
        else:  # a decay a channel, out of a low-rank pair
            dt_bias = small("dt_bias", _dt_bias_init, (nh * dk,), f32)
            a = jnp.exp(small("A_log", _a_log_init, (nh,), f32))
            f = proj(nh * dk, "f_b_proj")(proj(m.decay_rank, "f_a_proj")(h)).astype(f32)
            g = -a[:, None] * jax.nn.softplus(f + dt_bias).reshape(b, t, nh, dk)
            beta, g = jnp.where(token, beta, 0.0), jnp.where(token[..., None], g, 0.0)
        if t == 1:
            with jax.named_scope("delta.decode"):
                o, ssm = delta_ops.delta_decode(
                    ssm, layer_index, rows, q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0],
                    use_kernel=use_kernel,
                )
            o = o[:, None]
        else:
            with jax.named_scope("delta.prefill_scan"):
                o, ssm = delta_ops.delta_prefill(
                    ssm, layer_index, rows, q, k, v, g, beta, chunk=m.chunk, use_kernel=use_kernel,
                )

        # RMSNorm over a head's dv, THEN the gate
        with jax.named_scope("delta.gate_norm"):
            if m.gate_rank is None:
                gate, act = proj(nh * dv, "g_proj")(h), nn.silu
            else:
                gate, act = proj(nh * dv, "g_b_proj")(proj(m.gate_rank, "g_a_proj")(h)), jax.nn.sigmoid
            gate = gate.astype(f32).reshape(b, t, nh, dv)
            o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + self.rms_eps)
            o = o * small("o_norm_scale", nn.initializers.ones, (dv,), f32) * act(gate)
        out = proj(self.dim, "o_proj")(o.reshape(b, t, nh * dv).astype(self.dtype))
        return out, ssm, new_tail
