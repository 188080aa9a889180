"""The Mamba-2 mixer of a hybrid decoder layer (Granite-4.0-H).

After HF ``GraniteMoeHybridMambaLayer`` (one group, ``norm_before_gate=False``,
no limit on ``dt``), for ``h = RMSNorm(x)`` of one layer:

    [z | xBC | dt] = W_in h                    d_inner | d_inner + 2 N | heads
    xBC = silu(causal depthwise conv(xBC) + b_conv)     (d_conv taps, per channel)
    [x | B | C] = xBC                          x as [heads, head_dim]; B, C [N]
    dt = softplus(dt + dt_bias) ; A = -exp(A_log)        (per head)
    H_t = exp(dt_t A) H_{t-1} + dt_t x_t (x) B_t ; y_t = H_t C_t + D x_t
    out = W_out (RMSNorm(y * silu(z)) * w)     (the gate BEFORE the norm)

What a request carries from token to token is ``H`` (float32) and the
convolution's last ``d_conv - 1`` inputs; both live in the engine's recurrent
store (``VLM._forward`` reads and writes it). Padding must
not advance either: positions at or past a row's ``valid`` get ``dt = 0``,
and the convolution's tail is taken at the last valid position. The
recurrence itself is ops/ssm.py's, which alone decides how it runs.

The two projections compute in ``dtype`` like every matmul of the decoder; the
convolution, ``dt``, the recurrence and the gated norm compute in float32 and
their small parameters are stored in float32 (``VLM.param_dtype`` has the rule).
"""

from __future__ import annotations

from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from cosmos_curate_tpu.models.layers import dense
from cosmos_curate_tpu.ops import ssm as ssm_ops


def _a_log_init(key, shape, dtype):  # HF: A = 1..heads
    del key
    return jnp.log(jnp.arange(1, shape[0] + 1, dtype=dtype))


def _dt_bias_init(key, shape, dtype, dt_min=0.001, dt_max=0.1):
    """Mamba-2's own: softplus(dt_bias) log-uniform in [dt_min, dt_max] (HF
    keeps the two as ``time_step_min`` / ``time_step_max``), so that a seeded
    state remembers over 10 to 1000 tokens as a trained one does, and what
    is wrong in a carried state shows in the logits."""
    dt = jnp.exp(jax.random.uniform(key, shape, dtype) * jnp.log(dt_max / dt_min) + jnp.log(dt_min))
    return dt + jnp.log(-jnp.expm1(-dt))  # the inverse of softplus


def conv_with_tail(x, tail, w, valid):
    """A causal depthwise convolution over ``[tail | chunk]``, what every mixer
    here that carries a convolution's tail does (Mamba-2's, the delta rule's
    three, the short convolution's one). x: ``[B, T, C]``; tail: ``[B, (taps -
    1) * C]``, the rows' last inputs, a row's taps side by side; w: ``[taps,
    C]`` float32, ``w[i]`` meeting the input ``taps - 1 - i`` positions back;
    valid: [B] leading positions of the chunk that are tokens. Returns (the
    sums ``[B, T, C]`` float32: no bias, no activation; the new tail: the
    ``taps - 1`` inputs before position ``valid``, so padding never enters it
    and an idle row (``valid`` 0) keeps its own)."""
    b, t, c = x.shape
    taps = w.shape[0]
    window = jnp.concatenate([tail.reshape(b, taps - 1, c).astype(x.dtype), x], axis=1)
    new_tail = jax.vmap(lambda row, v: jax.lax.dynamic_slice_in_dim(row, v, taps - 1))(window, valid)
    new_tail = new_tail.reshape(b, -1).astype(tail.dtype)
    return sum(window[:, i : i + t].astype(jnp.float32) * w[i] for i in range(taps)), new_tail


class Mamba2Mixer(nn.Module):
    cfg: Any  # model.Mamba2Config
    dim: int
    rms_eps: float
    dtype: jnp.dtype = jnp.bfloat16
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, h, ssm, tail, rows, valid, *, layer_index=0, use_kernel=None):
        """h: [B, T, D]; ssm: ``[Lm, R, H, P, N]`` float32, this layer's
        states at ``[layer_index, rows]``; tail: ``[B, (d_conv - 1) *
        conv_dim]``, the rows' last convolution inputs; rows: [B]; valid: [B]
        leading positions of the chunk that are tokens. Returns (out [B, T,
        D], ssm, the new tail)."""
        m = self.cfg
        b, t, _ = h.shape
        nh, p, n, k = m.n_heads, m.head_dim, m.d_state, m.d_conv
        f32 = jnp.float32
        small = self.param  # the float32 parameters, by name
        zxbcdt = dense(
            2 * m.d_inner + 2 * n + nh, None, name="in_proj", use_bias=False,
            dtype=self.dtype, param_dtype=self.param_dtype,
        )(h)
        z, xbc, dt = jnp.split(zxbcdt, [m.d_inner, m.d_inner + m.conv_dim], axis=-1)

        # causal depthwise convolution over [tail | chunk]; the new tail is
        # the d_conv - 1 inputs before position ``valid``
        w = small("conv_kernel", nn.initializers.normal(0.2), (k, m.conv_dim), f32)
        bias = small("conv_bias", nn.initializers.zeros, (m.conv_dim,), f32)
        xbc, new_tail = conv_with_tail(xbc, tail, w, valid)
        xbc = nn.silu(bias + xbc)
        x, bmat, cmat = jnp.split(xbc, [m.d_inner, m.d_inner + n], axis=-1)
        x = x.reshape(b, t, nh, p)

        dt_bias = small("dt_bias", _dt_bias_init, (nh,), f32)
        a = -jnp.exp(small("A_log", _a_log_init, (nh,), f32))
        d = small("D", nn.initializers.ones, (nh,), f32)
        dt = jax.nn.softplus(dt.astype(f32) + dt_bias)
        dt = jnp.where(jnp.arange(t)[None, :, None] < valid[:, None, None], dt, 0.0)
        if t == 1:
            with jax.named_scope("ssm.decode"):
                y, ssm = ssm_ops.ssm_decode(
                    ssm, layer_index, rows, x[:, 0], dt[:, 0], a, bmat[:, 0], cmat[:, 0], d,
                    use_kernel=use_kernel,
                )
            y = y[:, None]
        else:
            with jax.named_scope("ssm.prefill_scan"):
                y, ssm = ssm_ops.ssm_prefill(
                    ssm, layer_index, rows, x, dt, a, bmat, cmat, d, chunk=m.chunk,
                    use_kernel=use_kernel,
                )

        # gated RMSNorm over all of d_inner, the gate first
        y = y.reshape(b, t, m.d_inner) * nn.silu(z.astype(f32))
        y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True) + self.rms_eps)
        y = y * small("norm_scale", nn.initializers.ones, (m.d_inner,), f32)
        out = dense(
            self.dim, None, name="out_proj", use_bias=False,
            dtype=self.dtype, param_dtype=self.param_dtype,
        )(y.astype(self.dtype))
        return out, ssm, new_tail
