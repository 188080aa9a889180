"""Flash attention as a Pallas TPU kernel.

The hot op of every transformer stage (ViT towers, the VLM captioner, the
T5-class encoder). Standard flash-attention scheme (public technique):
tile Q into ``block_q`` rows and stream K/V tiles of ``block_k`` through
VMEM, maintaining an online softmax (running max / normalizer / accumulator
in VMEM scratch) so the full ``S x S`` score matrix never materializes in
HBM — attention becomes matmul-bound on the MXU instead of HBM-bound.

Grid: ``(batch x heads, q_blocks, kv_blocks)`` with the kv dimension
innermost (TPU pallas grids iterate sequentially, so scratch carries the
running state across kv steps). Causal blocks strictly above the diagonal
are skipped entirely (`pl.when`), halving causal FLOPs.

On the CPU the kernel runs in interpreter mode so the same code path is
exercised by CPU tests.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30


def _flash_kernel(
    q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *, sm_scale, causal, seq_len, block_q, block_k
):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    num_k = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    # causal: skip kv blocks strictly above the diagonal
    q_start = qi * block_q
    k_start = ki * block_k
    live = (k_start <= q_start + block_q - 1) if causal else True

    @pl.when(live)
    def _step():
        q = q_ref[0].astype(jnp.float32) * sm_scale  # [block_q, d]
        k = k_ref[0].astype(jnp.float32)  # [block_k, d]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # [block_q, block_k]
        q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        mask = k_pos < seq_len  # padded tail keys contribute nothing
        if causal:
            mask &= k_pos <= q_pos
        s = jnp.where(mask, s, _NEG_INF)

        m_prev = m_ref[:, :1]  # [block_q, 1]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[:, :1] = l_ref[:, :1] * alpha + p.sum(axis=1, keepdims=True)
        acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
            p, v_ref[0].astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[:, :1] = m_new

    @pl.when(ki == num_k - 1)
    def _finish():
        o_ref[0] = (acc_ref[:] / jnp.maximum(l_ref[:, :1], 1e-30)).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("causal", "sm_scale", "block_q", "block_k", "interpret")
)
def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    sm_scale: float | None = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool | None = None,
) -> jax.Array:
    """q/k/v: [B, H, S, D] (self-attention lengths equal) -> [B, H, S, D].

    S is padded to the block size internally; padded keys are masked, padded
    query rows are sliced off. D should be a multiple of 128 for peak MXU
    utilization (works regardless).
    """
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    if interpret is None:
        interpret = jax.devices()[0].platform == "cpu"
    b, h, s, d = q.shape
    block_q = min(block_q, max(8, s))
    block_k = min(block_k, max(8, s))
    s_pad = ((s + block_q - 1) // block_q) * block_q
    s_pad = ((s_pad + block_k - 1) // block_k) * block_k

    def prep(x):
        x = x.reshape(b * h, s, d)
        if s_pad != s:
            x = jnp.pad(x, ((0, 0), (0, s_pad - s), (0, 0)))
        return x

    qf, kf, vf = prep(q), prep(k), prep(v)
    grid = (b * h, s_pad // block_q, s_pad // block_k)
    kernel = functools.partial(
        _flash_kernel,
        sm_scale=sm_scale,
        causal=causal,
        seq_len=s,
        block_q=block_q,
        block_k=block_k,
    )
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, qi, ki: (bh, ki, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, qi, ki: (bh, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, s_pad, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
        ],
        interpret=interpret,
    )(qf, kf, vf)
    return out[:, :s].reshape(b, h, s, d)
