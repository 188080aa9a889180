"""Single-token GQA decode attention as a Pallas TPU kernel.

The caption engine's decode step is KV-cache-bandwidth-bound: one new token
per slot attends to the whole slot cache (reference leans on FlashInfer
decode kernels via vLLM, models/vllm_interface.py:543 /
SPEED_OF_LIGHT.md). This kernel streams K/V blocks through VMEM with an
online softmax and two decode-specific wins over the generic flash kernel:

- **no transpose/repeat**: operates directly on the cache layout
  ``[B, Hkv, S, D]`` (BlockSpec picks the head plane; a K/V tile is
  ``[block_k, D]``), and queries stay grouped ``[B, Hkv, G, D]`` so GQA
  reads each KV byte once;
- **early exit**: the per-row valid length is scalar-prefetched, and KV
  blocks at or beyond it are skipped entirely (`pl.when`) — decode cost
  follows the *actual* sequence length, not the padded cache size.

On the CPU the kernel runs in interpreter mode (CPU tests exercise the
same code path).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from cosmos_curate_tpu.ops.tiling import round_up, sublanes

_NEG_INF = -1e30


def _decode_kernel(
    kvlen_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *, sm_scale, block_k, g_pad
):
    b = pl.program_id(0)
    ki = pl.program_id(2)
    num_k = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    kv_len = kvlen_ref[b]
    k_start = ki * block_k

    @pl.when(k_start < kv_len)
    def _step():
        q = q_ref[...].astype(jnp.float32) * sm_scale  # [g_pad, d]
        k = k_ref[...].astype(jnp.float32)  # [block_k, d]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # [g_pad, block_k]
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, (g_pad, block_k), 1)
        s = jnp.where(k_pos < kv_len, s, _NEG_INF)

        m_prev = m_ref[:, :1]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[:, :1] = l_ref[:, :1] * alpha + p.sum(axis=1, keepdims=True)
        acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
            p,
            v_ref[...].astype(jnp.float32),
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[:, :1] = m_new

    @pl.when(ki == num_k - 1)
    def _finish():
        o_ref[...] = (acc_ref[:] / jnp.maximum(l_ref[:, :1], 1e-30)).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("sm_scale", "block_k", "interpret")
)
def decode_attention(
    q: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    kv_len: jax.Array,
    *,
    sm_scale: float | None = None,
    block_k: int = 256,
    interpret: bool | None = None,
) -> jax.Array:
    """q: [B, Hkv, G, D] (one token per row, grouped GQA queries);
    k_cache/v_cache: [B, Hkv, S, D]; kv_len: [B] valid lengths (the new
    token's K/V already written). Returns [B, Hkv, G, D]."""
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    if interpret is None:
        interpret = jax.devices()[0].platform == "cpu"
    b, hk, g, d = q.shape
    s = k_cache.shape[2]
    block_k = min(block_k, s)
    if s % block_k:
        pad = block_k - s % block_k
        k_cache = jnp.pad(k_cache, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v_cache = jnp.pad(v_cache, ((0, 0), (0, 0), (0, pad), (0, 0)))
        s += pad
    g_pad = round_up(g, sublanes(q.dtype))
    if g_pad != g:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, g_pad - g), (0, 0)))

    grid = (b, hk, s // block_k)
    kernel = functools.partial(
        _decode_kernel, sm_scale=sm_scale, block_k=block_k, g_pad=g_pad
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            # index maps receive the scalar-prefetch ref as a trailing arg
            in_specs=[
                pl.BlockSpec((None, None, g_pad, d), lambda b_, h, ki, *_: (b_, h, 0, 0)),
                pl.BlockSpec((None, None, block_k, d), lambda b_, h, ki, *_: (b_, h, ki, 0)),
                pl.BlockSpec((None, None, block_k, d), lambda b_, h, ki, *_: (b_, h, ki, 0)),
            ],
            out_specs=pl.BlockSpec((None, None, g_pad, d), lambda b_, h, ki, *_: (b_, h, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((g_pad, d), jnp.float32),
                pltpu.VMEM((g_pad, 128), jnp.float32),
                pltpu.VMEM((g_pad, 128), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, hk, g_pad, d), q.dtype),
        interpret=interpret,
    )(kv_len.astype(jnp.int32), q, k_cache, v_cache)
    return out[:, :, :g]
