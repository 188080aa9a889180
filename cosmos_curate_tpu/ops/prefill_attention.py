"""Chunked-prefill GQA attention over a KV cache as a Pallas TPU kernel.

The caption engine's prefill attends a chunk of T new tokens against the
slot cache (its own K/V already written at ``write_index``). The XLA path
materializes fp32 logits ``[B, Hkv, G, T, S]`` — at T=256, S=4096 that is
the HBM hot spot of long-prompt captioning (the reference leans on
FlashInfer prefill kernels via vLLM, SPEED_OF_LIGHT.md). This kernel
streams K/V blocks through VMEM with an online softmax:

- **cache-native layout**: reads ``[B, Hkv, S, D]`` directly (a K/V tile
  is ``[block_k, D]``) and keeps GQA queries grouped so each KV byte is
  read once for all G grouped queries;
- **causality by absolute position**: query t's position is
  ``write_index + t`` (scalar-prefetched per row), so the SAME kernel
  serves bucket prefill (write_index=0) and later chunks of a chunked
  prefill (write_index>0) — matching DecoderLayer's mask semantics;
- **early exit**: K/V blocks entirely beyond the chunk's last causal
  position, or at/after the row's valid length, are skipped (`pl.when`).

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


def _prefill_kernel(
    write_ref,
    kvlen_ref,
    q_ref,
    k_ref,
    v_ref,
    o_ref,
    acc_ref,
    m_ref,
    l_ref,
    *,
    sm_scale,
    block_q,
    block_k,
    g,
):
    b = pl.program_id(0)
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    num_k = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    write = write_ref[b]
    kv_len = kvlen_ref[b]
    k_start = ki * block_k
    rows = g * block_q
    # last causal position any query in this q-tile can see
    last_pos = write + qi * block_q + block_q - 1

    @pl.when((k_start <= last_pos) & (k_start < kv_len))
    def _step():
        # rows are group-major: row r is query t_local = r % block_q of
        # group r // block_q, so the [g, block_q, d] tile flattens without
        # moving data (block_q is a whole number of sublane tiles)
        q = q_ref[...].astype(jnp.float32).reshape(rows, q_ref.shape[-1])
        q = q * sm_scale
        k = k_ref[...].astype(jnp.float32)  # [block_k, d]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # [rows, block_k]
        # query t_local's absolute position is write + qi*block_q + t_local
        t_local = jax.lax.broadcasted_iota(jnp.int32, (g, block_q, block_k), 1).reshape(
            rows, block_k
        )
        q_pos = write + qi * block_q + t_local
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, (rows, block_k), 1)
        ok = (k_pos <= q_pos) & (k_pos < kv_len)
        s = jnp.where(ok, s, _NEG_INF)

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
        out = acc_ref[:] / jnp.maximum(l_ref[:, :1], 1e-30)
        o_ref[...] = out.reshape(g, block_q, o_ref.shape[-1]).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("sm_scale", "block_q", "block_k", "interpret")
)
def prefill_attention(
    q: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    write_index: jax.Array,
    kv_len: jax.Array,
    *,
    sm_scale: float | None = None,
    block_q: int = 128,
    block_k: int = 256,
    interpret: bool | None = None,
) -> jax.Array:
    """q: [B, T, Hkv, G, D] (a prefill chunk, GQA-grouped); k_cache/v_cache:
    [B, Hkv, S, D] with the chunk's K/V already written at ``write_index``;
    write_index/kv_len: [B]. Returns [B, T, Hkv, G, D]."""
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    if interpret is None:
        interpret = jax.devices()[0].platform == "cpu"
    b, t, hk, g, d = q.shape
    s = k_cache.shape[2]
    block_q = min(block_q, round_up(t, sublanes(q.dtype)))
    t_pad = round_up(t, block_q)
    # heads-major, group-major queries: the kernel's [g, block_q, d] tile
    # keeps (block_q, d) as the tiled dims, like the K/V tiles
    q = q.transpose(0, 2, 3, 1, 4)  # [B, Hkv, G, T, D]
    if t_pad != t:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, 0), (0, t_pad - t), (0, 0)))
    block_k = min(block_k, s)
    if s % block_k:
        pad = block_k - s % block_k
        k_cache = jnp.pad(k_cache, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v_cache = jnp.pad(v_cache, ((0, 0), (0, 0), (0, pad), (0, 0)))
        s += pad

    grid = (b, hk, t_pad // block_q, s // block_k)
    kernel = functools.partial(
        _prefill_kernel, sm_scale=sm_scale, block_q=block_q, block_k=block_k, g=g
    )
    q_spec = pl.BlockSpec(
        (None, None, g, block_q, d), lambda b_, h, qi, ki, *_: (b_, h, 0, qi, 0)
    )
    kv_spec = pl.BlockSpec((None, None, block_k, d), lambda b_, h, qi, ki, *_: (b_, h, ki, 0))
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[q_spec, kv_spec, kv_spec],
            out_specs=q_spec,
            scratch_shapes=[
                pltpu.VMEM((g * block_q, d), jnp.float32),
                pltpu.VMEM((g * block_q, 128), jnp.float32),
                pltpu.VMEM((g * block_q, 128), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, hk, g, t_pad, d), q.dtype),
        interpret=interpret,
    )(write_index.astype(jnp.int32), kv_len.astype(jnp.int32), q, k_cache, v_cache)
    return out[:, :, :, :t].transpose(0, 3, 1, 2, 4)
