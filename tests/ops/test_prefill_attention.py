"""Prefill flash kernel vs the reference einsum-attention semantics
(DecoderLayer's mask: causal over cache order via write_index, bounded by
kv_len). Interpreter mode on CPU — same kernel code path as TPU."""

import numpy as np
import pytest

import jax.numpy as jnp

from cosmos_curate_tpu.ops.prefill_attention import prefill_attention


def _reference(q, k_cache, v_cache, write_index, kv_len):
    """Mirror of models/vlm/model.py DecoderLayer's XLA attention path."""
    b, t, hk, g, d = q.shape
    s = k_cache.shape[2]
    qf = q.astype(np.float64) * d**-0.5
    logits = np.einsum("btkgd,bksd->bkgts", qf, k_cache.astype(np.float64))
    k_pos = np.arange(s)[None, None, None, None, :]
    q_seq = write_index[:, None] + np.arange(t)[None, :]
    causal = k_pos <= q_seq[:, None, None, :, None]
    written = k_pos < kv_len[:, None, None, None, None]
    logits = np.where(causal & written, logits, -1e30)
    logits -= logits.max(axis=-1, keepdims=True)
    probs = np.exp(logits)
    probs /= probs.sum(axis=-1, keepdims=True)
    out = np.einsum("bkgts,bksd->btkgd", probs, v_cache.astype(np.float64))
    return out


CASES = [
    # (B, T, Hkv, G, D, S, write_indices, kv_extra)
    (1, 16, 2, 3, 32, 64, [0], 0),        # bucket prefill (write=0)
    (2, 16, 2, 3, 32, 64, [16, 32], 0),   # later chunks (write>0)
    (2, 12, 1, 4, 32, 64, [0, 20], 0),    # ragged T (pads to block_q)
    (1, 16, 2, 2, 32, 96, [48], 16),      # kv_len < write+T? no: extra slack
]


@pytest.mark.parametrize("case", CASES)
def test_matches_reference(case):
    b, t, hk, g, d, s, writes, extra = case
    rng = np.random.default_rng(sum(case[:6]))
    write_index = np.asarray(writes, np.int32)
    kv_len = write_index + t + extra
    q = rng.normal(size=(b, t, hk, g, d)).astype(np.float32)
    k = rng.normal(size=(b, hk, s, d)).astype(np.float32)
    v = rng.normal(size=(b, hk, s, d)).astype(np.float32)
    got = prefill_attention(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
        jnp.asarray(write_index), jnp.asarray(kv_len),
        block_q=8, block_k=16, interpret=True,
    )
    want = _reference(q, k, v, write_index, kv_len)
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-5, rtol=1e-4)


def test_early_exit_blocks_do_not_change_result():
    """Blocks beyond kv_len/causality are skipped; a huge garbage tail in
    the cache must not leak into the output."""
    rng = np.random.default_rng(0)
    b, t, hk, g, d, s = 1, 8, 2, 2, 32, 128
    write = np.asarray([0], np.int32)
    kv_len = write + t
    q = rng.normal(size=(b, t, hk, g, d)).astype(np.float32)
    k = rng.normal(size=(b, hk, s, d)).astype(np.float32)
    v = rng.normal(size=(b, hk, s, d)).astype(np.float32)
    poisoned_k = k.copy()
    poisoned_k[:, :, t:] = 1e6
    poisoned_v = v.copy()
    poisoned_v[:, :, t:] = -1e6
    a = prefill_attention(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
        jnp.asarray(write), jnp.asarray(kv_len), block_q=8, block_k=16, interpret=True,
    )
    bb = prefill_attention(
        jnp.asarray(q), jnp.asarray(poisoned_k), jnp.asarray(poisoned_v),
        jnp.asarray(write), jnp.asarray(kv_len), block_q=8, block_k=16, interpret=True,
    )
    np.testing.assert_allclose(np.asarray(a), np.asarray(bb), atol=1e-6)
