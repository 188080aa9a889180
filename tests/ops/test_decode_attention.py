"""Pallas decode-attention kernel parity tests (interpreter mode on CPU)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from cosmos_curate_tpu.ops.decode_attention import decode_attention


def _reference(q, k_cache, v_cache, kv_len):
    """Dense GQA decode attention (the model's XLA path)."""
    b, hk, g, d = q.shape
    s = k_cache.shape[2]
    logits = jnp.einsum(
        "bkgd,bksd->bkgs", q.astype(jnp.float32) * d**-0.5, k_cache.astype(jnp.float32)
    )
    mask = jnp.arange(s)[None, None, None, :] < kv_len[:, None, None, None]
    logits = jnp.where(mask, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bkgs,bksd->bkgd", probs, v_cache.astype(jnp.float32))


@pytest.mark.parametrize("b,hk,g,d,s", [(2, 2, 3, 16, 64), (1, 2, 6, 32, 256), (3, 1, 1, 16, 128)])
def test_matches_dense_reference(b, hk, g, d, s):
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((b, hk, g, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, hk, s, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, hk, s, d)), jnp.float32)
    kv_len = jnp.asarray(rng.integers(1, s + 1, b), jnp.int32)
    got = decode_attention(q, k, v, kv_len, block_k=32, interpret=True)
    want = _reference(q, k, v, kv_len)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=1e-4)


def test_blocks_beyond_kv_len_are_skipped_numerics():
    """Stale cache content beyond kv_len must not leak into the output —
    proves both the mask and the block skip. Garbage is huge-but-finite:
    stale cache rows are always finite in practice (zeros or old tokens),
    and softmax zeros times non-finite would poison any flash kernel."""
    rng = np.random.default_rng(1)
    b, hk, g, d, s = 1, 1, 2, 16, 128
    q = jnp.asarray(rng.standard_normal((b, hk, g, d)), jnp.float32)
    k = rng.standard_normal((b, hk, s, d)).astype(np.float32)
    v = rng.standard_normal((b, hk, s, d)).astype(np.float32)
    k[:, :, 40:] = 1e20
    v[:, :, 40:] = -1e20
    kv_len = jnp.asarray([40], jnp.int32)
    got = np.asarray(
        decode_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), kv_len, block_k=32, interpret=True)
    )
    assert np.isfinite(got).all()
    clean_k = k.copy()
    clean_v = v.copy()
    clean_k[:, :, 40:] = 0
    clean_v[:, :, 40:] = 0
    want = _reference(q, jnp.asarray(clean_k), jnp.asarray(clean_v), kv_len)
    np.testing.assert_allclose(got, np.asarray(want), atol=2e-5, rtol=1e-4)


def test_engine_decode_with_kernel_forced(monkeypatch):
    """End-to-end: the caption engine decodes identically with the Pallas
    decode kernel forced on (interpreter) vs the XLA path."""
    monkeypatch.setenv("CURATE_FLASH_DECODE", "0")
    from cosmos_curate_tpu.models.tokenizer import ByteTokenizer
    from cosmos_curate_tpu.models.vlm import (
        CaptionEngine,
        CaptionRequest,
        SamplingConfig,
        VLM_TINY_TEST,
    )

    tok = ByteTokenizer()

    def req(rid):
        return CaptionRequest(
            request_id=rid,
            prompt_ids=tok.encode("describe the scene"),
            sampling=SamplingConfig(max_new_tokens=6),
        )

    eng = CaptionEngine(VLM_TINY_TEST, max_batch=2, tokenizer=tok)
    eng.setup()
    eng.add_request(req("xla"))
    base = eng.run_until_complete()[0].text

    monkeypatch.setenv("CURATE_FLASH_DECODE", "1")
    eng2 = CaptionEngine(VLM_TINY_TEST, max_batch=2, tokenizer=tok)
    eng2.setup()
    eng2.add_request(req("pallas"))
    flash = eng2.run_until_complete()[0].text
    assert flash == base
