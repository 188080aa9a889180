"""Paged-attention op: table-driven kernel + byte-parity XLA reference.

The reference path (``use_kernel=False``) is the engine's CPU serving path
and must agree with a dense contiguous-cache oracle; the Pallas kernel
(interpreter mode off-TPU) must agree with the reference to float
tolerance. Block tables here are deliberately FRAGMENTED — logical order
never matches pool order — because in-place table walks are the whole
point of the op.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from cosmos_curate_tpu.ops.paged_attention import (
    heads_per_row,
    join_rows,
    paged_attention,
    paged_head_attention,
    split_rows,
)
from tests.ops.test_tpu_compile import WIDTHS  # (Hkv, G, D) of the flavors the kernels serve


def _dense_reference(q, k_cache, v_cache, write_index, kv_len, sm_scale):
    """Grouped causal attention against CONTIGUOUS caches — independent of
    the pool/table plumbing under test. q: [B,T,Hk,G,D]; caches [B,Hk,S,D]."""
    b, t, hk, g, d = q.shape
    s = k_cache.shape[2]
    logits = jnp.einsum(
        "btkgd,bksd->bkgts",
        q.astype(jnp.float32) * sm_scale,
        k_cache.astype(jnp.float32),
    )
    k_pos = jnp.arange(s)[None, None, None, None, :]
    q_seq = write_index[:, None] + jnp.arange(t)[None, :]
    causal = k_pos <= q_seq[:, None, None, :, None]
    written = k_pos < kv_len[:, None, None, None, None]
    logits = jnp.where(causal & written, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bkgts,bksd->btkgd", probs, v_cache.astype(jnp.float32))


def _fragmented_case(rng, *, b, t, hk, g, d, nbl, bs, n_blocks, dtype=jnp.float32):
    """A pool where each row's table is a shuffled, interleaved slice of the
    physical blocks (block 0 reserved as garbage, engine convention), plus
    the logical contiguous caches those tables describe."""
    l = 2  # two layers so layer_index != 0 is exercised
    layer = 1
    pool_k = jnp.asarray(rng.standard_normal((l, n_blocks, hk, bs, d)), dtype)
    pool_v = jnp.asarray(rng.standard_normal((l, n_blocks, hk, bs, d)), dtype)
    ids = rng.permutation(np.arange(1, n_blocks))[: b * nbl]
    tables = jnp.asarray(ids.reshape(b, nbl), jnp.int32)
    q = jnp.asarray(rng.standard_normal((b, t, hk, g, d)), dtype)

    def contiguous(pool):  # [B, nbl, Hk, bs, D] -> [B, Hk, S, D]
        blocks = np.asarray(pool)[layer][np.asarray(tables)]
        return blocks.swapaxes(1, 2).reshape(b, hk, nbl * bs, d)

    k_cache, v_cache = contiguous(pool_k), contiguous(pool_v)
    return q, pool_k, pool_v, tables, layer, jnp.asarray(k_cache), jnp.asarray(v_cache)


# The prefill kernel's two forms (``_paged_prefill``): where ``D`` makes no
# whole lane tile the pipeline feeds one page a grid step; at ``D`` = 128 the
# kernel copies a group of its row's own pages a loop trip, out of pages of 16
# and of 128 positions. (hk, g, d, nbl, bs): every lane is 384 positions long.
PREFILL_FORMS = {
    "pipeline-d16": (2, 3, 16, 24, 16),
    "own-copies-bs16": (2, 3, 128, 24, 16),
    "own-copies-bs128": (2, 3, 128, 3, 128),
}
# the pipeline's kernel multiplies in float32 whatever the pool holds: one type does
FORMS_AND_TYPES = [
    pytest.param(form, dtype, id=f"{form}-{name}")
    for form in sorted(PREFILL_FORMS)
    for dtype, name in ((jnp.float32, "f32"), (jnp.bfloat16, "bf16"))
    if not (form == "pipeline-d16" and dtype == jnp.bfloat16)
]


@pytest.fixture
def groups_of_64_keys(monkeypatch):
    """A served group is 1,024 keys, more than a CPU test wants to multiply:
    64 here, so 4 pages of 16 (one page of 128) a loop trip, and a context of
    150 positions is an odd number of groups."""
    import importlib

    module = importlib.import_module("cosmos_curate_tpu.ops.paged_attention")
    monkeypatch.setattr(module, "_PREFILL_GROUP_KEYS", 64)
    module._paged_prefill.clear_cache()
    yield
    module._paged_prefill.clear_cache()


def _tolerance(dtype):
    """float32 pools: today's; bfloat16: what
    ``test_bf16_kernel_within_online_softmax_tolerance`` holds decode to."""
    return dict(atol=2e-5, rtol=1e-4) if dtype == jnp.float32 else dict(atol=3e-2, rtol=3e-2)


class TestReferencePath:
    @pytest.mark.parametrize(
        "b,hk,g,d,nbl,bs", [(2, 2, 4, 16, 4, 16), (3, 1, 2, 32, 2, 8), (3, 1, 1, 16, 8, 16)]
    )
    def test_decode_matches_dense_oracle(self, b, hk, g, d, nbl, bs):
        rng = np.random.default_rng(0)
        q, pk, pv, tables, layer, kc, vc = _fragmented_case(
            rng, b=b, t=1, hk=hk, g=g, d=d, nbl=nbl, bs=bs, n_blocks=b * nbl + 3
        )
        kv_len = jnp.asarray(rng.integers(1, nbl * bs + 1, b), jnp.int32)
        write = kv_len - 1
        sm = d**-0.5
        got = paged_attention(
            q, pk, pv, tables, write, kv_len, layer_index=layer, use_kernel=False
        )
        want = _dense_reference(q, kc, vc, write, kv_len, sm)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=1e-4)

    def test_prefill_chunk_matches_dense_oracle(self):
        """A chunk written mid-context (write_index > 0) attends to cached
        prefix positions plus its own causal window."""
        rng = np.random.default_rng(1)
        b, t, hk, g, d, nbl, bs = 2, 12, 2, 3, 16, 4, 16
        q, pk, pv, tables, layer, kc, vc = _fragmented_case(
            rng, b=b, t=t, hk=hk, g=g, d=d, nbl=nbl, bs=bs, n_blocks=b * nbl + 2
        )
        write = jnp.asarray([0, 17], jnp.int32)  # one fresh row, one mid-context
        kv_len = write + t
        got = paged_attention(
            q, pk, pv, tables, write, kv_len, layer_index=layer, use_kernel=False
        )
        want = _dense_reference(q, kc, vc, write, kv_len, d**-0.5)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=1e-4)

    def test_unmapped_pool_blocks_do_not_leak(self):
        """Garbage in pool blocks OUTSIDE the tables must not reach the
        output — the op reads only through the table."""
        rng = np.random.default_rng(2)
        b, hk, g, d, nbl, bs = 1, 1, 2, 16, 2, 8
        n_blocks = b * nbl + 4
        q, pk, pv, tables, layer, kc, vc = _fragmented_case(
            rng, b=b, t=1, hk=hk, g=g, d=d, nbl=nbl, bs=bs, n_blocks=n_blocks
        )
        mapped = set(np.asarray(tables).ravel().tolist())
        unmapped = [i for i in range(n_blocks) if i not in mapped]
        pk = pk.at[:, jnp.asarray(unmapped)].set(1e20)
        pv = pv.at[:, jnp.asarray(unmapped)].set(-1e20)
        kv_len = jnp.asarray([nbl * bs], jnp.int32)
        got = np.asarray(
            paged_attention(
                q, pk, pv, tables, kv_len - 1, kv_len, layer_index=layer, use_kernel=False
            )
        )
        assert np.isfinite(got).all()
        want = _dense_reference(q, kc, vc, kv_len - 1, kv_len, d**-0.5)
        np.testing.assert_allclose(got, np.asarray(want), atol=2e-5, rtol=1e-4)


class TestInterpretKernel:
    """The Pallas kernels in interpreter mode vs the reference path."""

    # kv_len None: drawn per row. Where D is a whole number of lane tiles
    # the kernel copies its own pages in a loop over the row's table; where
    # it is not, the pipeline delivers the same groups (``_paged_decode``).
    # Either way a group is P table entries (``_decode_pages``: 8 of 16
    # tokens at these widths, the whole table where it is shorter), so
    # nbl = 20 is groups of 8, 8 and 4.
    @pytest.mark.parametrize(
        "b,hk,g,d,nbl,bs,kv_len",
        [
            pytest.param(2, 2, 4, 16, 4, 16, None, id="one-group"),
            pytest.param(1, 2, 6, 32, 3, 8, None, id="g6-bs8"),
            pytest.param(3, 1, 1, 16, 8, 16, None, id="g1-one-kv-head"),
            pytest.param(2, 2, 4, 128, 20, 16, [131, 257], id="ragged-groups"),
            pytest.param(3, 2, 6, 128, 20, 16, [1, 320, 128], id="idle-row-beside-full-lane"),
            pytest.param(2, 1, 7, 128, 40, 16, [640, 333], id="one-kv-head-tp4-shard"),
            pytest.param(2, 8, 2, 64, 20, 16, [200, 17], id="d64-base"),
            pytest.param(3, 2, 4, 16, 20, 16, [320, 1, 129], id="d16-ragged-groups"),
        ],
    )
    def test_decode_kernel_matches_reference(self, b, hk, g, d, nbl, bs, kv_len):
        rng = np.random.default_rng(3)
        q, pk, pv, tables, layer, _, _ = _fragmented_case(
            rng, b=b, t=1, hk=hk, g=g, d=d, nbl=nbl, bs=bs, n_blocks=b * nbl + 2
        )
        if kv_len is None:
            kv_len = rng.integers(1, nbl * bs + 1, b)
        kv_len = jnp.asarray(kv_len, jnp.int32)
        write = kv_len - 1
        got = paged_attention(
            q, pk, pv, tables, write, kv_len,
            layer_index=layer, use_kernel=True, interpret=True,
        )
        want = paged_attention(
            q, pk, pv, tables, write, kv_len, layer_index=layer, use_kernel=False
        )
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=1e-4)

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
    @pytest.mark.parametrize(
        "d,r", [(128, 1), (64, 1), (64, 2)], ids=["own-copies", "pipeline-d64", "own-copies-d64-packed"]
    )
    def test_decode_kernel_never_reads_a_page_past_the_valid_length(self, d, r, dtype):
        """Every table entry at or past a row's valid length points at a
        block of NaN (the engine points them at its garbage block 0): the
        kernel fetches no such entry, so nothing of that block can reach
        the result, not even multiplied by a zero probability. The
        reference gathers whole tables, so it reads the clean one. (``r``
        KV heads a pool row: 64-wide heads in pairs are the kernel that
        copies for itself, alone the pipeline's.)"""
        rng = np.random.default_rng(7)
        b, hk, g, nbl, bs = 3, 2, 4, 20, 16
        q, pk, pv, tables, layer, _, _ = _fragmented_case(
            rng, b=b, t=1, hk=hk, g=g, d=d, nbl=nbl, bs=bs, n_blocks=b * nbl + 2, dtype=dtype
        )
        pk, pv = join_rows(pk, r), join_rows(pv, r)
        kv_len = jnp.asarray([1, 130, 257], jnp.int32)  # 1, 9 and 17 live pages of 20
        want = paged_attention(
            q, pk, pv, tables, kv_len - 1, kv_len, layer_index=layer, use_kernel=False
        )
        poison = 0  # the engine's garbage block: no table of the case maps it
        assert poison not in np.asarray(tables)
        pk, pv = pk.at[:, poison].set(jnp.nan), pv.at[:, poison].set(jnp.nan)
        dead = np.arange(nbl)[None, :] * bs >= np.asarray(kv_len)[:, None]
        tables = jnp.where(dead, poison, tables)
        got = np.asarray(
            paged_attention(
                q, pk, pv, tables, kv_len - 1, kv_len,
                layer_index=layer, use_kernel=True, interpret=True,
            ),
            np.float32,
        )
        assert np.isfinite(got).all()
        tol = 2e-5 if dtype == jnp.float32 else 3e-2
        np.testing.assert_allclose(got, np.asarray(want, np.float32), atol=tol, rtol=tol)

    @pytest.mark.parametrize(
        "bs,hk,d,dtype,nbl,pages",
        [
            pytest.param(16, 2, 128, jnp.bfloat16, 256, 16, id="qwen2vl-2b-4096"),
            pytest.param(16, 2, 128, jnp.bfloat16, 64, 16, id="qwen2vl-2b-1024"),
            pytest.param(16, 1, 128, jnp.bfloat16, 256, 32, id="qwen25vl-7b-shard"),
            pytest.param(16, 8, 64, jnp.bfloat16, 64, 8, id="base-d64"),
            pytest.param(16, 4, 128, jnp.bfloat16, 64, 8, id="d64-two-heads-a-row"),
            pytest.param(8, 2, 32, jnp.float32, 3, 3, id="shorter-table"),
        ],
    )
    def test_pages_a_group_come_from_the_shapes(self, bs, hk, d, dtype, nbl, pages):
        from cosmos_curate_tpu.ops.paged_attention import _decode_pages

        got = _decode_pages(bs, hk, d, dtype, nbl)
        assert got == pages
        assert got * bs >= min(128, nbl * bs)  # a group fills the MXU's 128 columns

    def test_decode_layers_share_one_trace(self):
        """The layer is a scalar the decode kernel reads at run time, so a
        model's 28 calls trace and lower the kernel once (a static index
        made it 28 times, which a program pays at every set-up: the
        persistent cache keeps compiled programs, not traces)."""
        from cosmos_curate_tpu.ops.paged_attention import _paged_decode

        rng = np.random.default_rng(8)
        b, hk, g, d, nbl, bs = 2, 2, 4, 128, 10, 16
        q, pk, pv, tables, _, _, _ = _fragmented_case(
            rng, b=b, t=1, hk=hk, g=g, d=d, nbl=nbl, bs=bs, n_blocks=b * nbl + 2
        )
        kv_len = jnp.asarray([150, 33], jnp.int32)
        _paged_decode.clear_cache()
        for layer in (0, 1):
            got = paged_attention(
                q, pk, pv, tables, kv_len - 1, kv_len,
                layer_index=layer, use_kernel=True, interpret=True,
            )
            want = paged_attention(
                q, pk, pv, tables, kv_len - 1, kv_len, layer_index=layer, use_kernel=False
            )
            np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=1e-4)
        assert _paged_decode._cache_size() == 1

    # write_index and valid tokens a row; ``poison``: every table entry past
    # a row's valid length points at a block of garbage (the engine points
    # them at its block 0), which the kernel must keep out of the result
    # while the reference reads the clean table: huge and finite where the
    # pipeline feeds the kernel (a skipped grid step's page is still
    # fetched), NaN where the kernel copies for itself (it starts no copy of
    # such an entry). A chunk's rows past ``t_valid`` are padding: the engine
    # reads none of them, but both sides define them the same way, except in
    # a row with NO valid key (a padding row, ``kv_len`` 0), where the kernel
    # runs no trip and returns zeros.
    @pytest.mark.parametrize("form,dtype", FORMS_AND_TYPES)
    @pytest.mark.parametrize(
        "t,write,t_valid,poison",
        [
            pytest.param(13, [0, 23], [13, 13], False, id="offset-and-ragged-t"),
            pytest.param(16, [0, 0], [16, 16], False, id="bucket-from-zero"),
            pytest.param(16, [16, 32], [16, 16], False, id="later-chunks"),
            pytest.param(16, [0, 20], [16, 9], False, id="padded-chunk"),
            pytest.param(16, [48, 5], [32, 40], False, id="valid-length-past-the-chunk"),
            pytest.param(8, [0, 17], [8, 8], True, id="garbage-past-the-valid-length"),
            pytest.param(16, [0, 30], [0, 16], True, id="padding-row-beside-one-group"),
            pytest.param(16, [150, 64], [16, 16], True, id="three-groups-and-two"),
            pytest.param(24, [299, 200], [24, 11], True, id="mid-page-writes-five-groups-padded"),
        ],
    )
    def test_prefill_kernel_matches_reference(self, groups_of_64_keys, t, write, t_valid, poison, form, dtype):
        """write_index 0 and > 0, a chunk length that does and does not tile
        block_q (the pad rows must not disturb the valid window), fewer
        valid tokens than the chunk holds, and more; a walk of one group, of
        an even and of an odd number."""
        hk, g, d, nbl, bs = PREFILL_FORMS[form]
        rng = np.random.default_rng(4)
        b = 2
        q, pk, pv, tables, layer, _, _ = _fragmented_case(
            rng, b=b, t=t, hk=hk, g=g, d=d, nbl=nbl, bs=bs, n_blocks=b * nbl + 2, dtype=dtype
        )
        write = jnp.asarray(write, jnp.int32)
        kv_len = write + jnp.asarray(t_valid, jnp.int32)
        want = paged_attention(
            q, pk, pv, tables, write, kv_len, layer_index=layer, use_kernel=False
        )
        if poison:
            assert 0 not in np.asarray(tables)
            bad = 1e6 if d == 16 else jnp.nan
            pk, pv = pk.at[:, 0].set(bad), pv.at[:, 0].set(-bad)
            dead = np.arange(nbl)[None, :] * bs >= np.asarray(kv_len)[:, None]
            tables = jnp.where(dead, 0, tables)
        got = np.asarray(
            paged_attention(
                q, pk, pv, tables, write, kv_len,
                layer_index=layer, use_kernel=True, interpret=True, block_q=8,
            ),
            np.float32,
        )
        assert np.isfinite(got).all()
        keyed = np.asarray(kv_len) > 0
        np.testing.assert_allclose(got[keyed], np.asarray(want, np.float32)[keyed], **_tolerance(dtype))
        assert not got[~keyed].any()

    @pytest.mark.parametrize(
        "bs,rows,nbl,pages",
        [
            pytest.param(128, 768, 96, 8, id="trinity-12288"),
            pytest.param(16, 768, 768, 64, id="trinity-12288-in-blocks-of-16"),
            pytest.param(16, 768, 256, 64, id="qwen2vl-2b-4096"),
            pytest.param(16, 896, 256, 48, id="qwen25vl-7b-shard"),
            pytest.param(16, 1024, 64, 48, id="granite-two-heads-a-row"),
            pytest.param(16, 768, 20, 20, id="shorter-table"),
            pytest.param(256, 48, 4, 4, id="pages-of-256"),
        ],
    )
    def test_prefill_pages_a_group_come_from_the_shapes(self, bs, rows, nbl, pages):
        from cosmos_curate_tpu.ops.paged_attention import _prefill_pages

        got = _prefill_pages(bs, rows, nbl)
        assert got == pages
        # whole MXU widths of keys, a float32 score tile within 3 MiB
        assert got * bs % 128 == 0 or got == nbl
        assert rows * got * bs * 4 <= 3 * 1024 * 1024 or got == 1

    def test_prefill_layers_share_one_trace(self):
        """The layer is a scalar the prefill kernels read at run time too."""
        from cosmos_curate_tpu.ops.paged_attention import _paged_prefill

        rng = np.random.default_rng(8)
        b, t, hk, g, d, nbl, bs = 2, 16, 2, 3, 128, 10, 16
        q, pk, pv, tables, _, _, _ = _fragmented_case(
            rng, b=b, t=t, hk=hk, g=g, d=d, nbl=nbl, bs=bs, n_blocks=b * nbl + 2
        )
        write = jnp.asarray([100, 3], jnp.int32)
        _paged_prefill.clear_cache()
        for layer in (0, 1):
            got = paged_attention(
                q, pk, pv, tables, write, write + t, layer_index=layer, use_kernel=True, interpret=True
            )
            want = paged_attention(q, pk, pv, tables, write, write + t, layer_index=layer, use_kernel=False)
            np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=1e-4)
        assert _paged_prefill._cache_size() == 1

    def test_bf16_kernel_within_online_softmax_tolerance(self):
        """bf16 online softmax (kernel) vs dense softmax (reference) differ
        by a couple of ulps at magnitude ~1 — the engine's byte contract
        lives on the reference path, the kernel only owes float agreement."""
        rng = np.random.default_rng(5)
        b, hk, g, d, nbl, bs = 2, 2, 4, 16, 4, 16
        q, pk, pv, tables, layer, _, _ = _fragmented_case(
            rng, b=b, t=1, hk=hk, g=g, d=d, nbl=nbl, bs=bs,
            n_blocks=b * nbl + 2, dtype=jnp.bfloat16,
        )
        kv_len = jnp.asarray([nbl * bs, 17], jnp.int32)
        got = paged_attention(
            q, pk, pv, tables, kv_len - 1, kv_len,
            layer_index=layer, use_kernel=True, interpret=True,
        )
        want = paged_attention(
            q, pk, pv, tables, kv_len - 1, kv_len, layer_index=layer, use_kernel=False
        )
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32), atol=3e-2, rtol=3e-2
        )


# (Hkv, G, D) whose pool the engine stores two KV heads a 128-lane row
PACKED_WIDTHS = {name: WIDTHS[name] for name in ("base", "granite-4.0-h-micro")}


class TestWindow:
    """``window=W``: query ``i`` sees keys ``i - W < j <= i``. The reference
    against a dense oracle with the same mask; the kernels (both decode forms,
    and prefill) against the reference at the window's edges and at page edges;
    and no page wholly behind the window is fetched."""

    @staticmethod
    def _dense(q, k_cache, v_cache, write, kv_len, sm_scale, window):
        s = k_cache.shape[2]
        logits = jnp.einsum("btkgd,bksd->bkgts", q.astype(jnp.float32) * sm_scale, k_cache.astype(jnp.float32))
        k_pos = jnp.arange(s)[None, None, None, None, :]
        q_pos = (write[:, None] + jnp.arange(q.shape[1])[None, :])[:, None, None, :, None]
        seen = (k_pos <= q_pos) & (k_pos > q_pos - window) & (k_pos < kv_len[:, None, None, None, None])
        probs = jax.nn.softmax(jnp.where(seen, logits, -1e30), axis=-1)
        return jnp.einsum("bkgts,bksd->btkgd", probs, v_cache.astype(jnp.float32))

    @pytest.mark.parametrize("t", [1, 24], ids=["decode", "chunk"])
    def test_reference_matches_a_dense_oracle(self, t):
        rng = np.random.default_rng(5)
        b, hk, g, d, nbl, bs, window = 3, 2, 3, 16, 8, 16, 40
        q, pk, pv, tables, layer, kc, vc = _fragmented_case(
            rng, b=b, t=t, hk=hk, g=g, d=d, nbl=nbl, bs=bs, n_blocks=b * nbl + 2
        )
        kv_len = jnp.asarray([30, 41, 128], jnp.int32)
        write = kv_len - t
        got = paged_attention(q, pk, pv, tables, write, kv_len, layer_index=layer, use_kernel=False, window=window)
        want = self._dense(q, kc, vc, write, kv_len, d**-0.5, window)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=1e-4)
        # and a window no shorter than the context is no window
        free = paged_attention(q, pk, pv, tables, write, kv_len, layer_index=layer, use_kernel=False)
        wide = paged_attention(q, pk, pv, tables, write, kv_len, layer_index=layer, use_kernel=False, window=128)
        np.testing.assert_allclose(np.asarray(wide), np.asarray(free), atol=1e-6)

    # W = 40 over pages of 16, groups of 8 pages (128 keys): the edges W - 1, W,
    # W + 1; a first key on a page's first and last slot; a first group past 0
    @pytest.mark.parametrize(
        "hk,g,d", [(2, 4, 128), (2, 2, 16), (4, 2, 64)], ids=["own-copies", "pipeline-d16", "d64-packed"]
    )
    @pytest.mark.parametrize(
        "window,kv_len",
        [
            pytest.param(40, [39, 40, 41], id="edges-of-the-window"),
            pytest.param(40, [56, 55, 57], id="first-key-at-page-edges"),
            pytest.param(40, [1, 300, 169], id="idle-row-and-later-groups"),
            pytest.param(130, [320, 131, 258], id="window-over-a-group"),
            pytest.param(16, [16, 17, 32], id="window-of-one-page"),
        ],
    )
    def test_decode_kernels_match_the_reference_and_skip_what_is_behind(self, hk, g, d, window, kv_len):
        rng = np.random.default_rng(9)
        b, nbl, bs = 3, 20, 16
        q, pk, pv, tables, layer, _, _ = _fragmented_case(
            rng, b=b, t=1, hk=hk, g=g, d=d, nbl=nbl, bs=bs, n_blocks=b * nbl + 2
        )
        r = heads_per_row(hk, d)
        pk, pv = join_rows(pk, r), join_rows(pv, r)
        kv_len = jnp.asarray(kv_len, jnp.int32)
        want = paged_attention(
            q, pk, pv, tables, kv_len - 1, kv_len, layer_index=layer, use_kernel=False, window=window
        )
        # every entry wholly behind a row's window, or at or past its length,
        # names a block of NaN: the kernels fetch none of them
        page = np.arange(nbl)[None, :]
        lens = np.asarray(kv_len)[:, None]
        dead = (page * bs >= lens) | ((page + 1) * bs <= np.maximum(lens - window, 0))
        assert dead.any() and 0 not in np.asarray(tables)
        pk, pv = pk.at[:, 0].set(jnp.nan), pv.at[:, 0].set(jnp.nan)
        got = paged_attention(
            q, pk, pv, jnp.where(dead, 0, tables), kv_len - 1, kv_len,
            layer_index=layer, use_kernel=True, interpret=True, window=window,
        )
        assert np.isfinite(np.asarray(got)).all()
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=1e-4)

    # groups of 64 keys: a window of 100 starts mid-group; ``write`` past the
    # window: whole groups behind it, their entries poisoned with the dead ones
    @pytest.mark.parametrize("form,dtype", FORMS_AND_TYPES)
    @pytest.mark.parametrize(
        "window,t,write,t_valid",
        [
            pytest.param(40, 16, [24, 25, 23], [16, 16, 16], id="edges-of-the-window"),
            pytest.param(40, 32, [0, 64, 100], [32, 32, 20], id="from-zero-later-and-padded"),
            pytest.param(16, 24, [8, 40, 96], [24, 24, 24], id="window-under-the-chunk"),
            pytest.param(200, 16, [0, 90, 112], [16, 16, 16], id="window-over-the-context"),
            pytest.param(100, 16, [150, 201, 333], [16, 16, 9], id="window-starts-mid-group"),
            pytest.param(130, 24, [0, 256, 360], [0, 24, 24], id="padding-row-and-writes-past-the-window"),
        ],
    )
    def test_prefill_kernel_matches_the_reference_and_skips_what_is_behind(
        self, groups_of_64_keys, window, t, write, t_valid, form, dtype
    ):
        hk, g, d, nbl, bs = PREFILL_FORMS[form]
        rng = np.random.default_rng(10)
        b = 3
        q, pk, pv, tables, layer, _, _ = _fragmented_case(
            rng, b=b, t=t, hk=hk, g=g, d=d, nbl=nbl, bs=bs, n_blocks=b * nbl + 2, dtype=dtype
        )
        write = jnp.asarray(write, jnp.int32)
        kv_len = write + jnp.asarray(t_valid, jnp.int32)
        want = paged_attention(
            q, pk, pv, tables, write, kv_len, layer_index=layer, use_kernel=False, window=window
        )
        # every entry at or past a row's valid length, and every entry wholly
        # behind its first query's window, names a block of garbage (NaN
        # where the kernel copies for itself: it starts no copy of either)
        page = np.arange(nbl)[None, :]
        dead = (page * bs >= np.asarray(kv_len)[:, None]) | (
            (page + 1) * bs <= np.maximum(np.asarray(write)[:, None] - window + 1, 0)
        )
        assert dead.any() and 0 not in np.asarray(tables)
        bad = 1e6 if d == 16 else jnp.nan
        pk, pv = pk.at[:, 0].set(bad), pv.at[:, 0].set(-bad)
        got = np.asarray(
            paged_attention(
                q, pk, pv, jnp.where(dead, 0, tables), write, kv_len,
                layer_index=layer, use_kernel=True, interpret=True, block_q=8, window=window,
            ),
            np.float32,
        )
        assert np.isfinite(got).all()
        valid = np.arange(t)[None, :] < np.asarray(t_valid)[:, None]  # padding rows are nobody's
        np.testing.assert_allclose(got[valid], np.asarray(want, np.float32)[valid], **_tolerance(dtype))

    def test_a_table_that_repeats_a_ring_of_blocks_reads_the_newest(self):
        """The engine's window table: logical block ``j`` in ring block ``j %
        ring``. Under the window the kernels and the reference read only
        entries whose ring block still holds their own positions."""
        rng = np.random.default_rng(11)
        hk, g, d, bs, ring, nbl, window = 2, 2, 16, 4, 6, 20, 10
        kv_len = jnp.asarray([77], jnp.int32)
        blocks = np.arange(1, ring + 1)
        tables = jnp.asarray(np.resize(blocks, nbl)[None], jnp.int32)
        # the ring as 77 positions written in order leave it, and the
        # contiguous cache those positions would make
        k_all = rng.standard_normal((nbl * bs, hk, d)).astype(np.float32)
        v_all = rng.standard_normal((nbl * bs, hk, d)).astype(np.float32)
        pk, pv = np.zeros((1, ring + 1, hk, bs, d), np.float32), np.zeros((1, ring + 1, hk, bs, d), np.float32)
        for pos in range(77):
            block, off = blocks[(pos // bs) % ring], pos % bs
            pk[0, block, :, off], pv[0, block, :, off] = k_all[pos], v_all[pos]
        q = jnp.asarray(rng.standard_normal((1, 1, hk, g, d)), jnp.float32)
        want = self._dense(
            q, jnp.asarray(k_all.transpose(1, 0, 2)[None]), jnp.asarray(v_all.transpose(1, 0, 2)[None]),
            kv_len - 1, kv_len, d**-0.5, window,
        )
        for kernel in (False, True):
            got = paged_attention(
                q, jnp.asarray(pk), jnp.asarray(pv), tables, kv_len - 1, kv_len,
                use_kernel=kernel, interpret=True, window=window,
            )
            np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=1e-4)


class TestPackedPool:
    """A pool of ``r`` KV heads a row (``[L, NB, Hkv / r, bs, r * D]``, made by
    ``join_rows`` as ``init_block_pool`` shapes it) against the same K/V one
    head a row: the XLA reference to the bit (it splits the pages it gathered
    and runs the same lines on the same shapes), the kernels to the tolerance
    they owe the reference (their groups of pages differ with the page's
    shape, so their online softmax rounds differently)."""

    @staticmethod
    def _case(widths, t, dtype):
        hk, g, d = PACKED_WIDTHS[widths]
        rng = np.random.default_rng(11 + t)
        b, nbl, bs = 3, 20, 16
        q, pk, pv, tables, layer, _, _ = _fragmented_case(
            rng, b=b, t=t, hk=hk, g=g, d=d, nbl=nbl, bs=bs, n_blocks=b * nbl + 2, dtype=dtype
        )
        kv_len = jnp.asarray([320, 37, 129], jnp.int32)
        write = kv_len - t
        r = heads_per_row(hk, d)
        assert r == 2
        return (q, pk, pv, tables, write, kv_len), (q, join_rows(pk, r), join_rows(pv, r), tables, write, kv_len), layer

    @pytest.mark.parametrize("t", [1, 16], ids=["decode", "chunk-T16"])
    @pytest.mark.parametrize("widths", sorted(PACKED_WIDTHS))
    def test_reference_is_bit_equal_to_the_unpacked_pool(self, widths, t):
        unpacked, packed, layer = self._case(widths, t, jnp.bfloat16)
        assert packed[1].shape[2:] == (4, 16, 128)
        want = paged_attention(*unpacked, layer_index=layer, use_kernel=False)
        got = paged_attention(*packed, layer_index=layer, use_kernel=False)
        assert got.shape == want.shape and got.dtype == want.dtype
        np.testing.assert_array_equal(np.asarray(got, np.float32), np.asarray(want, np.float32))

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
    @pytest.mark.parametrize("t", [1, 16], ids=["decode", "chunk-T16"])
    @pytest.mark.parametrize("widths", sorted(PACKED_WIDTHS))
    def test_kernels_out_of_a_packed_pool_match_the_reference(self, widths, t, dtype):
        """The decode kernel that copies for itself and the prefill kernel,
        handed ``Hkv / r`` heads of ``r * G`` query rows and ``r * D`` lanes:
        a head's zeros in its neighbour's lanes keep the neighbour's keys
        out of its scores, and its own lanes of the output are its values."""
        unpacked, packed, layer = self._case(widths, t, dtype)
        want = paged_attention(*unpacked, layer_index=layer, use_kernel=False)
        got = paged_attention(
            *packed, layer_index=layer, use_kernel=True, interpret=True, block_q=8
        )
        np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want, np.float32), **_tolerance(dtype))

    def test_scale_defaults_from_the_true_head_dim(self):
        unpacked, packed, layer = self._case("base", 1, jnp.float32)
        want = paged_attention(*unpacked, layer_index=layer, sm_scale=64**-0.5, use_kernel=False)
        for use_kernel in (False, True):
            got = paged_attention(*packed, layer_index=layer, use_kernel=use_kernel, interpret=True)
            np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=1e-4)

    @pytest.mark.parametrize(
        "hk,d,r",
        [
            pytest.param(8, 64, 2, id="base-and-granite"),
            pytest.param(2, 128, 1, id="qwen2vl-2b"),
            pytest.param(1, 128, 1, id="one-head-a-chip"),
            pytest.param(2, 16, 1, id="tiny-test-makes-no-tile"),
            pytest.param(8, 16, 8, id="eight-heads-of-16"),
            pytest.param(3, 64, 1, id="odd-heads-are-not-padded"),
            pytest.param(4, 96, 1, id="96-divides-no-tile"),
            pytest.param(4, 256, 1, id="wider-than-a-tile"),
        ],
    )
    def test_heads_a_row_come_from_the_widths(self, hk, d, r):
        assert heads_per_row(hk, d) == r
        pages = jnp.arange(5 * hk * 16 * d, dtype=jnp.float32).reshape(5, hk, 16, d)
        packed = join_rows(pages, r)
        assert packed.shape == (5, hk // r, 16, r * d)
        # head j of a row lives in lanes [j * D, (j + 1) * D)
        for j in range(r):
            np.testing.assert_array_equal(
                np.asarray(packed[:, :, :, j * d : (j + 1) * d]), np.asarray(pages[:, j::r])
            )
        np.testing.assert_array_equal(np.asarray(split_rows(packed, r)), np.asarray(pages))


class TestHeadParallel:
    def test_sharded_heads_two_a_row_bit_equal_to_single_device(self, cpu_mesh):
        """Eight 64-wide KV heads over the model axis (4): two a chip, one
        pool row a chip, the row's heads never on two chips."""
        rng = np.random.default_rng(12)
        b, hk, g, d, nbl, bs = 2, 8, 2, 64, 3, 8
        q, pk, pv, tables, layer, _, _ = _fragmented_case(
            rng, b=b, t=1, hk=hk, g=g, d=d, nbl=nbl, bs=bs, n_blocks=b * nbl + 2
        )
        kv_len = jnp.asarray([nbl * bs, 11], jnp.int32)
        r = heads_per_row(hk // 4, d)
        assert r == 2
        sharded = paged_head_attention(
            cpu_mesh, q, join_rows(pk, r), join_rows(pv, r), tables, kv_len - 1, kv_len,
            layer_index=layer, use_kernel=False,
        )
        planes = [
            paged_attention(
                q[:, :, h : h + 2], pk[:, :, h : h + 2], pv[:, :, h : h + 2],
                tables, kv_len - 1, kv_len, layer_index=layer, use_kernel=False,
            )
            for h in range(0, hk, 2)
        ]
        assert np.array_equal(np.asarray(sharded), np.concatenate(planes, axis=2))

    def test_sharded_heads_bit_equal_to_single_device(self, cpu_mesh):
        """shard_map over the model axis (Hkv sharded, tables replicated)
        must be BIT-equal to the unsharded op run on each shard's head
        plane: head planes never interact in attention, so sharding adds
        nothing to the arithmetic. (Against the unsharded op over ALL
        heads at once XLA's CPU dot may pick another summation order for
        the larger head batch — that comparison owes float agreement.)"""
        rng = np.random.default_rng(6)
        b, hk, g, d, nbl, bs = 2, 4, 2, 16, 3, 8  # hk divides model axis (4)
        q, pk, pv, tables, layer, _, _ = _fragmented_case(
            rng, b=b, t=1, hk=hk, g=g, d=d, nbl=nbl, bs=bs, n_blocks=b * nbl + 2
        )
        kv_len = jnp.asarray([nbl * bs, 11], jnp.int32)
        sharded = paged_head_attention(
            cpu_mesh, q, pk, pv, tables, kv_len - 1, kv_len,
            layer_index=layer, use_kernel=False,
        )
        planes = [
            paged_attention(
                q[:, :, h : h + 1], pk[:, :, h : h + 1], pv[:, :, h : h + 1],
                tables, kv_len - 1, kv_len, layer_index=layer, use_kernel=False,
            )
            for h in range(hk)
        ]
        assert np.array_equal(np.asarray(sharded), np.concatenate(planes, axis=2))
        single = paged_attention(
            q, pk, pv, tables, kv_len - 1, kv_len, layer_index=layer, use_kernel=False
        )
        np.testing.assert_allclose(np.asarray(sharded), np.asarray(single), atol=1e-6, rtol=0)


def test_kernel_choice_reads_no_environment():
    """Which attention implementation runs is decided in
    ``ops/paged_attention.py`` from the platform and the shapes; the model
    and the kernels consult no environment variable (the four switches that
    did are gone, and none comes back unnoticed)."""
    import pathlib
    import re

    import cosmos_curate_tpu

    root = pathlib.Path(cosmos_curate_tpu.__file__).parent
    hits = [
        f"{path.relative_to(root)}:{n}: {line.strip()}"
        for sub in ("models/vlm", "ops")
        for path in sorted((root / sub).rglob("*.py"))
        for n, line in enumerate(path.read_text().splitlines(), 1)
        if re.search(r"\benviron\b|\bgetenv\b", line)
    ]
    assert not hits, hits
