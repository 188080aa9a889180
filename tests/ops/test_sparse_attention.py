"""``ops/sparse_attention.py``: each Pallas kernel in interpret mode against its
XLA lines (ties included), the choice against a sort in numpy, a decode step's
gather and its walk against the dense lines under their mask, the one choice
between the two, and the kernels compiled for a described v5e at the served
widths."""

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cosmos_curate_tpu.ops import sparse_attention as sa

L, NB, BS, W, DI, HI = 2, 40, 4, 128, 8, 4
HK, G, D = 2, 2, 16
B, NBL = 3, 8
S = NBL * BS
# (T, write offsets, valid lengths): a decode step, a whole chunk, a chunk that
# starts inside a block and one row of which is short of the others
CHUNKS = {
    "decode": (1, [5, 17, 30], [6, 18, 31]),
    "chunk-of-8": (8, [0, 9, 20], [8, 17, 26]),
    "ragged-chunk": (12, [3, 0, 18], [15, 7, 30]),
}


@pytest.fixture(scope="module")
def pools():
    rng = np.random.default_rng(0)
    keys = jnp.asarray(rng.normal(size=(L, NB, 1, BS, DI)), jnp.bfloat16)
    pool_i = jnp.zeros((L, NB, 1, BS, W), jnp.bfloat16).at[..., :DI].set(keys)
    pool_k = jnp.asarray(rng.normal(size=(L, NB, HK, BS, D)), jnp.bfloat16)
    pool_v = jnp.asarray(rng.normal(size=(L, NB, HK, BS, D)), jnp.bfloat16)
    tables = jnp.asarray(rng.permutation(NB - 1)[: B * NBL].reshape(B, NBL) + 1, jnp.int32)
    return pool_i, pool_k, pool_v, tables


def _chunk(name, seed=1):
    t, write, kv_len = CHUNKS[name]
    rng = np.random.default_rng(seed)
    qi = jnp.asarray(rng.normal(size=(B, t, HI, DI)), jnp.bfloat16)
    w = jnp.asarray(rng.normal(size=(B, t, HI)), jnp.float32)
    return t, qi, w, jnp.asarray(write, jnp.int32), jnp.asarray(kv_len, jnp.int32)


def _scores(pools, name, ties=False):
    pool_i, _, _, tables = pools
    t, qi, w, write, kv_len = _chunk(name)
    scores = sa.index_scores(qi, w, pool_i, tables, write, kv_len, layer_index=1, use_kernel=False)
    if ties:  # whole numbers: many equal scores, the choice must break them by position
        scores = jnp.where(jnp.isfinite(scores), jnp.round(scores) + 0.0, scores)  # (+ 0.0: no -0.0, the op's contract)
    live = jnp.minimum(kv_len[:, None], write[:, None] + jnp.arange(t)[None] + 1)
    return scores, live, write, kv_len


def _numpy_order(row):
    """The definition: what a query may choose, the highest score first, a tie to the lower position."""
    return [s for s in sorted(range(row.size), key=lambda s: (-float(row[s]), s)) if np.isfinite(row[s])]


def _numpy_choice(scores, k):
    """The k first of the definition's order, as a mask."""
    out = np.zeros(scores.shape, bool)
    for idx in np.ndindex(scores.shape[:-1]):
        out[idx][_numpy_order(scores[idx])[:k]] = True
    return out


@pytest.mark.parametrize("name", sorted(CHUNKS))
def test_index_score_kernel_matches_its_xla_lines(pools, name):
    pool_i, _, _, tables = pools
    _, qi, w, write, kv_len = _chunk(name)
    want = np.asarray(sa.index_scores(qi, w, pool_i, tables, write, kv_len, layer_index=1, use_kernel=False))
    got = np.asarray(sa.index_scores(qi, w, pool_i, tables, write, kv_len, layer_index=1, use_kernel=True, interpret=True))
    seen = np.isfinite(want)
    assert (np.isfinite(got) == seen).all()  # -inf exactly where a query may not choose
    np.testing.assert_allclose(got[seen], want[seen], atol=1e-5)
    t = qi.shape[1]
    pos = np.arange(S)[None, None]
    q_pos = np.asarray(write)[:, None, None] + np.arange(t)[None, :, None]
    assert (seen == ((pos <= q_pos) & (pos < np.asarray(kv_len)[:, None, None]))).all()


def test_index_scores_are_the_formula(pools):
    """sum_j w[j] * relu(q[j] . k), in float64 numpy, out of the paged array."""
    pool_i, _, _, tables = pools
    _, qi, w, write, kv_len = _chunk("ragged-chunk")
    got = np.asarray(sa.index_scores(qi, w, pool_i, tables, write, kv_len, layer_index=1, use_kernel=False))
    keys = np.asarray(pool_i[1], np.float64)[np.asarray(tables)][:, :, 0].reshape(B, S, W)[..., :DI]
    dots = np.einsum("bthd,bsd->bths", np.asarray(qi, np.float64), keys)
    want = (np.maximum(dots, 0) * np.asarray(w, np.float64)[..., None]).sum(axis=2)
    seen = np.isfinite(got)
    np.testing.assert_allclose(got[seen], want[seen], atol=1e-4)


def _numpy_numbers(scores, k):
    """``(tau, p_star)`` by the definition: the key and the position of the k-th
    in the order (score down, position up); rows with fewer than k to choose
    from get None."""
    def key(x):
        i = int(np.float32(x).view(np.int32))
        return i if i >= 0 else i ^ 0x7FFFFFFF

    out = {}
    for idx in np.ndindex(scores.shape[:-1]):
        order = _numpy_order(scores[idx])
        out[idx] = (key(scores[idx][order[k - 1]]), order[k - 1]) if len(order) >= k else None
    return out


def _bits(*patterns):
    return np.array(patterns, np.uint32).view(np.float32)


CRAFT_S, CRAFT_K = 64, 8
ROWS = sa._SELECT_ROWS  # the queries a block of the threshold kernel holds


def _asked(depth):
    """The value passes a block runs whose last query settles after ``depth``
    bits: the kernel asks whether it may stop every ``_BITS_A_CHECK`` bits."""
    step = sa._BITS_A_CHECK
    return -(-depth // step) * step


def _crafted(name):
    """Scores that force one of the threshold kernel's exits: ``[1, T, 64]``
    against a top-k of 8, ``T`` queries in blocks of ``ROWS``. Returns (scores,
    live, the value passes each block must have run or None, whether each block
    must have run the tie search)."""
    rng = np.random.default_rng(CRAFTED.index(name))
    t, live = ROWS, np.full(ROWS, 40)

    def spread(n, low, high):  # n distinct values, shuffled
        return rng.permutation(np.linspace(low, high, n)).astype(np.float32)

    def rows(values_of):  # a row: its live values in position order, -inf after them
        out = np.full((t, CRAFT_S), -np.inf, np.float32)
        for r in range(t):
            out[r, : live[r]] = values_of(r)
        return out

    def ordinary(r):
        return rng.standard_normal(live[r]).astype(np.float32) + 0.0

    passes, tied = None, [False]
    if name == "part-in-the-first-bit":  # k above zero, the others below: the sign bit settles the set
        scores = rows(lambda r: rng.permutation(np.concatenate([spread(CRAFT_K, 1, 2), spread(32, -2, -1)])))
        passes = [_asked(1)]
    elif name == "equal-but-for-the-last-bit":  # the k-th and the next key part in bit 0: all 32 passes, no tie
        pair = _bits(0x3F800001, 0x3F800000)
        scores = rows(lambda r: rng.permutation(np.concatenate([spread(CRAFT_K - 1, 2, 3), pair, spread(31, 0.1, 0.9)])))
        passes = [32]
    elif name == "every-score-equal":
        scores = rows(lambda r: np.full(live[r], 1.5, np.float32))
        passes, tied = [32], [True]
    elif name == "a-tie-in-one-row-of-two-blocks":  # row 3 needs 3 of 6 equal scores; every other row ties nowhere
        t, live = 2 * ROWS, np.full(2 * ROWS, 40)
        tying = np.concatenate([spread(5, 2, 3), np.full(6, 1.0, np.float32), spread(29, -1, 0.5)])
        scores = rows(lambda r: rng.permutation(tying) if r == 3 else ordinary(r))
        tied = [True, False]
    elif name == "k-keys-exactly":  # row 2 may choose from exactly k: it takes them all, and its numbers are the sort's
        live[2] = CRAFT_K
        scores = rows(ordinary)
    elif name == "denormals-and-padding":
        tiny = np.concatenate([_bits(*range(1, 21)), [np.float32(0.0)], _bits(*range(0x80000001, 0x80000014))])
        scores = rows(lambda r: rng.permutation(tiny))
    elif name == "just-over-k":
        live = np.full(ROWS, CRAFT_K + 1)
        scores = rows(ordinary)
    elif name == "the-block-that-straddles-k":  # a causal chunk: its first queries see fewer than k, one k, the rest more
        live = np.arange(CRAFT_K - 3, CRAFT_K - 3 + ROWS)
        scores = rows(ordinary)
    elif name == "rows-at-different-depths":  # row 5 settles at bit 11 (21 passes), every other row at the sign bit
        pair = _bits(0x3F800800, 0x3F800000)
        deep = np.concatenate([spread(CRAFT_K - 1, 2, 3), pair, spread(31, 0.1, 0.9)])
        shallow = np.concatenate([spread(CRAFT_K, 1, 2), spread(32, -2, -1)])
        scores = rows(lambda r: rng.permutation(deep) if r == 5 else rng.permutation(shallow))
        passes = [_asked(21)]
    return scores[None], live[None], passes, tied


CRAFTED = (
    "part-in-the-first-bit", "equal-but-for-the-last-bit", "every-score-equal", "a-tie-in-one-row-of-two-blocks",
    "k-keys-exactly", "denormals-and-padding", "just-over-k", "the-block-that-straddles-k", "rows-at-different-depths",
)
SELECT_CASES = [
    pytest.param(name, k, ties, id=f"{name}-{k}-{'ties' if ties else 'distinct'}")
    for ties in (False, True) for k in (3, 6, 40) for name in sorted(CHUNKS)
] + [pytest.param(name, CRAFT_K, None, id=name) for name in CRAFTED]


@pytest.mark.parametrize("name,k,ties", SELECT_CASES)
def test_select_kernel_and_sort_choose_what_the_definition_chooses(pools, name, k, ties):
    """The masks are the definition's; wherever a query leaves something out the
    kernel's two numbers ARE the sort's (and numpy's); and the kernel's own
    account says which exit it took: never more than 32 value passes, fewer
    where the keys part early, the tie search only in a block where a tie
    straddles k."""
    if name in CHUNKS:
        scores, live, _, _ = _scores(pools, name, ties)
        want_passes, want_tied = None, None
    else:
        scores, live, want_passes, want_tied = _crafted(name)
        scores, live = jnp.asarray(scores), jnp.asarray(live, jnp.int32)
    want = _numpy_choice(np.asarray(scores), k)
    by_sort = sa.select_threshold(scores, k, live, use_kernel=False)
    tau, p_star, passes, tied = (
        np.asarray(x) for x in sa.select_threshold(scores, k, live, use_kernel=True, interpret=True, with_passes=True)
    )
    np.testing.assert_array_equal(np.asarray(sa.chosen_mask(scores, *by_sort)), want)
    np.testing.assert_array_equal(np.asarray(sa.chosen_mask(scores, jnp.asarray(tau), jnp.asarray(p_star))), want)
    assert (want.sum(-1) == np.minimum(np.asarray(live), k)).all()  # every position while there are no more than k
    plain = sa.select_threshold(scores, k, live, use_kernel=True, interpret=True)  # the program everybody else runs
    assert len(plain) == 2 and (np.asarray(plain[0]) == tau).all() and (np.asarray(plain[1]) == p_star).all()
    live, passes, tied = np.asarray(live).reshape(-1), passes.reshape(-1), tied.reshape(-1) > 0
    # a block (rows as the kernel lays them: batch x query, flattened) answers at once unless one of its queries chooses
    starts = range(0, live.size, ROWS)
    searches = np.repeat([(live[i : i + ROWS] > k).any() for i in starts], ROWS)[: live.size]
    for flat, (idx, number) in enumerate(_numpy_numbers(np.asarray(scores), k).items()):
        if live[flat] > k or (live[flat] == k and searches[flat]):
            assert (int(tau[idx]), int(p_star[idx])) == number, (idx, "the kernel against the definition")
        if live[flat] > k:
            assert (int(by_sort[0][idx]), int(by_sort[1][idx])) == number, (idx, "the sort against the definition")
    assert ((0 <= passes) & (passes <= 32)).all() and not passes[~searches].any() and not tied[~searches].any()
    assert (passes[tied] == 32).all()
    if want_passes is not None:
        assert passes[::ROWS].tolist() == want_passes
    if want_tied is not None:
        assert tied.tolist() == np.repeat(want_tied, ROWS).tolist()


def test_order_key_keeps_the_order_of_floats():
    x = np.array([-np.inf, -3.5, -1e-30, 0.0, 1e-30, 2.0, 7.25, np.inf], np.float32)
    keys = np.asarray(sa.order_key(jnp.asarray(x)))
    assert (np.diff(keys.astype(np.int64)) > 0).all() and keys[0] == sa.KEY_UNSEEN


@pytest.mark.parametrize("name", sorted(CHUNKS))
def test_prefill_kernel_matches_dense_attention_under_the_mask(pools, name):
    _, pool_k, pool_v, tables = pools
    scores, live, write, kv_len = _scores(pools, name, ties=True)
    chosen = sa.chosen_mask(scores, *sa.select_threshold(scores, 6, live, use_kernel=False))
    t = scores.shape[1]
    q = jnp.asarray(np.random.default_rng(4).normal(size=(B, t, HK, G, D)), jnp.bfloat16)
    call = functools.partial(
        sa.sparse_prefill_attention, q, pool_k, pool_v, tables, write, kv_len, chosen, layer_index=1
    )
    want, got = np.asarray(call(use_kernel=False), np.float32), np.asarray(call(use_kernel=True, interpret=True), np.float32)
    np.testing.assert_allclose(got, want, atol=2e-2)  # the kernel hands back bfloat16
    # and the mask bites: attention over every visible position is something else
    seen = jnp.isfinite(scores)
    dense = np.asarray(sa.sparse_prefill_attention(q, pool_k, pool_v, tables, write, kv_len, seen, layer_index=1, use_kernel=False))
    if t > 1:
        assert np.abs(dense - want).max() > 0.05


def test_decode_reads_the_chosen_positions_and_no_others(pools):
    """The gather's result is the dense lines under the chosen set's mask; a
    position that was not chosen can hold anything (NaN) and change nothing."""
    _, pool_k, pool_v, tables = pools
    scores, _, write, kv_len = _scores(pools, "decode", ties=True)
    k = 6
    positions, valid, tau, p_star = sa.decode_positions(scores[:, 0], k)
    chosen = sa.chosen_mask(scores[:, 0], tau, p_star)
    np.testing.assert_array_equal(np.asarray(chosen), _numpy_choice(np.asarray(scores[:, 0]), k))
    for b in range(B):  # the positions ARE the set
        assert sorted(np.asarray(positions[b])[np.asarray(valid[b])]) == np.nonzero(np.asarray(chosen[b]))[0].tolist()
    q = jnp.asarray(np.random.default_rng(5).normal(size=(B, HK, G, D)), jnp.bfloat16)
    got = sa.sparse_decode_attention(q, pool_k, pool_v, tables, positions, valid, layer_index=1)
    want = sa.sparse_prefill_attention(
        q[:, None], pool_k, pool_v, tables, write, kv_len, chosen[:, None], layer_index=1, use_kernel=False
    )[:, 0]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
    # poison every position of the rows' blocks that was NOT chosen
    flat = np.asarray(pool_k, np.float32).copy()
    for b in range(B):
        for s in range(S):
            if not bool(chosen[b, s]):
                flat[1, int(tables[b, s // BS]), :, s % BS] = np.nan
    poisoned = sa.sparse_decode_attention(q, jnp.asarray(flat, jnp.bfloat16), pool_v, tables, positions, valid, layer_index=1)
    np.testing.assert_allclose(np.asarray(poisoned), np.asarray(got), atol=1e-6)


def test_a_row_with_fewer_positions_than_k_takes_them_all(pools):
    scores, _, _, kv_len = _scores(pools, "decode")
    positions, valid, tau, p_star = sa.decode_positions(scores[:, 0], 12)
    assert np.asarray(valid.sum(-1)).tolist() == np.minimum(np.asarray(kv_len), 12).tolist()
    assert int(tau[0]) == sa.KEY_UNSEEN  # row 0 sees 6 positions: nothing is left out


def test_pack_choice_is_a_bit_a_position():
    chosen = np.zeros((2, 70), bool)
    chosen[0, [0, 31, 32, 69]] = True
    chosen[1, 5] = True
    words = np.asarray(sa.pack_choice(jnp.asarray(chosen)))
    assert words.shape == (2, 3) and words.dtype == np.uint32
    back = ((words[..., None] >> np.arange(32, dtype=np.uint32)) & 1).reshape(2, -1)[:, :70].astype(bool)
    np.testing.assert_array_equal(back, chosen)



# -- a decode step's walk under the chosen set's mask ---------------------------

WALK_G, WALK_NBL = 8, 4  # Keye's eight query rows a KV head; a lane of four pages


def _walk_case(bs, ties):
    """Five rows of a four-page lane against a top-k of ``bs + bs // 2``: a context
    below it, a dead row between live ones, one AT it, one past it whose last page
    is partly filled, and the whole lane. Table entries past a row's valid length
    name a block of NaNs: a kernel that copied one would say so."""
    rng = np.random.default_rng(bs + ties)
    k, lane = bs + bs // 2, WALK_NBL * bs
    kv_len = np.array([bs - 3, 0, k, 2 * bs + bs // 3, lane], np.int32)
    nb = len(kv_len) * WALK_NBL + 2
    poison = nb - 1
    pool_k = np.asarray(rng.normal(size=(L, nb, HK, bs, D)), np.float32)
    pool_v = np.asarray(rng.normal(size=(L, nb, HK, bs, D)), np.float32)
    pool_k[:, poison], pool_v[:, poison] = np.nan, np.nan
    tables = rng.permutation(nb - 2)[: len(kv_len) * WALK_NBL].reshape(-1, WALK_NBL) + 1
    tables = np.where(np.arange(WALK_NBL)[None] * bs < kv_len[:, None], tables, poison)
    scores = rng.normal(size=(len(kv_len), lane)) * 2
    if ties:  # whole numbers: the k-th largest score is shared, the mask must break it by position
        scores = np.round(scores)
    scores = np.where(np.arange(lane)[None] < kv_len[:, None], scores + 0.0, -np.inf)
    q = jnp.asarray(rng.normal(size=(len(kv_len), HK, WALK_G, D)), jnp.bfloat16)
    return (
        q, jnp.asarray(pool_k, jnp.bfloat16), jnp.asarray(pool_v, jnp.bfloat16), jnp.asarray(tables, jnp.int32),
        jnp.asarray(kv_len), jnp.asarray(scores, jnp.float32), k,
    )


@pytest.mark.parametrize("pages", [1, 2, WALK_NBL], ids=["a-page-a-trip", "two-pages-a-trip", "the-lane-a-trip"])
@pytest.mark.parametrize("ties", [False, True], ids=["distinct", "ties"])
@pytest.mark.parametrize("bs", [16, 128])
def test_decode_walk_is_dense_attention_under_the_mask_and_the_gather(bs, ties, pages):
    q, pool_k, pool_v, tables, kv_len, scores, k = _walk_case(bs, ties)
    live = np.asarray(kv_len) > 0
    positions, valid, tau, p_star = sa.decode_positions(scores, k)
    chosen = sa.chosen_mask(scores, tau, p_star)
    assert np.asarray(chosen.sum(-1)).tolist() == np.minimum(np.asarray(kv_len), k).tolist()
    if ties:  # the threshold's score is shared with a position the set leaves out
        key = np.asarray(sa.order_key(scores))
        assert any(((key[b] == int(tau[b])) & ~np.asarray(chosen[b])).any() for b in (3, 4))
    got = sa._sparse_decode(
        q, pool_k, pool_v, tables, kv_len, scores, tau, p_star, layer_index=1, sm_scale=D**-0.5, pages=pages,
        interpret=True,
    )
    got = np.asarray(got)
    assert got.shape == (5, HK, WALK_G, D) and got.dtype == np.float32 and np.isfinite(got).all()
    assert not got[1].any()  # the dead row walked nothing
    # the XLA lines over the rows' own pages (a dead entry's NaNs replaced: the mask multiplies them)
    safe = jnp.where(jnp.arange(WALK_NBL)[None] * bs < kv_len[:, None], tables, 0)
    k_rows, v_rows = sa._pages_in_order(pool_k, safe, 1), sa._pages_in_order(pool_v, safe, 1)
    want = sa.sparse_reference_attention(q[:, None], k_rows, v_rows, chosen[:, None], sm_scale=D**-0.5)[:, 0]
    np.testing.assert_allclose(got[live], np.asarray(want)[live], atol=2e-5)
    gathered = sa.sparse_decode_attention(q, pool_k, pool_v, tables, positions, valid, layer_index=1)
    np.testing.assert_allclose(got[live], np.asarray(gathered)[live], atol=2e-5)
    # and the mask bites: attention over every live position is something else
    dense = sa.sparse_reference_attention(q[:, None], k_rows, v_rows, jnp.isfinite(scores)[:, None], sm_scale=D**-0.5)
    assert np.abs(np.asarray(dense)[3:, 0] - got[3:]).max() > 0.05


@pytest.mark.parametrize(
    "lane,bs,head_dim,on_tpu,interpret,walks",
    [
        (sa._WALK_MAX_LANE, 128, 128, True, False, True),
        (sa._WALK_MAX_LANE + 128, 128, 128, True, False, False),
        (8192, 128, 128, False, False, False),
        (8192, 4, 128, True, False, False),
        (8192, 128, 64, True, False, False),
        (64, 4, 16, True, True, True),
    ],
    ids=["at-the-crossover", "a-page-past-it", "off-the-chip", "blocks-under-a-tile", "half-a-lane-tile", "interpret-mode"],
)
def test_the_one_choice_between_the_walk_and_the_gather(monkeypatch, lane, bs, head_dim, on_tpu, interpret, walks):
    monkeypatch.setattr(sa, "_on_tpu", lambda: on_tpu)
    assert sa.decode_walks(lane, bs, head_dim, interpret=interpret) is walks


@pytest.mark.parametrize("use_kernel", [False, True], ids=["the-gather", "the-walk"])
def test_decode_attention_takes_the_path_the_choice_names(monkeypatch, use_kernel):
    q, pool_k, pool_v, tables, kv_len, scores, k = _walk_case(16, True)
    called = []
    for name in ("_sparse_decode", "_sparse_select", "sparse_decode_attention", "decode_positions"):
        fn = getattr(sa, name)
        monkeypatch.setattr(sa, name, lambda *a, _f=fn, _n=name, **kw: (called.append(_n), _f(*a, **kw))[1])
    attn, tau, p_star = sa.decode_attention(
        q, pool_k, pool_v, tables, kv_len, scores, k, layer_index=1, use_kernel=use_kernel, interpret=True
    )
    assert called == (["_sparse_select", "_sparse_decode"] if use_kernel else ["decode_positions", "sparse_decode_attention"])
    want_tau, want_p = sa.decode_positions(scores, k)[2:]
    live = np.asarray(kv_len) > k  # (a row that leaves nothing out may name any threshold under its scores)
    np.testing.assert_array_equal(
        np.asarray(sa.chosen_mask(scores, tau, p_star)), np.asarray(sa.chosen_mask(scores, want_tau, want_p))
    )
    assert (np.asarray(tau)[live] == np.asarray(want_tau)[live]).all() and attn.shape == q.shape


# -- the chip's compiler, no chip ---------------------------------------------

os.environ.setdefault("TPU_LOG_DIR", "disabled")


@pytest.fixture(scope="module")
def v5e():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"cannot describe a v5e topology here: {e}")
    was_enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_enabled)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("lane", [8192, 32768])
@pytest.mark.parametrize("kernel", ["index_score", "select", "prefill", "decode"])
def test_kernel_compiles_for_v5e_at_the_served_widths(v5e, kernel, lane):
    """Keye's widths (4 KV heads x 8 x 128, 16 index heads x 64 in 128 lanes,
    blocks of 128, a 256-token chunk of two rows) in both of its lanes: block
    shapes and VMEM the chip's compiler would refuse fail here."""

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    rows, t, bs, blocks = 2, 256, 128, 600
    pool, pool_i = arg((8, blocks, 4, bs, 128), jnp.bfloat16), arg((8, blocks, 1, bs, 128), jnp.bfloat16)
    tables, vec = arg((rows, lane // bs), jnp.int32), arg((rows,), jnp.int32)
    if kernel == "index_score":
        fn = functools.partial(sa.index_scores, layer_index=1, use_kernel=True, interpret=False)
        args = (arg((rows, t, 16, 64), jnp.bfloat16), arg((rows, t, 16), jnp.float32), pool_i, tables, vec, vec)
    elif kernel == "select":
        fn = lambda s, n: sa.select_threshold(s, 2048, n, use_kernel=True, interpret=False)  # noqa: E731
        args = (arg((rows, t, lane), jnp.float32), arg((rows, t), jnp.int32))
    elif kernel == "decode":  # a decode step's rows: the threshold kernel at one query a row, then the walk
        fn = lambda *a: sa.decode_attention(*a, 2048, layer_index=1, use_kernel=True, interpret=False)[0]  # noqa: E731
        args = (arg((rows, 4, 8, 128), jnp.bfloat16), pool, pool, tables, vec, arg((rows, lane), jnp.float32))
    else:
        fn = functools.partial(sa.sparse_prefill_attention, layer_index=1, use_kernel=True, interpret=False)
        args = (arg((rows, t, 4, 8, 128), jnp.bfloat16), pool, pool, tables, vec, vec, arg((rows, t, lane), jnp.bool_))
    assert "tpu_custom_call" in jax.jit(fn).lower(*args).compile().as_text()
