"""Attention over the positions a learned indexer picks, out of the paged pools.

DeepSeek-Sparse-Attention's shape (PAPERS.md) on this repo's block pools: beside
K/V every position keeps ONE small index key a layer (``models/vlm/paged_kv.py::
init_index_pool``: ``[L, NB, 1, bs, W]``, the key in the first lanes of a whole
128-lane row, written through the same table by the same write index). A query
scores every position it can see with a few small heads,

    I(t, s) = sum_j w_t[j] * relu(qI_t[j] . kI_s)        (float32, + 0.0: no -0.0)

and attends, with its real heads, to the ``k`` positions that score highest: a
tie goes to the lower position, and while a query sees no more than ``k``
positions it sees them all, which is the dense paged path's mathematics. Four
steps, each with its XLA lines and, on a TPU, a Pallas kernel under a pinned
name (a device trace's rows find it by that name):

- :func:`index_scores` (``_sparse_index_score``): one grid step a (row, block of
  queries); the loop walks the row's OWN table in groups of pages as far as the
  block's last query can see, as ``ops/paged_attention.py``'s kernels do (one
  copy a live page into one of two buffers, the next group in flight), the index
  heads of a block of queries one MXU operand ``[heads * block_q, W]``, relu and
  the weighted sum over heads on the float32 scores. What nobody may choose
  (the future, the lane past the row's length) reads ``-inf``.
- :func:`select_threshold` (``_sparse_select``): the choice WITHOUT a sort. The
  float32 scores map to int32 keys of the same order (:func:`order_key`); the
  ``k``-th largest key is found bit by bit (counts of ``key >= candidate`` over
  the live lanes of a block of 16 queries, held in VMEM) ONLY AS FAR AS THE SET
  IS OPEN: once every query of the block has exactly ``k`` keys at or above its
  prefix the search ends (float32 scores of 10-30 thousand positions part at
  bit 22-26 of 32) and one pass reads the two numbers off the set; the second
  bisection, over the positions of the keys EQUAL to the threshold, runs only
  in a block where such a tie straddles ``k``. A query's choice is then two
  numbers ``(tau, p_star)`` and an elementwise test (:func:`chosen_mask`);
  XLA's sort-based ``top_k`` of 2,048 out of 32,768 for a 256-query chunk took
  5.8 ms on one v5e, this arithmetic in XLA 1.0 ms (PERF.md, PR 40), the kernel
  with all its 49 passes 0.65 ms at 30 thousand positions and 0.29 since it
  stops (PR 42). A block of queries that sees no more than ``k`` positions
  costs nothing.
- :func:`sparse_prefill_attention` (``_sparse_prefill``): a chunk's attention as
  the paged prefill kernel's dense walk UNDER THE CHOSEN SET'S MASK (an additive
  bfloat16 tile a group of keys, copied beside the K/V pages): the same
  mathematics, every live page still read. A kernel that walks only chosen
  positions is ROADMAP R8's.
- :func:`decode_attention`: a decode step's one query a row, its choice and its
  attention, by one of two reads (:func:`decode_walks`, below):
  **the walk** (``_sparse_decode``): the choice from the threshold kernel at one
  query a row (two numbers a row; 16 rows of a 32,768 lane 0.058 ms where
  ``lax.top_k`` took 0.40), then the paged decode kernel's walk of the row's
  OWN live pages (one grid step a row, one copy a page for all of its KV heads,
  groups of 1,024 keys through two buffers, online softmax in float32) with the
  chosen set as a mask on each group's scores: the row's float32 index scores
  come in whole through a ``BlockSpec``, ``(tau, p_star)`` are prefetched scalars
  and :func:`chosen_mask`'s test runs on the group's slice. It reads every live
  position's K/V to attend to 2,048 of them, at HBM speed: 12 rows at 10-30
  thousand positions 0.70 ms a layer, 87% of the bytes' time at 819 GB/s
  (PERF.md, PR 41; groups of 128 / 256 / 512 / 2,048 keys 1.23 / 0.99 / 0.72 /
  0.71; ``_sparse_prefill`` at one query a row 1.26).
  **The gather** (:func:`decode_positions` + :func:`sparse_decode_attention`):
  positions from ``lax.top_k``, then ONLY those positions' K/V out of the pools:
  position -> (block, offset) through the row's table, one XLA gather a pool,
  2.1 ms a layer for the same 12 rows WHATEVER their contexts (a 256-byte row in
  13 ns). No kernel can do that read: Mosaic copies no slice of an HBM array
  under its tiling's 8 rows (``Slice shape along dimension 3 must be aligned to
  tiling (8)``, the compiler for a described v5e, PR 40). A pool whose
  positions are a leading dimension would let a kernel copy them (ROADMAP R8).

Which side runs is decided here and nowhere else: on a TPU the kernels (where
the block size makes whole tiles: ``_kernel_ok``), elsewhere the XLA lines; and
between a decode step's two reads by the lane's length (``_WALK_MAX_LANE``: the
walk's cost grows with a row's context, the gather's does not), so off the chip a
decode step is the gather's XLA lines, as it always was.
Tests pick with ``use_kernel=`` / ``interpret=``, or patch ``_on_tpu``.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from cosmos_curate_tpu.ops.tiling import round_up, sublanes

_NEG_INF = -1e30  # attention's mask: finite, so that a row wholly masked stays a number
_GROUP_KEYS = 1024  # positions a trip of the scoring, prefill and decode kernels' loops covers
# the longest lane whose decode steps WALK their rows' live pages (the gather
# beyond): the walk costs 2.76 ns a live position a layer (12 rows: 0.57 / 1.11 /
# 1.64 / 2.17 ms at 16 / 32 / 48 / 64 thousand positions each, 91% of HBM peak),
# the gather 2.08 ms whatever the contexts, so they cross at 63 thousand
# positions a row, and with the choice each needs (the threshold kernel 0.09 ms,
# ``lax.top_k`` 0.88 at this lane, 1.97 at 131,072) at 87 thousand: a lane of
# 65,536 never holds such a row, a lane of 131,072 does (past 118 thousand:
# 4.47 ms against 4.05 at the full lane); one v5e, PERF.md, PR 41
_WALK_MAX_LANE = 65536
_INT_MIN = -(2**31)
# order_key(-inf): what no query may choose
KEY_UNSEEN = (0xFF800000 ^ 0x7FFFFFFF) - 2**32
_VMEM_LIMIT = 64 * 1024 * 1024
# the threshold kernel's block of queries and how often its value search asks
# whether it may stop. Every counting pass ends in a sum across lanes and a
# handful of operations on one number a query that the next pass waits for,
# about 0.17 us whatever the context where a block's 15 loop trips at 30
# thousand positions take 0.23, and a block of 16 queries pays that once where
# two blocks of 8 pay it twice (a 256-query chunk at 30.5k: 0.343 ms at 8 rows
# and trips of 2,048 lanes, 0.293 at 16 rows x 1,024, 0.276 at 32 x 512, where
# a decode step's 12 rows take 0.043 ms against 0.034: they fill half a block).
# The flag is a vector made a scalar, which the loop has to wait for: read
# every bit 0.400 ms, every second 0.379, every fourth 0.387 (8 rows, 1,024
# lanes); one v5e, PERF.md, PR 42
_SELECT_ROWS = 16
_BITS_A_CHECK = 2


def _on_tpu() -> bool:
    """Tests put an engine on the kernels (interpret mode) by patching this."""
    return jax.devices()[0].platform == "tpu"


def _kernel_ok(bs: int) -> bool:
    """The kernels copy pages of ``bs`` bfloat16 rows into tiled buffers: whole
    tiles of 16 rows on the chip (the test-size engines' blocks of 4 take the
    XLA lines there; interpret mode takes anything)."""
    return bs % 16 == 0


def _choose(use_kernel, interpret, bs: int | None = None) -> tuple[bool, bool]:
    """(kernel?, interpret?) from what the caller asked and where the code runs;
    ``bs``: the block size of the pool the kernel would copy pages of, if any."""
    if use_kernel is None:
        use_kernel = _on_tpu()
    if interpret is None:
        interpret = jax.devices()[0].platform == "cpu"
    return bool(use_kernel and (interpret or bs is None or _kernel_ok(bs))), bool(interpret)


def order_key(scores):
    """float32 -> int32 with the same order (``-0.0`` below ``+0.0``: the
    scores carry none, they end in ``+ 0.0``)."""
    i = jax.lax.bitcast_convert_type(scores, jnp.int32)
    return jnp.where(i >= 0, i, i ^ jnp.int32(0x7FFFFFFF))


def _group_pages(bs: int, nbl: int) -> int:
    """Table entries a trip of a loop covers: ``_GROUP_KEYS`` positions where
    the lane is a whole number of such groups, else one page."""
    keys = math.gcd(nbl * bs, _GROUP_KEYS)
    return keys // bs if keys >= bs and keys % bs == 0 else 1


# -- (a) the index scores ------------------------------------------------------


def _seen(write_index, kv_len, t: int, s: int):
    """``[B, T, S]``: position ``s`` is at or before query ``t`` of the chunk
    written at ``write_index`` and inside the row's valid length."""
    pos = jnp.arange(s, dtype=jnp.int32)[None, None, :]
    q_pos = write_index[:, None, None] + jnp.arange(t, dtype=jnp.int32)[None, :, None]
    return (pos <= q_pos) & (pos < kv_len[:, None, None])


def index_scores_reference(qi, w, keys, write_index, kv_len):
    """The XLA lines the kernel is held to. qi: ``[B, T, Hi, Di]``; w: ``[B, T,
    Hi]`` float32, scaled; keys: ``[B, S, Di]`` (the row's index keys in
    position order). Returns ``[B, T, S]`` float32, ``-inf`` where a query may
    not choose."""
    s = jnp.einsum("bthd,bsd->bths", qi, keys.astype(qi.dtype), preferred_element_type=jnp.float32)
    scores = (jax.nn.relu(s) * w.astype(jnp.float32)[..., None]).sum(axis=2) + 0.0
    return jnp.where(_seen(write_index, kv_len, qi.shape[1], keys.shape[1]), scores, -jnp.inf)


def _index_score_kernel(
    layer_ref, write_ref, kvlen_ref, tbl_ref, q_ref, w_ref, k_hbm, o_ref, k_buf, sems,
    *, bs, pages, bq, n_heads,
):
    """One grid step is one block of ``bq`` queries of one row: its index heads
    are the rows of one operand, head-major (``[n_heads * bq, W]``). The loop
    walks the row's own table in groups of ``pages`` entries up to the page of
    the newest key the block's last query can see; what lies past it keeps the
    ``-inf`` the output block starts as."""
    b, qi = pl.program_id(0), pl.program_id(1)
    layer, write, kv_len = layer_ref[0], write_ref[b], kvlen_ref[b]
    group = pages * bs
    q_first = write + qi * bq
    n_pages = pl.cdiv(jnp.minimum(kv_len, q_first + bq), bs)
    n_groups = pl.cdiv(n_pages, pages)

    def each_live_page(i, slot, act):
        first = i * pages

        def page(p, carry):
            rows = pl.ds(pl.multiple_of(p * bs, bs), bs)
            act(pltpu.make_async_copy(k_hbm.at[layer, tbl_ref[b, first + p], 0], k_buf.at[slot, rows], sems.at[slot]))
            return carry

        jax.lax.fori_loop(0, jnp.clip(n_pages - first, 0, pages), page, 0)

    @pl.when((b == 0) & (qi == 0))
    def _():  # a dead page's rows are masked by position and have to be numbers for that
        k_buf[...] = jnp.zeros_like(k_buf)

    o_ref[...] = jnp.full(o_ref.shape, -jnp.inf, o_ref.dtype)
    each_live_page(0, 0, lambda copy: copy.start())
    q, w = q_ref[...], w_ref[...]  # [n_heads * bq, W], [n_heads * bq, 1]
    row = jax.lax.broadcasted_iota(jnp.int32, (bq, group), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (bq, group), 1)

    def one_group(i, carry):
        slot = jax.lax.rem(i, 2)
        each_live_page(i + 1, 1 - slot, lambda copy: copy.start())
        each_live_page(i, slot, lambda copy: copy.wait())
        s = jax.lax.dot_general(q, k_buf[slot], (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        s = jnp.maximum(s, 0.0) * w  # [n_heads * bq, group]
        acc = s[:bq]
        for j in range(1, n_heads):
            acc = acc + s[j * bq : (j + 1) * bq]
        k_start = i * group
        seen = (col + k_start <= q_first + row) & (col + k_start < kv_len)
        o_ref[:, pl.ds(pl.multiple_of(k_start, group), group)] = jnp.where(seen, acc + 0.0, -jnp.inf)
        return carry

    jax.lax.fori_loop(0, n_groups, one_group, 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _sparse_index_score(qi, w, pool_i, tables, write_index, kv_len, *, layer_index, interpret):
    b, t, hi, di = qi.shape
    _, _, _, bs, width = pool_i.shape
    nbl = tables.shape[1]
    pages = _group_pages(bs, nbl)
    bq = 8 if t <= 8 else 32
    t_pad = round_up(t, bq)
    nq = t_pad // bq

    def by_block(x, lanes):  # [B, T, Hi, lanes] -> [B, nq, Hi * bq, lanes], head-major in a block
        x = jnp.pad(x, ((0, 0), (0, t_pad - t), (0, 0), (0, lanes - x.shape[-1])))
        return x.reshape(b, nq, bq, hi, lanes).swapaxes(2, 3).reshape(b, nq, hi * bq, lanes)

    q_spec = pl.BlockSpec((None, None, hi * bq, width), lambda b_, i, *_: (b_, i, 0, 0))
    w_spec = pl.BlockSpec((None, None, hi * bq, 1), lambda b_, i, *_: (b_, i, 0, 0))
    out = pl.pallas_call(
        functools.partial(_index_score_kernel, bs=bs, pages=pages, bq=bq, n_heads=hi),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(b, nq),
            in_specs=[q_spec, w_spec, pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((None, bq, nbl * bs), lambda b_, i, *_: (b_, i, 0)),
            scratch_shapes=[pltpu.VMEM((2, pages * bs, width), pool_i.dtype), pltpu.SemaphoreType.DMA((2,))],
        ),
        out_shape=jax.ShapeDtypeStruct((b, t_pad, nbl * bs), jnp.float32),
        # never "parallel": the buffers zeroed in the first step serve every later one
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"), vmem_limit_bytes=_VMEM_LIMIT
        ),
        interpret=interpret,
    )(
        jnp.asarray(layer_index, jnp.int32).reshape(1), write_index.astype(jnp.int32),
        kv_len.astype(jnp.int32), tables.astype(jnp.int32),
        by_block(qi.astype(pool_i.dtype), width), by_block(w.astype(jnp.float32)[..., None], 1), pool_i,
    )
    return out[:, :t]


def index_scores(
    qi, w, pool_i, tables, write_index, kv_len, *, layer_index=0, use_kernel=None, interpret=None
):
    """``I(t, s)`` of a chunk's queries against every position of their rows,
    straight out of the paged index-key array. qi: ``[B, T, Hi, Di]``, roped; w:
    ``[B, T, Hi]`` float32, scaled; pool_i: ``[L, NB, 1, bs, W]``; tables:
    ``[B, nbl]``. Returns ``[B, T, nbl * bs]`` float32 with ``-inf`` wherever a
    query may not choose (after it, or past ``kv_len``)."""
    bs = pool_i.shape[3]
    if use_kernel is None and qi.shape[1] == 1:
        # a decode step's one query a row: the XLA lines. They read the lane's
        # whole table where the kernel walks the live pages, and still took 0.55
        # ms for 16 rows of a 32,768 lane where the kernel took 2.7 (one v5e,
        # PERF.md PR 40: a matmul of 8 padded queries a trip leaves the trip's
        # fixed cost bare)
        use_kernel = False
    use_kernel, interpret = _choose(use_kernel, interpret, bs)
    if use_kernel:
        return _sparse_index_score(
            qi, w, pool_i, tables, write_index, kv_len, layer_index=layer_index, interpret=interpret
        )
    b, nbl = tables.shape
    keys = pool_i[layer_index, tables, 0].reshape(b, nbl * bs, -1)[..., : qi.shape[-1]]
    return index_scores_reference(qi, w, keys, write_index, kv_len)


# -- (b) the choice -----------------------------------------------------------


def select_threshold_reference(scores, k: int):
    """``(tau, p_star)`` by XLA's sort: the key of the ``k``-th largest score of
    every query and its position (``lax.top_k`` puts the lower index first among
    equals). scores: ``[B, T, S]``."""
    s = scores.shape[-1]
    if s < k:
        scores = jnp.pad(scores, ((0, 0), (0, 0), (0, k - s)), constant_values=-jnp.inf)
    vals, idx = jax.lax.top_k(scores, k)
    return order_key(vals[..., -1]), idx[..., -1].astype(jnp.int32)


def _in_choice(key, pos, tau, p_star):
    """The test both forms of the mask make: a key above the threshold, or equal
    to it at a position up to ``p_star``; never what no query may choose."""
    return (key > KEY_UNSEEN) & ((key > tau) | ((key == tau) & (pos <= p_star)))


def chosen_mask(scores, tau, p_star):
    """``[B, T, S]`` bool: the positions a query attends to, from its two numbers."""
    pos = jnp.arange(scores.shape[-1], dtype=jnp.int32)
    return _in_choice(order_key(scores), pos, tau[..., None], p_star[..., None])


def _select_kernel(live_ref, s_ref, o_ref, key_ref, *, k, chunk, pos_bits):
    """One grid step is a block of queries (``_SELECT_ROWS``) and the lanes any
    of them can see (``live_ref``: the most, prefetched), in chunks of ``chunk``
    lanes. It counts only as long as the answer is open. The value search
    carries, a query, how many keys stand at or above its prefix, and ends when
    that is ``k`` for every query of the block (the set is settled, whatever
    the lower bits of ``tau`` are; it asks every ``_BITS_A_CHECK`` bits); one
    closing pass then reads ``tau`` (the least key of the set) and ``p_star``
    (the last position that holds it). Only a block in which, after all 32
    bits, some query still has more than ``k`` keys at or above ``tau`` (a tie
    straddles ``k``) counts on: how many of the equal keys it needs, and the
    position of the last of those, bit by bit. A query with fewer than ``k``
    keys to choose from leaves nothing out, is not searched for and answers
    ``(KEY_UNSEEN, s)`` as a block of such queries does. The answers leave in
    lanes 0 (``tau``) and 1 (``p_star``) of a 128-lane row; lanes 2 and 3 say
    what the block did: value passes run, and whether the tie search ran."""
    n_live = live_ref[pl.program_id(0)]
    n_chunks = pl.cdiv(n_live, chunk)
    rows, s = s_ref.shape
    lane = jax.lax.broadcasted_iota(jnp.int32, (rows, 128), 1)

    def answer(tau, p_star, passes, tied):
        o_ref[...] = jnp.where(
            lane == 0, tau, jnp.where(lane == 1, p_star, jnp.where(lane == 2, passes, jnp.where(lane == 3, tied, 0)))
        )

    @pl.when(n_live <= k)
    def _():  # nothing to leave out
        answer(jnp.int32(KEY_UNSEEN), jnp.int32(s), 0, 0)

    @pl.when(n_live > k)
    def _():
        def cols(c):
            return pl.ds(pl.multiple_of(c * chunk, chunk), chunk)

        col = jax.lax.broadcasted_iota(jnp.int32, (rows, chunk), 1)

        def count(pred):
            def body(c, acc):
                return acc + pred(key_ref[:, cols(c)], c * chunk + col).astype(jnp.int32)

            acc = jax.lax.fori_loop(0, n_chunks, body, jnp.zeros((rows, chunk), jnp.int32))
            return acc.sum(axis=1, keepdims=True)

        def to_keys(c, acc):
            key = order_key(s_ref[:, cols(c)])
            key_ref[:, cols(c)] = key
            return acc + (key > KEY_UNSEEN).astype(jnp.int32)

        # the keys a query may choose from; with fewer than k it takes them all
        n_seen = jax.lax.fori_loop(0, n_chunks, to_keys, jnp.zeros((rows, chunk), jnp.int32)).sum(axis=1, keepdims=True)
        searched = n_seen >= k

        def still_open(cnt):  # a scalar: some searched query's set still holds more than k keys
            return jnp.max(jnp.where(searched & (cnt != k), 1, 0))

        def value_bit(bit, ans, cnt):  # in the order of unsigned keys (key ^ INT_MIN), the top bit first
            cand = ans | jnp.left_shift(jnp.int32(1), 31 - bit)
            threshold = cand ^ jnp.int32(_INT_MIN)
            # of the keys a query may choose (what it may not lies under every one of them)
            above = jnp.minimum(count(lambda key, _: key >= threshold), n_seen)
            take = above >= k
            return jnp.where(take, cand, ans), jnp.where(take, above, cnt)

        def value_bits(state):
            bit, ans, cnt, _ = state
            for i in range(_BITS_A_CHECK):
                ans, cnt = value_bit(bit + i, ans, cnt)
            return bit + _BITS_A_CHECK, ans, cnt, still_open(cnt)

        passes, ans, _, tied = jax.lax.while_loop(
            lambda state: (state[0] < 32) & (state[3] > 0),
            value_bits,
            (jnp.int32(0), jnp.zeros((rows, 1), jnp.int32), n_seen, still_open(n_seen)),
        )

        def searched_or_all(tau, p_star):
            return jnp.where(searched, tau, KEY_UNSEEN), jnp.where(searched, p_star, s)

        @pl.when(tied == 0)
        def _():  # the set {choosable key >= prefix} holds exactly k keys: its least key, that key's last position
            floor = jnp.maximum(ans ^ jnp.int32(_INT_MIN), jnp.int32(KEY_UNSEEN + 1))

            def last_least(c, carry):
                least, at = carry
                key = key_ref[:, cols(c)]
                better = (key >= floor) & (key <= least)  # (a later chunk is a higher position)
                return jnp.where(better, key, least), jnp.where(better, c * chunk + col, at)

            least, at = jax.lax.fori_loop(
                0, n_chunks, last_least,
                (jnp.full((rows, chunk), jnp.iinfo(jnp.int32).max, jnp.int32), jnp.full((rows, chunk), -1, jnp.int32)),
            )
            tau = least.min(axis=1, keepdims=True)
            p_star = jnp.where(least == tau, at, -1).max(axis=1, keepdims=True)
            answer(*searched_or_all(tau, p_star), passes, 0)

        @pl.when(tied > 0)
        def _():  # all 32 bits ran, so the prefix IS tau, and some query has more than k keys at or above it
            tau = ans ^ jnp.int32(_INT_MIN)
            need = k - count(lambda key, _: key > tau)  # of the keys equal to tau, from the lowest position

            def position_bit(bit, ans):
                cand = ans | jnp.left_shift(jnp.int32(1), pos_bits - 1 - bit)
                return jnp.where(count(lambda key, pos: (key == tau) & (pos < cand)) < need, cand, ans)

            p_star = jax.lax.fori_loop(0, pos_bits, position_bit, jnp.zeros((rows, 1), jnp.int32))
            answer(*searched_or_all(tau, p_star), passes, 1)


@functools.partial(jax.jit, static_argnames=("k", "interpret", "with_passes"))
def _sparse_select(scores, n_live, *, k, interpret, with_passes=False):
    r, s = scores.shape
    rows = _SELECT_ROWS
    r_pad = round_up(r, rows)
    chunk = math.gcd(s, _GROUP_KEYS)
    scores = jnp.pad(scores, ((0, r_pad - r), (0, 0)), constant_values=-jnp.inf)
    live = jnp.pad(n_live.astype(jnp.int32), (0, r_pad - r)).reshape(-1, rows).max(axis=1)
    out = pl.pallas_call(
        functools.partial(_select_kernel, k=k, chunk=chunk, pos_bits=max(1, (s - 1).bit_length())),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(r_pad // rows,),
            in_specs=[pl.BlockSpec((rows, s), lambda i, *_: (i, 0))],
            out_specs=pl.BlockSpec((rows, 128), lambda i, *_: (i, 0)),
            scratch_shapes=[pltpu.VMEM((rows, s), jnp.int32)],
        ),
        out_shape=jax.ShapeDtypeStruct((r_pad, 128), jnp.int32),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",), vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(live, scores)
    return tuple(out[:r, i] for i in range(4 if with_passes else 2))


def select_threshold(scores, k: int, n_live, *, use_kernel=None, interpret=None, with_passes=False):
    """The choice of every query as two numbers: ``tau``, the :func:`order_key`
    of its ``k``-th largest score, and ``p_star``, the position up to which the
    scores EQUAL to ``tau`` are taken (ties go to the lower position).
    scores: ``[B, T, S]`` with ``-inf`` for what may not be chosen; n_live: ``[B,
    T]``, how many positions each query may choose from (a bound on the work,
    not part of the answer). Returns ``(tau, p_star)``, each ``[B, T]`` int32;
    :func:`chosen_mask` turns them into the set. A query that leaves nothing out
    (no more than ``k`` positions to choose from) may name any threshold under
    its scores. ``with_passes`` (the kernel's own account, for tests and
    ``scripts/select_passes.py``; a program of its own) adds two ``[B, T]``
    arrays: the value passes the query's block of ``_SELECT_ROWS`` ran (0-32) and
    whether it ran the tie search."""
    use_kernel, interpret = _choose(use_kernel, interpret)
    if not use_kernel:
        if with_passes:
            raise ValueError("with_passes reads the kernel's counters: use_kernel must hold")
        return select_threshold_reference(scores, k)
    b, t, s = scores.shape
    out = _sparse_select(
        scores.reshape(b * t, s), n_live.reshape(b * t), k=k, interpret=interpret, with_passes=with_passes
    )
    return tuple(x.reshape(b, t) for x in out)


# -- (d) a prefill chunk's attention under the chosen set -----------------------


def sparse_reference_attention(q, k, v, chosen, *, sm_scale):
    """Dense attention under a mask, the XLA lines both attention kernels are
    held to. q: ``[B, T, Hkv, G, D]``; k, v: ``[B, Hkv, S, D]``; chosen: ``[B, T,
    S]`` bool. float32 throughout. Returns ``[B, T, Hkv, G, D]`` float32."""
    s = jnp.einsum(
        "bthgd,bhsd->bhgts", q.astype(jnp.float32), k.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST,
    ) * sm_scale
    s = jnp.where(chosen[:, None, None], s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhgts,bhsd->bthgd", p, v.astype(jnp.float32), precision=jax.lax.Precision.HIGHEST)


def _pages_in_order(pool, tables, layer_index):
    """A row's pages as ``[B, Hkv, S, D]``, gathered (the reference's view)."""
    pages = pool[layer_index, tables]  # [B, nbl, Hkv, bs, D]
    b, nbl, hk, bs, d = pages.shape
    return pages.swapaxes(1, 2).reshape(b, hk, nbl * bs, d)


def _sparse_prefill_kernel(
    layer_ref, write_ref, kvlen_ref, tbl_ref, q_ref, k_hbm, v_hbm, bias_hbm, o_ref,
    k_buf, v_buf, bias_buf, sems, acc_ref, m_ref, l_ref,
    *, sm_scale, block_q, bs, g, pages,
):
    """``ops/paged_attention.py::_paged_prefill_kernel``'s walk (one grid step a
    block of queries of one row against one KV head, groups of the row's own
    pages through two buffers) with one more copy a group: the block's tile of
    the additive mask, 0 where a query chose the key and ``-1e30`` where it did
    not (causality and the row's length are inside the choice)."""
    b, h, qi = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    layer, write, kv_len = layer_ref[0], write_ref[b], kvlen_ref[b]
    d = q_ref.shape[-1]
    rows, group = g * block_q, pages * bs
    q_first = write + qi * block_q
    n_pages = pl.cdiv(jnp.minimum(kv_len, q_first + block_q), bs)
    n_groups = pl.cdiv(n_pages, pages)

    def each_copy(i, slot, act):
        first = i * pages

        def page(p, carry):
            block = tbl_ref[b, first + p]
            keys = pl.ds(pl.multiple_of(p * bs, bs), bs)
            for pool, buf, sem in ((k_hbm, k_buf, sems.at[0, slot]), (v_hbm, v_buf, sems.at[1, slot])):
                act(pltpu.make_async_copy(pool.at[layer, block, h], buf.at[slot, keys], sem))
            return carry

        live = jnp.clip(n_pages - first, 0, pages)
        jax.lax.fori_loop(0, live, page, 0)

        @pl.when(live > 0)
        def _():
            tile = bias_hbm.at[
                b, pl.ds(pl.multiple_of(qi * block_q, block_q), block_q),
                pl.ds(pl.multiple_of(first * bs, group), group),
            ]
            act(pltpu.make_async_copy(tile, bias_buf.at[slot], sems.at[2, slot]))

    @pl.when((b == 0) & (h == 0) & (qi == 0))
    def _():  # a dead page's V rows are multiplied by p = 0 and have to be finite for that
        v_buf[...] = jnp.zeros_like(v_buf)
        k_buf[...] = jnp.zeros_like(k_buf)

    each_copy(0, 0, lambda copy: copy.start())
    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    q = q_ref[...].reshape(rows, d)  # group-major: row r is query r % block_q of head r // block_q

    def one_group(i, carry):
        slot = jax.lax.rem(i, 2)
        each_copy(i + 1, 1 - slot, lambda copy: copy.start())
        each_copy(i, slot, lambda copy: copy.wait())
        s = jax.lax.dot_general(q, k_buf[slot], (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        bias = bias_buf[slot].astype(jnp.float32)  # [block_q, group]
        s = s * sm_scale + jnp.broadcast_to(bias[None], (g, block_q, group)).reshape(rows, group)
        m_prev = m_ref[:, :1]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[:, :1] = l_ref[:, :1] * alpha + p.sum(axis=1, keepdims=True)
        m_ref[:, :1] = m_new
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v_buf[slot].astype(jnp.float32), (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        return carry

    jax.lax.fori_loop(0, n_groups, one_group, 0)
    out = acc_ref[...] / jnp.maximum(l_ref[:, :1], 1e-30)
    o_ref[...] = out.reshape(g, block_q, d).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("sm_scale", "interpret"))
def _sparse_prefill(q, pool_k, pool_v, tables, write_index, kv_len, chosen, *, layer_index, sm_scale, interpret):
    b, t, hk, g, d = q.shape
    nbl = tables.shape[1]
    bs = pool_k.shape[3]
    block_q = min(128, round_up(t, 16))
    t_pad = round_up(t, block_q)
    pages = _group_pages(bs, nbl)
    rows = g * block_q
    q = jnp.pad(q.astype(pool_k.dtype).transpose(0, 2, 3, 1, 4), ((0, 0),) * 3 + ((0, t_pad - t), (0, 0)))
    bias = jnp.where(chosen, 0.0, _NEG_INF).astype(jnp.bfloat16)
    bias = jnp.pad(bias, ((0, 0), (0, t_pad - t), (0, 0)), constant_values=_NEG_INF)
    q_spec = pl.BlockSpec((None, None, g, block_q, d), lambda b_, h, qi, *_: (b_, h, 0, qi, 0))
    any_spec = pl.BlockSpec(memory_space=pl.ANY)
    page_buffer = pltpu.VMEM((2, pages * bs, d), pool_k.dtype)
    out = pl.pallas_call(
        functools.partial(_sparse_prefill_kernel, sm_scale=sm_scale, block_q=block_q, bs=bs, g=g, pages=pages),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(b, hk, t_pad // block_q),
            in_specs=[q_spec, any_spec, any_spec, any_spec],
            out_specs=q_spec,
            scratch_shapes=[
                page_buffer, page_buffer, pltpu.VMEM((2, block_q, pages * bs), jnp.bfloat16),
                pltpu.SemaphoreType.DMA((3, 2)),
                pltpu.VMEM((rows, d), jnp.float32), pltpu.VMEM((rows, 128), jnp.float32),
                pltpu.VMEM((rows, 128), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, hk, g, t_pad, d), pool_k.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * 3, vmem_limit_bytes=_VMEM_LIMIT
        ),
        interpret=interpret,
    )(
        jnp.asarray(layer_index, jnp.int32).reshape(1), write_index.astype(jnp.int32),
        kv_len.astype(jnp.int32), tables.astype(jnp.int32), q, pool_k, pool_v, bias,
    )
    return out[:, :, :, :t].transpose(0, 3, 1, 2, 4)


def sparse_prefill_attention(
    q, pool_k, pool_v, tables, write_index, kv_len, chosen, *, layer_index=0, sm_scale=None,
    use_kernel=None, interpret=None,
):
    """A chunk's attention to the positions each query chose, out of the pools.
    q: ``[B, T, Hkv, G, D]``; pools: ``[L, NB, Hkv, bs, D]``; chosen: ``[B, T, nbl
    * bs]`` bool (inside it: at or before the query, inside ``kv_len``). Returns
    ``[B, T, Hkv, G, D]``."""
    sm_scale = q.shape[-1] ** -0.5 if sm_scale is None else sm_scale
    use_kernel, interpret = _choose(use_kernel, interpret, pool_k.shape[3])
    if use_kernel and (interpret or q.shape[-1] % 128 == 0):
        return _sparse_prefill(
            q, pool_k, pool_v, tables, write_index, kv_len, chosen, layer_index=layer_index,
            sm_scale=float(sm_scale), interpret=interpret,
        )
    k, v = _pages_in_order(pool_k, tables, layer_index), _pages_in_order(pool_v, tables, layer_index)
    return sparse_reference_attention(q, k, v, chosen, sm_scale=sm_scale)


# -- (c) a decode step's attention over the chosen set: the gather, the walk, the choice


def decode_positions(scores, k: int):
    """A decode step's choice as positions. scores: ``[B, S]`` (one query a row,
    ``-inf`` for what it may not choose). Returns (positions ``[B, k]`` int32,
    valid ``[B, k]`` bool, tau ``[B]``, p_star ``[B]``): the ``k`` highest-scoring
    positions, ties to the lower one, ``valid`` false where the row has fewer
    than ``k`` to choose from (those come last); and the same choice as
    :func:`select_threshold`'s two numbers, for :func:`chosen_mask`."""
    s = scores.shape[-1]
    if s < k:
        scores = jnp.pad(scores, ((0, 0), (0, k - s)), constant_values=-jnp.inf)
    vals, idx = jax.lax.top_k(scores, k)
    valid = vals > -jnp.inf
    idx = idx.astype(jnp.int32)
    return jnp.where(valid, idx, 0), valid, order_key(vals[:, -1]), idx[:, -1]


def pack_choice(chosen):
    """``[B, S]`` bool -> ``[B, ceil(S / 32)]`` uint32, position ``s`` in bit ``s % 32``
    of word ``s // 32``: what a query chose, small enough to hand out of a
    timed program (the benchmark's ``correct`` reads it against the reference's
    own set)."""
    b, s = chosen.shape
    words = -(-s // 32)
    bits = jnp.pad(chosen, ((0, 0), (0, words * 32 - s))).reshape(b, words, 32).astype(jnp.uint32)
    return (bits << jnp.arange(32, dtype=jnp.uint32)).sum(axis=-1, dtype=jnp.uint32)


def sparse_decode_attention(q, pool_k, pool_v, tables, positions, valid, *, layer_index=0, sm_scale=None):
    """One query a row over the K/V of its chosen positions ONLY. q: ``[B, Hkv,
    G, D]``; pools: ``[L, NB, Hkv, bs, D]``; tables: ``[B, nbl]``; positions,
    valid: ``[B, k]`` (:func:`decode_positions`). One XLA gather a pool, position
    -> (block, offset) through the row's table, a ``[D]`` row a (head, position)
    out of the pool read as its rows (no slice or copy of the pool is made), then
    the dense lines over the ``k`` gathered keys. Returns ``[B, Hkv, G, D]``
    float32."""
    sm_scale = q.shape[-1] ** -0.5 if sm_scale is None else sm_scale
    bs = pool_k.shape[3]
    _, nb, hk, _, d = pool_k.shape
    block = jnp.take_along_axis(tables, positions // bs, axis=1)[:, None, :]  # [B, 1, k]
    head = jnp.arange(hk)[None, :, None]
    # the pools as their rows, ``[L * NB * Hkv * bs, D]`` (no data moves: the
    # last two dimensions are whole tiles), a ``[D]`` row a (head, position):
    # 9.6 ms for 12 x 2,048 positions x 8 layers of one pool on one v5e where
    # indexing four dimensions took 13.5; a window a position (``pool[layer,
    # block, :, off]``) gathers twice as fast again and makes XLA keep the pool
    # position-major, a copy of the whole pool a layer a step (16 x 3.3 GB in the
    # decode program compiled for a described v5e; PERF.md, PR 40)
    row = ((layer_index * nb + block) * hk + head) * bs + (positions % bs)[:, None, :]  # [B, Hkv, k]
    k = pool_k.reshape(-1, d)[row]
    v = pool_v.reshape(-1, d)[row]
    return sparse_reference_attention(q[:, None], k, v, valid[:, None], sm_scale=sm_scale)[:, 0]


def _sparse_decode_kernel(
    layer_ref, kvlen_ref, tau_ref, pstar_ref, tbl_ref, q_ref, s_ref, k_hbm, v_hbm, o_ref, k_buf, v_buf, sems,
    *, sm_scale, bs, pages,
):
    """``ops/paged_attention.py::_paged_decode_kernel``'s walk (one grid step a
    row with all of its KV heads, one copy a live page a pool since a page's
    ``[Hkv, bs, D]`` is contiguous, groups of ``pages`` table entries through two
    buffers, as far as the row's valid length and no further) under the chosen
    set's mask: the row's float32 index scores come in whole (``s_ref``: ``[1,
    S]``), the choice as two prefetched numbers, and a group's mask is
    :func:`chosen_mask`'s test on its slice of the scores (``-inf``, so masked,
    at and past the valid length)."""
    b = pl.program_id(0)
    layer, kv_len, tau, p_star = layer_ref[0], kvlen_ref[b], tau_ref[b], pstar_ref[b]
    hk, g_pad, d = q_ref.shape
    group = pages * bs
    n_pages = pl.cdiv(kv_len, bs)

    def each_live_page(i, slot, act):
        first = i * pages

        def page(p, carry):  # no copy for an entry at or past the valid length: its block id is garbage
            block = tbl_ref[b, first + p]
            rows = pl.ds(pl.multiple_of(p * bs, bs), bs)
            for pool, buf, sem in ((k_hbm, k_buf, sems.at[0, slot]), (v_hbm, v_buf, sems.at[1, slot])):
                act(pltpu.make_async_copy(pool.at[layer, block], buf.at[slot, :, rows], sem))
            return carry

        jax.lax.fori_loop(0, jnp.clip(n_pages - first, 0, pages), page, 0)

    @pl.when(b == 0)
    def _():  # a dead page's V rows are multiplied by p = 0 and have to be finite for that
        v_buf[...] = jnp.zeros_like(v_buf)

    each_live_page(0, 0, lambda copy: copy.start())
    q = q_ref[...]  # [hk, g_pad, d], the pool's type
    col = jax.lax.broadcasted_iota(jnp.int32, (1, group), 1)

    def one_group(i, state):
        slot = jax.lax.rem(i, 2)
        each_live_page(i + 1, 1 - slot, lambda copy: copy.start())
        each_live_page(i, slot, lambda copy: copy.wait())
        k_start = i * group
        key = order_key(s_ref[:, pl.ds(pl.multiple_of(k_start, group), group)])  # [1, group]
        chosen = _in_choice(key, col + k_start, tau, p_star)
        new = []
        for h, (acc, m_prev, l_prev) in enumerate(state):
            s = jax.lax.dot_general(
                q[h], k_buf[slot, h], (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
            )  # [g_pad, group]
            s = jnp.where(chosen, s * sm_scale, _NEG_INF)
            m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_new = l_prev * alpha + p.sum(axis=1, keepdims=True)
            acc = acc * alpha + jax.lax.dot_general(
                p, v_buf[slot, h].astype(jnp.float32), (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
            )
            new.append((acc, m_new, l_new))
        return tuple(new)

    init = (
        jnp.zeros((g_pad, d), jnp.float32),
        jnp.full((g_pad, 1), _NEG_INF, jnp.float32),
        jnp.zeros((g_pad, 1), jnp.float32),
    )
    state = jax.lax.fori_loop(0, pl.cdiv(n_pages, pages), one_group, (init,) * hk)
    for h, (acc, _, l) in enumerate(state):
        o_ref[h] = acc / jnp.maximum(l, 1e-30)


@functools.partial(jax.jit, static_argnames=("sm_scale", "pages", "interpret"))
def _sparse_decode(q, pool_k, pool_v, tables, kv_len, scores, tau, p_star, *, layer_index, sm_scale, pages, interpret):
    b, hk, g, d = q.shape
    nbl = tables.shape[1]
    bs = pool_k.shape[3]
    g_pad = round_up(g, sublanes(pool_k.dtype))
    q = jnp.pad(q.astype(pool_k.dtype), ((0, 0), (0, 0), (0, g_pad - g), (0, 0)))
    q_spec = pl.BlockSpec((None, hk, g_pad, d), lambda b_, *_: (b_, 0, 0, 0))
    # a row's scores as a block of their own: a one-row slice of a tiled [B, S]
    # array is a copy Mosaic refuses (under 8 rows)
    s_spec = pl.BlockSpec((None, 1, nbl * bs), lambda b_, *_: (b_, 0, 0))
    any_spec = pl.BlockSpec(memory_space=pl.ANY)
    group_buffer = pltpu.VMEM((2, hk, pages * bs, d), pool_k.dtype)
    out = pl.pallas_call(
        functools.partial(_sparse_decode_kernel, sm_scale=sm_scale, bs=bs, pages=pages),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(b,),
            in_specs=[q_spec, s_spec, any_spec, any_spec],
            out_specs=q_spec,
            scratch_shapes=[group_buffer, group_buffer, pltpu.SemaphoreType.DMA((2, 2))],
        ),
        out_shape=jax.ShapeDtypeStruct((b, hk, g_pad, d), jnp.float32),
        # never "parallel": the V buffers zeroed in the first row serve every later one
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",), vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(
        jnp.asarray(layer_index, jnp.int32).reshape(1), kv_len.astype(jnp.int32), tau.astype(jnp.int32),
        p_star.astype(jnp.int32), tables.astype(jnp.int32), q, scores.astype(jnp.float32)[:, None], pool_k, pool_v,
    )
    return out[:, :, :g]


def decode_walks(lane: int, bs: int, head_dim: int, *, use_kernel=None, interpret=None) -> bool:
    """The one choice between the walk and the gather for a decode step over a
    lane of ``lane`` positions (``tables.shape[1] * bs``, static): the walk where
    the kernel runs at all (a TPU, whole tiles) and the lane is no longer than
    ``_WALK_MAX_LANE``. The engine's counters ask the same question."""
    use_kernel, interpret = _choose(use_kernel, interpret, bs)
    return use_kernel and (interpret or head_dim % 128 == 0) and lane <= _WALK_MAX_LANE


def decode_attention(
    q, pool_k, pool_v, tables, kv_len, scores, k: int, *, layer_index=0, sm_scale=None,
    use_kernel=None, interpret=None,
):
    """A decode step's choice and its attention over the chosen set, one query a
    row. q: ``[B, Hkv, G, D]``; pools: ``[L, NB, Hkv, bs, D]``; tables: ``[B,
    nbl]``; scores: ``[B, nbl * bs]`` float32 (:func:`index_scores` of the one
    query, ``-inf`` at and past ``kv_len``). Returns (attention ``[B, Hkv, G, D]``
    float32, tau ``[B]``, p_star ``[B]``): :func:`chosen_mask` of the two numbers
    is the set the softmax saw. What feeds the mask stands under the scope
    ``attn.select``, the attention under ``attn.sparse``."""
    sm_scale = q.shape[-1] ** -0.5 if sm_scale is None else sm_scale
    nbl, bs = tables.shape[1], pool_k.shape[3]
    if decode_walks(nbl * bs, bs, q.shape[-1], use_kernel=use_kernel, interpret=interpret):
        interpret = _choose(True, interpret)[1]
        with jax.named_scope("attn.select"):
            tau, p_star = _sparse_select(scores, kv_len, k=k, interpret=interpret)
        with jax.named_scope("attn.sparse"):
            attn = _sparse_decode(
                q, pool_k, pool_v, tables, kv_len, scores, tau, p_star, layer_index=layer_index,
                sm_scale=float(sm_scale), pages=_group_pages(bs, nbl), interpret=interpret,
            )
        return attn, tau, p_star
    with jax.named_scope("attn.select"):
        positions, valid, tau, p_star = decode_positions(scores, k)
    with jax.named_scope("attn.sparse"):
        attn = sparse_decode_attention(
            q, pool_k, pool_v, tables, positions, valid, layer_index=layer_index, sm_scale=sm_scale
        )
    return attn, tau, p_star
