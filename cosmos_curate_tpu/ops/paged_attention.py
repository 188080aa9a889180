"""Paged GQA attention that reads the KV block pool through the block table.

vLLM's PagedAttention kernel (Kwon et al., SOSP 2023 — PAPERS.md) computes
attention directly against non-contiguous KV blocks: the kernel walks the
slot's block table and streams each physical block through on-chip memory.
PR 11 gave the caption engine the paged *pool* but kept gather-based
programs — every prefill chunk and every decode step materialized a
contiguous ``[L, n_slots, lane_length]`` copy of the whole KV working set
and scattered it back. This op deletes that copy:

- **the table is scalar-prefetched**, so a kernel resolves table entry
  ``j`` of row ``b`` to physical pool block ``table[b, j]`` and reads that
  page in place — nothing is gathered;
- **logical positions from the table index**: table entry ``j`` covers
  logical positions ``[j*bs, (j+1)*bs)`` regardless of where the block
  lives in the pool, so masking is identical to the contiguous kernels;
- **decode walks a row's own pages, several a step** (PR 27): one grid
  step a row with all of its KV heads, a loop over groups of ``P``
  consecutive table entries whose trip count comes from that row's valid
  length, so the work follows the context and not the lane. The pools stay
  in HBM; the kernel starts one asynchronous copy a page (a page's ``[Hkv,
  bs, D]`` is contiguous, so one copy serves every head) into one of two
  ``[Hkv, P * bs, D]`` buffers, the next group in flight while this one is
  computed, and starts none for an entry at or past the valid length.
  ``P`` comes from the shapes (``_decode_pages``): at least 128 keys, and
  more while a buffer stays within 128 KiB. The layer is a prefetched
  scalar like the table, so a model's layers share one trace of the
  kernel. Where ``D`` makes no whole lane tile and cannot be packed into
  one (below: the test-size flavors, ``D`` = 16 with two KV heads) Mosaic
  slices no HBM array, and the pipeline delivers the same groups through
  ``P`` ``BlockSpec``s a pool instead (``_paged_decode_blockspec_kernel``).
  That second form compiles at every width and is kept for exactly those
  widths: at ``D`` = 128 its ``2 * P`` pipeline copies a step cost 71-142
  us a call against 13-30 for the kernel's own (PR 27, one v5e), and at
  ``D`` = 64 it ran at 8% of its roofline behind a pool XLA stored twice
  over (PR 30: below);
- **prefill walks a block of queries' own pages, a group a loop trip**
  (PR 39): one grid step a (row, KV head, block of ``block_q`` queries), the
  pools in HBM as for decode, table, ``write_index``, ``kv_len`` and the layer
  prefetched scalars. The loop walks the row's own table in groups of ``P``
  entries from the first page the block's first query can see to the page
  that holds ``min(kv_len, the block's last query + 1) - 1`` and no further:
  the trip count comes from the row, the causal bound and the window, not
  from the table's length. One asynchronous copy a live page a pool (this
  head's ``[bs, D]`` of it) into one of two ``[P * bs, D]`` buffers, group
  ``i + 1`` in flight while group ``i`` is computed, no copy for an entry
  past the newest visible key or wholly behind the window (its block id is
  garbage), no trip for a padding row (``kv_len`` 0). ``P`` comes from the
  shapes (``_prefill_pages``): 1,024 keys, fewer where a block's float32
  score tile would pass 3 MiB. q.K is fed to the MXU in the pool's own type
  where that is bfloat16 (every product exact in the float32 it accumulates
  in, ``sm_scale`` applied to the float32 scores); the softmax state and p.V
  are the float32 lines they were. Where ``D`` makes no whole lane tile
  (``D`` = 16, as above) the pipeline delivers one page a grid step over the
  table instead (``_paged_prefill_blockspec_kernel``, the kernel every width
  had before PR 39: a table entry that holds nothing visible still costs
  its grid step there, 0.25 us, and a live one 2 us).

**A pool row is a whole lane tile** (PR 34). The pool is ``[L, NB, Hp, bs,
W]`` bfloat16 and the chip tiles its last two dimensions ``(16, 128)``. At
``D`` = 128 a head's row is one tile row: ``Hp`` = ``Hkv``, ``W`` = ``D``. A
row of ``D`` = 64 would be half of one: XLA then keeps the pool twice over,
compressed (the block dimension minor-most) at the program's boundary and
padded to 128 lanes for the Pallas operand, and under memory pressure
changes between the two around every layer's kernel call (Granite-4.0-H:
20 whole-pool copies a decode program, 3.35 of 5.3 s of device time; PERF.md
PR 34). So where ``D`` < 128 divides 128 and ``r = 128 // D`` divides the KV
heads a chip holds (:func:`heads_per_row`), ``r`` consecutive KV heads share
a row: ``Hp`` = ``Hkv / r``, ``W`` = ``r * D``, the same bytes with no lane
padding, head ``j`` of a row's ``r`` in lanes ``[j * D, (j + 1) * D)``. The
kernels never learn of it. :func:`paged_attention` reads ``r`` off the
shapes at its door (``pool.shape[-1] // q.shape[-1]``) and hands them
``Hkv / r`` heads of ``r * G`` query rows and ``r * D`` lanes: a head's
queries sit in their own lanes of the row and zeros in the others, so a
product with the row is that head's own score, and of the ``[r * G, r *
D]`` output the ``r`` diagonal ``[G, D]`` blocks are kept. On a 128-wide
MXU a contraction of 64 costs the passes of one of 128; the bytes moved are
the true K/V bytes. ``r`` = 1 is the pool as it always was, and its
programs are byte for byte what they were. The XLA reference splits the
pages it gathered back into head planes (:func:`split_rows`) and runs the
same lines on the same shapes as ever.

**A window** (``window=W``, the sliding-attention layers of a decoder that
mixes them with full layers): query ``i`` sees keys ``j`` with ``i - W < j <=
i``. The mathematics is one more comparison in ``reference_attention``. The
kernels also stop doing work for what it masks: a decode row's walk starts at
the group of pages that holds position ``kv_len - W`` (its first group and its
trip count from ``kv_len``; no copy for a page wholly behind the window, the
partial first page masked), and a block of queries' walk in the prefill
kernel starts at the page of the oldest key its FIRST query can see
(``_window_first_page``; groups are counted from that page, so none is
copied or multiplied that lies wholly behind it, and the keys the block's
later queries no longer see are masked); the ``BlockSpec`` form's grid spans
the ``W + block_q`` positions a block can see, whatever the table spans. A
table entry still covers positions ``[j
* bs, (j + 1) * bs)``: a window layer's table may name the same physical block
at entries a whole ring apart (``models/vlm/engine.py``: the window pool), and
nothing here knows. ``window=None`` traces exactly what it traced before.

Every way of reaching a page, the decode kernel's copy of ``pool[layer,
block]`` (all of a page's heads), the prefill kernel's of ``pool[layer, block,
head]`` and a ``BlockSpec`` ``(None, None, ..., bs, W)`` of the pool, pins
the pool operand to the row-major layout with ``(bs, W)`` tiled. Whatever
produces the pool inside the same program must leave it in that layout, or
XLA puts a relayout ``copy`` of the whole pool in front of every call: the
write, ``models/vlm/paged_kv.paged_update``, keeps its side of the contract
by indexing every dimension but the last (its module docstring; pinned by
``tests/ops/test_tpu_compile.py``, which counts pool-shaped copies at every
width that is served: none). A change to the page's shape here is a change
to that contract.

Which implementation runs is decided here and nowhere else, from what the
code can observe: on a TPU the Pallas kernels (the row's width ``% 128``
picks the form of each, above), elsewhere ``reference_attention``, the plain XLA lines, over
the row's pages gathered for the einsum (never scattered back); NOT
interpret-mode Pallas. ``DecoderLayer``'s slot-cache branch (the engine's
``gather`` programs) calls the same function, so the byte-identical parity
contract (tests/models/test_paged_kv.py) holds on CPU by construction.
Tests and ``chip_smoke.py`` pick a side with ``use_kernel=`` / ``interpret=``.

``paged_head_attention`` wraps the op in a ``shard_map`` over the model
mesh axis: KV pool and queries shard over heads, block tables and lengths
replicate — the tensor-parallel form traced by shardcheck's
``vlm-paged-head-attention`` contract (analysis/shard_check.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from cosmos_curate_tpu.ops.tiling import round_up, sublanes

_NEG_INF = -1e30
_GROUP_BUFFER_BYTES = 128 * 1024  # one of the decode kernel's four page buffers
_LANES = 128  # the minor dimension of a tile, whatever the type
_PREFILL_GROUP_KEYS = 1024  # keys a trip of the prefill kernel's loop covers, where they fit
_SCORE_TILE_BYTES = 3 * 1024 * 1024  # ...as a float32 score tile of a block of queries


def heads_per_row(n_kv_heads: int, head_dim: int) -> int:
    """``r``: KV heads stored side by side in one pool row (the module
    docstring's layout contract). ``128 // head_dim`` where that makes the
    row one whole lane tile and divides ``n_kv_heads``, the heads ONE chip
    holds; otherwise 1, the pool as ``[.., Hkv, bs, D]``. Padding heads
    with zeros to force a tile is not done: it would store bytes nobody
    reads."""
    if head_dim >= _LANES or _LANES % head_dim or n_kv_heads % (_LANES // head_dim):
        return 1
    return _LANES // head_dim


def split_rows(pages: jax.Array, r: int) -> jax.Array:
    """Pool pages ``[..., Hp, bs, r * D]`` as head planes ``[..., Hp * r, bs,
    D]``: what the XLA reference and the ``gather`` programs' views are
    made of. A copy (a row's heads interleave in memory), of pages that
    were gathered, and so copied, anyway."""
    if r == 1:
        return pages
    *lead, hp, bs, w = pages.shape
    return pages.reshape(*lead, hp, bs, r, w // r).swapaxes(-3, -2).reshape(*lead, hp * r, bs, w // r)


def join_rows(planes: jax.Array, r: int) -> jax.Array:
    """:func:`split_rows` undone: head planes ``[..., Hkv, bs, D]`` as pool
    pages ``[..., Hkv / r, bs, r * D]``."""
    if r == 1:
        return planes
    *lead, hk, bs, d = planes.shape
    return planes.reshape(*lead, hk // r, r, bs, d).swapaxes(-3, -2).reshape(*lead, hk // r, bs, r * d)


def _queries_by_row(q: jax.Array, r: int) -> jax.Array:
    """Grouped queries ``[B, T, Hkv, G, D]`` against a pool of ``r`` heads a
    row: ``[B, T, Hkv / r, r * G, r * D]``, head ``j`` of a row in query
    rows ``[j * G, (j + 1) * G)`` and lanes ``[j * D, (j + 1) * D)``, zeros
    in the other heads' lanes, so that a product with the pool's row is the
    head's own score and nothing of its neighbours'."""
    if r == 1:
        return q
    b, t, hk, g, d = q.shape
    q = q.reshape(b, t, hk // r, r, g, d)
    own = jnp.eye(r, dtype=q.dtype)[:, None, :, None]  # [r, 1, r, 1]
    return (q[:, :, :, :, :, None, :] * own).reshape(b, t, hk // r, r * g, r * d)


def _outputs_by_head(out: jax.Array, r: int) -> jax.Array:
    """What the kernels return for :func:`_queries_by_row`'s queries, ``[B,
    T, Hkv / r, r * G, r * D]``, cut back to ``[B, T, Hkv, G, D]``: query
    rows of head ``j`` keep lanes ``[j * D, (j + 1) * D)``, their own head's
    values (the other lanes hold their probabilities times a neighbour's)."""
    if r == 1:
        return out
    b, t, hp, rg, rd = out.shape
    g, d = rg // r, rd // r
    out = out.reshape(b, t, hp, r, g, r, d)
    return jnp.stack([out[:, :, :, j, :, j] for j in range(r)], axis=3).reshape(b, t, hp * r, g, d)


def reference_attention(q, k, v, write_index, kv_len, *, sm_scale, window=None):
    """The XLA attention every kernel here is held to, and the one
    ``DecoderLayer``'s slot-cache branch runs. q: ``[B, T, Hkv, G, D]``
    unscaled grouped queries; k, v: the rows' K/V ``[B, Hkv, S, D]`` with
    this chunk already written; write_index / kv_len: ``[B]``. Heads stay
    grouped against the KV's ``Hkv`` (no ``jnp.repeat``: the bytes read are
    the true KV size). Causality is over cache order (``write_index`` +
    chunk offset): under m-rope the rope positions are not monotone in it.
    ``window``: a query sees the ``window`` newest positions up to its own and
    nothing older. Returns ``[B, T, Hkv, G, D]`` in q's dtype."""
    t, s = q.shape[1], k.shape[2]
    qg = q * sm_scale
    logits = jnp.einsum(
        "btkgd,bksd->bkgts", qg.astype(jnp.float32), k.astype(jnp.float32)
    )
    k_pos = jnp.arange(s)[None, None, None, None, :]  # cache slot index
    q_seq = write_index[:, None] + jnp.arange(t)[None, :]  # [B, T]
    causal = k_pos <= q_seq[:, None, None, :, None]
    written = k_pos < kv_len[:, None, None, None, None]
    seen = causal & written
    if window is not None:
        seen &= k_pos > q_seq[:, None, None, :, None] - window
    logits = jnp.where(seen, logits, _NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bkgts,bksd->btkgd", probs.astype(q.dtype), v)


def _paged_reference(q, pool_k, pool_v, tables, write_index, kv_len, *, layer_index, sm_scale, window=None):
    """``reference_attention`` over the rows' pages, gathered for the
    einsum (no scatter-back): the same primitives on the same shapes as the
    slot-cache branch, so CPU outputs are bit-equal to the gather programs."""
    b, _, hk, _, d = q.shape
    s = tables.shape[1] * pool_k.shape[3]
    r = pool_k.shape[-1] // d
    # [B, nbl, Hkv, bs, D] (a packed pool's pages split into head planes
    # first) -> the slot-row view [B, Hkv, S, D]
    k = split_rows(pool_k[layer_index][tables], r).swapaxes(1, 2).reshape(b, hk, s, d)
    v = split_rows(pool_v[layer_index][tables], r).swapaxes(1, 2).reshape(b, hk, s, d)
    return reference_attention(q, k, v, write_index, kv_len, sm_scale=sm_scale, window=window)


def _decode_pages(bs: int, hk: int, d: int, dtype, nbl: int) -> int:
    """Table entries a step of the decode kernels covers (``P``): at least
    128 keys, so a score product fills the MXU's width, whatever that
    makes of a buffer (``Hkv`` 8 at ``D`` 128: 256 KiB, 1 MiB across the
    four); as many more as keep one buffer of a group's ``[Hkv, P * bs,
    D]`` pages (lane padding counted) within 128 KiB, two for K and two
    for V; never more than the table holds."""
    page_bytes = hk * bs * round_up(d, 128) * jnp.dtype(dtype).itemsize
    pages = max(pl.cdiv(128, bs), _GROUP_BUFFER_BYTES // page_bytes)
    return min(pages, nbl)


def _attend_group(q, k, v, k_start, kv_len, acc, m_prev, l_prev, first_key=None):
    """One online-softmax step of one KV head over a group of keys, all in
    float32. q: ``[g_pad, D]``, scaled; k, v: ``[N, D]`` of the pool's
    dtype, the first of them at logical position ``k_start``; keys at or
    past ``kv_len`` (and, under a window, before ``first_key``) are masked by
    position. Returns the new state."""
    s = jax.lax.dot_general(
        q, k.astype(jnp.float32), (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # [g_pad, N]
    k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    seen = k_pos < kv_len if first_key is None else (k_pos < kv_len) & (k_pos >= first_key)
    s = jnp.where(seen, s, _NEG_INF)
    m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_new = l_prev * alpha + p.sum(axis=1, keepdims=True)
    acc = acc * alpha + jax.lax.dot_general(
        p, v.astype(jnp.float32), (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    return acc, m_new, l_new


def _paged_decode_kernel(
    layer_ref, kvlen_ref, tbl_ref, q_ref, k_hbm, v_hbm, o_ref, k_buf, v_buf, sems,
    *, sm_scale, bs, pages, window=None,
):
    """One grid step is one row with all of its KV heads: a page's
    ``[Hkv, bs, D]`` is contiguous in the pool, so one copy a page serves
    every head. The loop walks the row's OWN table in groups of ``pages``
    entries, as far as its valid length and no further; group ``i + 1`` is
    in flight while group ``i`` is computed. Under a ``window`` the walk
    starts at the group that holds the row's oldest visible key, and no page
    wholly before that key is copied."""
    b = pl.program_id(0)
    layer, kv_len = layer_ref[0], kvlen_ref[b]
    hk, g_pad, d = q_ref.shape
    group = pages * bs
    n_groups = pl.cdiv(kv_len, group)
    if window is not None:
        first_key = jnp.maximum(kv_len - window, 0)  # the one query is at kv_len - 1
        first_group = first_key // group
    edge = {} if window is None else {"first_key": first_key}

    def each_live_page(i, slot, act):
        # no copy for a table entry at or past the valid length: its block
        # id is garbage (the engine's block 0, or anything)
        first = i * pages
        behind = 0 if window is None else jnp.clip(first_key // bs - first, 0, pages)

        def page(p, carry):
            block = tbl_ref[b, first + p]
            rows = pl.ds(pl.multiple_of(p * bs, bs), bs)
            for pool, buf, sem in ((k_hbm, k_buf, sems.at[0, slot]), (v_hbm, v_buf, sems.at[1, slot])):
                act(pltpu.make_async_copy(pool.at[layer, block], buf.at[slot, :, rows], sem))
            return carry

        jax.lax.fori_loop(behind, jnp.clip(pl.cdiv(kv_len, bs) - first, 0, pages), page, 0)

    # a dead page's rows of a V buffer are multiplied by p = 0 and have to
    # be finite for that: the scratch starts as zeros, and a later row finds
    # an earlier row's pages there (rows run in order on one core: the
    # call's grid is "arbitrary", see `_paged_decode`)
    @pl.when(b == 0)
    def _():
        v_buf[...] = jnp.zeros_like(v_buf)

    each_live_page(0 if window is None else first_group, 0, lambda copy: copy.start())
    q = q_ref[...].astype(jnp.float32) * sm_scale  # [hk, g_pad, d]

    def two_groups(pair, state):
        # two groups an iteration, so that each names its buffer statically.
        # A group past the row's last has no live page: no copy, no wait,
        # and its keys, all masked, leave the state as it was (p = 0,
        # alpha = 1); the first group walked (group 0, or the one with the
        # window's oldest key) holds a valid key whenever the loop runs.
        for slot in (0, 1):
            i = 2 * pair + slot
            if window is not None:
                i = first_group + i
            each_live_page(i + 1, 1 - slot, lambda copy: copy.start())
            each_live_page(i, slot, lambda copy: copy.wait())
            state = tuple(
                _attend_group(q[h], k_buf[slot, h], v_buf[slot, h], i * group, kv_len, *state[h], **edge)
                for h in range(hk)
            )
        return state

    init = (
        jnp.zeros((g_pad, d), jnp.float32),
        jnp.full((g_pad, 1), _NEG_INF, jnp.float32),
        jnp.zeros((g_pad, 1), jnp.float32),
    )
    walked = n_groups if window is None else n_groups - first_group
    state = jax.lax.fori_loop(0, pl.cdiv(walked, 2), two_groups, (init,) * hk)
    for h, (acc, _, l) in enumerate(state):
        o_ref[h] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def _paged_decode_blockspec_kernel(
    layer_ref, kvlen_ref, tbl_ref, q_ref, *refs, sm_scale, bs, pages, window=None
):
    """The same grouping where the kernel cannot copy for itself (see
    ``_paged_decode``): grid ``(row, group)``, the group's ``pages`` pages
    of K and of V delivered by as many ``BlockSpec``s, the softmax state in
    scratch across a row's grid steps. A group past the valid length costs
    a grid step and nothing else. Kept for exactly the widths that make no
    whole lane tile even packed (``heads_per_row`` = 1 under 128 lanes:
    ``tiny-test`` and the other test-size flavors, ``D`` = 16 with 2 or 4
    KV heads, which a chip serves too: ``local split --caption-model
    tiny-test``); every served width (``base`` and Granite packed, the
    Qwens) takes ``_paged_decode_kernel``."""
    k_refs, v_refs = refs[:pages], refs[pages : 2 * pages]
    o_ref, acc_ref, m_ref, l_ref = refs[2 * pages :]
    b, i = pl.program_id(0), pl.program_id(1)
    kv_len = kvlen_ref[b]
    hk = q_ref.shape[0]

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    live = i * pages * bs < kv_len
    edge = {}
    if window is not None:  # a group wholly behind the window costs a grid step too
        edge["first_key"] = jnp.maximum(kv_len - window, 0)
        live &= (i + 1) * pages * bs > edge["first_key"]

    @pl.when(live)
    def _step():
        for h in range(hk):
            k = jnp.concatenate([ref[h] for ref in k_refs], axis=0)  # [pages * bs, d]
            v = jnp.concatenate([ref[h] for ref in v_refs], axis=0)
            q = q_ref[h].astype(jnp.float32) * sm_scale
            acc_ref[h], m_ref[h], l_ref[h] = _attend_group(
                q, k, v, i * pages * bs, kv_len, acc_ref[h], m_ref[h], l_ref[h], **edge
            )

    @pl.when(i == pl.num_programs(1) - 1)
    def _finish():
        o_ref[...] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def _prefill_pages(bs: int, rows: int, nbl: int) -> int:
    """Table entries a trip of the prefill kernel's loop covers (``P``), for a
    block of ``rows`` query rows (``G * block_q``): 1,024 keys, because a
    trip's cost is mostly fixed in the keys (the row maxima and sums across
    lanes, the softmax state's and the accumulator's round trip, the copies'
    issue: on one v5e at Trinity's widths a 12k context took 6.87 / 4.69 /
    2.71 / 2.74 ms at 256 / 512 / 1,024 / 2,048 keys, PERF.md PR 39), and
    fewer, in whole MXU widths of 128, where the float32 score tile ``[rows,
    P * bs]`` would pass 3 MiB (the kernel holds a handful of arrays of that
    shape, and a call's VMEM is 16 MiB); at least one page, never more than
    the table holds."""
    keys = min(_PREFILL_GROUP_KEYS, max(128, _SCORE_TILE_BYTES // (4 * rows) // 128 * 128))
    return min(max(1, keys // bs), nbl)


def _paged_prefill_kernel(
    layer_ref, write_ref, kvlen_ref, tbl_ref, q_ref, k_hbm, v_hbm, o_ref,
    k_buf, v_buf, sems, acc_ref, m_ref, l_ref,
    *, sm_scale, block_q, bs, g, pages, window=None,
):
    """One grid step is one block of ``block_q`` queries of one row against
    one KV head (one pool row). The loop walks the row's OWN table in groups
    of ``pages`` entries from the first page the block's first query can see
    (0, or the window's) to the page of the newest key its last query can
    (``min(kv_len, last query + 1) - 1``), and no further: one copy a live
    page a pool into one of two ``[pages * bs, D]`` buffers, group ``i + 1``
    in flight while group ``i`` is computed, none for an entry past that
    newest key or wholly behind the window. A padding row (``kv_len`` 0) runs
    no trip. Every key of a group is masked by position (masking only the
    groups a mask can bite in bought nothing on the chip). Where q and the
    pool are bfloat16 q.K is fed to the MXU as it is stored, accumulated in
    float32 (every product exact) with ``sm_scale`` applied to the float32
    scores; anything else is multiplied in float32. p.V is the float32
    product it always was. (What Mosaic makes of a float32 x float32
    ``dot_general`` with no ``precision`` named is ONE bfloat16 pass, by a
    probe on the chip, PERF.md PR 39: so the old kernel's q * sm_scale
    reached the MXU rounded to bfloat16, and p does, then as now.)"""
    b, h, qi = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    layer, write, kv_len = layer_ref[0], write_ref[b], kvlen_ref[b]
    d = q_ref.shape[-1]
    rows, group = g * block_q, pages * bs
    q_first = write + qi * block_q  # the block's first query's position
    first_page = 0 if window is None else _window_first_page(write, qi, block_q, bs, window)
    n_pages = pl.cdiv(jnp.minimum(kv_len, q_first + block_q), bs)  # live: [first_page, n_pages)
    n_groups = pl.cdiv(jnp.maximum(n_pages - first_page, 0), pages)
    narrow = q_ref.dtype == k_buf.dtype == jnp.bfloat16

    def each_live_page(i, slot, act):
        first = first_page + i * pages

        def page(p, carry):
            block = tbl_ref[b, first + p]
            keys = pl.ds(pl.multiple_of(p * bs, bs), bs)
            for pool, buf, sem in ((k_hbm, k_buf, sems.at[0, slot]), (v_hbm, v_buf, sems.at[1, slot])):
                act(pltpu.make_async_copy(pool.at[layer, block, h], buf.at[slot, keys], sem))
            return carry

        jax.lax.fori_loop(0, jnp.clip(n_pages - first, 0, pages), page, 0)

    # a dead page's rows of a V buffer are multiplied by p = 0 and have to be
    # finite for that: the scratch starts as zeros, and a later step finds an
    # earlier step's pages there (steps run in order on one core: "arbitrary")
    @pl.when((b == 0) & (h == 0) & (qi == 0))
    def _():
        v_buf[...] = jnp.zeros_like(v_buf)

    each_live_page(0, 0, lambda copy: copy.start())
    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    # rows are group-major: row r is query t_local = r % block_q of query head
    # r // block_q, so the [g, block_q, d] tile flattens without moving data
    # (block_q is a whole number of sublane tiles)
    q = q_ref[...].reshape(rows, d)
    if not narrow:
        q = q.astype(jnp.float32) * sm_scale
    col = jax.lax.broadcasted_iota(jnp.int32, (rows, group), 1)
    # key column less the query's offset in the block: <= q_first - k_start is causal
    ahead = col - jax.lax.broadcasted_iota(jnp.int32, (g, block_q, group), 1).reshape(rows, group)

    def one_group(i, carry):
        slot = jax.lax.rem(i, 2)
        each_live_page(i + 1, 1 - slot, lambda copy: copy.start())
        each_live_page(i, slot, lambda copy: copy.wait())
        k, v = k_buf[slot], v_buf[slot]  # [group, d]
        k_start = (first_page + i * pages) * bs
        if narrow:
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
            s = s * sm_scale
        else:
            s = jax.lax.dot_general(
                q, k.astype(jnp.float32), (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
            )
        ok = (ahead <= q_first - k_start) & (col < kv_len - k_start)
        if window is not None:
            ok &= ahead > q_first - window - k_start
        s = jnp.where(ok, s, _NEG_INF)
        m_prev = m_ref[:, :1]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[:, :1] = l_ref[:, :1] * alpha + p.sum(axis=1, keepdims=True)
        m_ref[:, :1] = m_new
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v.astype(jnp.float32), (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        return carry

    jax.lax.fori_loop(0, n_groups, one_group, 0)
    out = acc_ref[...] / jnp.maximum(l_ref[:, :1], 1e-30)
    o_ref[...] = out.reshape(g, block_q, d).astype(o_ref.dtype)


def _paged_prefill_blockspec_kernel(
    layer_ref, write_ref, kvlen_ref, tbl_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref,
    *, sm_scale, block_q, bs, g, window=None,
):
    """The kernel the pipeline feeds where this one cannot copy for itself
    (see ``_paged_prefill``): grid ``(row, KV head, block of queries, table
    entry)``, one page of K and of V a grid step by ``BlockSpec``, the softmax
    state in scratch across a block's steps, float32 products. An entry that
    holds nothing the block can see costs a grid step and nothing else. Kept
    for exactly the widths ``_paged_decode_blockspec_kernel`` is kept for
    (``D`` = 16: the test-size flavors); every served width takes
    ``_paged_prefill_kernel``."""
    del layer_ref  # the index map's
    b = pl.program_id(0)
    qi = pl.program_id(2)
    ji = pl.program_id(3)
    num_j = pl.num_programs(3)

    @pl.when(ji == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    write = write_ref[b]
    kv_len = kvlen_ref[b]
    k_start = ji * bs
    if window is not None:
        # the grid's last dimension counts pages from the first one this block
        # of queries can see (`_window_first_page`, the K/V index map alike)
        k_start = k_start + _window_first_page(write, qi, block_q, bs, window) * bs
    rows = g * block_q
    last_pos = write + qi * block_q + block_q - 1

    @pl.when((k_start <= last_pos) & (k_start < kv_len))
    def _step():
        # rows are group-major: row r is query t_local = r % block_q of
        # group r // block_q, so the [g, block_q, d] tile flattens without
        # moving data (block_q is a whole number of sublane tiles)
        q = q_ref[...].astype(jnp.float32).reshape(rows, q_ref.shape[-1])
        q = q * sm_scale
        k = k_ref[...].astype(jnp.float32)  # [bs, d]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # [rows, bs]
        t_local = jax.lax.broadcasted_iota(jnp.int32, (g, block_q, bs), 1).reshape(rows, bs)
        q_pos = write + qi * block_q + t_local
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, (rows, bs), 1)
        ok = (k_pos <= q_pos) & (k_pos < kv_len)
        if window is not None:
            ok &= k_pos > q_pos - window
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

    @pl.when(ji == num_j - 1)
    def _finish():
        out = acc_ref[:] / jnp.maximum(l_ref[:, :1], 1e-30)
        o_ref[...] = out.reshape(g, block_q, o_ref.shape[-1]).astype(o_ref.dtype)


def _window_first_page(write, qi, block_q: int, bs: int, window: int):
    """The page that holds the oldest key the first query of block ``qi`` of a
    chunk written at ``write`` can see under ``window``."""
    return jnp.maximum(write + qi * block_q - window + 1, 0) // bs


def _prefill_block_q(t: int, dtype, block_q: int = 128) -> int:
    """Queries a grid step of the prefill kernels takes of a chunk of ``t``."""
    return min(block_q, round_up(t, sublanes(dtype)))


def prefill_pages_walked(write_index, kv_len, t: int, bs: int, dtype, window=None) -> tuple[int, int]:
    """The prefill kernel's walk by the host's arithmetic, for a counter:
    (table entries the loops of one call visit for one KV head, blocks of
    queries the call has), over the rows of ``write_index`` / ``kv_len``
    (numpy) for a chunk of ``t`` queries of ``dtype``. A block of queries
    walks from the page of the oldest key its first query can see to the
    page of the newest its last one can, within the valid length."""
    block_q = _prefill_block_q(t, dtype)
    first = write_index[:, None] + np.arange(0, t, block_q)[None, :]  # [rows, blocks]: their first queries
    n_pages = -(-np.minimum(kv_len[:, None], first + block_q) // bs)
    first_page = 0 if window is None else np.maximum(first - window + 1, 0) // bs
    return int(np.maximum(n_pages - first_page, 0).sum()), first.size


@functools.partial(jax.jit, static_argnames=("sm_scale", "interpret", "window"))
def _paged_decode(q, pool_k, pool_v, tables, kv_len, *, layer_index, sm_scale, interpret, window=None):
    """q: [B, Hkv, G, D]; pools: [L, NB, Hkv, bs, D]; tables: [B, nbl]
    (against a pool of ``r`` heads a row these are ``Hkv / r``, ``r * G``
    and ``r * D``: ``paged_attention``).
    ``layer_index`` is a run-time scalar here, prefetched with the table:
    a model's layers share one trace and one lowering of the kernel."""
    b, hk, g, d = q.shape
    nbl = tables.shape[1]
    bs = pool_k.shape[3]
    g_pad = round_up(g, sublanes(q.dtype))
    if g_pad != g:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, g_pad - g), (0, 0)))

    pages = _decode_pages(bs, hk, d, pool_k.dtype, nbl)
    q_spec = pl.BlockSpec((None, hk, g_pad, d), lambda b_, *_: (b_, 0, 0, 0))
    if d % 128 == 0:
        # the pools stay in HBM and the kernel copies the pages it wants:
        # the operand is the pool as the write left it, row-major with
        # (bs, D) tiled
        kernel = functools.partial(_paged_decode_kernel, sm_scale=sm_scale, bs=bs, pages=pages)
        if window is not None:
            kernel = functools.partial(kernel, window=window)
        grid = (b,)
        pool_specs = [pl.BlockSpec(memory_space=pl.ANY)] * 2
        group_buffer = pltpu.VMEM((2, hk, pages * bs, d), pool_k.dtype)
        scratch = [group_buffer, group_buffer, pltpu.SemaphoreType.DMA((2, 2))]
    else:
        # Mosaic slices no HBM array whose last dimension is under a lane
        # tile ("Slice shape along dimension 4 must be aligned to tiling
        # (128), but is 64"), so for a row that packing could not make a
        # whole tile (the test-size flavors, D = 16) the pipeline fetches
        # the group: one BlockSpec a page. An entry at or past the valid
        # length names the row's last live page again (not fetched twice,
        # masked by position), so no block past the length is ever read.
        kernel = functools.partial(
            _paged_decode_blockspec_kernel, sm_scale=sm_scale, bs=bs, pages=pages
        )
        if window is not None:
            kernel = functools.partial(kernel, window=window)
        grid = (b, pl.cdiv(nbl, pages))

        def page_spec(p):
            def index(b_, i, layer, kvlen, tbl):
                last_live = jnp.maximum(kvlen[b_] - 1, 0) // bs
                entry = jnp.minimum(i * pages + p, last_live)
                if window is not None:  # ...nor one wholly behind the window
                    entry = jnp.maximum(entry, jnp.maximum(kvlen[b_] - window, 0) // bs)
                return layer[0], tbl[b_, entry], 0, 0, 0

            return pl.BlockSpec((None, None, hk, bs, d), index)

        pool_specs = [page_spec(p) for p in range(pages)] * 2
        scratch = [
            pltpu.VMEM((hk, g_pad, d), jnp.float32),
            pltpu.VMEM((hk, g_pad, 1), jnp.float32),
            pltpu.VMEM((hk, g_pad, 1), jnp.float32),
        ]
    n_pool = len(pool_specs) // 2
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=grid,
            in_specs=[q_spec, *pool_specs],
            out_specs=q_spec,
            scratch_shapes=scratch,
        ),
        out_shape=jax.ShapeDtypeStruct((b, hk, g_pad, d), q.dtype),
        # never "parallel": scratch carries state from one grid step to the
        # next (a row's softmax state over its groups; the V buffers zeroed
        # in the first row), so no core may start in the middle of the grid
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",) * len(grid)),
        interpret=interpret,
    )(
        jnp.asarray(layer_index, jnp.int32).reshape(1), kv_len.astype(jnp.int32),
        tables.astype(jnp.int32), q, *[pool_k] * n_pool, *[pool_v] * n_pool,
    )
    return out[:, :, :g]


@functools.partial(jax.jit, static_argnames=("sm_scale", "block_q", "interpret", "window"))
def _paged_prefill(
    q, pool_k, pool_v, tables, write_index, kv_len, *, layer_index, sm_scale, block_q, interpret,
    window=None,
):
    """q: [B, T, Hkv, G, D]; pools: [L, NB, Hkv, bs, D]; tables: [B, nbl]
    (against a pool of ``r`` heads a row these are ``Hkv / r``, ``r * G`` and
    ``r * D``: ``paged_attention``). ``layer_index`` is a run-time scalar,
    prefetched with the table as in ``_paged_decode``: a model's layers of
    one kind share one trace and one lowering of the kernel."""
    b, t, hk, g, d = q.shape
    nbl = tables.shape[1]
    bs = pool_k.shape[3]
    block_q = _prefill_block_q(t, q.dtype, block_q)
    t_pad = round_up(t, block_q)
    # heads-major, group-major queries: the kernel's [g, block_q, d] tile
    # keeps (block_q, d) as the tiled dims, like the KV pages
    q = q.transpose(0, 2, 3, 1, 4)  # [B, Hkv, G, T, D]
    if t_pad != t:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, 0), (0, t_pad - t), (0, 0)))

    edge = {} if window is None else {"window": window}
    rows = g * block_q
    state = [
        pltpu.VMEM((rows, d), jnp.float32),
        pltpu.VMEM((rows, 128), jnp.float32),
        pltpu.VMEM((rows, 128), jnp.float32),
    ]
    if d % 128 == 0:
        # the pools stay in HBM and the kernel copies the pages it wants, as
        # the decode kernel does: the operand is the pool as the write left it
        pages = _prefill_pages(bs, rows, nbl)
        kernel = functools.partial(
            _paged_prefill_kernel, sm_scale=sm_scale, block_q=block_q, bs=bs, g=g, pages=pages, **edge
        )
        grid = (b, hk, t_pad // block_q)
        pool_spec = pl.BlockSpec(memory_space=pl.ANY)
        group_buffer = pltpu.VMEM((2, pages * bs, d), pool_k.dtype)
        scratch = [group_buffer, group_buffer, pltpu.SemaphoreType.DMA((2, 2)), *state]
    else:
        # Mosaic slices no HBM array whose last dimension is under a lane
        # tile (`_paged_decode`): for a row that packing could not make a
        # whole tile (the test-size flavors, D = 16) the pipeline fetches one
        # page a grid step, over the table or, under a window, over the pages
        # a block of queries can see (`window + block_q - 1` positions of
        # them, one more where they straddle a page's edge)
        kernel = functools.partial(
            _paged_prefill_blockspec_kernel, sm_scale=sm_scale, block_q=block_q, bs=bs, g=g, **edge
        )
        span = nbl if window is None else min(nbl, pl.cdiv(window + block_q - 1, bs) + 1)
        grid = (b, hk, t_pad // block_q, span)

        def page(b_, h, qi, ji, layer, write, kvlen, tbl):
            if window is not None:
                ji = jnp.minimum(_window_first_page(write[b_], qi, block_q, bs, window) + ji, nbl - 1)
            return layer[0], tbl[b_, ji], h, 0, 0

        pool_spec = pl.BlockSpec((None, None, None, bs, d), page)
        scratch = state
    q_spec = pl.BlockSpec((None, None, g, block_q, d), lambda b_, h, qi, *_: (b_, h, 0, qi, 0))
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=grid,
            in_specs=[q_spec, pool_spec, pool_spec],
            out_specs=q_spec,
            scratch_shapes=scratch,
        ),
        out_shape=jax.ShapeDtypeStruct((b, hk, g, t_pad, d), q.dtype),
        # never "parallel": scratch carries state from one grid step to the
        # next (the softmax state over a block's pages; the V buffers zeroed
        # in the first step), so no core may start in the middle of the grid
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",) * len(grid)),
        interpret=interpret,
    )(
        jnp.asarray(layer_index, jnp.int32).reshape(1),
        write_index.astype(jnp.int32),
        kv_len.astype(jnp.int32),
        tables.astype(jnp.int32),
        q,
        pool_k,
        pool_v,
    )
    return out[:, :, :, :t].transpose(0, 3, 1, 2, 4)


def paged_attention(
    q: jax.Array,
    pool_k: jax.Array,
    pool_v: jax.Array,
    tables: jax.Array,
    write_index: jax.Array,
    kv_len: jax.Array,
    *,
    layer_index: int = 0,
    sm_scale: float | None = None,
    block_q: int = 128,
    use_kernel: bool | None = None,
    interpret: bool | None = None,
    window: int | None = None,
) -> jax.Array:
    """Attention straight out of the paged KV pool, no gathered working set.

    q: ``[B, T, Hkv, G, D]`` UNSCALED grouped queries (this op applies
    ``sm_scale``, in the kernels as in the reference);
    pool_k/pool_v: the full block pools ``[L, NB, Hkv / r, bs, r * D]``
    (``r`` KV heads a row, read off these shapes: the module docstring;
    ``sm_scale`` defaults from the true ``D``) with the chunk's K/V already
    written through the table; tables: ``[B, nbl]`` logical-to-physical
    block ids; write_index/kv_len: ``[B]``. Serves both decode (T=1) and
    chunked prefill (T>1). ``window``: a sliding-attention layer's (the module
    docstring); None = every earlier position. Returns ``[B, T, Hkv, G, D]``.

    ``use_kernel=None`` means the Pallas kernels on a TPU and the XLA
    reference (:func:`reference_attention` over the gathered pages) elsewhere.
    """
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    if use_kernel is None:
        use_kernel = jax.devices()[0].platform == "tpu"
    edge = {} if window is None else {"window": int(window)}
    if not use_kernel:
        return _paged_reference(
            q, pool_k, pool_v, tables, write_index, kv_len,
            layer_index=layer_index, sm_scale=sm_scale, **edge,
        )
    if interpret is None:
        interpret = jax.devices()[0].platform == "cpu"
    r = pool_k.shape[-1] // q.shape[-1]  # KV heads a pool row: the module docstring
    q = _queries_by_row(q, r)
    if q.shape[1] == 1:
        out = _paged_decode(
            q[:, 0], pool_k, pool_v, tables, kv_len,
            layer_index=layer_index, sm_scale=sm_scale, interpret=interpret, **edge,
        )[:, None]
    else:
        out = _paged_prefill(
            q, pool_k, pool_v, tables, write_index, kv_len,
            layer_index=layer_index, sm_scale=sm_scale, block_q=block_q, interpret=interpret,
            **edge,
        )
    return _outputs_by_head(out, r)


def paged_head_attention(
    mesh,
    q: jax.Array,
    pool_k: jax.Array,
    pool_v: jax.Array,
    tables: jax.Array,
    write_index: jax.Array,
    kv_len: jax.Array,
    *,
    layer_index: int = 0,
    sm_scale: float | None = None,
    block_q: int = 128,
    use_kernel: bool | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """Head-parallel paged attention over the model mesh axis.

    Queries, KV pools, and the output shard on their ``Hkv`` dimension over
    ``parallel/axes.MODEL`` (the pools' is ``Hkv / r`` where ``r`` heads
    share a row: ``r`` was judged on the heads one chip holds, so a row
    never straddles two chips); block tables and lengths replicate (every
    shard walks the same table against its own head plane — attention is
    embarrassingly parallel over KV heads). Accepts an ``AbstractMesh`` so
    shardcheck's ``vlm-paged-head-attention`` contract traces this call
    site device-free. On a mesh without the model axis (or extent 1) the
    computation is identical to :func:`paged_attention` bit-for-bit.
    """
    from jax.sharding import PartitionSpec as P

    from cosmos_curate_tpu.parallel.axes import MODEL
    from cosmos_curate_tpu.parallel.sharding import shard_map

    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    axis = MODEL if MODEL in mesh.axis_names else None
    qspec = P(None, None, axis, None, None)  # [B, T, Hkv, G, D]
    pspec = P(None, None, axis, None, None)  # [L, NB, Hkv / r, bs, r * D]
    fn = functools.partial(
        paged_attention,
        layer_index=layer_index,
        sm_scale=sm_scale,
        block_q=block_q,
        use_kernel=use_kernel,
        interpret=interpret,
    )
    return shard_map(
        fn,
        mesh=mesh,
        in_specs=(qspec, pspec, pspec, P(None, None), P(None), P(None)),
        out_specs=qspec,
    )(q, pool_k, pool_v, tables, write_index, kv_len)
