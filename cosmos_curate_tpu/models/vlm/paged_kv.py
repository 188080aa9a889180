"""Paged KV-cache primitives for the caption engine.

vLLM's PagedAttention block-table design (Kwon et al. 2023 — PAPERS.md)
re-shaped for XLA's static-shape compilation: KV memory is ONE block pool
``[L, n_blocks, Hkv / r, block_size, r * Dh]`` (``r`` KV heads a row: below)
and every slot owns a block *table* instead of a worst-case-length cache
row, so a request's KV footprint is ``ceil(len / block_size)`` blocks. The
engine has two sets of programs over
that pool (``paged_attention=``). The default, ``"auto"``, writes a chunk's
K/V through the block table (:func:`paged_update`) and attends straight out
of the pool (ops/paged_attention.py, which alone decides between its Pallas
kernels and the XLA reference): no contiguous working set exists. The
other, ``"gather"``, is the reference the parity tests and the benchmark's
``correct`` compare against: it gathers each slot's blocks into a
contiguous ``[lane_length]`` view
(:func:`gather_block_views`) — the exact shapes the slot-row engine
compiled, so greedy outputs stay byte-identical — runs the unchanged model,
and scatters the written blocks back (:func:`scatter_block_views`).

The layout contract between the write and the kernels: the pool has ONE
device layout from a program's entry to its exit, the one the paged
kernels' operand demands — row-major with the last two dimensions tiled,
because what a kernel fetches is a page: one head row's ``[bs, W]`` at
prefill (``BlockSpec((None, None, None, bs, W))``), all of them copied by
the decode kernel itself. Two things keep it.

**The row is a whole lane tile** (PR 34). The chip tiles a bfloat16 array's
last two dimensions ``(16, 128)``. ``Dh`` = 128 fills a tile's lanes; a row
of ``Dh`` = 64 fills half, and XLA then holds the pool in two forms, lanes
padded for the kernels and compressed at the program's boundary, and
copies the whole pool from one to the other around the kernel calls (20
copies a decode program of Granite-4.0-H, two thirds of its device time).
So where ``Dh`` < 128 divides 128 and ``r = 128 // Dh`` divides the KV
heads a chip holds (``ops/paged_attention.heads_per_row``: the decision
lives there), :func:`init_block_pool` makes the pool ``[L, NB, Hkv / r, bs,
r * Dh]``: ``r`` consecutive heads side by side in one 128-lane row, the
same bytes, nothing padded. A token's ``[Hkv, Dh]`` is already that in
memory, so the write reshapes and moves nothing; the kernels see fewer,
wider heads (``ops/paged_attention.paged_attention``); the ``gather``
programs' views, the shared prefix's blocks and the XLA reference pack and
unpack by reshape (``join_rows`` / ``split_rows``), so the model sees
``[L, N, Hkv, S, Dh]`` as ever. The allocator, the tables, prefix blocks and
``copy_blocks`` deal in whole blocks and do not know. ``r`` = 1 (``Dh`` =
128; the test-size flavors' 16 with two heads, which make no tile) is the
pool as it was before.

**The write indexes every dimension but the last.** XLA chooses a
scatter's layout from its update window: a window that spans ``[Hkv, Dh]``
(the head left as a slice, as this write was first phrased) makes those two
dimensions minor, and every layer then pays a relayout ``copy`` of the whole K and V pool in
front of its kernel call (2 x 28 x 1.13 ms of an 85 ms Qwen2-VL-2B decode
step: PERF.md, PR 25). :func:`paged_update` therefore indexes every pool
dimension but the last, so an update is one row and the donated pool
is updated in place; ``tests/ops/test_tpu_compile.py`` compiles write +
kernel for a described chip and fails on a pool-shaped copy.

Why duplicate scatter indices are safe: shared-prefix blocks appear in MANY
slots' tables at once (that is the point — zero device copies at
admission). The gather programs' scatter-back therefore writes the same
block several times, and XLA leaves the winning order undefined. The
engine's invariant makes every such write identical: a slot's own K/V
writes always start at the prefix boundary (copy-on-write gives it a
private copy of any partially-filled shared tail block first), so shared
blocks are only ever written back with their unchanged gathered contents
(and :func:`paged_update` never touches them).
Block 0 is a reserved garbage block: free table entries point at it and
the decode program's unconditional writes for idle rows land there — its
contents are never read unmasked.

The allocator is host-side and refcounted: the shared-prefix LRU holds one
reference per block it caches, every admitted slot holds one per shared
block it maps, and a block returns to the free list only when the last
reference drops — evicting a prefix whose blocks are still mapped by
in-flight slots defers the free instead of corrupting them.
"""

from __future__ import annotations

import functools

import jax.numpy as jnp


class PoolExhausted(RuntimeError):
    """The block pool cannot supply the requested allocation right now.

    Admission treats this as backpressure (the request waits for in-flight
    slots to free their blocks), not as an error."""


class BlockAllocator:
    """Refcounted free-list allocator over pool block ids.

    Block 0 is the reserved garbage block (never handed out): free table
    entries point at it so the static-shape decode program has a harmless
    write target for idle rows. All mutation runs under the engine lock —
    the allocator itself is deliberately lock-free.
    """

    def __init__(self, n_blocks: int) -> None:
        if n_blocks < 2:
            raise ValueError(f"block pool needs >= 2 blocks, got {n_blocks}")
        self.n_blocks = n_blocks
        self._refs = [0] * n_blocks
        # LIFO free list: recently freed blocks are re-used first (their
        # pool pages are the warmest)
        self._free = list(range(n_blocks - 1, 0, -1))

    @property
    def capacity(self) -> int:
        """Allocatable blocks (the garbage block is not)."""
        return self.n_blocks - 1

    @property
    def free_blocks(self) -> int:
        return len(self._free)

    @property
    def used_blocks(self) -> int:
        return self.capacity - len(self._free)

    def can_alloc(self, n: int) -> bool:
        return n <= len(self._free)

    def alloc(self, n: int) -> list[int]:
        """n fresh blocks with refcount 1; raises PoolExhausted when the
        free list cannot supply them (callers requeue and wait)."""
        if n > len(self._free):
            raise PoolExhausted(
                f"need {n} KV blocks, {len(self._free)} free of {self.capacity}"
            )
        ids = [self._free.pop() for _ in range(n)]
        for b in ids:
            self._refs[b] = 1
        return ids

    def incref(self, ids) -> None:
        for b in ids:
            if self._refs[b] <= 0:
                raise ValueError(f"incref on free block {b}")
            self._refs[b] += 1

    def decref(self, ids) -> list[int]:
        """Drop one reference per id; blocks reaching zero return to the
        free list. Returns the freed ids."""
        freed: list[int] = []
        for b in ids:
            r = self._refs[b]
            if r <= 0:
                raise ValueError(f"decref on free block {b}")
            self._refs[b] = r - 1
            if r == 1:
                self._free.append(b)
                freed.append(b)
        return freed

    def ref(self, block_id: int) -> int:
        return self._refs[block_id]


def init_block_pool(
    cfg, n_blocks: int, block_size: int, dtype=jnp.bfloat16, sharding=None, n_layers: int | None = None
):
    """The K and V block pools: ``[L, n_blocks, Hkv / r, block_size, r * Dh]``,
    ``L`` the layers that hold K/V (a hybrid's state-space layers keep their
    state in the engine's recurrent store instead,
    ``model.init_recurrent_store``) — heads-major, so one head row's page is
    a contiguous ``[block_size, r * Dh]`` tile (the shape the TPU's compiler
    accepts as a kernel block). ``r`` KV heads share a row where that makes
    the row one whole 128-lane tile (``Dh`` 64: two; the module docstring
    has why), judged on the heads ONE chip holds; else ``r`` = 1.
    ``sharding`` creates them already placed (head planes over a mesh).
    ``n_layers``: a flavor that mixes window and full attention layers keeps
    two such pools, one a kind, each with the layers of its kind (the engine's
    module docstring); None = every layer that holds K/V."""
    from cosmos_curate_tpu.ops.paged_attention import heads_per_row

    layers = len(cfg.kv_layers) if n_layers is None else n_layers
    shape = (layers, n_blocks, cfg.n_kv_heads, block_size, cfg.head_dim)
    held = cfg.n_kv_heads if sharding is None else sharding.shard_shape(shape)[2]
    r = heads_per_row(held, cfg.head_dim)
    shape = (*shape[:2], cfg.n_kv_heads // r, block_size, r * cfg.head_dim)
    return jnp.zeros(shape, dtype, device=sharding), jnp.zeros(shape, dtype, device=sharding)


def init_latent_pool(cfg, n_blocks: int, block_size: int, dtype=jnp.bfloat16):
    """The pool of a latent-attention flavor (``cfg.mla``): ONE array ``[L,
    n_blocks, 1, block_size, W]``, a row a token a layer holding ``[c_kv |
    k_rope | zeros]`` (``MLAConfig.cache_width``: 512 + 64 padded to 640, a
    whole number of lane tiles; the chip stores a 576-wide array in 640 lanes
    anyway and Mosaic slices none, PERF.md PR 33). The keys and the values of
    absorbed attention are both read out of that row, so nothing is stored
    twice. It has the K pool's five dimensions with one head plane: block
    tables, the allocator, prefix blocks, copy-on-write and the ``gather``
    programs' views treat it as they treat a K pool. The engine's programs
    thread a V pool too; a latent flavor's is the same shape at WIDTH ZERO, no
    bytes, so that no program grows a second signature."""
    shape = (len(cfg.kv_layers), n_blocks, 1, block_size)
    return jnp.zeros((*shape, cfg.mla.cache_width), dtype), jnp.zeros((*shape, 0), dtype)


def init_index_pool(cfg, n_blocks: int, block_size: int, dtype=jnp.bfloat16):
    """The index keys of a flavor with a learned indexer (``cfg.indexer``): a
    SECOND array a position beside K/V, ``[L, n_blocks, 1, block_size, W]``, one
    key of ``indexer.head_dim`` values a position a layer in the first lanes
    of a row of ``W`` = ``IndexerConfig.cache_width`` (64 values in a whole
    128-lane row, zeros above: a 64-wide minor dimension is half a lane tile,
    which XLA stores padded or twice over, PR 34's lesson; the row costs 256 B
    where the key is 128, counted by ``stats()``'s ``index_pool_bytes_per_chip``;
    two positions a row, or 8-bit keys, would halve it: ROADMAP R8). It has the
    K pool's block dimension and no table of its own: block ``j`` of a row's
    table holds its positions' K, V AND index keys, so the allocator, prefix
    blocks and copy-on-write treat the three arrays as one
    (:func:`latent_update` writes it: one row a position, as a latent pool)."""
    shape = (len(cfg.kv_layers), n_blocks, 1, block_size, cfg.indexer.cache_width)
    return jnp.zeros(shape, dtype)


def latent_update(pool, rows, tables, write_index, *, layer_index=0):
    """Write a chunk's latent rows into the latent pool through the block
    table: :func:`paged_update`'s rule (index every pool dimension but the
    last, so one update is a ``[W]`` row and the donated pool stays in the
    kernel's operand layout) for the one array there is. pool: ``[L, NB, 1,
    bs, W]``; rows: ``[B, T, W]``; tables: ``[B, nbl]``; write_index: ``[B]``."""
    bs = pool.shape[3]
    pos = write_index[:, None] + jnp.arange(rows.shape[1])[None, :]  # [B, T]
    blk = jnp.take_along_axis(tables, pos // bs, axis=1)
    return pool.at[layer_index, blk, 0, pos % bs].set(rows.astype(pool.dtype))


def gather_block_views(pool_k, pool_v, tables, heads_per_row: int = 1):
    """Per-slot contiguous KV views through the block tables.

    pool_k/v: ``[L, NB, Hkv / r, bs, r * Dh]``, ``r`` = ``heads_per_row``
    (a pool cannot say of itself whether a 128-lane row is one head or
    two: its maker does); tables: ``[N, nbl]`` int32 block ids. Returns
    ``[L, N, Hkv, nbl * bs, Dh]`` views — the same shape the slot-row
    engine's cache rows had, so the model and its compiled programs are
    unchanged."""
    from cosmos_curate_tpu.ops.paged_attention import split_rows

    l, _, hp, bs, w = pool_k.shape
    n, nbl = tables.shape
    hk, dh = hp * heads_per_row, w // heads_per_row
    # [L, N, nbl, Hkv, bs, Dh] -> blocks of one head side by side (V by its
    # own width: a latent flavor's V pool has width 0)
    vk = split_rows(pool_k[:, tables], heads_per_row).swapaxes(2, 3).reshape(l, n, hk, nbl * bs, dh)
    vv = split_rows(pool_v[:, tables], heads_per_row).swapaxes(2, 3)
    return vk, vv.reshape(l, n, hk, nbl * bs, vv.shape[-1])


def scatter_block_views(pool_k, pool_v, tables, view_k, view_v):
    """Write updated per-slot views back into the pool blocks (packed again
    where the pool holds several heads a row: the views' ``Hkv`` over the
    pool's says how many).

    Duplicate table entries (shared prefix blocks, garbage padding) write
    identical values by the engine's copy-on-write invariant — see the
    module docstring — so the scatter's undefined duplicate-write order
    cannot change pool contents."""
    from cosmos_curate_tpu.ops.paged_attention import join_rows

    l, _, hk, _, dh = view_k.shape
    n, nbl = tables.shape
    bs, r = pool_k.shape[3], hk // pool_k.shape[2]
    bk = join_rows(view_k.reshape(l, n, hk, nbl, bs, dh).swapaxes(2, 3), r)
    bv = join_rows(view_v.reshape(l, n, hk, nbl, bs, view_v.shape[-1]).swapaxes(2, 3), r)
    return pool_k.at[:, tables].set(bk), pool_v.at[:, tables].set(bv)


def paged_update(pool_k, pool_v, k, v, tables, write_index, *, layer_index=0):
    """Write a chunk's K/V into the block pools through the block table.

    pool_k/v: ``[L, NB, Hkv / r, bs, r * Dh]``; k/v: ``[B, T, Hkv, Dh]`` (the
    chunk, rope already applied); tables: ``[B, nbl]``; write_index:
    ``[B]`` (token ``t`` of row ``b`` lands at logical position
    ``write_index[b] + t``). Returns the updated pools.

    A token's ``[Hkv, Dh]`` read as ``[Hkv / r, r * Dh]`` is the pool's rows
    (adjacent heads are adjacent in memory: nothing moves). Every pool
    dimension but the last is indexed, the head row too, so one update is a
    whole row and XLA's scatter keeps the pool in the paged kernels' operand
    layout (the module docstring's layout contract). No ``unique_indices``:
    idle rows collide in block 0 by design."""
    bs = pool_k.shape[3]
    if pool_k.shape[-1] != k.shape[-1]:  # several heads a row
        rows = (*k.shape[:2], pool_k.shape[2], pool_k.shape[4])
        k, v = k.reshape(rows), v.reshape(rows)
    pos = write_index[:, None] + jnp.arange(k.shape[1])[None, :]  # [B, T]
    blk = jnp.take_along_axis(tables, pos // bs, axis=1)[:, :, None]
    off = (pos % bs)[:, :, None]
    head = jnp.arange(pool_k.shape[2])[None, None, :]  # [1, 1, Hkv / r]
    new_k = pool_k.at[layer_index, blk, head, off].set(k.astype(pool_k.dtype))
    new_v = pool_v.at[layer_index, blk, head, off].set(v.astype(pool_v.dtype))
    return new_k, new_v


def paged_head_update(mesh, pool_k, pool_v, k, v, tables, write_index, *, layer_index=0):
    """:func:`paged_update` head-parallel over the model mesh axis: the
    pools and the chunk shard on their ``Hkv`` dimension (each shard writes
    its own head planes; a pool row's ``r`` heads live on one chip, see
    :func:`init_block_pool`), block tables and positions replicate. It is the
    same function under a ``shard_map``, so an extent-1 model axis is
    bit-equal to it. Accepts an ``AbstractMesh`` so shardcheck's
    ``vlm-paged-head-scatter`` contract traces this call site device-free
    (analysis/shard_check.py)."""
    from jax.sharding import PartitionSpec as P

    from cosmos_curate_tpu.parallel.axes import MODEL
    from cosmos_curate_tpu.parallel.sharding import shard_map

    axis = MODEL if MODEL in mesh.axis_names else None
    pspec = P(None, None, axis, None, None)
    kspec = P(None, None, axis, None)
    return shard_map(
        functools.partial(paged_update, layer_index=layer_index),
        mesh=mesh,
        in_specs=(pspec, pspec, kspec, kspec, P(None, None), P(None)),
        out_specs=(pspec, pspec),
    )(pool_k, pool_v, k, v, tables, write_index)


def paged_gather(mesh, pool_k, pool_v, tables):
    """Data-parallel block-table gather: slot rows (tables) shard over the
    mesh's batch axes while the pool is replicated — the fan-out shape for
    data-parallel engine replicas served from one pool snapshot. Accepts an
    ``AbstractMesh`` too, so shardcheck's ``vlm-paged-gather`` contract
    traces this exact call site device-free (analysis/shard_check.py)."""
    from jax.sharding import PartitionSpec as P

    from cosmos_curate_tpu.parallel.axes import BATCH_AXES
    from cosmos_curate_tpu.parallel.sharding import shard_map

    axes = tuple(a for a in BATCH_AXES if a in mesh.axis_names)
    tspec = P(axes) if axes else P(None)
    vspec = P(None, axes) if axes else P(None, None)
    return shard_map(
        gather_block_views,
        mesh=mesh,
        in_specs=(P(), P(), tspec),
        out_specs=(vspec, vspec),
    )(pool_k, pool_v, tables)
