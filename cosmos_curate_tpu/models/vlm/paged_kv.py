"""Paged KV-cache primitives for the caption engine.

vLLM's PagedAttention block-table design (Kwon et al. 2023 — PAPERS.md)
re-shaped for XLA's static-shape compilation: KV memory is ONE block pool
``[L, n_blocks, Hkv, block_size, Dh]`` and every slot owns a block *table*
instead of a worst-case-length cache row, so a request's KV footprint is
``ceil(len / block_size)`` blocks. The engine has two sets of programs over
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
kernels' operand demands — row-major ``[L, NB, Hkv, bs, Dh]`` with
``(bs, Dh)`` tiled, because what a kernel fetches is a page: one head's
``[bs, Dh]`` at prefill (``BlockSpec((None, None, None, bs, Dh))``), all
heads' ``[Hkv, bs, Dh]`` copied by the decode kernel itself. XLA chooses a
scatter's layout from its update window: a window that spans ``[Hkv, Dh]``
(the head left as a slice, as this write was first phrased) makes those two
dimensions minor, and every layer then pays a relayout ``copy`` of the whole K and V pool in
front of its kernel call (2 x 28 x 1.13 ms of an 85 ms Qwen2-VL-2B decode
step: PERF.md, PR 25). :func:`paged_update` therefore indexes every pool
dimension but ``Dh``, so an update is one ``[Dh]`` row and the donated pool
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


def init_block_pool(cfg, n_blocks: int, block_size: int, dtype=jnp.bfloat16, sharding=None):
    """The K and V block pools: ``[L, n_blocks, Hkv, block_size, Dh]``, ``L``
    the layers that hold K/V (a hybrid's state-space layers keep their state
    in the engine's recurrent store instead, ``model.init_recurrent_store``) —
    heads-major, so one head's page is a contiguous ``[block_size, Dh]``
    tile (the shape the TPU's compiler accepts as a kernel block).
    ``sharding`` creates them already placed (head planes over a mesh)."""
    shape = (len(cfg.kv_layers), n_blocks, cfg.n_kv_heads, block_size, cfg.head_dim)
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


def gather_block_views(pool_k, pool_v, tables):
    """Per-slot contiguous KV views through the block tables.

    pool_k/v: ``[L, NB, Hkv, bs, Dh]``; tables: ``[N, nbl]`` int32 block
    ids. Returns ``[L, N, Hkv, nbl * bs, Dh]`` views — the same shape the
    slot-row engine's cache rows had, so the model and its compiled
    programs are unchanged."""
    l, _, hk, bs, dh = pool_k.shape
    n, nbl = tables.shape
    # [L, N, nbl, Hkv, bs, Dh] -> blocks of one head side by side (V by its
    # own width: a latent flavor's V pool has width 0)
    vk = pool_k[:, tables].swapaxes(2, 3).reshape(l, n, hk, nbl * bs, dh)
    vv = pool_v[:, tables].swapaxes(2, 3).reshape(l, n, hk, nbl * bs, pool_v.shape[-1])
    return vk, vv


def scatter_block_views(pool_k, pool_v, tables, view_k, view_v):
    """Write updated per-slot views back into the pool blocks.

    Duplicate table entries (shared prefix blocks, garbage padding) write
    identical values by the engine's copy-on-write invariant — see the
    module docstring — so the scatter's undefined duplicate-write order
    cannot change pool contents."""
    l, _, hk, bs, dh = pool_k.shape
    n, nbl = tables.shape
    bk = view_k.reshape(l, n, hk, nbl, bs, dh).swapaxes(2, 3)
    bv = view_v.reshape(l, n, hk, nbl, bs, pool_v.shape[-1]).swapaxes(2, 3)
    return pool_k.at[:, tables].set(bk), pool_v.at[:, tables].set(bv)


def paged_update(pool_k, pool_v, k, v, tables, write_index, *, layer_index=0):
    """Write a chunk's K/V into the block pools through the block table.

    pool_k/v: ``[L, NB, Hkv, bs, Dh]``; k/v: ``[B, T, Hkv, Dh]`` (the
    chunk, rope already applied); tables: ``[B, nbl]``; write_index:
    ``[B]`` (token ``t`` of row ``b`` lands at logical position
    ``write_index[b] + t``). Returns the updated pools.

    Every pool dimension but ``Dh`` is indexed, the head too, so one
    update is a ``[Dh]`` row and XLA's scatter keeps the pool in the paged
    kernels' operand layout (the module docstring's layout contract). No
    ``unique_indices``: idle rows collide in block 0 by design."""
    bs = pool_k.shape[3]
    pos = write_index[:, None] + jnp.arange(k.shape[1])[None, :]  # [B, T]
    blk = jnp.take_along_axis(tables, pos // bs, axis=1)[:, :, None]
    off = (pos % bs)[:, :, None]
    head = jnp.arange(pool_k.shape[2])[None, None, :]  # [1, 1, Hkv]
    new_k = pool_k.at[layer_index, blk, head, off].set(k.astype(pool_k.dtype))
    new_v = pool_v.at[layer_index, blk, head, off].set(v.astype(pool_v.dtype))
    return new_k, new_v


def paged_head_update(mesh, pool_k, pool_v, k, v, tables, write_index, *, layer_index=0):
    """:func:`paged_update` head-parallel over the model mesh axis: the
    pools and the chunk shard on their ``Hkv`` dimension (each shard writes
    its own head plane), block tables and positions replicate. It is the
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
