"""Absorbed multi-head latent attention (MLA) straight out of a paged latent pool.

A latent-attention layer (DeepSeek-V2; ``models/vlm/model.py::LatentAttentionLayer``)
caches ONE row a token a layer, ``[c_kv (kv_lora_rank) | k_rope | zero padding]``,
and no per-head K or V. With the up-projections absorbed into the query and the
output (``q~_i = W_UK_i^T q_nope_i``, ``o_i = W_UV_i u_i``) attention over that
cache is multi-QUERY attention: every head scores against the same row (its
whole width; the padding meets zeros of the query) and sums the same row's
first ``v_width`` values. So K and V are one array, read once, and a decode
step does ``2 * H * (W + v_width)`` operations a cached row of ``2 * W`` bytes:
at DeepSeek-V2's 128 heads that is the v5e's ridge, neither side's bound.

**One kernel, two names.** ``_latent_rows`` walks a query row's own pages as
``ops/paged_attention._paged_decode`` does since PR 27 (the pool stays in HBM,
one asynchronous copy a page into one of two buffers, the next group in flight
while this one is computed, no copy past the valid length), the layer a
prefetched scalar. A decode step is one query row a slot (``mla_decode``).
A prefill chunk is the same call over ``B * T`` query rows (``mla_prefill``):
token ``t`` of row ``b`` is a query row whose valid length ends at its own
position, so causality is the length and no masked half of a score tile is ever
computed; a row's ``T`` tokens share its table (``rows_per_table``). That
re-reads a page once a token where a tiled prefill kernel would share it over a
block of queries, which at 242 operations a byte costs at most the factor two
between the two bounds; what it buys is one kernel body to keep right.

**The layout contract** is ``paged_kv``'s: the pool ``[L, NB, 1, bs, W]`` keeps
``(bs, W)`` tiled from a program's entry to its exit, ``W`` a whole number of
128-lane tiles (Mosaic slices no HBM array whose last dimension is not: 576
was refused, PERF.md PR 33), and ``paged_kv.latent_update`` writes it by
indexing every dimension but the last.

Which implementation runs is decided in ``latent_attention`` and nowhere else:
the kernel on a TPU, elsewhere ``latent_reference_attention`` (plain XLA, the
one the slot-cache ``gather`` programs run) over the row's gathered pages.
``decompressed_reference_attention`` is the layer's non-absorbed equations over
the same cache rows, what the absorbed forms are held to in the tests.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from cosmos_curate_tpu.ops.tiling import round_up, sublanes

_NEG_INF = -1e30
_GROUP_KEYS = 256  # cached rows a step of the kernel's loop covers


def _on_tpu() -> bool:
    """Tests put an engine on the kernel (interpret mode) by patching this."""
    return jax.devices()[0].platform == "tpu"


def latent_reference_attention(q, rows, write_index, kv_len, *, sm_scale, v_width):
    """The XLA attention the kernel is held to. q: ``[B, T, H, W]`` absorbed,
    unscaled queries; rows: the slots' cache rows ``[B, S, W]`` with this chunk
    written; write_index / kv_len: ``[B]``. Causal over cache order. Returns
    ``[B, T, H, v_width]`` in q's dtype: per head, the weighted sum of the
    rows' first ``v_width`` values (the compressed latent)."""
    t, s = q.shape[1], rows.shape[1]
    logits = jnp.einsum(
        "bthw,bsw->bhts", q.astype(jnp.float32) * sm_scale, rows.astype(jnp.float32)
    )
    k_pos = jnp.arange(s)[None, None, None, :]
    q_seq = write_index[:, None] + jnp.arange(t)[None, :]  # [B, T]
    ok = (k_pos <= q_seq[:, None, :, None]) & (k_pos < kv_len[:, None, None, None])
    probs = jax.nn.softmax(jnp.where(ok, logits, _NEG_INF), axis=-1)
    return jnp.einsum("bhts,bsc->bthc", probs.astype(q.dtype), rows[..., :v_width])


def decompressed_reference_attention(
    q_nope, q_rope, rows, w_uk, w_uv, write_index, kv_len, *, sm_scale
):
    """The layer's equations as published, not absorbed: per-head keys and
    values expanded from every cached row. q_nope: ``[B, T, H, Dn]``; q_rope:
    ``[B, T, H, Dr]``; rows: ``[B, S, W]`` = ``[c_kv | k_rope | padding]``;
    w_uk: ``[C, H, Dn]``, w_uv: ``[C, H, Dv]`` (the two halves of
    ``kv_b_proj``). Returns the heads' outputs ``[B, T, H, Dv]``, float32."""
    c, dr = w_uk.shape[0], q_rope.shape[-1]
    f32 = lambda x: x.astype(jnp.float32)
    c_kv, k_rope = f32(rows[..., :c]), f32(rows[..., c : c + dr])
    k_nope = jnp.einsum("bsc,chd->bshd", c_kv, f32(w_uk))
    v = jnp.einsum("bsc,chd->bshd", c_kv, f32(w_uv))
    logits = jnp.einsum("bthd,bshd->bhts", f32(q_nope), k_nope)
    logits = (logits + jnp.einsum("bthr,bsr->bhts", f32(q_rope), k_rope)) * sm_scale
    t, s = q_nope.shape[1], rows.shape[1]
    k_pos = jnp.arange(s)[None, None, None, :]
    q_seq = write_index[:, None] + jnp.arange(t)[None, :]
    ok = (k_pos <= q_seq[:, None, :, None]) & (k_pos < kv_len[:, None, None, None])
    probs = jax.nn.softmax(jnp.where(ok, logits, _NEG_INF), axis=-1)
    return jnp.einsum("bhts,bshd->bthd", probs, v)


def _latent_rows_kernel(
    layer_ref, kvlen_ref, tbl_ref, q_ref, pool_hbm, o_ref, buf, sems,
    *, sm_scale, bs, pages, nbl, rows_per_table,
):
    """One grid step is one query row, all heads against the one latent row a
    position: scores over the buffer's whole width, values its first
    ``o_ref.shape[-1]`` lanes. The loop walks the row's OWN table in groups of
    ``pages`` entries, as far as its valid length and no further; group
    ``i + 1`` is in flight while group ``i`` is computed."""
    r = pl.program_id(0)
    layer, kv_len = layer_ref[0], kvlen_ref[r]
    table = (r // rows_per_table) * nbl  # where the row's entries start
    h, vw = o_ref.shape
    group = pages * bs

    def each_live_page(i, slot, act):
        # no copy for an entry at or past the valid length: its block id is garbage
        first = i * pages

        def page(p, carry):
            block = tbl_ref[table + first + p]
            rows = pl.ds(pl.multiple_of(p * bs, bs), bs)
            act(pltpu.make_async_copy(pool_hbm.at[layer, block, 0], buf.at[slot, rows], sems.at[slot]))
            return carry

        jax.lax.fori_loop(0, jnp.clip(pl.cdiv(kv_len, bs) - first, 0, pages), page, 0)

    # a dead page's rows are multiplied by p = 0 as values and have to be finite
    # for that: the scratch starts as zeros, and a later row finds an earlier
    # row's pages there (rows run in order on one core: the grid is "arbitrary")
    @pl.when(r == 0)
    def _():
        buf[...] = jnp.zeros_like(buf)

    each_live_page(0, 0, lambda copy: copy.start())
    q = q_ref[...]  # [h, w], the pool's dtype: the MXU's operands

    def attend(k, k_start, acc, m_prev, l_prev):
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * sm_scale  # [h, group]
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(k_pos < kv_len, s, _NEG_INF)
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_prev * alpha + p.sum(axis=1, keepdims=True)
        acc = acc * alpha + jax.lax.dot_general(
            p.astype(k.dtype), k[:, :vw], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return acc, m_new, l_new

    def two_groups(pair, state):
        # two groups an iteration, so that each names its buffer statically. A
        # group past the row's last has no live page: no copy, no wait, and its
        # keys, all masked, leave the state as it was
        for slot in (0, 1):
            i = 2 * pair + slot
            each_live_page(i + 1, 1 - slot, lambda copy: copy.start())
            each_live_page(i, slot, lambda copy: copy.wait())
            state = attend(buf[slot], i * group, *state)
        return state

    init = (
        jnp.zeros((h, vw), jnp.float32),
        jnp.full((h, 1), _NEG_INF, jnp.float32),
        jnp.zeros((h, 1), jnp.float32),
    )
    acc, _, l = jax.lax.fori_loop(0, pl.cdiv(pl.cdiv(kv_len, group), 2), two_groups, init)
    o_ref[...] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("sm_scale", "v_width", "rows_per_table", "interpret")
)
def _latent_rows(q, pool, tables, kv_len, *, layer_index, sm_scale, v_width, rows_per_table, interpret):
    """q: ``[R, H, W]`` absorbed queries, ``R = tables.shape[0] * rows_per_table``;
    pool: ``[L, NB, 1, bs, W]``; tables: ``[B, nbl]``; kv_len: ``[R]``, a query
    row's own. ``layer_index`` is a run-time scalar, prefetched with the table:
    a model's layers share one trace and one lowering."""
    r, h, w = q.shape
    bs = pool.shape[3]
    nbl = tables.shape[1]
    if w % 128 or pool.shape[-1] != w:
        raise ValueError(f"latent rows of width {pool.shape[-1]} (queries {w}): not whole lane tiles")
    h_pad = round_up(h, sublanes(pool.dtype))
    q = q.astype(pool.dtype)
    if h_pad != h:
        q = jnp.pad(q, ((0, 0), (0, h_pad - h), (0, 0)))
    vw = round_up(v_width, 128)  # the values' lanes, a whole number of tiles
    pages = min(nbl, max(1, _GROUP_KEYS // bs))
    kernel = functools.partial(
        _latent_rows_kernel, sm_scale=sm_scale, bs=bs, pages=pages, nbl=nbl,
        rows_per_table=rows_per_table,
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(r,),
            in_specs=[
                pl.BlockSpec((None, h_pad, w), lambda r_, *_: (r_, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((None, h_pad, vw), lambda r_, *_: (r_, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, pages * bs, w), pool.dtype),
                pltpu.SemaphoreType.DMA((2,)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((r, h_pad, vw), q.dtype),
        # never "parallel": the buffers zeroed in the first row serve the rest
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="mla_decode" if rows_per_table == 1 else "mla_prefill",
    )(
        jnp.asarray(layer_index, jnp.int32).reshape(1), kv_len.astype(jnp.int32),
        # one dimension: scalar memory pads a second to 128 entries a row
        tables.astype(jnp.int32).reshape(-1), q, pool,
    )
    return out[:, :h, :v_width]


def latent_attention(
    q: jax.Array,
    pool: jax.Array,
    tables: jax.Array,
    write_index: jax.Array,
    kv_len: jax.Array,
    *,
    layer_index: int = 0,
    sm_scale: float,
    v_width: int,
    use_kernel: bool | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """Absorbed latent attention straight out of the paged latent pool.

    q: ``[B, T, H, W]`` absorbed, UNSCALED queries (``[W_UK^T q_nope | q_rope |
    0]``); pool: ``[L, NB, 1, bs, W]`` with the chunk's rows already written
    through the table; tables: ``[B, nbl]``; write_index / kv_len: ``[B]``.
    Serves decode (T = 1) and chunked prefill. Returns ``[B, T, H, v_width]``:
    per head the softmax-weighted sum of the cached latents, which the layer's
    ``W_UV`` then expands.

    ``use_kernel=None`` means the Pallas kernel on a TPU and the XLA reference
    over the gathered pages elsewhere."""
    b, t, h, w = q.shape
    if use_kernel is None:
        use_kernel = _on_tpu()
    if not use_kernel:
        rows = pool[layer_index][tables][:, :, 0].reshape(b, -1, w)  # [B, S, W]
        return latent_reference_attention(
            q, rows, write_index, kv_len, sm_scale=sm_scale, v_width=v_width
        )
    if interpret is None:
        interpret = jax.devices()[0].platform == "cpu"
    # token t of row b: a query row valid up to its own position; padding
    # (at or past the row's valid length) walks no page and reads as zeros
    own = write_index[:, None] + jnp.arange(1, t + 1)[None, :]  # [B, T]
    own = jnp.where(own <= kv_len[:, None], own, 0)
    out = _latent_rows(
        q.reshape(b * t, h, w), pool, tables, own.reshape(-1),
        layer_index=layer_index, sm_scale=sm_scale, v_width=v_width, rows_per_table=t,
        interpret=interpret,
    )
    return out.reshape(b, t, h, v_width)
