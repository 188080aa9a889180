"""The gated delta rule over a slot-indexed state store.

A hybrid decoder's linear-attention layer (models/vlm/gated_delta.py) keeps,
for every request, a matrix ``S`` in ``[heads, dk, dv]`` that one token
advances by

    S_t = a_t S_{t-1} + k_t (x) ( beta_t (v_t - (a_t S_{t-1})^T k_t) )      o_t = S_t^T q_t

(Yang, Kautz & Hatamizadeh 2024, "Gated Delta Networks"; ``a = exp(g)`` a
scalar a head and a token, ``beta`` in (0, 2), ``q`` already scaled), or, with
a decay a CHANNEL (Kimi Delta Attention, arXiv:2510.26692), ``Diag(a_t)`` in
place of ``a_t``: a vector over ``dk`` that multiplies the ROWS of ``S``. ONE
rule serves both: the decay's shape, ``[B, H]`` or ``[B, H, dk]`` (a ``T``
after ``B`` in a chunk), is the only difference a caller sees. Unlike
Mamba-2's (ops/ssm.py) the update READS the state: ``u = S^T k`` comes before
the write, so a decode step makes two passes over a state where Mamba-2's
makes one, and a prefill chunk's updates depend on each other.

The engine holds those states in ONE store ``[Ll, R, dk, H * dv]`` float32:
a row a slot (row 0 the garbage row), the heads SIDE BY SIDE on the lanes. A
head's ``[dk, dv]`` plane with ``dv`` = 192 would be padded to 256 lanes on
the chip, a third more bytes to hold and to move; 30 heads side by side are
5760 = 45 x 128 lanes and nothing is padded (:func:`pack_state` /
:func:`unpack_state` say what the layout is). ``rows`` [B] says which store
row each batch row reads and writes, ``layer`` which plane. Padding must not
advance a state: the caller hands in ``beta = 0, g = 0`` wherever a position
is not a token (then ``a = 1`` and the written term vanishes), and points
idle rows at row 0.

- :func:`delta_decode`: one token a row. Memory-bound by construction: a
  row's state (2.1 MiB at Olmo-Hybrid's 30 x 96 x 192) is read once and
  written once. The Pallas kernel walks the rows of a lane,
  ``heads_per_step`` heads a grid step, the store aliased to its output so
  that only the visited blocks move. ``dk`` lies on the sublanes, so ``k``
  and ``q`` come in as columns (``[dk, heads]`` blocks, a head's column spread
  over its own ``dv`` lanes) and ``v``, ``a``, ``beta`` and ``o`` as rows as
  wide as the block; a decay a channel lies on the sublanes as ``k`` does and
  comes in as a third column, a scalar one stays a row (a static choice of
  the one kernel: the scalar rule's program is what it was).
- :func:`delta_prefill`: a chunk of tokens a row, in the chunked (WY / UT)
  form: within a step of ``chunk`` tokens the updates are solved at once,

      T = (I + strict_tril(diag(beta) K K^T * decay))^-1
      W = T diag(beta) V        Kc = T diag(beta a) K
      V' = W - Kc S_in          O = (Q a) S_in + tril(Q K^T * decay) V'
      S_out = a_last S_in + (K * decay to the end)^T V'

  and one state is handed from step to step. The inverse of the unit lower
  triangular matrix is taken by blocks (:func:`_unit_lower_inverse`): a 16 x
  16 block as the product ``(I + N)(I + N^2)(I + N^4)(I + N^8)`` of its
  nilpotent part, two blocks merged as ``[[A, 0], [C, D]]^-1 = [[A^-1, 0],
  [-D^-1 C A^-1, D^-1]]``; the product over all 64 would pass through terms
  a million times the result's size. Everything is float32 at ``highest``:
  what the inverse amplifies is what bfloat16 operands would round. Plain XLA.
  Under a decay a channel ``decay[l, s]`` stands INSIDE the sum over ``dk``,
  ``A[l, s] = sum_d k_l[d] k_s[d] exp(cs_l[d] - cs_s[d])``, and the textbook
  factoring ``(k_l e^{cs_l}) . (k_s e^{-cs_s})`` overflows float32 within a
  chunk under a strong decay (80 a token is ``A`` = 16 at a softplus of 5).
  :func:`_channel_decay_products` never takes ``exp`` of a positive number: a
  chunk is cut into sub-blocks of 16; between two sub-blocks both sides are
  referred to the LATER one's first position (``e^{cs_l - ref} <= 1`` and
  ``e^{ref - cs_s} <= 1``: a matrix product again), inside one the exponent
  ``cs_l - cs_s`` is formed position pair by position pair.

Which implementation runs is decided here and nowhere else, as in ops/ssm.py:
on a TPU the kernel and the chunked form; elsewhere, and for the engine's
``gather`` programs (``use_kernel=False``), the recurrence itself in plain
XLA, a ``lax.scan`` over tokens (:func:`delta_scan_reference`), which is what
the other two are held to.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_HIGHEST = jax.lax.Precision.HIGHEST
_mm = functools.partial(jnp.matmul, precision=_HIGHEST)


def pack_state(state):
    """``[..., H, dk, dv]`` -> the store's ``[..., dk, H * dv]``."""
    *lead, h, dk, dv = state.shape
    return jnp.moveaxis(state, -3, -2).reshape(*lead, dk, h * dv)


def unpack_state(packed, heads: int):
    """The store's ``[..., dk, H * dv]`` -> ``[..., H, dk, dv]``."""
    *lead, dk, wide = packed.shape
    return jnp.moveaxis(packed.reshape(*lead, dk, heads, wide // heads), -2, -3)


def delta_step_reference(state, q, k, v, g, beta):
    """One token. state: ``[B, H, dk, dv]`` float32; q, k: ``[B, H, dk]``; v:
    ``[B, H, dv]``; g (the decay's logarithm, <= 0): ``[B, H]``, or ``[B, H,
    dk]`` a decay a channel; beta: ``[B, H]`` (``g = 0, beta = 0`` leaves the
    state as it is). Returns (o ``[B, H, dv]``, new state)."""
    q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
    state = state * (jnp.exp(g)[..., None, None] if g.ndim == beta.ndim else jnp.exp(g)[..., None])
    u = jnp.einsum("bhkv,bhk->bhv", state, k, precision=_HIGHEST)
    state = state + k[..., :, None] * (beta[..., None] * (v - u))[..., None, :]
    return jnp.einsum("bhkv,bhk->bhv", state, q, precision=_HIGHEST), state


def delta_scan_reference(state, q, k, v, g, beta):
    """The recurrence token by token. q, k: ``[B, T, H, dk]``; v: ``[B, T, H,
    dv]``; g: ``[B, T, H]`` or ``[B, T, H, dk]``; beta: ``[B, T, H]``. Returns
    (o ``[B, T, H, dv]`` float32, final state)."""

    def step(s, inp):
        o, s = delta_step_reference(s, *inp)
        return s, o

    state, os = jax.lax.scan(step, state, tuple(x.swapaxes(0, 1) for x in (q, k, v, g, beta)))
    return os.swapaxes(0, 1), state


def _unit_lower_inverse(m):
    """The inverse of ``m`` ``[..., n, n]``, unit lower triangular, by blocks
    (module docstring)."""
    n = m.shape[-1]
    if n <= 16:
        eye = jnp.eye(n, dtype=m.dtype)
        power = eye - m  # N, nilpotent: sum_i N^i = prod_j (I + N^(2^j))
        inv, reach = eye + power, 2
        while reach < n:
            power = _mm(power, power)
            inv, reach = _mm(inv, eye + power), 2 * reach
        return inv
    half = n // 2
    a, c, d = m[..., :half, :half], m[..., half:, :half], m[..., half:, half:]
    if 2 * half == n:
        ai, di = _unit_lower_inverse(jnp.stack([a, d]))
    else:
        ai, di = _unit_lower_inverse(a), _unit_lower_inverse(d)
    top = jnp.concatenate([ai, jnp.zeros_like(m[..., :half, half:])], axis=-1)
    return jnp.concatenate([top, jnp.concatenate([-_mm(_mm(di, c), ai), di], axis=-1)], axis=-2)


_SUB = 16  # positions of a sub-block of the channel-decay products (and of a block of the inverse)


def _channel_decay_products(q, k, cs):
    """The in-chunk products under a decay a channel (module docstring). q, k,
    cs: ``[..., c, dk]``, ``cs`` the decay's logarithm summed from the chunk's
    first position (decreasing). Returns (``A``, ``QK``) ``[..., c, c]``: ``A[l, s]
    = sum_d k_l[d] k_s[d] exp(cs_l[d] - cs_s[d])`` at ``s <= l`` and zeros
    above, ``QK`` the same with ``q_l``. No ``exp`` of a positive number."""
    *lead, c, dk = k.shape
    sub = min(_SUB, c)
    nb = c // sub

    def blocks(x):
        return x.reshape(*lead, nb, sub, dk)

    qb, kb, csb = blocks(q), blocks(k), blocks(cs)
    first = csb[..., :1, :]  # a sub-block's first position: what both sides are referred to
    # between sub-blocks: the rows of block i and every earlier position of the
    # chunk, both against block i's first position (the later ones are masked)
    rows = jnp.exp(csb - first)
    cols = k[..., None, :, :] * jnp.exp(jnp.minimum(first - cs[..., None, :, :], 0.0))  # [.., nb, c, dk]
    earlier = (jnp.arange(c) // sub)[None, :] < jnp.arange(nb)[:, None]  # [nb, c]
    # inside a sub-block: the exponent position pair by position pair
    lower = jnp.tril(jnp.ones((sub, sub), bool))[..., None]
    decay = jnp.exp(jnp.where(lower, csb[..., :, None, :] - csb[..., None, :, :], -jnp.inf))
    eye = jnp.eye(nb, dtype=k.dtype)

    def products(x):  # [.., nb, sub, dk] against k -> [.., c, c]
        between = jnp.where(earlier[:, None, :], _mm(x * rows, cols.swapaxes(-1, -2)), 0.0)
        inside = jnp.sum(x[..., :, None, :] * kb[..., None, :, :] * decay, axis=-1)  # [.., nb, sub, sub]
        return (between.reshape(*lead, nb, sub, nb, sub) + jnp.einsum("...ils,ij->...iljs", inside, eye)).reshape(*lead, c, c)

    return products(kb), products(qb)


def _channel_chunk_step(state, inp):
    """One chunk under a decay a channel: the scalar form's step with its
    products made here, a chunk at a time (the pairwise exponents of a whole
    prefill program's chunks at once would be a gigabyte)."""
    q, k, v, g, beta = inp  # [B, H, c, ...]
    c = k.shape[-2]
    cs = jnp.cumsum(g, axis=-2)  # [B, H, c, dk]
    a, qk = _channel_decay_products(q, k, cs)
    solve = _unit_lower_inverse(jnp.eye(c, dtype=jnp.float32) + jnp.tril(a * beta[..., None], -1))
    w = _mm(solve, v * beta[..., None])
    kc = _mm(solve, k * beta[..., None] * jnp.exp(cs))
    v_new = w - _mm(kc, state)
    o = _mm(q * jnp.exp(cs), state) + _mm(qk, v_new)
    k_out = k * jnp.exp(cs[..., -1:, :] - cs)
    return state * jnp.exp(cs[..., -1, :])[..., None] + _mm(k_out.swapaxes(-1, -2), v_new), o


def delta_chunk_scan(state, q, k, v, g, beta, *, chunk: int):
    """The same recurrence in the chunked form of the module docstring. Shapes
    as :func:`delta_scan_reference`; any ``T`` (the tail is padded with
    positions that advance nothing)."""
    bsz, t, h, dk = k.shape
    dv = v.shape[-1]
    c = min(chunk, t)
    if g.ndim > beta.ndim and c > _SUB:  # a decay a channel: chunks of whole sub-blocks
        c = -(-c // _SUB) * _SUB
    pad = -t % c
    q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
    if pad:  # beta = 0, g = 0
        q, k, v, g, beta = (jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2)) for x in (q, k, v, g, beta))
    n = (t + pad) // c

    def steps(x):  # [B, T, H, ...] -> [n, B, H, c, ...]
        x = x.reshape(bsz, n, c, *x.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(x, 3, 2), 1, 0)

    q, k, v, g, beta = (steps(x) for x in (q, k, v, g, beta))
    if g.ndim > beta.ndim:
        state, os = jax.lax.scan(_channel_chunk_step, state, (q, k, v, g, beta))
        return jnp.moveaxis(jnp.moveaxis(os, 0, 1), 2, 3).reshape(bsz, t + pad, h, dv)[:, :t], state
    cs = jnp.cumsum(g, axis=-1)  # [n, B, H, c], decreasing
    lower = jnp.tril(jnp.ones((c, c), bool))
    decay = jnp.exp(jnp.where(lower, cs[..., :, None] - cs[..., None, :], -jnp.inf))  # [.., l, s]
    kb = k * beta[..., None]
    within = _mm(kb, k.swapaxes(-1, -2)) * decay
    solve = _unit_lower_inverse(jnp.eye(c, dtype=jnp.float32) + jnp.tril(within, -1))
    w = _mm(solve, v * beta[..., None])  # [.., c, dv]
    kc = _mm(solve, kb * jnp.exp(cs)[..., None])  # [.., c, dk]
    qk = _mm(q, k.swapaxes(-1, -2)) * decay
    q_in = q * jnp.exp(cs)[..., None]
    k_out = k * jnp.exp(cs[..., -1:] - cs)[..., None]
    a_last = jnp.exp(cs[..., -1])

    def one_step(s, inp):
        w_i, kc_i, qk_i, q_i, k_i, a_i = inp
        v_new = w_i - _mm(kc_i, s)
        o = _mm(q_i, s) + _mm(qk_i, v_new)
        return s * a_i[..., None, None] + _mm(k_i.swapaxes(-1, -2), v_new), o

    state, os = jax.lax.scan(one_step, state, (w, kc, qk, q_in, k_out, a_last))
    # [n, B, H, c, dv] -> [B, T, H, dv]
    o = jnp.moveaxis(jnp.moveaxis(os, 0, 1), 2, 3).reshape(bsz, t + pad, h, dv)
    return o[:, :t], state


def _on_tpu() -> bool:
    return jax.devices()[0].platform == "tpu"


def decode_in_place(use_kernel: bool | None = None) -> bool:
    """Whether :func:`delta_decode` will run the Pallas kernel, which walks the
    store's own rows and updates them in place; otherwise a caller does
    better to hand in the rows it gathered (see ``VLM._forward``)."""
    return _on_tpu() if use_kernel is None else use_kernel


def delta_prefill(store, layer, rows, q, k, v, g, beta, *, chunk: int, use_kernel: bool | None = None):
    """Advance the states ``store[layer, rows]`` over a chunk of tokens.
    store: ``[Ll, R, dk, H * dv]`` float32; rows: ``[B]``; the rest as
    :func:`delta_scan_reference`, ``g = 0, beta = 0`` at padding. Rows that
    share a store row must carry the same inputs (the engine's duplicated
    padding rows do). Returns (o ``[B, T, H, dv]`` float32, store)."""
    state = unpack_state(store[layer, rows], k.shape[2])
    if decode_in_place(use_kernel):
        o, state = delta_chunk_scan(state, q, k, v, g, beta, chunk=chunk)
    else:
        o, state = delta_scan_reference(state, q, k, v, g, beta)
    return o, store.at[layer, rows].set(pack_state(state))


def _delta_decode_kernel(layer_ref, rows_ref, *refs, dv, together, channel_decay):
    """One grid step is ``hb`` heads of one row. k_ref / q_ref: ``[dk, hb]``
    columns, a head a lane; vab_ref: ``[3, hb * dv]``, the rows ``v``, ``a``
    and ``beta`` as wide as the block (a head's scalar over its ``dv`` lanes);
    state_ref / out_ref: ``[dk, hb * dv]``, the same block of the aliased
    store; o_ref: ``[1, hb * dv]``. The block is walked ``together`` heads at
    a time: the fewest whose lanes make whole 128-lane tiles. With
    ``channel_decay`` the decay is a third column, spread as ``k`` is: the
    three come as ONE ``[dk, 3 * hb]`` block, ``k | q | a`` (a block of few
    lanes is padded to 128 in memory; three of them would be a fifth of the
    state's own traffic at 64 heads of 128), and vab_ref holds ``v`` and
    ``beta`` alone."""
    del layer_ref, rows_ref  # the index maps read them
    if channel_decay:
        cols_ref, vab_ref, state_ref, o_ref, out_ref = refs
        dk, hb = cols_ref.shape[0], cols_ref.shape[1] // 3
        cols = cols_ref[...]
        kcols, qcols, acols = cols[:, :hb], cols[:, hb : 2 * hb], cols[:, 2 * hb :]
    else:
        k_ref, q_ref, vab_ref, state_ref, o_ref, out_ref = refs
        dk, hb = k_ref.shape
        kcols, qcols = k_ref[...], q_ref[...]
    width = together * dv
    lane = jax.lax.broadcasted_iota(jnp.int32, (dk, width), 1)
    for s in range(hb // together):
        first, lanes = s * together, pl.ds(s * width, width)

        def spread(cols):  # a head's column over its own dv lanes: [dk, width]
            wide = jnp.broadcast_to(cols[:, first : first + 1], (dk, width))
            for i in range(1, together):
                wide = jnp.where(lane >= i * dv, cols[:, first + i : first + i + 1], wide)
            return wide

        kw = spread(kcols)
        if channel_decay:
            v, a, beta = vab_ref[0:1, lanes], spread(acols), vab_ref[1:2, lanes]
        else:
            v, a, beta = vab_ref[0:1, lanes], vab_ref[1:2, lanes], vab_ref[2:3, lanes]
        decayed = state_ref[:, lanes] * a
        u = jnp.sum(decayed * kw, axis=0, keepdims=True)
        new = decayed + kw * (beta * (v - u))
        out_ref[:, lanes] = new
        o_ref[:, lanes] = jnp.sum(new * spread(qcols), axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("heads_per_step", "interpret"))
def _delta_decode(store, layer, rows, q, k, v, a, beta, *, heads_per_step, interpret):
    """store: ``[Ll, R, dk, H * dv]``; q, k: ``[B, H, dk]``; v: ``[B, H,
    dv]``; a (the decay itself): ``[B, H]`` or, a decay a channel, ``[B, H,
    dk]``; beta: ``[B, H]``. ``layer`` is a run-time scalar, prefetched with
    ``rows``: a model's layers share one trace and one lowering of the kernel.
    Returns (o ``[B, H, dv]``, store)."""
    bsz, h, dk = k.shape
    dv = v.shape[-1]
    hb = heads_per_step
    groups, wide = h // hb, hb * dv
    channel_decay = a.ndim == 3
    # the fewest heads whose lanes are whole tiles; the whole block where none are
    together = next((n for n in range(1, hb + 1) if hb % n == 0 and (n * dv) % 128 == 0), hb)

    def columns(x):  # [B, H, dk] -> [B, H / hb, dk, hb]
        return x.astype(jnp.float32).reshape(bsz, groups, hb, dk).swapaxes(2, 3)

    def over_lanes(x):  # [B, H] -> [B, H / hb, hb * dv]
        return jnp.broadcast_to(x[..., None], (bsz, h, dv)).reshape(bsz, groups, wide)

    vab = [v.astype(jnp.float32).reshape(bsz, groups, wide)]
    vab = jnp.stack(vab + ([] if channel_decay else [over_lanes(a)]) + [over_lanes(beta)], axis=2)
    layer = jnp.asarray(layer, jnp.int32).reshape(1)
    small = [columns(k), columns(q)]
    if channel_decay:  # k | q | a, one block (the kernel's docstring)
        small = [jnp.concatenate([*small, columns(a)], axis=-1)]
    state_spec = pl.BlockSpec((None, None, dk, wide), lambda i, j, layer, rows: (layer[0], rows[i], 0, j))
    o, store = pl.pallas_call(
        functools.partial(_delta_decode_kernel, dv=dv, together=together, channel_decay=channel_decay),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(bsz, groups),
            in_specs=[
                *[pl.BlockSpec((None, None, dk, x.shape[-1]), lambda i, j, *_: (i, j, 0, 0)) for x in small],
                pl.BlockSpec((None, None, vab.shape[2], wide), lambda i, j, *_: (i, j, 0, 0)),
                state_spec,
            ],
            out_specs=[pl.BlockSpec((None, None, 1, wide), lambda i, j, *_: (i, j, 0, 0)), state_spec],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((bsz, groups, 1, wide), jnp.float32),
            jax.ShapeDtypeStruct(store.shape, store.dtype),
        ],
        # the store comes after the two prefetched scalars and the small
        # inputs, and it is output 1: only the visited blocks move
        input_output_aliases={3 + len(small): 1},
        # rows may share the garbage row: no two cores in one row's blocks
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(layer, rows.astype(jnp.int32), *small, vab, store)
    return o.reshape(bsz, h, dv), store


_STEP_BYTES = 2**20  # of state a grid step of the decode kernel, at most (PR 44 measured 10 of Olmo's 30 heads best)


def heads_a_step(h: int, dk: int, dv: int, at_most: int | None = None) -> int:
    """Heads a grid step of the decode kernel takes: the most that divide ``h``,
    up to ``at_most`` (None: as many as ``_STEP_BYTES`` of float32 state hold)."""
    if at_most is None:
        at_most = max(1, _STEP_BYTES // (4 * dk * dv))
    return max(n for n in range(1, min(at_most, h) + 1) if h % n == 0)


def delta_decode(
    store, layer, rows, q, k, v, g, beta, *, use_kernel: bool | None = None,
    interpret: bool | None = None, heads_per_step: int | None = None,
):
    """Advance the states ``store[layer, rows]`` by one token a row. q, k:
    ``[B, H, dk]``; v: ``[B, H, dv]``; g: ``[B, H]`` or ``[B, H, dk]``; beta:
    ``[B, H]``; g and beta both 0 for a row that must not move (idle rows
    point at row 0 and may collide there). ``heads_per_step``: at most so many
    heads a grid step of the kernel (None: as many as ``_STEP_BYTES`` of
    state hold). Returns (o ``[B, H, dv]`` float32, store)."""
    h, dk = k.shape[1:]
    if not decode_in_place(use_kernel):
        o, state = delta_step_reference(unpack_state(store[layer, rows], h), q, k, v, g, beta)
        return o, store.at[layer, rows].set(pack_state(state))
    if interpret is None:
        interpret = jax.devices()[0].platform == "cpu"
    hb = heads_a_step(h, dk, v.shape[-1], heads_per_step)
    return _delta_decode(store, layer, rows, q, k, v, jnp.exp(g), beta, heads_per_step=hb, interpret=interpret)
