"""The Mamba-2 recurrence over a slot-indexed state store.

A hybrid decoder's state-space layer (models/vlm/mamba2.py) keeps, for every
request, a state ``H`` in ``[heads, head_dim, d_state]`` that one token
advances by

    H_t = exp(dt_t A) H_{t-1} + dt_t x_t (x) B_t        y_t = H_t C_t + D x_t

(Dao & Gu 2024, "Transformers are SSMs": scalar ``A`` a head, ``B`` and ``C``
shared by the heads). The engine holds those states in ONE store ``[Lm, R, H,
P, N]`` float32, a row a slot with row 0 the garbage row (as block 0 of the KV
pool is the garbage block), and both operations here work on the store in
place: ``rows`` [B] says which store row each batch row reads and writes,
``layer`` which plane. Padding must not advance a state: the caller hands in
``dt = 0`` wherever a position is not a token (then ``exp(0 A) = 1`` and the
input term vanishes), and points idle rows at row 0.

- :func:`ssm_decode`: one token a row. Memory-bound by construction: a row's
  state (2 MiB at Granite-4.0-H's 64 x 64 x 128) is read once and written
  once, against 2 x 64 x 64 x 128 multiply-adds. The Pallas kernel walks the
  rows of a lane, ``heads_per_step`` heads a grid step, the store aliased to
  its output so that only the visited blocks move; the state keeps ``d_state``
  on the lanes, so what is per ``(head, head_dim)`` comes in as columns
  (``[P, heads]`` blocks, sliced a head at a time and spread over the lanes)
  and ``y`` leaves as columns.
- :func:`ssm_prefill`: a chunk of tokens a row, in the chunked SSD form: within
  a step of ``chunk`` tokens the outputs are two matrix products on the MXU
  (``(C B^T * decay) (dt x)``, bfloat16 operands as every activation here),
  and one state is handed from step to step, always in float32 and contracted
  at ``highest`` precision. Plain XLA in this PR; a Pallas scan is later work.

Which implementation runs is decided here and nowhere else, as in
ops/paged_attention.py: on a TPU the kernel and the SSD form; elsewhere, and
for the engine's ``gather`` programs (``use_kernel=False``), the recurrence
itself in plain XLA, a ``lax.scan`` over tokens (:func:`ssm_scan_reference`),
which is what the other two are held to. Tests and ``chip_smoke.py`` pick a
side with ``use_kernel=`` / ``interpret=``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_HIGHEST = jax.lax.Precision.HIGHEST


def ssm_step_reference(state, x, dt, a, b, c, d):
    """One token. state: ``[B, H, P, N]`` float32; x: ``[B, H, P]``; dt:
    ``[B, H]`` (after softplus; 0 leaves the state as it is); a, d: ``[H]``
    (``a`` negative); b, c: ``[B, N]``. Returns (y ``[B, H, P]``, new state)."""
    x = x.astype(jnp.float32)
    decay = jnp.exp(dt * a)  # [B, H]
    new = state * decay[..., None, None] + (dt[..., None] * x)[..., None] * b[:, None, None, :]
    y = jnp.einsum("bhpn,bn->bhp", new, c, precision=_HIGHEST)
    return y + d[:, None] * x, new


def ssm_scan_reference(state, x, dt, a, b, c, d):
    """The recurrence token by token. x: ``[B, T, H, P]``; dt: ``[B, T, H]``;
    b, c: ``[B, T, N]``. Returns (y ``[B, T, H, P]`` float32, final state)."""

    def step(h, inp):
        xt, dtt, bt, ct = inp
        y, h = ssm_step_reference(h, xt, dtt, a, bt, ct, d)
        return h, y

    state, ys = jax.lax.scan(
        step, state, (x.swapaxes(0, 1), dt.swapaxes(0, 1), b.swapaxes(0, 1), c.swapaxes(0, 1))
    )
    return ys.swapaxes(0, 1), state


def ssd_chunk_scan(state, x, dt, a, b, c, d, *, chunk: int):
    """The same recurrence in the chunked SSD form. Within a step of
    ``chunk`` tokens, with ``cs`` the running sum of ``dt A``:

        y_l = sum_{s<=l} (C_l . B_s) exp(cs_l - cs_s) dt_s x_s      (two matmuls)
              + exp(cs_l) H_in C_l                                  (the entering state)
        H_out = exp(cs_last) H_in + sum_s exp(cs_last - cs_s) dt_s x_s (x) B_s

    The two products within a step take bfloat16 operands and accumulate in
    float32; everything that touches a state is float32 at ``highest``.
    Shapes as :func:`ssm_scan_reference`."""
    bsz, t, h, p = x.shape
    step_len = min(chunk, t)
    pad = -t % step_len
    x32 = x.astype(jnp.float32)
    if pad:  # dt = 0: the padding advances nothing
        x32, dt, b, c = (jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2)) for v in (x32, dt, b, c))
    n_steps = (t + pad) // step_len

    def steps(v):  # [B, T, ...] -> [n_steps, B, step_len, ...]
        return v.reshape(bsz, n_steps, step_len, *v.shape[2:]).swapaxes(0, 1)

    causal = jnp.tril(jnp.ones((step_len, step_len), bool))

    def one_step(h_in, inp):
        xc, dtc, bc, cc = inp  # [B, L, H, P], [B, L, H], [B, L, N], [B, L, N]
        cs = jnp.cumsum(dtc * a, axis=1).swapaxes(1, 2)  # [B, H, L], decreasing
        seg = cs[:, :, :, None] - cs[:, :, None, :]  # [B, H, l, s]
        decay = jnp.exp(jnp.where(causal, seg, -jnp.inf))
        g = jnp.einsum(
            "bln,bsn->bls", cc.astype(jnp.bfloat16), bc.astype(jnp.bfloat16),
            preferred_element_type=jnp.float32,
        )
        dtx = dtc[..., None] * xc  # [B, L, H, P]
        y = jnp.einsum(
            "bhls,bshp->blhp", (g[:, None] * decay).astype(jnp.bfloat16), dtx.astype(jnp.bfloat16),
            preferred_element_type=jnp.float32,
        )
        y += jnp.einsum("bln,bhpn->blhp", cc, h_in, precision=_HIGHEST) * jnp.exp(cs).swapaxes(1, 2)[..., None]
        to_end = jnp.exp(cs[:, :, -1:] - cs).swapaxes(1, 2)  # [B, L, H]
        h_out = h_in * jnp.exp(cs[:, :, -1])[..., None, None] + jnp.einsum(
            "blhp,bln->bhpn", dtx * to_end[..., None], bc, precision=_HIGHEST
        )
        return h_out, y

    state, ys = jax.lax.scan(one_step, state, (steps(x32), steps(dt), steps(b), steps(c)))
    y = ys.swapaxes(0, 1).reshape(bsz, t + pad, h, p)[:, :t]
    return y + d[:, None] * x32[:, :t], state


def _on_tpu() -> bool:
    return jax.devices()[0].platform == "tpu"


def decode_in_place(use_kernel: bool | None = None) -> bool:
    """Whether :func:`ssm_decode` will run the Pallas kernel, which walks the
    store's own rows and updates them in place; otherwise a caller does
    better to hand in the rows it gathered (see ``VLM._forward``)."""
    return _on_tpu() if use_kernel is None else use_kernel


def ssm_prefill(store, layer, rows, x, dt, a, b, c, d, *, chunk: int, use_kernel: bool | None = None):
    """Advance the states ``store[layer, rows]`` over a chunk of tokens.
    store: ``[Lm, R, H, P, N]`` float32; rows: ``[B]``; x: ``[B, T, H, P]``;
    dt: ``[B, T, H]``, 0 at padding; b, c: ``[B, T, N]``. Rows that share a
    store row must carry the same inputs (the engine's duplicated padding
    rows do). Returns (y ``[B, T, H, P]`` float32, store)."""
    state = store[layer, rows]
    if decode_in_place(use_kernel):
        y, state = ssd_chunk_scan(state, x, dt, a, b, c, d, chunk=chunk)
    else:
        y, state = ssm_scan_reference(state, x, dt, a, b, c, d)
    return y, store.at[layer, rows].set(state)


def _ssm_decode_kernel(layer_ref, rows_ref, decay_ref, dtx_ref, b_ref, c_ref, state_ref, y_ref, out_ref):
    """One grid step is ``hb`` heads of one row. decay_ref / dtx_ref / y_ref:
    ``[P, hb]`` columns, a head a lane; b_ref / c_ref: ``[1, N]``; state_ref
    / out_ref: ``[hb, P, N]``, the same block of the aliased store."""
    del layer_ref, rows_ref  # the index maps read them
    p, hb = y_ref.shape
    b_row, c_row = b_ref[...], c_ref[...]
    lane = jax.lax.broadcasted_iota(jnp.int32, (p, hb), 1)
    decay, dtx = decay_ref[...], dtx_ref[...]
    ys = jnp.zeros((p, hb), jnp.float32)
    for i in range(hb):
        # a head's column, spread over the state's lanes
        new = state_ref[i] * decay[:, i : i + 1] + dtx[:, i : i + 1] * b_row  # [P, N]
        out_ref[i] = new
        ys = jnp.where(lane == i, jnp.sum(new * c_row, axis=1, keepdims=True), ys)
    y_ref[...] = ys


@functools.partial(jax.jit, static_argnames=("heads_per_step", "interpret"))
def _ssm_decode(store, layer, rows, decay, dtx, b, c, *, heads_per_step, interpret):
    """store: ``[Lm, R, H, P, N]``; decay: ``[B, H]``; dtx: ``[B, H, P]``; b,
    c: ``[B, N]``. ``layer`` is a run-time scalar, prefetched with ``rows``:
    a model's layers share one trace and one lowering of the kernel. Returns
    (``H_new C`` ``[B, H, P]``, store)."""
    bsz, h, p = dtx.shape
    n = store.shape[-1]
    hb = heads_per_step
    groups = h // hb

    def columns(v):  # [B, H, P] -> [B, H / hb, P, hb]
        return v.reshape(bsz, groups, hb, p).swapaxes(2, 3)

    column_spec = pl.BlockSpec((None, None, p, hb), lambda i, j, *_: (i, j, 0, 0))
    vector_spec = pl.BlockSpec((None, 1, n), lambda i, j, *_: (i, 0, 0))
    state_spec = pl.BlockSpec(
        (None, None, hb, p, n), lambda i, j, layer, rows: (layer[0], rows[i], j, 0, 0)
    )
    y, store = pl.pallas_call(
        _ssm_decode_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(bsz, groups),
            in_specs=[column_spec, column_spec, vector_spec, vector_spec, state_spec],
            out_specs=[column_spec, state_spec],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((bsz, groups, p, hb), jnp.float32),
            jax.ShapeDtypeStruct(store.shape, store.dtype),
        ],
        # operand 6 (after the two prefetched scalars and four small inputs)
        # is the store, and it is output 1: only the visited blocks move
        input_output_aliases={6: 1},
        # rows may share the garbage row: no two cores in one row's blocks
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(
        jnp.asarray(layer, jnp.int32).reshape(1), rows.astype(jnp.int32),
        columns(jnp.broadcast_to(decay[..., None], dtx.shape)), columns(dtx),
        b[:, None, :], c[:, None, :], store,
    )
    return y.swapaxes(2, 3).reshape(bsz, h, p), store


def ssm_decode(
    store, layer, rows, x, dt, a, b, c, d, *, use_kernel: bool | None = None,
    interpret: bool | None = None, heads_per_step: int = 32,
):
    """Advance the states ``store[layer, rows]`` by one token a row. x: ``[B,
    H, P]``; dt: ``[B, H]``, 0 for a row that must not move (idle rows point
    at row 0 and may collide there); b, c: ``[B, N]``. Returns (y ``[B, H,
    P]`` float32, store)."""
    x = x.astype(jnp.float32)
    if not decode_in_place(use_kernel):
        y, state = ssm_step_reference(store[layer, rows], x, dt, a, b, c, d)
        return y, store.at[layer, rows].set(state)
    if interpret is None:
        interpret = jax.devices()[0].platform == "cpu"
    y, store = _ssm_decode(
        store, layer, rows, jnp.exp(dt * a), dt[..., None] * x, b.astype(jnp.float32),
        c.astype(jnp.float32), heads_per_step=min(heads_per_step, x.shape[1]), interpret=interpret,
    )
    return y + d[:, None] * x, store
