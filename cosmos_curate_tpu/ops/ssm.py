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
  rows of a lane, :func:`heads_a_step` heads a grid step (Granite's whole
  row), the store aliased to its output so that only the visited blocks
  move. The state keeps ``d_state`` on the lanes and everything else is
  shaped to that, so that the block's DMA is all a grid step waits for: the
  decay is a scalar a (row, head) read from SMEM; ``dt x`` comes as the rows
  the caller has and is turned over in the kernel (a row repeated down the
  sublanes, transposed); ``y = H C`` is a float32 product on the MXU at
  ``highest``, ``C H^T``, and leaves lane-dense. What the kernel must not do
  is reduce over lanes a head AND slice lanes out to spread them a head a
  register: the two together take 8.7 us of a 64-head step whose DMA takes
  6.8, each alone under 3 (PERF.md section 6, PR 55;
  ``scripts/ssm_decode_probe.py`` times the kernel alone).
- :func:`ssm_prefill`: a chunk of tokens a row, in the chunked SSD form: within
  a step of ``chunk`` tokens the outputs are two matrix products on the MXU
  (``(C B^T * decay) (dt x)``, bfloat16 operands as every activation here),
  and one state is handed from step to step, always in float32 and contracted
  at ``highest`` precision. Plain XLA: ROADMAP S15 has what a Pallas scan
  would save (the ``[B, H, chunk, chunk]`` decay).

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
    """One grid step is ``hb`` heads of one row. decay_ref: ``[B, H]`` in
    SMEM, a scalar a (row, head); dtx_ref: ``[hb, P]`` rows, as the caller
    has them; b_ref / c_ref: ``[1, N]``; state_ref / out_ref: ``[hb, P, N]``,
    the same block of the aliased store; y_ref: ``[1, hb * P]``, the heads'
    ``P`` outputs side by side. Nothing here reduces over lanes or slices a
    lane out to spread it: together those outlast the block's DMA."""
    del layer_ref, rows_ref  # the index maps read them
    hb, p, n = state_ref.shape
    row, first = pl.program_id(0), pl.program_id(1) * hb
    b_row = b_ref[...]
    c_rows = jnp.broadcast_to(c_ref[...], (8, n))  # the fewest rows a matmul takes
    for i in range(hb):
        # dt x, a value a state row: the head's [1, P] repeated down the
        # sublanes and turned over, ``[P, N]`` with every lane of a row alike
        dtx = jnp.broadcast_to(dtx_ref[i : i + 1, :], (n, p)).T
        new = state_ref[i] * decay_ref[row, first + i] + dtx * b_row
        out_ref[i] = new
        # H C on the otherwise idle MXU, ``c new^T`` as ``q k^T`` in the paged
        # kernels: lane-dense, float32 in and out at six bfloat16 passes
        y = jax.lax.dot_general(
            c_rows, new, (((1,), (1,)), ((), ())), precision=_HIGHEST, preferred_element_type=jnp.float32
        )
        y_ref[:, pl.ds(i * p, p)] = y[0:1]


@functools.partial(jax.jit, static_argnames=("heads_per_step", "interpret"))
def _ssm_decode(store, layer, rows, decay, dtx, b, c, *, heads_per_step, interpret):
    """store: ``[Lm, R, H, P, N]``; decay: ``[B, H]``; dtx: ``[B, H, P]``; b,
    c: ``[B, N]``. ``layer`` is a run-time scalar, prefetched with ``rows``
    and ``decay``: a model's layers share one trace and one lowering of the
    kernel. Returns (``H_new C`` ``[B, H, P]``, store)."""
    bsz, h, p = dtx.shape
    n = store.shape[-1]
    hb = heads_per_step
    groups = h // hb
    vector_spec = pl.BlockSpec((None, 1, n), lambda i, j, *_: (i, 0, 0))
    state_spec = pl.BlockSpec(
        (None, None, hb, p, n), lambda i, j, layer, rows, decay: (layer[0], rows[i], j, 0, 0)
    )
    y, store = pl.pallas_call(
        _ssm_decode_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(bsz, groups),
            in_specs=[
                pl.BlockSpec((None, None, hb, p), lambda i, j, *_: (i, j, 0, 0)),
                vector_spec, vector_spec, state_spec,
            ],
            out_specs=[pl.BlockSpec((None, None, 1, hb * p), lambda i, j, *_: (i, j, 0, 0)), state_spec],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((bsz, groups, 1, hb * p), jnp.float32),
            jax.ShapeDtypeStruct(store.shape, store.dtype),
        ],
        # operand 6 (after the three prefetched arrays and three small inputs)
        # is the store, and it is output 1: only the visited blocks move
        input_output_aliases={6: 1},
        # rows may share the garbage row: no two cores in one row's blocks
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"), vmem_limit_bytes=_VMEM_BYTES
        ),
        interpret=interpret,
    )(
        jnp.asarray(layer, jnp.int32).reshape(1), rows.astype(jnp.int32), decay,
        dtx.reshape(bsz, groups, hb, p), b[:, None, :], c[:, None, :], store,
    )
    return y.reshape(bsz, h, p), store


# of state a grid step of the decode kernel, at most: Granite-4.0-H's whole row
# (PR 55's probe: 3% under two steps a row)
_STEP_BYTES = 2**21
# what the kernel asks for: the step's block in and out, two buffers each, and the small blocks
_VMEM_BYTES = 16 * 2**20


def heads_a_step(h: int, p: int, n: int, at_most: int | None = None) -> int:
    """Heads a grid step of the decode kernel takes: the most that divide ``h``,
    up to ``at_most`` (None: as many as ``_STEP_BYTES`` of float32 state hold)."""
    if at_most is None:
        at_most = max(1, _STEP_BYTES // (4 * p * n))
    return max(k for k in range(1, min(at_most, h) + 1) if h % k == 0)


def ssm_decode(
    store, layer, rows, x, dt, a, b, c, d, *, use_kernel: bool | None = None,
    interpret: bool | None = None, heads_per_step: int | None = None,
):
    """Advance the states ``store[layer, rows]`` by one token a row. x: ``[B,
    H, P]``; dt: ``[B, H]``, 0 for a row that must not move (idle rows point
    at row 0 and may collide there); b, c: ``[B, N]``. ``heads_per_step``: at
    most so many heads a grid step of the kernel (None: as many as
    ``_STEP_BYTES`` of state hold; a value is for tests and the probe).
    Returns (y ``[B, H, P]`` float32, store)."""
    x = x.astype(jnp.float32)
    if not decode_in_place(use_kernel):
        y, state = ssm_step_reference(store[layer, rows], x, dt, a, b, c, d)
        return y, store.at[layer, rows].set(state)
    if interpret is None:
        interpret = jax.devices()[0].platform == "cpu"
    hb = heads_a_step(x.shape[1], x.shape[2], store.shape[-1], heads_per_step)
    y, store = _ssm_decode(
        store, layer, rows, jnp.exp(dt * a), dt[..., None] * x, b.astype(jnp.float32),
        c.astype(jnp.float32), heads_per_step=hb, interpret=interpret,
    )
    return y + d[:, None] * x, store
