"""A grouped matrix product: rows sorted by group, one table a group.

``out[r] = lhs[r] @ rhs[g]`` for the rows ``r`` of group ``g``, the groups
consecutive runs of ``group_sizes`` rows from row 0. What a sparse expert layer
needs once its assignments are sorted by expert
(``models/vlm/model.py::MoEFFN._sorted_experts``): the work follows the rows
there are, not ``groups x rows``, and rows past the groups' total (the
assignments of experts this program does not hold) cost nothing.

Which implementation runs is decided here and nowhere else: on a TPU the
Pallas ``gmm`` kernel JAX ships (``jax.experimental.pallas.ops.tpu.megablox``:
its grid covers only the row tiles that hold a group's rows, found from
``group_sizes`` at run time), elsewhere ``jax.lax.ragged_dot``, the plain XLA
operation of the same meaning. The tiles are chosen here: ``gmm``'s default of
128 x 128 moves 32 KiB of a table a grid step, a tenth of what a step costs at
the HBM's rate, and a decode step is nothing but reading tables (PERF.md PR 33).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from cosmos_curate_tpu.ops.tiling import round_up

_ROWS = 128  # rows a tile: about the rows a held expert gets from a prefill group
_TILE = 1024  # most of a table's side a grid step takes: 2 MiB of bfloat16


def _on_tpu() -> bool:
    """Tests put a model on the kernel (interpret mode) by patching this."""
    return jax.devices()[0].platform == "tpu"


def _side(n: int) -> int:
    """The largest multiple of 128 that divides ``n`` and is at most
    ``_TILE``; ``n`` itself where there is none (a test's width)."""
    return next((t for t in range(_TILE, 0, -128) if n % t == 0), n)


def grouped_matmul(lhs, rhs, group_sizes, *, use_kernel: bool | None = None, interpret: bool | None = None):
    """lhs: ``[M, K]``, rows sorted by group; rhs: ``[G, K, N]``; group_sizes:
    ``[G]`` int32, summing to at most ``M``. Returns ``[M, N]`` in lhs's dtype
    (accumulated in float32). Rows past the groups' total are UNSPECIFIED: the
    caller masks them."""
    if use_kernel is None:
        use_kernel = _on_tpu()
    if not use_kernel:
        return jax.lax.ragged_dot(
            lhs, rhs, group_sizes, preferred_element_type=jnp.float32
        ).astype(lhs.dtype)
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    if interpret is None:
        interpret = jax.devices()[0].platform == "cpu"
    m = lhs.shape[0]
    m_pad = round_up(m, _ROWS)
    if m_pad != m:
        lhs = jnp.pad(lhs, ((0, m_pad - m), (0, 0)))
    out = gmm(
        lhs, rhs, group_sizes.astype(jnp.int32), preferred_element_type=lhs.dtype,
        tiling=(_ROWS, _side(rhs.shape[1]), _side(rhs.shape[2])), interpret=interpret,
    )
    return out[:m]
