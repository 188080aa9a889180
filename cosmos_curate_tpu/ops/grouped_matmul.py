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
operation of the same meaning.

The tiles are chosen here (``tiles``), from the tables' K and N and from ONE
thing the caller can say about its rows. ``gmm``'s default of 128 x 128 moves
32 KiB of a table a grid step, a tenth of what a step costs at the HBM's rate,
and a decode step is nothing but reading tables (PERF.md PR 33), so a step
takes up to 1024 of each side. ``gmm`` walks column tiles outside, the row
tiles that hold a group's rows next and K's tiles inside, and its pipeline
skips a copy only where two consecutive steps name the same block of a table.
With K cut, a table whose rows straddle two row tiles is therefore fetched
again for the second, whole. Where the program holds only a share of the
experts most rows belong to no table and few tables straddle: K is cut and
nothing more is said. Where EVERY table is held and every row belongs to one
(``whole=True``: M / G rows a table in the mean, a prefill pass's 128 a table
straddle almost always) a step takes K whole and as many columns as a call's
VMEM holds (``_STEP``: two blocks of a table, of rows and of results in flight
beside the accumulator), the block index stays put between the row tiles that
share a table, and a pass reads each table once (PERF.md PR 59).
"""

from __future__ import annotations

import functools
import logging

import jax
import jax.numpy as jnp

from cosmos_curate_tpu.ops.tiling import round_up

_ROWS = 128  # rows a tile: about the rows a held expert gets from a prefill group
_TILE = 1024  # most of a table's side a grid step takes: 2 MiB of bfloat16
# bytes of a grid step's buffers with K whole. A call gets 16 MiB of VMEM; by this count the chip's compiler
# takes 15.6 MiB and refuses 16.1 (tests/ops/test_tpu_compile.py compiles the cells' shapes)
_STEP = 15 << 20

logger = logging.getLogger(__name__)


def _on_tpu() -> bool:
    """Tests put a model on the kernel (interpret mode) by patching this."""
    return jax.devices()[0].platform == "tpu"


def _side(n: int) -> int:
    """The largest multiple of 128 that divides ``n`` and is at most
    ``_TILE``; ``n`` itself where there is none (a test's width)."""
    return next((t for t in range(_TILE, 0, -128) if n % t == 0), n)


def _step_bytes(rows: int, k: int, cols: int, itemsize: int) -> int:
    """What ``gmm`` keeps in VMEM for a ``(rows, k, cols)`` step: the pipeline's
    two blocks each of a table, of lhs and of the result, and the float32 accumulator."""
    return 2 * (k * cols + rows * k + rows * cols) * itemsize + rows * cols * 4


@functools.cache
def tiles(k: int, n: int, *, whole: bool = False, itemsize: int = 2) -> tuple[int, int, int]:
    """The (rows, K, N) a grid step of ``gmm`` takes of ``[G, k, n]`` tables.
    ``whole``: K entire and the widest side of N (a multiple of 128 that
    divides it) whose step fits ``_STEP``; where not even 128 columns fit (a K
    no flavor has), and wherever ``whole`` is not said, both sides cut by
    ``_side``."""
    chosen = (_ROWS, _side(k), _side(n))
    if whole:
        sides = [t for t in range(n, 0, -128) if n % t == 0 and t % 128 == 0] or [n]
        cols = next((t for t in sides if _step_bytes(_ROWS, k, t, itemsize) <= _STEP), None)
        if cols is not None:
            chosen = (_ROWS, k, cols)
    logger.debug("gmm tiles for tables [*, %d, %d] (whole=%s): %s", k, n, whole, chosen)
    return chosen


def grouped_matmul(
    lhs, rhs, group_sizes, *, whole: bool = False, use_kernel: bool | None = None, interpret: bool | None = None
):
    """lhs: ``[M, K]``, rows sorted by group; rhs: ``[G, K, N]``; group_sizes:
    ``[G]`` int32, summing to at most ``M``. Returns ``[M, N]`` in lhs's dtype
    (accumulated in float32). Rows past the groups' total are UNSPECIFIED: the
    caller masks them. ``whole`` (static): every table there is is in ``rhs``
    and the groups sum to ``M``; it chooses tiles (``tiles``) and nothing else."""
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
        tiling=tiles(rhs.shape[1], rhs.shape[2], whole=whole, itemsize=rhs.dtype.itemsize), interpret=interpret,
    )
    return out[:m]
