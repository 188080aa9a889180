"""Operations and bytes of the state-space decode recurrence (``_ssm_decode``,
ops/ssm.py), from its shapes alone. Kept with the benchmark so that no PR that
claims a gain can change the yardstick."""

from __future__ import annotations


def ssm_decode_bytes(rows: int, *, n_layers: int, n_heads: int, head_dim: int, d_state: int,
                     state_bytes: int = 4) -> int:
    """Bytes one decode step over ``rows`` decoding rows has to move for the
    recurrence: a row's state ``[n_heads, head_dim, d_state]`` read once and
    written once, its ``x`` in and ``y`` out (``[n_heads, head_dim]``), ``dt``
    (``[n_heads]``), ``B`` and ``C`` (``[d_state]``), float32, in every
    state-space layer. Left out: the convolution's tails and ``z`` (the
    kernel does not touch them), and whatever a form of the kernel moves
    besides (its idle rows' garbage row, ``dt`` spread over ``head_dim``):
    that is not work the step asked for, so it lowers the share."""
    state = n_heads * head_dim * d_state * state_bytes
    small = (2 * n_heads * head_dim + n_heads + 2 * d_state) * 4
    return int(rows) * (2 * state + small) * n_layers


def ssm_decode_flops(rows: int, *, n_layers: int, n_heads: int, head_dim: int, d_state: int) -> int:
    """Per state element: the decay multiply, the input's outer product and
    its add, the multiply-add of ``H C``: 5 operations; plus ``D x``."""
    return int(rows) * n_layers * (5 * n_heads * head_dim * d_state + 2 * n_heads * head_dim)
