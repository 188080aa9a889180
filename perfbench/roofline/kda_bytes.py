"""Operations and bytes of Kimi Delta Attention's recurrence (the delta rule
with a decay a CHANNEL: ``_delta_decode`` with its third column and the chunked
prefill scan, ops/delta_rule.py), from its shapes alone: LOGICAL, unpadded
sizes of the WORK, whatever implements it, so that a layout that pads or a
form that computes more than the recurrence asks for lowers the share, as it
should. Kept with the benchmark so that no PR that claims a gain can change
the yardstick."""

from __future__ import annotations

CHUNK = 64  # tokens the prefill scan solves at once


def kda_decode_bytes(rows: int, *, n_layers: int, n_heads: int, key_dim: int, value_dim: int,
                     state_bytes: int = 4) -> int:
    """Bytes one decode step over ``rows`` decoding rows has to move for the
    recurrence: a row's state ``[n_heads, key_dim, value_dim]`` read once and
    written once; ``q``, ``k`` and the decay (``[n_heads, key_dim]`` each: the
    decay is a column, not a scalar), ``v`` in and ``o`` out (``[n_heads,
    value_dim]``), ``beta`` (``[n_heads]``), float32, in every linear-attention
    layer. Left out: the convolutions' tails, the low-rank projections and the
    gate (the kernel does not touch them), and whatever a form of the kernel
    moves besides (its idle rows' garbage row, a column padded to 128 lanes)."""
    state = n_heads * key_dim * value_dim * state_bytes
    small = (3 * n_heads * key_dim + 2 * n_heads * value_dim + n_heads) * 4
    return int(rows) * (2 * state + small) * n_layers


def kda_decode_flops(rows: int, *, n_layers: int, n_heads: int, key_dim: int, value_dim: int) -> int:
    """Per state element: the decay multiply, the multiply-add of ``S^T k``,
    the outer product's multiply and add, the multiply-add of ``S^T q``: 7."""
    return int(rows) * n_layers * 7 * n_heads * key_dim * value_dim


def kda_prefill_flops(chunks: int, *, n_layers: int, n_heads: int, key_dim: int, value_dim: int) -> int:
    """Multiply-adds x2 of the chunked form for ``chunks`` 64-token chunks (a
    row's, counted once a chunk that holds a token), a head, a layer: the two
    in-chunk products ``A`` and ``QK`` (``C^2 dk`` each, the decay between two
    positions one more multiply a term: ``2 C^2 dk`` more), the unit lower
    triangular inverse (``C^3 / 3``), its two products (``C^2 (dk + dv)``), the
    two products with the entering state and the state's update (``C dk dv``
    each), and ``tril(QK) V'`` (``C^2 dv``). The exponentials are not counted."""
    c, dk, dv = CHUNK, key_dim, value_dim
    a_chunk = 4 * c * c * dk + c**3 // 3 + c * c * (dk + dv) + 3 * c * dk * dv + c * c * dv
    return int(chunks) * n_layers * n_heads * 2 * a_chunk


def kda_prefill_bytes(chunks: int, *, n_layers: int, n_heads: int, key_dim: int, value_dim: int) -> int:
    """What the scan has to move for them: ``q``, ``k`` and the decay (``[C,
    dk]`` each) and ``v`` (``[C, dv]``) in and ``o`` out, float32, and ``beta``;
    the state is carried on the chip from chunk to chunk and is read and
    written once a prefill program, which is left out."""
    c, dk, dv = CHUNK, key_dim, value_dim
    return int(chunks) * n_layers * n_heads * (c * (3 * dk + 2 * dv) + c) * 4
