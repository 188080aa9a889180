"""Operations and bytes of the gated delta rule (``_delta_decode`` and the
chunked prefill scan, ops/delta_rule.py), from its shapes alone: LOGICAL,
unpadded sizes, so that a layout that pads lowers the share, as it should.
Kept with the benchmark so that no PR that claims a gain can change the
yardstick."""

from __future__ import annotations

CHUNK = 64  # tokens the prefill scan solves at once


def delta_decode_bytes(rows: int, *, n_layers: int, n_heads: int, key_dim: int, value_dim: int,
                       state_bytes: int = 4) -> int:
    """Bytes one decode step over ``rows`` decoding rows has to move for the
    recurrence: a row's state ``[n_heads, key_dim, value_dim]`` read once and
    written once, ``q`` and ``k`` (``[n_heads, key_dim]``) and ``v``
    (``[n_heads, value_dim]``) in, ``o`` out, ``g`` and ``beta`` (``[n_heads]``),
    float32, in every linear-attention layer. Left out: the convolutions' tails
    and the gate (the kernel does not touch them), and whatever a form of the
    kernel moves besides (its idle rows' garbage row, ``g`` and ``beta`` spread
    over the lanes): that is not work the step asked for, so it lowers the share."""
    state = n_heads * key_dim * value_dim * state_bytes
    small = (2 * n_heads * key_dim + 2 * n_heads * value_dim + 2 * n_heads) * 4
    return int(rows) * (2 * state + small) * n_layers


def delta_decode_flops(rows: int, *, n_layers: int, n_heads: int, key_dim: int, value_dim: int) -> int:
    """Per state element: the decay multiply, the multiply-add of ``S^T k``,
    the outer product's multiply and add, the multiply-add of ``S^T q``: 7."""
    return int(rows) * n_layers * 7 * n_heads * key_dim * value_dim


def delta_prefill_flops(chunks: int, *, n_layers: int, n_heads: int, key_dim: int, value_dim: int) -> int:
    """Multiply-adds x2 of the chunked form for ``chunks`` 64-token chunks (a
    row's, counted once a chunk that holds a token), a head, a layer: ``K K^T``
    and ``Q K^T`` (``C^2 dk`` each), the unit lower triangular inverse (``C^3 /
    3``, what a substitution takes), its two products (``C^2 (dk + dv)``), the
    two products with the entering state and the state's update (``C dk dv``
    each), and ``tril(Q K^T) V'`` (``C^2 dv``)."""
    c, dk, dv = CHUNK, key_dim, value_dim
    a_chunk = 2 * c * c * dk + c**3 // 3 + c * c * (dk + dv) + 3 * c * dk * dv + c * c * dv
    return int(chunks) * n_layers * n_heads * 2 * a_chunk


def delta_prefill_bytes(chunks: int, *, n_layers: int, n_heads: int, key_dim: int, value_dim: int) -> int:
    """What the scan has to move for them: ``q``, ``k`` (``[C, dk]``) and ``v``
    (``[C, dv]``) in and ``o`` out, float32, ``g`` and ``beta``; the state is
    carried on the chip from chunk to chunk and is read and written once a
    prefill program, which is under a tenth of this and left out."""
    c, dk, dv = CHUNK, key_dim, value_dim
    return int(chunks) * n_layers * n_heads * (c * (2 * dk + 2 * dv) + 2 * c) * 4
