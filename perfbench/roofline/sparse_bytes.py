"""Operations and bytes of attention over the positions a learned indexer
picks, from the WORK alone (live positions, chosen positions, heads, widths):
the least any implementation must move, so a share reads the same whatever
implements it and cannot pass 100% by a cleverer read. A padded row, a
neighbour brought along, a page read whole or a sort all show as a LOWER share.

A query at a context of ``n`` positions scores all ``n`` (one index key of
``index_dim`` values a position, ``index_heads`` heads) and attends to
``min(n, top_k)`` of them.
"""

from __future__ import annotations


def index_score_bytes(live_positions: int, *, n_layers: int, index_dim: int, dtype_bytes: int = 2) -> int:
    """Index-key bytes of the live positions a call's rows see, read once a
    layer (a prefill chunk's queries share their row's keys)."""
    return n_layers * int(live_positions) * index_dim * dtype_bytes


def index_score_flops(query_positions: int, *, n_layers: int, index_heads: int, index_dim: int) -> int:
    """``2 x index_heads x index_dim`` operations a (query, position) pair a layer."""
    return n_layers * int(query_positions) * 2 * index_heads * index_dim


def prefill_pairs(write: int, valid: int) -> int:
    """(query, position) pairs of a chunk of ``valid`` queries written at
    ``write``: query ``write + t`` sees ``write + t + 1`` positions."""
    return valid * write + valid * (valid + 1) // 2


def chosen_decode_kv_bytes(
    valid_lengths, *, n_layers: int, top_k: int, n_kv_heads: int, head_dim: int, dtype_bytes: int = 2
) -> int:
    """K and V bytes one decode step has to read: ``min(context, top_k)``
    positions a row a layer."""
    position = n_kv_heads * head_dim * dtype_bytes * 2
    return n_layers * sum(min(int(n), top_k) for n in valid_lengths if n > 0) * position
