"""Operations and bytes of attention where window and full layers are mixed,
from the shapes and the rows' positions alone: the SAME work whatever
implements it (a kernel that reads more than this, or computes pairs the mask
throws away, shows as a lower share, never as a higher one).

A window layer's query at position ``i`` sees keys ``i - window < j <= i``; a
full layer's sees ``j <= i``. Bytes are counted BY POSITION (``n_kv_heads x
head_dim`` values of K and as many of V), not in pages of the engine's block
size: the count does not move with that knob, and what a kernel reads beyond the
visible positions because it fetches whole pages shows as a lower share.
"""

from __future__ import annotations


def window_decode_kv_bytes(
    valid_lengths, *, n_full: int, n_window: int, window: int, n_kv_heads: int, head_dim: int,
    dtype_bytes: int = 2,
) -> int:
    """K and V bytes one decode step has to read: for every row of the step
    (``valid_length`` positions, the query the newest), its whole context in
    each of the ``n_full`` layers and its last ``window`` positions in each of
    the ``n_window`` layers."""
    position = n_kv_heads * head_dim * dtype_bytes * 2
    seen = sum(n_full * int(n) + n_window * min(int(n), window) for n in valid_lengths if n > 0)
    return seen * position


def window_decode_flops(
    valid_lengths, *, n_full: int, n_window: int, window: int, n_heads: int, head_dim: int
) -> int:
    """Multiply-adds x2 of q k^T and p v for one decode step over the keys each
    row can see."""
    seen = sum(n_full * int(n) + n_window * min(int(n), window) for n in valid_lengths if n > 0)
    return seen * n_heads * head_dim * 2 * 2


def window_prefill_pairs(write: int, valid: int, window: int | None) -> int:
    """Visible (query, key) pairs of a chunk of ``valid`` queries written at
    ``write``: query ``write + t`` sees ``write + t + 1`` keys, or ``window``
    of them where that is fewer."""
    first, last = write + 1, write + valid  # keys the first and the last query see, no window
    if window is None or last <= window:
        return (first + last) * valid // 2
    if first >= window:
        return window * valid
    ramp = window - first  # queries still under the window
    return (first + window - 1) * ramp // 2 + window * (valid - ramp)


def window_prefill_flops(
    rows, *, n_full: int, n_window: int, window: int, n_heads: int, head_dim: int
) -> int:
    """``rows``: (write, valid) of every live row of one prefill program."""
    pairs = sum(
        n_full * window_prefill_pairs(int(w), int(v), None) + n_window * window_prefill_pairs(int(w), int(v), window)
        for w, v in rows
    )
    return pairs * n_heads * head_dim * 2 * 2


def window_prefill_bytes(
    rows, *, n_full: int, n_window: int, window: int, n_heads: int, n_kv_heads: int, head_dim: int,
    dtype_bytes: int = 2,
) -> int:
    """What a prefill program's attention has to move: the positions of K and V
    a row's chunk can see (a window layer's from the first query's oldest
    visible key on), ONCE a layer (a kernel that fetches them again for every
    block of queries or every head moves more), its queries in and its outputs
    out."""
    position = n_kv_heads * head_dim * dtype_bytes * 2
    total = 0
    for w, v in rows:
        w, v = int(w), int(v)
        kv = n_full * (w + v) + n_window * (w + v - max(w - window + 1, 0))
        total += kv * position + (n_full + n_window) * v * n_heads * head_dim * dtype_bytes * 2
    return total
