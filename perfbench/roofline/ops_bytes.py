"""Operations and bytes a kernel call needs, from its shapes alone. Kept with
the benchmark so that no PR that claims a gain can change the yardstick."""

from __future__ import annotations


def paged_decode_kv_bytes(
    valid_lengths, *, n_layers: int, n_kv_heads: int, head_dim: int, block_size: int,
    dtype_bytes: int = 2,
) -> int:
    """K and V bytes one decode step has to read: for every row of the step,
    the pages that hold its ``valid_length`` positions (a page is the unit the
    pool can be read in), for every KV head, K and V, in every layer. Queries,
    outputs and the block table are left out: at these sizes they are under a
    thousandth of the K/V bytes."""
    pages = sum(-(-int(n) // block_size) for n in valid_lengths if n > 0)
    return pages * block_size * n_kv_heads * head_dim * dtype_bytes * 2 * n_layers


def paged_decode_flops(
    valid_lengths, *, n_layers: int, n_heads: int, head_dim: int
) -> int:
    """Multiply-adds x2 of q k^T and p v for one decode step over the valid
    positions of every row."""
    return sum(int(n) for n in valid_lengths) * n_heads * head_dim * 2 * 2 * n_layers


def roofline_share(*, flops: float, bytes_moved: float, seconds: float, peaks: dict) -> tuple[float, str]:
    """(least time the chip could take / time taken, which bound it was)."""
    t_compute = flops / peaks["flops_bf16"]
    t_memory = bytes_moved / peaks["hbm_bytes_per_s"]
    bound = "memory" if t_memory >= t_compute else "compute"
    return max(t_compute, t_memory) / seconds, bound
