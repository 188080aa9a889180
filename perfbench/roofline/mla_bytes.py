"""Operations and bytes of absorbed latent-attention decode (``mla_decode``,
ops/latent_attention.py), from its shapes alone. Kept with the benchmark so that
no PR that claims a gain can change the yardstick."""

from __future__ import annotations


def _positions_in_whole_pages(valid_lengths, block_size: int) -> int:
    return sum(-(-int(n) // block_size) for n in valid_lengths if n > 0) * block_size


def mla_decode_bytes(valid_lengths, *, n_layers: int, key_width: int, block_size: int,
                     dtype_bytes: int = 2, **_) -> int:
    """Bytes one decode step HAS to read: for every row of the step, the pages
    that hold its valid positions (a page is the unit the pool can be read in),
    ONE latent row of ``key_width`` values a position a layer (DeepSeek-V2: 576
    values, 1,152 B), keys and values being the same row. What a pool stores
    besides (its rows are padded to 640 lanes) is not work the step asked for,
    so padding lowers the share. Queries, outputs and the table are left out."""
    return _positions_in_whole_pages(valid_lengths, block_size) * key_width * dtype_bytes * n_layers


def mla_decode_flops(valid_lengths, *, n_layers: int, n_heads: int, key_width: int, value_width: int,
                     block_size: int, **_) -> int:
    """Multiply-adds x2 of every head's score against a cached row's
    ``key_width`` values and of its weighted sum over the row's first
    ``value_width``: ``n_heads * (key_width + value_width) * 2`` a position a
    layer (DeepSeek-V2: 278,528), over the same whole pages."""
    return (
        _positions_in_whole_pages(valid_lengths, block_size)
        * n_heads * (key_width + value_width) * 2 * n_layers
    )
