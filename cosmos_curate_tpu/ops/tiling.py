"""TPU tile arithmetic shared by the Pallas kernels."""

from __future__ import annotations

import jax.numpy as jnp


def sublanes(dtype) -> int:
    """Rows of one native TPU tile for ``dtype``: 8 for 32-bit, 16 for
    16-bit (two rows pack into each sublane). A block's second-to-last
    dimension is a whole number of these, or the array's own extent."""
    return 8 * max(1, 4 // jnp.dtype(dtype).itemsize)


def round_up(n: int, multiple: int) -> int:
    return -(-n // multiple) * multiple
