"""Operations and bytes of the held experts' grouped matrix products (``gmm``,
ops/grouped_matmul.py), from their shapes alone. Kept with the benchmark so that
no PR that claims a gain can change the yardstick."""

from __future__ import annotations


def expert_table_bytes(layer_passes: int, *, dim: int, width: int, held: int, dtype_bytes: int = 2, **_) -> int:
    """Bytes of expert tables ``layer_passes`` passes through a sparse layer
    have to read when every held expert is touched: gate, up and down of
    ``held`` experts, ``3 * dim * width`` values each (DeepSeek-V2: 20 x 23.6 M
    x 2 B = 944 MB a pass). At 192 or more assignments over 20 experts a pass
    an expert goes untouched once in 15,000 passes."""
    return int(layer_passes) * held * 3 * dim * width * dtype_bytes


def expert_activation_bytes(assignments: int, *, dim: int, width: int, dtype_bytes: int = 2, **_) -> int:
    """An assignment's row in (``dim``), its gate and up out (``2 * width``),
    their product in (``width``) and the result out (``dim``)."""
    return int(assignments) * (2 * dim + 3 * width) * dtype_bytes


def expert_flops(assignments: int, *, dim: int, width: int, **_) -> int:
    """Three ``dim x width`` products an assignment, 2 operations a
    multiply-add (DeepSeek-V2: 6 x 5120 x 1536 = 47.2 M)."""
    return int(assignments) * 6 * dim * width
