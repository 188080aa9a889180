"""Operations and bytes of LFM2's two mechanisms, from their shapes alone: the
gated short convolution (models/vlm/short_conv.py: two projections, a
three-tap depthwise convolution and its tails) and an expert layer whose 64
tables are ALL on the chip (``gmm``, ops/grouped_matmul.py; the counts are
``expert_bytes.py``'s at ``held`` = every expert). LOGICAL sizes of the WORK,
whatever implements it, so that a layout that pads or a program that moves an
array twice lowers the share, as it should. Kept with the benchmark so that no
PR that claims a gain can change the yardstick."""

from __future__ import annotations

from perfbench.roofline import expert_bytes


def short_conv_weight_bytes(programs: int, *, n_layers: int, dim: int, taps: int, dtype_bytes: int = 2, **_) -> int:
    """What ``programs`` programs have to read of the mixers' parameters: ``W_in``
    (``dim x 3 dim``) and ``W_out`` (``dim x dim``) in the serving type and the
    ``taps x dim`` float32 taps, once a program a conv layer (LFM2: 4 x 2,048^2
    x 2 B + 3 x 2,048 x 4 B = 33.58 MB a layer, 268.6 MB over the eight)."""
    return int(programs) * n_layers * (4 * dim * dim * dtype_bytes + taps * dim * 4)


def short_conv_token_bytes(tokens: int, rows: int, *, n_layers: int, dim: int, taps: int, dtype_bytes: int = 2, **_) -> int:
    """What the mixers move for ``tokens`` tokens of ``rows`` rows: a token's
    normed input in and its output out (``dim`` each; the three slices between
    the projections need never leave the chip), and a row's tails read and
    written once a program (``(taps - 1) x dim`` each way), a conv layer."""
    return n_layers * (int(tokens) * 2 * dim + int(rows) * 2 * (taps - 1) * dim) * dtype_bytes


def short_conv_flops(tokens: int, *, n_layers: int, dim: int, taps: int, **_) -> int:
    """Multiply-adds x2 of the two projections (``4 dim^2`` a token), the two
    gates and the taps (``(2 + 2 taps) dim``), a conv layer."""
    return int(tokens) * n_layers * (2 * 4 * dim * dim + (2 + 2 * taps) * dim)


def whole_expert_table_bytes(layer_passes: int, **shape) -> int:
    """``expert_bytes.expert_table_bytes`` where every expert is held: gate, up
    and down of all ``held`` = ``router_outputs`` experts (LFM2: 64 x 9.44 M x
    2 B = 1.208 GB a pass through a sparse layer)."""
    if shape["held"] != shape["router_outputs"]:
        raise ValueError(f"not a whole layer: {shape['held']} of {shape['router_outputs']} experts held")
    return expert_bytes.expert_table_bytes(layer_passes, **shape)


def whole_assignments(tokens: int, *, top_k: int, sparse_layers: int, **_) -> int:
    """Every one of a token's ``top_k`` assignments is computed here, in every
    sparse layer: nothing is on another chip."""
    return int(tokens) * top_k * sparse_layers
