"""Roofline share of the delta-rule decode kernel under a decay a channel
(``_delta_decode`` with its third column: Kimi Delta Attention): the bytes the
recurrence had to move in the decode steps of the traced slice (every decoding
row's ``[heads, dk, dv]`` float32 state read and written once in every
linear-attention layer, with q, k, the decay column, v, o and beta:
roofline/kda_bytes.py, logical sizes), at the chip's peak HBM bandwidth, over
the kernel's device time. A state element costs 8 bytes and 7 operations, so
memory bounds it. Nothing to read where the driver records no such layers."""

from perfbench.catalog import peaks
from perfbench.roofline import kda_bytes, ops_bytes

UNIT, LAYER, MOVES, SOURCE = "%", "kernels", "output_tok_per_s", "device_trace"


def read(run):
    delta, piece = run.get("delta_trace"), run.get("slice") or {}
    spent = (delta or {}).get("kernel_s", {}).get("delta_decode")
    if not spent or not piece.get("decode_lengths") or "kda_shape" not in piece:
        return None
    rows = sum(len(step) for step in piece["decode_lengths"])
    share, bound = ops_bytes.roofline_share(
        flops=kda_bytes.kda_decode_flops(rows, **piece["kda_shape"]),
        bytes_moved=kda_bytes.kda_decode_bytes(rows, **piece["kda_shape"]),
        seconds=spent, peaks=peaks(run["device"]["kind"]),
    )
    if bound != "memory":
        raise ValueError(f"the decode recurrence bound by {bound}: this metric is misnamed for it")
    return 100.0 * share
