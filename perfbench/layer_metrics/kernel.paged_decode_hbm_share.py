"""Roofline share of the paged decode kernel: the K/V bytes the decode steps
of the traced slice had to read (from the valid length of every row of every
step, in whole pages; one chip's KV heads under a mesh), at the chip's peak
HBM bandwidth, over the kernel's device time on the first chip. Decode
attention does 2 operations a byte at most, so memory bounds it."""

from perfbench.catalog import peaks
from perfbench.roofline import ops_bytes

UNIT, LAYER, MOVES, SOURCE = "%", "kernels", "output_tok_per_s", "device_trace"


def read(run):
    trace, piece = run.get("trace"), run.get("slice") or {}
    if trace is None or not piece.get("decode_lengths"):
        return None
    steps = piece["decode_lengths"]
    kv_bytes = sum(ops_bytes.paged_decode_kv_bytes(step, **piece["kv_shape"]) for step in steps)
    flops = sum(ops_bytes.paged_decode_flops(step, **piece["attention_shape"]) for step in steps)
    share, bound = ops_bytes.roofline_share(
        flops=flops, bytes_moved=kv_bytes, seconds=trace.kernel_s["paged_decode"],
        peaks=peaks(run["device"]["kind"]),
    )
    if bound != "memory":
        raise ValueError(f"paged decode attention bound by {bound}: this metric is misnamed for it")
    return 100.0 * share
