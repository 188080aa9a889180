"""Roofline share of the latent-attention decode kernel (``mla_decode``): the
latent rows the decode steps of the traced slice had to read (from the valid
length of every row of every step, in whole pages, 1,152 B a position a layer)
and the operations on them (278,528 a position a layer), each at the chip's
peak, the LARGER of the two times over the kernel's device time. At 242
operations a byte against the v5e's ridge of 240 the two bounds are half a per
cent apart: which it was is logged, not raised. Nothing to read where the trace
names no such kernel."""

from perfbench.catalog import peaks
from perfbench.measure import log
from perfbench.roofline import mla_bytes, ops_bytes

UNIT, LAYER, MOVES, SOURCE = "%", "kernels", "output_tok_per_s", "device_trace"


def read(run):
    trace, piece = run.get("trace"), run.get("slice") or {}
    if trace is None or not piece.get("decode_lengths") or "mla_shape" not in piece:
        return None
    if not trace.kernel_s.get("mla_decode"):
        return None
    steps, shape = piece["decode_lengths"], piece["mla_shape"]
    share, bound = ops_bytes.roofline_share(
        flops=sum(mla_bytes.mla_decode_flops(step, **shape) for step in steps),
        bytes_moved=sum(mla_bytes.mla_decode_bytes(step, **shape) for step in steps),
        seconds=trace.kernel_s["mla_decode"], peaks=peaks(run["device"]["kind"]),
    )
    log(f"kernel.mla_decode_roofline_share: the larger bound is {bound}")
    return 100.0 * share
