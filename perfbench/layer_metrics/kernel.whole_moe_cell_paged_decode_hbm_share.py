"""Roofline share of the paged decode kernel in the cell whose recurrent layers
are gated short convolutions beside WHOLE expert layers (LFM2: the two attention
layers alone hold K/V, two 64-wide heads a 128-lane pool row): the K/V bytes the
decode steps of the traced slice had to read (from the valid length of every row
of every step, in whole pages), at the chip's peak HBM bandwidth, over the
kernel's device time: ``kernel.paged_decode_hbm_share``'s arithmetic on this
driver's record, as ``kernel.kda_cell_paged_decode_hbm_share`` is on the Solar
driver's (an existing entry's ``workloads`` list cannot be extended outside a
``benchmark`` PR: PERF.md section 7). Nothing to read where the driver records
no such cell."""

from perfbench.catalog import peaks
from perfbench.roofline import ops_bytes

UNIT, LAYER, MOVES, SOURCE = "%", "kernels", "output_tok_per_s", "device_trace"


def read(run):
    trace, piece = run.get("trace"), run.get("slice") or {}
    if trace is None or not piece.get("decode_lengths") or "conv_shape" not in piece:
        return None
    spent = trace.kernel_s.get("paged_decode")
    if not spent:
        return None
    steps = piece["decode_lengths"]
    share, bound = ops_bytes.roofline_share(
        flops=sum(ops_bytes.paged_decode_flops(step, **piece["attention_shape"]) for step in steps),
        bytes_moved=sum(ops_bytes.paged_decode_kv_bytes(step, **piece["kv_shape"]) for step in steps),
        seconds=spent, peaks=peaks(run["device"]["kind"]),
    )
    if bound != "memory":
        raise ValueError(f"paged decode attention bound by {bound}: this metric is misnamed for it")
    return 100.0 * share
