"""Roofline share of the delta rule's chunked prefill scan: for the prefill
programs of the traced slice, the operations of the 64-token chunks that held
a token (``K K^T``, the inverse, ``Q K^T``, the products with the state, the
state's update: roofline/delta_bytes.py) at the chip's bfloat16 peak and their
bytes at its HBM bandwidth, whichever bound is the larger, over the device
time of ``_delta_prefill`` where the scan is a kernel and of the instructions
under the scope ``delta.prefill_scan`` where it is XLA. The scan computes in
float32 at ``highest`` (six passes of the bfloat16 unit), which the bound does
not pay for. Nothing to read where the driver records neither."""

from perfbench.catalog import peaks
from perfbench.roofline import delta_bytes, ops_bytes

UNIT, LAYER, MOVES, SOURCE = "%", "kernels", "output_tok_per_s", "device_trace"


def read(run):
    piece, scopes = run.get("slice") or {}, run.get("scope_s") or {}
    spent = ((run.get("delta_trace") or {}).get("kernel_s", {}).get("delta_prefill") or 0.0) + sum(
        s for (_kind, scope), s in scopes.items() if scope == "delta.prefill_scan"
    )
    chunks = sum(
        -(-int(valid) // delta_bytes.CHUNK) for rows in piece.get("prefill_valid") or [] for valid in rows
    )
    if not spent or not chunks or "delta_shape" not in piece:
        return None
    share, _bound = ops_bytes.roofline_share(
        flops=delta_bytes.delta_prefill_flops(chunks, **piece["delta_shape"]),
        bytes_moved=delta_bytes.delta_prefill_bytes(chunks, **piece["delta_shape"]),
        seconds=spent, peaks=peaks(run["device"]["kind"]),
    )
    return 100.0 * share
