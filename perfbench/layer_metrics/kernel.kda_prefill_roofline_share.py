"""Roofline share of the chunked prefill scan under a decay a channel (Kimi
Delta Attention): for the prefill programs of the traced slice, the operations
of the 64-token chunks that held a token (roofline/kda_bytes.py: the WORK of
the chunked form, whatever implements it) at the chip's bfloat16 peak and their
bytes at its HBM bandwidth, whichever bound is the larger, over the device time
of ``_delta_prefill`` where the scan is a kernel and of the instructions under
the scope ``delta.prefill_scan`` where it is XLA. The scan computes in float32
at ``highest`` and forms a sub-block's decays pair by pair, which the bound does
not pay for. Nothing to read where the driver records neither."""

from perfbench.catalog import peaks
from perfbench.roofline import kda_bytes, ops_bytes

UNIT, LAYER, MOVES, SOURCE = "%", "kernels", "output_tok_per_s", "device_trace"


def read(run):
    piece, scopes = run.get("slice") or {}, run.get("scope_s") or {}
    spent = ((run.get("delta_trace") or {}).get("kernel_s", {}).get("delta_prefill") or 0.0) + sum(
        s for (_kind, scope), s in scopes.items() if scope == "delta.prefill_scan"
    )
    chunks = sum(-(-int(valid) // kda_bytes.CHUNK) for rows in piece.get("prefill_valid") or [] for valid in rows)
    if not spent or not chunks or "kda_shape" not in piece:
        return None
    share, _bound = ops_bytes.roofline_share(
        flops=kda_bytes.kda_prefill_flops(chunks, **piece["kda_shape"]),
        bytes_moved=kda_bytes.kda_prefill_bytes(chunks, **piece["kda_shape"]),
        seconds=spent, peaks=peaks(run["device"]["kind"]),
    )
    return 100.0 * share
