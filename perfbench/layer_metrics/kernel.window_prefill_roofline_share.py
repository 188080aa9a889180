"""Roofline share of the paged prefill kernel where window and full layers are
mixed: the operations of the (query, key) pairs the masks leave visible and the
bytes of the positions a chunk can see, read once a layer, with its queries and
outputs (``roofline/window_bytes.py``), whichever bound is the larger, over the
kernel's device time in the traced slice. The live rows of every prefill
program of the slice, as the driver recorded them. Nothing to read elsewhere."""

from perfbench.catalog import peaks
from perfbench.roofline import window_bytes

UNIT, LAYER, MOVES, SOURCE = "%", "kernels", "output_tok_per_s", "device_trace"


def read(run):
    trace, piece = run.get("trace"), run.get("slice") or {}
    shape, programs = piece.get("window_shape"), piece.get("prefill_rows")
    if trace is None or not shape or not programs or not trace.kernel_s.get("paged_prefill"):
        return None
    ops = {k: shape[k] for k in ("n_full", "n_window", "window", "n_heads", "head_dim")}
    kv = dict(ops, **{k: shape[k] for k in ("n_kv_heads", "dtype_bytes")})
    peak = peaks(run["device"]["kind"])
    least = sum(
        max(
            window_bytes.window_prefill_flops(rows, **ops) / peak["flops_bf16"],
            window_bytes.window_prefill_bytes(rows, **kv) / peak["hbm_bytes_per_s"],
        )
        for rows in programs
    )
    return 100.0 * least / trace.kernel_s["paged_prefill"]
