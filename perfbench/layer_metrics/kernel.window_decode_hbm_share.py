"""Roofline share of the paged decode kernel where window and full layers are
mixed: the K/V bytes the decode steps of the traced slice had to read (a full
layer's by the row's length, a window layer's bounded by the window, counted by
position: ``roofline/window_bytes.py``), at the chip's peak HBM bandwidth, over the
kernel's device time. Nothing to read where the driver records no such layers."""

from perfbench.catalog import peaks
from perfbench.roofline import window_bytes

UNIT, LAYER, MOVES, SOURCE = "%", "kernels", "output_tok_per_s", "device_trace"


def read(run):
    trace, piece = run.get("trace"), run.get("slice") or {}
    shape, steps = piece.get("window_shape"), piece.get("decode_lengths")
    if trace is None or not shape or not steps or not trace.kernel_s.get("paged_decode"):
        return None
    kv = {k: shape[k] for k in ("n_full", "n_window", "window", "n_kv_heads", "head_dim", "dtype_bytes")}
    moved = sum(window_bytes.window_decode_kv_bytes(step, **kv) for step in steps)
    return 100.0 * moved / peaks(run["device"]["kind"])["hbm_bytes_per_s"] / trace.kernel_s["paged_decode"]
