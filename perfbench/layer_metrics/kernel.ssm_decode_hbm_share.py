"""Roofline share of the state-space decode kernel (``_ssm_decode``): the
bytes the recurrence had to move in the decode steps of the traced slice (every
decoding row's state read and written once in every state-space layer, with
its small inputs: roofline/ssm_bytes.py), at the chip's peak HBM bandwidth,
over the kernel's device time. A state element costs 8 bytes and 5
operations, so memory bounds it. Nothing to read where the trace names no such
kernel (a program without state-space layers)."""

from perfbench.catalog import peaks
from perfbench.roofline import ops_bytes, ssm_bytes

UNIT, LAYER, MOVES, SOURCE = "%", "kernels", "output_tok_per_s", "device_trace"


def read(run):
    ssm, piece = run.get("ssm_trace"), run.get("slice") or {}
    if ssm is None or not piece.get("decode_lengths") or "ssm_shape" not in piece:
        return None
    rows = sum(len(step) for step in piece["decode_lengths"])
    share, bound = ops_bytes.roofline_share(
        flops=ssm_bytes.ssm_decode_flops(rows, **piece["ssm_shape"]),
        bytes_moved=ssm_bytes.ssm_decode_bytes(rows, **piece["ssm_shape"]),
        seconds=ssm["kernel_s"]["ssm_decode"], peaks=peaks(run["device"]["kind"]),
    )
    if bound != "memory":
        raise ValueError(f"the decode recurrence bound by {bound}: this metric is misnamed for it")
    return 100.0 * share
