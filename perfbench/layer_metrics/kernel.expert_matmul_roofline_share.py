"""Roofline share of the held experts' grouped matrix products (``gmm``, two a
sparse layer a program, decode and prefill programs alike: a trace cannot tell
them apart): the tables every pass had to read (all held experts touched:
roofline/expert_bytes.py) plus the activations and operations of the DECODE
programs' assignments (the device's count over the window, scaled to the
slice's decode programs; the prefill programs' assignments are not counted by
the program, so their activations and operations are left out, which lowers the
share), the larger of the two bounds over the kernel's device time. Nothing to
read where the trace names no such kernel."""

from perfbench.catalog import peaks
from perfbench.measure import log
from perfbench.roofline import expert_bytes, ops_bytes

UNIT, LAYER, MOVES, SOURCE = "%", "kernels", "output_tok_per_s", "device_trace"


def read(run):
    experts, piece, counters = run.get("expert_trace"), run.get("slice") or {}, run.get("latent") or {}
    programs = (run.get("stats_delta") or {}).get("paged_kernel_steps")
    if experts is None or "expert_shape" not in piece or not programs:
        return None
    shape = piece["expert_shape"]
    passes = experts["kernel_calls"]["expert_matmul"] // 2
    assignments = counters["expert_assignments_held"] * len(piece["decode_lengths"]) / programs
    share, bound = ops_bytes.roofline_share(
        flops=expert_bytes.expert_flops(assignments, **shape),
        bytes_moved=expert_bytes.expert_table_bytes(passes, **shape)
        + expert_bytes.expert_activation_bytes(assignments, **shape),
        seconds=experts["kernel_s"]["expert_matmul"], peaks=peaks(run["device"]["kind"]),
    )
    log(f"kernel.expert_matmul_roofline_share: {passes} passes, the larger bound is {bound}")
    return 100.0 * share
