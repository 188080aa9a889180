"""Roofline share of the grouped matrix products (``gmm``, two a sparse layer a
program, decode and prefill programs alike) where EVERY expert of a layer is
held: 64 tables of 2,048 x 1,536 x 3, 1.21 GB a pass. Nothing is on another
chip, so every assignment of every token is this chip's, and the count is the
traffic's own: 4 a live row of each decode program and 4 a valid token of each
prefill program of the slice, in each of the eight sparse layers (idle rows and
padding are multiplied too and are no work anybody asked for: left out, which
lowers the share). A pass counts the tables of the experts its program's tokens
can be EXPECTED to touch under even routing (``gmm`` visits an expert's tiles
only where an assignment lies): all 64 at a decode program's 1,024 assignments,
63% of them at a last chunk of 16 tokens. The larger of the bytes' and the
operations' bound (roofline/lfm2_bytes.py, expert_bytes.py) over the kernel's
device time. Nothing to read where the trace names no such kernel or the driver
records no such cell."""

from perfbench.catalog import load_module, peaks
from perfbench.measure import log
from perfbench.roofline import expert_bytes, lfm2_bytes, ops_bytes

UNIT, LAYER, MOVES, SOURCE = "%", "kernels", "output_tok_per_s", "device_trace"

# the share of the experts that a program's routed tokens touch under even routing: the Solar cell's reader's
touched_share = load_module("layer_metrics", "kernel.kda_cell_expert_matmul_roofline_share").touched_share


def read(run):
    experts, piece = run.get("expert_trace"), run.get("slice") or {}
    spent = (experts or {}).get("kernel_s", {}).get("expert_matmul")
    shape = piece.get("expert_shape") or {}
    if not spent or "conv" not in run or shape.get("held") != shape.get("router_outputs") or not shape:
        return None
    tokens = [len(step) for step in piece["decode_lengths"]] + [sum(rows) for rows in piece["prefill_valid"]]
    if not tokens:
        return None
    passes = experts["kernel_calls"]["expert_matmul"] // 2
    touched = sum(touched_share(n, **shape) for n in tokens) / len(tokens)
    assignments = lfm2_bytes.whole_assignments(sum(tokens), **shape)
    share, bound = ops_bytes.roofline_share(
        flops=expert_bytes.expert_flops(assignments, **shape),
        bytes_moved=touched * lfm2_bytes.whole_expert_table_bytes(passes, **shape)
        + expert_bytes.expert_activation_bytes(assignments, **shape),
        seconds=spent, peaks=peaks(run["device"]["kind"]),
    )
    log(
        f"kernel.whole_moe_expert_matmul_roofline_share: {passes} passes over {len(tokens)} programs that touch "
        f"{100 * touched:.2f}% of the experts, {assignments} assignments, the larger bound is {bound}"
    )
    return 100.0 * share
