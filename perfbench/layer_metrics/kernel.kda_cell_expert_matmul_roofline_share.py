"""Roofline share of the held experts' grouped matrix products (``gmm``, two a
sparse layer a program, decode and prefill programs alike) in the cell that
holds a recurrent store beside sorted experts: the tables a pass had to read
plus the activations and operations of the DECODE programs' assignments (the
device's count over the window, idle rows' among them since their rows are
multiplied too, scaled to the slice's decode programs; the prefill programs'
assignments are not counted by the program, which lowers the share), the larger
of the two bounds over the kernel's device time.

``kernel.expert_matmul_roofline_share`` counts every held expert's tables in
every pass, which holds at 20 experts and 192 assignments a pass; here a pass
has 40 experts of 320 router outputs and a prefill program whose last chunk
holds 16 tokens touches a third of them (``gmm`` visits an expert's tiles only
where an assignment lies: counted whole, the tables read 98-102% of the HBM peak
in six traced runs, PERF.md PR 49). So a pass counts the tables of the experts
its program's tokens can be EXPECTED to touch under even routing, ``1 - (1 -
1 / outputs) ** (tokens x top_k)`` of them, from the live rows of each decode
program and the valid tokens of each prefill program of the slice; what idle
rows' and padding's one garbage token touches besides (at most ``top_k``
experts a pass) is left out, which lowers the share. Nothing to read where
the trace names no such kernel or the driver records no such cell."""

from perfbench.catalog import peaks
from perfbench.measure import log
from perfbench.roofline import expert_bytes, ops_bytes

UNIT, LAYER, MOVES, SOURCE = "%", "kernels", "output_tok_per_s", "device_trace"


def touched_share(tokens: int, *, router_outputs: int, top_k: int, **_) -> float:
    """The share of the experts that ``tokens`` routed tokens touch, expected
    under even routing: an expert is missed by all ``tokens x top_k`` choices."""
    return 1.0 - (1.0 - 1.0 / router_outputs) ** (tokens * top_k)


def read(run):
    experts, piece, counters = run.get("expert_trace"), run.get("slice") or {}, run.get("kda") or {}
    programs = (run.get("stats_delta") or {}).get("paged_kernel_steps")
    spent = (experts or {}).get("kernel_s", {}).get("expert_matmul")
    shape = piece.get("expert_shape") or {}
    if not spent or "router_outputs" not in shape or not programs or "expert_assignments_held" not in counters:
        return None
    tokens = [len(step) for step in piece["decode_lengths"]] + [sum(rows) for rows in piece["prefill_valid"]]
    touched = sum(touched_share(n, **shape) for n in tokens) / len(tokens)
    passes = experts["kernel_calls"]["expert_matmul"] // 2
    assignments = counters["expert_assignments_held"] * len(piece["decode_lengths"]) / programs
    share, bound = ops_bytes.roofline_share(
        flops=expert_bytes.expert_flops(assignments, **shape),
        bytes_moved=touched * expert_bytes.expert_table_bytes(passes, **shape)
        + expert_bytes.expert_activation_bytes(assignments, **shape),
        seconds=spent, peaks=peaks(run["device"]["kind"]),
    )
    log(
        f"kernel.kda_cell_expert_matmul_roofline_share: {passes} passes over {len(tokens)} programs that touch "
        f"{100 * touched:.2f}% of the held experts, the larger bound is {bound}"
    )
    return 100.0 * share
