"""Assignments that LIVE rows made per decode program, summed over the sparse
layers, where every expert of a layer is held (``stats()``'s
``expert_assignments_held_live``, counted on the device by the recurrent decode
program's rider and read after the window, over delta ``paged_kernel_steps``):
nothing is left out, so it is 4 x the live rows in each of the eight sparse
layers, 8,192 when all 256 rows decode, 16 an expert a layer: a deployment's own
load. The idle rows' token is routed and multiplied too
(``expert_assignments_held``: 8,192 a program whatever decodes); the difference
is work no request asked for. Nothing to read where the engine counts none."""

UNIT, LAYER, MOVES, SOURCE = "count", "caption engine", "output_tok_per_s", "program_counter"


def read(run):
    counters = run.get("conv")
    programs = (run.get("stats_delta") or {}).get("paged_kernel_steps")
    if not counters or not programs or "expert_assignments_held_live" not in counters:
        return None
    return counters["expert_assignments_held_live"] / programs
