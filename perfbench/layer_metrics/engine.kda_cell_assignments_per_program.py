"""Assignments that landed on the experts held here per decode program, summed
over the layers, in the cell that holds a recurrent store beside sorted experts
(``stats()``'s ``expert_assignments_held``, counted on the device by the
recurrent decode program's rider and read after the window, over delta
``paged_kernel_steps``). What the grouped matrix product's cost follows: 256
rows x 8 x 40/320 = 256 a layer, 1,024 over the four layers of a program whose
rows all route evenly. Nothing to read where the engine counts none."""

UNIT, LAYER, MOVES, SOURCE = "count", "caption engine", "output_tok_per_s", "program_counter"


def read(run):
    counters = run.get("kda")
    programs = (run.get("stats_delta") or {}).get("paged_kernel_steps")
    if not counters or not programs or "expert_assignments_held" not in counters:
        return None
    return counters["expert_assignments_held"] / programs
