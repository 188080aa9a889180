"""Assignments that landed on the experts held here per decode program, summed
over the sparse layers, where window and full attention layers are mixed
(``stats()``'s ``expert_assignments_held``, counted on the device and read after
the window, over delta ``paged_kernel_steps``). What the grouped matrix
product's cost follows: 40 rows x 4 x 32/256 x 4 layers = 80 over both lanes'
programs where every row decodes; a lane's program sees its own rows'. Nothing
to read where the engine counts none."""

UNIT, LAYER, MOVES, SOURCE = "count", "caption engine", "output_tok_per_s", "program_counter"


def read(run):
    counters = run.get("windowed")
    programs = (run.get("stats_delta") or {}).get("paged_kernel_steps")
    if not counters or not programs or "expert_assignments_held" not in counters:
        return None
    return counters["expert_assignments_held"] / programs
