"""Assignments that landed on the experts held here per decode program, summed
over the sparse layers (``stats()``'s ``expert_assignments_held``, counted on the
device and read after the window, over delta ``paged_kernel_steps``). What the
grouped matrix product's cost follows: 256 rows x 6 x 20/160 x 6 layers = 1,152
where every row decodes. Nothing to read where the engine counts none."""

UNIT, LAYER, MOVES, SOURCE = "count", "caption engine", "output_tok_per_s", "program_counter"


def read(run):
    counters = run.get("latent")
    programs = (run.get("stats_delta") or {}).get("paged_kernel_steps")
    if not counters or not programs or "expert_assignments_held" not in counters:
        return None
    return counters["expert_assignments_held"] / programs
