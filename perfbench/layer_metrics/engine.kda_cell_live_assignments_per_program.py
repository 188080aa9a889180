"""Assignments that LIVE rows made on the experts held here per decode program,
summed over the layers, in the cell that holds a recurrent store beside sorted
experts (``stats()``'s ``expert_assignments_held_live``: the recurrent decode
program's rider counts them apart, its idle rows being those that carry store
row 0; over delta ``paged_kernel_steps``). What requests asked of the grouped
matrix product: 256 rows x 8 x 40/320 = 256 a layer, 1,024 over the four layers
when every row is live. ``engine.kda_cell_assignments_per_program`` counts the
idle rows' too (their token routes like any other and their rows are
multiplied): that is what the product's cost follows, and the difference is
work no request asked for. Nothing to read where the engine counts none."""

UNIT, LAYER, MOVES, SOURCE = "count", "caption engine", "output_tok_per_s", "program_counter"


def read(run):
    counters = run.get("kda")
    programs = (run.get("stats_delta") or {}).get("paged_kernel_steps")
    if not counters or not programs or "expert_assignments_held_live" not in counters:
        return None
    return counters["expert_assignments_held_live"] / programs
