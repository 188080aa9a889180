"""Window-pool blocks an admission copied from a shared prefix's entry into the
row's own ring, per admission that copied any (the ``prefix_tail_copy`` phase's
``blocks`` over its ``n``, over the window): a row that will wrap its ring shares
no window block, and of a prefix longer than the ring the entry holds only the
tail a later query can still see, ``ceil(window / block) + 1`` blocks at most (9
at 1,024 / 128; a block of the window pool is every window layer deep). Nothing
to read where the account has no such phase (every program before PR 57) or no
admission copied."""

UNIT, LAYER, MOVES, SOURCE = "count", "caption engine", "output_tok_per_s", "program_counter"


def read(run):
    phases = run.get("phase_delta") or {}
    copies = phases.get("prefix_tail_copy_n")
    if not copies:
        return None
    return phases["prefix_tail_copy_blocks"] / copies
