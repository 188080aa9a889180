"""Share of the prompt tokens admitted in the window whose K/V came from the
shared-prefix cache and were not prefilled: ``prefix_tokens_saved`` over
``prefix_tokens_saved + prefill_tokens`` of ``stats()``, over the window. Where
window and full layers mix and the prefix is longer than a row's ring of window
blocks, a hit references the entry's blocks in the full pool and copies its
window tail (``engine.prefix_tail_blocks_per_hit``); before PR 57 such a prefix
was re-prefilled by every request and this read 0. Nothing to read where the
driver keeps no ``prefix`` block (every program before PR 57)."""

UNIT, LAYER, MOVES, SOURCE = "%", "caption engine", "output_tok_per_s", "program_counter"


def read(run):
    counters = run.get("prefix")
    prefilled = (run.get("stats_delta") or {}).get("prefill_tokens")
    if not counters or prefilled is None or "prefix_tokens_saved" not in counters:
        return None
    saved = counters["prefix_tokens_saved"]
    if saved + prefilled <= 0:
        return None
    return 100.0 * saved / (saved + prefilled)
