"""Share of the table entries the decode programs' rows span that the decode
kernel's walk leaves out: 1 - ``paged_decode_pages_walked`` /
``paged_decode_pages_spanned`` of ``stats()``, over the window, layer by layer
(a full layer walks a row's length; a window layer starts at the page of the
row's oldest visible key). Nothing to read where the engine keeps one pool."""

UNIT, LAYER, MOVES, SOURCE = "%", "kernels", "output_tok_per_s", "program_counter"


def read(run):
    counters = run.get("windowed")
    if not counters or not counters.get("paged_decode_pages_spanned"):
        return None
    return 100.0 * (1.0 - counters["paged_decode_pages_walked"] / counters["paged_decode_pages_spanned"])
