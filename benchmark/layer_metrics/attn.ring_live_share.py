"""Of the ring cells the decode program read in the window, the share that
the steps needed: `ring_cells_live` over `ring_cells_spanned`, summed over
the window's `decode` spans.  A window layer's ring is read whole a step;
a row younger than the window needs only its `position + 1` cells of it, so
the share falls with the rows that have not yet filled their rings.  A
program whose spans carry no such counts (no ring, or one from before the
count) leaves the metric out."""

from benchmark import program_spans


def read(seen):
    spans = [s for s in program_spans.named(seen, "decode") or ()
             if "ring_cells_spanned" in s.attrs]
    spanned = sum(s.attrs["ring_cells_spanned"] for s in spans)
    if not spanned:
        return None
    return 100.0 * sum(s.attrs["ring_cells_live"] for s in spans) / spanned
