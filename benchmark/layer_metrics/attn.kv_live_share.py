"""Of the K/V cells the decode program's whole-state reads covered in the
window, the share that the steps needed: `kv_cells_live` over
`kv_cells_spanned`, summed over the window's `decode` spans.  A program
whose spans carry no such counts (no layer that counts its state in cells)
leaves the metric out."""

from benchmark import program_spans


def read(seen):
    spans = [s for s in program_spans.named(seen, "decode") or ()
             if "kv_cells_spanned" in s.attrs]
    spanned = sum(s.attrs["kv_cells_spanned"] for s in spans)
    if not spanned:
        return None
    return 100.0 * sum(s.attrs["kv_cells_live"] for s in spans) / spanned
