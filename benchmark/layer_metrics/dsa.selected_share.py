"""Of the cached positions that the indexed layers' decode steps could have
attended to in the window, the share they picked: `dsa_cells_selected` over
`dsa_cells_live`, summed over the window's `decode` spans.  100 % means the
selection was idle (no row past the number of positions a layer picks).
The counts come from the rows' positions on the host and the number of
positions the configuration has a layer pick: the share describes the traffic and says how much a sparse
read can save; no change to the program moves it, and whether the read is
sparse is `attn.kv_live_share`'s to say (over the layers' `kv_cells_read`).
A program whose spans carry no such counts (no layer with an indexer) leaves
the metric out."""

from benchmark import program_spans


def read(seen):
    spans = [s for s in program_spans.named(seen, "decode") or ()
             if "dsa_cells_live" in s.attrs]
    live = sum(s.attrs["dsa_cells_live"] for s in spans)
    if not live:
        return None
    return 100.0 * sum(s.attrs["dsa_cells_selected"] for s in spans) / live
