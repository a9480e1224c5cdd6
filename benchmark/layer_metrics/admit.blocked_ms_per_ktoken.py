"""What an admission keeps the loop blocked on the device for, a thousand
padded prompt tokens: the median over the window's `admit.readback` spans
(from the return of the admission program's call to its first token on the
host: with nothing else in flight, the program's device time and the
transfer) of their milliseconds over `bucket / 1024` of their `admit` span.
A rate, so that which buckets a seed draws moves it less than it moves
`admit.host_ms_p50`.  A program without the span leaves the metric out."""
import statistics

from benchmark import program_spans


def read(seen):
    window = program_spans.serve_window(seen)
    if not window:
        return None
    by_sid = {s.sid: s for s in window}
    rates = []
    for s in window:
        if s.name != "admit.readback":
            continue
        up = s
        while up is not None and up.name != "admit":
            up = by_sid.get(up.parent)
        if up is not None and up.attrs.get("bucket"):
            rates.append(program_spans.seconds(s) * 1e3
                         / (up.attrs["bucket"] / 1024.0))
    return statistics.median(rates) if rates else None
