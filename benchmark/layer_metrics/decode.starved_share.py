"""Share of the serving window in which the loop had no program on the
device and work to give it: the `starved_ns` of the loop's top-level spans
whose `starved_cause` is the host's (`admit`: the next program was an
admission's; `restart`: the first decode step after one, from the host's
arrays; `sync`: a step of a loop that reads every step back before the
next), each cause's count times its median (`program_spans.share` says why
not the sums), over the window's length, first submit to last.  `empty`
(no stream live, none pending) is the traffic's and is left out.  This is
the time the chip waits for the host; `decode.host_overhead_share` is the
host's busy share of a step, most of it hidden behind the step in flight.
A program whose spans carry no `starved_ns` leaves the metric out."""
import statistics

from benchmark import program_spans

HOSTS = ("admit", "restart", "sync")


def read(seen):
    window = program_spans.serve_window(seen)
    if not window or not any("starved_ns" in s.attrs for s in window):
        return None
    by_cause = {}
    for s in window:
        if s.attrs.get("starved_cause") in HOSTS:
            by_cause.setdefault(s.attrs["starved_cause"], []).append(
                s.attrs["starved_ns"])
    # the window's ends, as `serve_window` finds them
    took = sorted(program_spans.submits(
        s for s in program_spans.record(seen)
        if s.name == "admit").values())[-seen["counters"]["requests"]:]
    length = took[-1] - took[0]
    if length <= 0:
        return None
    typical = sum(len(v) * statistics.median(v) for v in by_cause.values())
    return 100.0 * typical / length
