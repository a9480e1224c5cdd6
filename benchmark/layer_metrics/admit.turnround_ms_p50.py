"""What one turn-round at an admission costs the device: over the window's
runs of admissions (the `admit` spans of the loop's thread between two
`decode` spans), the median of the run's `starved_ns` summed with that of
the `decode` span that takes the loop up again after it.  The first is the
last readback's delivery, the releases and the admission's launch (cause
`admit`); the second the first token's delivery, the schedule and a decode
step launched from the host's arrays (cause `restart`).  A program whose
spans carry no `starved_ns` leaves the metric out."""
import statistics

from benchmark import program_spans


def read(seen):
    loop = program_spans.loop_thread(seen)
    if not loop or not any("starved_ns" in s.attrs for s in loop):
        return None
    loop = sorted(loop, key=lambda s: s.start_ns)
    runs, run = [], None        # None until the window's first `decode` span
    for s in loop:
        if s.name == "admit":
            if run is not None:
                run.append(s)
        elif s.name == "decode":
            if run:
                runs.append(sum(a.attrs.get("starved_ns", 0)
                                for a in run + [s]))
            run = []
    if not runs:
        return None
    return statistics.median(runs) / 1e6
