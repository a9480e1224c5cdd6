"""Device time an admission costs outside its prefill: every module of the
traced window that is neither a decode nor a prefill program (the fresh
row's zeros, the scatters into the slot table, the slices), over the
admissions of that window."""
from benchmark import program_spans


def read(seen):
    admitted = seen["counters"].get("traced_admitted")
    other = program_spans.other_module_seconds(
        seen, (program_spans.DECODE, program_spans.PREFILL))
    if other is None or not admitted:
        return None
    return other / admitted * 1e3
