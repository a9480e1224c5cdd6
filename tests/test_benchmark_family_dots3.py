"""The benchmark's own tests of the family `dots3_note`
(`benchmark/tests/test_family_dots3.py`, PR 34), collected for tier 1 as
`test_benchmark_family_mellum.py` collects the third family's: its counts,
its pins, the cut against the uncut model, the program at the published
widths and the parent's refusal.  Its whole rehearsals are collected by
`test_benchmark_family_dots3_rehearsals.py` and
`test_benchmark_family_dots3_faults.py`, each on a worker of its own."""

from benchmark.tests.test_family_dots3 import *     # noqa: F401,F403

del test_a_whole_rehearsal_is_correct_and_reads_its_metrics     # noqa: F821
del test_a_ring_written_one_cell_off_is_not_correct             # noqa: F821
del test_an_indexer_that_keeps_half_is_not_correct              # noqa: F821
del test_a_window_one_short_is_not_correct                      # noqa: F821
