"""The benchmark's own tests of the family `mellum`
(`benchmark/tests/test_family_mellum.py`, PR 32), collected for tier 1 as
`test_benchmark_family_ling3.py` collects the second family's: its counts, its
pins, the cut against the uncut model, and the router's precision shown in
float32.  Its whole rehearsals are collected by
`test_benchmark_family_mellum_rehearsals.py` and
`test_benchmark_family_mellum_faults.py`, each on a worker of its own."""

from benchmark.tests.test_family_mellum import *     # noqa: F401,F403

del test_a_whole_rehearsal_is_correct_and_reads_its_metrics     # noqa: F821
del test_a_ring_written_one_cell_off_is_not_correct             # noqa: F821
del test_a_window_one_short_is_not_correct                      # noqa: F821
del test_yarn_left_off_the_full_layers_is_not_correct           # noqa: F821
