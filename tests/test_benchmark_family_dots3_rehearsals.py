"""The whole rehearsal of the family `dots3_note`'s cell
(`benchmark/tests/test_family_dots3.py`) and the first of its faulted ones,
collected apart from `test_benchmark_family_dots3.py` so that they run on a
worker of their own; the other two faulted rehearsals are in
`test_benchmark_family_dots3_faults.py`."""

from benchmark.tests.test_family_dots3 import (    # noqa: F401
    rehearsal_limits,
    test_a_whole_rehearsal_is_correct_and_reads_its_metrics,
    test_a_ring_written_one_cell_off_is_not_correct)
