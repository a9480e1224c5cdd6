"""The whole rehearsal of the family `ling3`'s cell
(`benchmark/tests/test_family_ling3.py`), collected apart from
`test_benchmark_family_ling3.py` so that it runs on a worker of its own; the
two faulted rehearsals are in `test_benchmark_family_ling3_faults.py`."""

from benchmark.tests.test_family_ling3 import (     # noqa: F401
    test_a_whole_rehearsal_is_correct_and_reads_its_metrics)
