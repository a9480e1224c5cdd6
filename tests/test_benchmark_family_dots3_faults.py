"""Two faulted rehearsals of the family `dots3_note`'s cell
(`benchmark/tests/test_family_dots3.py`): an indexer that keeps half of what
the model keeps, a window one position short.  Collected apart from
`test_benchmark_family_dots3_rehearsals.py` so that they run on a worker of
their own."""

from benchmark.tests.test_family_dots3 import (    # noqa: F401
    rehearsal_limits,
    test_an_indexer_that_keeps_half_is_not_correct,
    test_a_window_one_short_is_not_correct)
