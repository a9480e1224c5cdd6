"""Two faulted rehearsals of the family `mellum`'s cell
(`benchmark/tests/test_family_mellum.py`): a window one position short, YaRN
left off the full layers.  Collected apart from
`test_benchmark_family_mellum_rehearsals.py` so that they run on a worker of
their own."""

from benchmark.tests.test_family_mellum import (    # noqa: F401
    rehearsal_limits,
    test_a_window_one_short_is_not_correct,
    test_yarn_left_off_the_full_layers_is_not_correct)
