"""The whole rehearsal of the family `cohere2_moe`'s cell
(`benchmark/tests/test_family_cohere2.py`), collected apart from
`test_benchmark_family_cohere2.py` so that it runs on a worker of its own."""

from benchmark.tests.test_family_cohere2 import (    # noqa: F401
    rehearsal_limits,
    test_a_whole_rehearsal_is_correct_and_reads_its_metrics)
