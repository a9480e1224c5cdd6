"""The whole rehearsal of the family `qwen3_next`'s cell
(`benchmark/tests/test_family_qwen3next.py`), collected apart from
`test_benchmark_family_qwen3next.py` so that it runs on a worker of its own."""

from benchmark.tests.test_family_qwen3next import (    # noqa: F401
    rehearsal_limits,
    test_a_whole_rehearsal_is_correct_and_reads_its_metrics)
