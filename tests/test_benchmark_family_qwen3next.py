"""The benchmark's own tests of the family `qwen3_next`
(`benchmark/tests/test_family_qwen3next.py`), collected for tier 1 as
`test_benchmark_family_cohere2.py` collects the seventh family's: its
counts, its pins, the cut against the uncut model, the eight ranks' shares
of an expert layer, a prefill then decode through the delta-rule states and
the table against the reference on logits with the faults it catches, and
the reader of `decode.carry_read_mb_p50`.  Its whole rehearsal is collected
by `test_benchmark_family_qwen3next_rehearsals.py`, on a worker of its own."""

from benchmark.tests.test_family_qwen3next import *     # noqa: F401,F403

del test_a_whole_rehearsal_is_correct_and_reads_its_metrics     # noqa: F821
