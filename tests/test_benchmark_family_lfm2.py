"""The benchmark's own tests of the family `lfm2_moe`
(`benchmark/tests/test_family_lfm2.py`), collected for tier 1 as
`test_benchmark_family_mellum.py` collects the third family's: its counts,
its pins, the cut against the uncut model, and a prefill then decode through
the slot table against the reference on logits.  Its whole rehearsal is collected
by `test_benchmark_family_lfm2_rehearsals.py`, on a worker of its own."""

from benchmark.tests.test_family_lfm2 import *       # noqa: F401,F403

del test_a_whole_rehearsal_is_correct_and_reads_its_metrics     # noqa: F821
