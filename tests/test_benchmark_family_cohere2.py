"""The benchmark's own tests of the family `cohere2_moe`
(`benchmark/tests/test_family_cohere2.py`), collected for tier 1 as
`test_benchmark_family_lfm2.py` collects the sixth family's: its counts, its
pins, the cut against the uncut model, the eight ranks' shares of an expert
layer, a prefill then decode through the rings and the table against the
reference on logits, and the reader of `attn.ring_live_share`.  Its whole rehearsal is collected by
`test_benchmark_family_cohere2_rehearsals.py`, on a worker of its own."""

from benchmark.tests.test_family_cohere2 import *       # noqa: F401,F403

del test_a_whole_rehearsal_is_correct_and_reads_its_metrics     # noqa: F821
