"""The benchmark's own tests of the family `ling3`
(`benchmark/tests/test_family_ling3.py`, PR 28), collected for tier 1 as
`test_benchmark_family_gpt2.py` collects the first family's: its counts and
its pins.  Its three whole rehearsals are collected by
`test_benchmark_family_ling3_rehearsals.py` and
`test_benchmark_family_ling3_faults.py`, each on a worker of its own."""

from benchmark.tests.test_family_ling3 import *      # noqa: F401,F403

del test_a_whole_rehearsal_is_correct_and_reads_its_metrics     # noqa: F821
del test_a_token_altered_where_it_is_produced_is_not_correct    # noqa: F821
del test_a_state_that_never_advances_is_not_correct             # noqa: F821
