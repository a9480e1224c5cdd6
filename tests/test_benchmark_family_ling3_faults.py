"""The faulted rehearsals of the family `ling3`'s cell
(`benchmark/tests/test_family_ling3.py`): a token altered where it is
produced, a state that never advances.  Collected apart from
`test_benchmark_family_ling3_rehearsals.py` so that they run on a worker of
their own."""

from benchmark.tests.test_family_ling3 import (     # noqa: F401
    test_a_token_altered_where_it_is_produced_is_not_correct,
    test_a_state_that_never_advances_is_not_correct)
