"""The toy family's faulted rehearsals of `benchmark/tests/test_families.py`
(an adapter that scales one leaf, training and serving), collected apart from
`test_benchmark_family_gpt2.py` so that they run on a worker of their own."""

import benchmark_toy    # noqa: F401  (the toy at one block)
from benchmark.tests.test_families import (     # noqa: F401  (toy: a fixture)
    test_an_adapter_that_scales_one_leaf_is_not_correct, toy)
