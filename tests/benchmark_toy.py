"""The toy family of `benchmark/tests/test_families.py`, as tier 1 rehearses
it.  Its cases rehearse it whole four times (train, serve, and each with a
scaled leaf), and what they show is that a family comes in by files alone:
one block shows it as two do, so `depth` 1 is laid over the original's 2
here, which halves what each rehearsal compiles.  A `benchmark` PR can move
this into the original.  No test file: nothing here is collected."""

from benchmark.tests.test_families import TOY_CONFIG

TOY_CONFIG["depth"] = 1
