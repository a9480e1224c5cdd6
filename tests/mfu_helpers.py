"""What the `test_mfu_*.py` files share.  No test file: nothing here is
collected."""

import jax
import numpy as np


def _assert_tree_bitwise(a, b, where=""):
    la, ta = jax.tree_util.tree_flatten(a)
    lb, tb = jax.tree_util.tree_flatten(b)
    assert ta == tb, f"tree structure mismatch {where}"
    for i, (x, y) in enumerate(zip(la, lb)):
        assert x.dtype == y.dtype and x.shape == y.shape, \
            f"leaf {i} meta mismatch {where}"
        assert np.array_equal(np.asarray(x), np.asarray(y)), \
            f"leaf {i} bits differ {where}"
