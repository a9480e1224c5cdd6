"""The two forms of the held experts' product (`nn/layers/experts.py`: sorted
picks through `jax.lax.ragged_dot`, and every held expert over all the rows
as one batched product) hold each other equal at toy widths in float32, and
the rule that chooses between them from static shapes gives what PERF.md's
table says for every program a cell runs."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.nn.conf import MoESpec
from deeplearning4j_tpu.nn.layers import experts

N, HIDDEN = 24, 16


def spec_of(n_routed, n_held, top_k, first_held=0):
    return MoESpec(n_routed=n_routed, n_held=n_held, hidden=HIDDEN, shared_hidden=0,
                   first_held=first_held, top_k=top_k)


def picks(spec, rows, seed):
    """Distinct experts a row among all the routed ones, and weights that
    sum to 1 a row, as `route` gives them."""
    rng = np.random.default_rng(seed)
    ids = np.stack([rng.permutation(spec.n_routed)[: spec.top_k] for _ in range(rows)])
    w = rng.random((rows, spec.top_k)).astype(np.float32) + 0.1
    return ids.astype(np.int32), w / w.sum(-1, keepdims=True)


# rows x top_k of the third case is 192 picks: the sorted form builds its
# `lax.cond` there (3/8 of the picks are 72, over the 64 it asks for)
FORMS = {
    "every expert held": (spec_of(8, 8, 2), 16, None),
    "a quarter held from the third quarter on": (spec_of(16, 4, 4, first_held=8), 48, None),
    "a row whose picks are all absent": (spec_of(16, 4, 4, first_held=8), 48, [0, 1, 2, 15]),
    "top_k 1": (spec_of(8, 8, 1), 16, None),
}


@pytest.mark.parametrize("case", list(FORMS))
def test_the_batched_form_is_the_sorted_form(case):
    spec, rows, absent = FORMS[case]
    ids, weights = picks(spec, rows, seed=len(case))
    if absent is not None:
        ids[3] = absent
        assert not np.any((ids[3] >= spec.first_held) & (ids[3] < spec.first_held + spec.n_held))
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(3), 3)
    params = {"Wgu": jax.random.normal(k1, (spec.n_held, N, 2 * HIDDEN), jnp.float32),
              "Wd": jax.random.normal(k2, (spec.n_held, HIDDEN, N), jnp.float32)}
    u = jax.random.normal(k3, (rows, N), jnp.float32)
    out = {}
    for name, form in (("sorted", experts._sorted_experts), ("batched", experts._batched_experts)):
        y, counts = jax.jit(lambda p, u, i, w, form=form: form(
            p, spec, jnp.float32, u, i, w))(params, u, ids, weights)
        out[name] = np.asarray(y), np.asarray(counts)
    scale = np.abs(out["sorted"][0]).max()
    np.testing.assert_allclose(out["batched"][0] / scale, out["sorted"][0] / scale, atol=1e-5)
    np.testing.assert_array_equal(out["batched"][1], out["sorted"][1])
    mine = (ids >= spec.first_held) & (ids < spec.first_held + spec.n_held)
    assert out["batched"][1][0] == mine.sum()
    assert out["batched"][1][1] == len(np.unique(ids[mine]))
    if absent is not None:
        np.testing.assert_array_equal(out["batched"][0][3], 0.0)


MELLUM = spec_of(64, 64, 8)             # mellum2-12b-a2.5b-pp8: every expert held
LING = spec_of(512, 128, 8)             # ling-3.0-flash-ep4: rank 0 of 4
# the least rows at which 8 picks of 64 are expected to hit 9 experts of 10
HIT_EDGE = 18


@pytest.mark.parametrize("what,spec,rows,form", [
    ("Mellum decode, 64 slots: 99.98 % expected", MELLUM, 64, "batched"),
    ("Ling decode, 64 slots: 63.5 % expected", LING, 64, "sorted"),
    ("Ling admission, bucket 512", LING, 512, "sorted"),
    ("Ling admission, bucket 1024", LING, 1024, "sorted"),
    ("Mellum admission, a block of 1,024", MELLUM, 1024, "sorted"),
    ("Mellum admission, 8,192", MELLUM, 8192, "sorted"),
    ("the last row count under the ridge", MELLUM, experts.BATCHED_ROWS_MOST, "batched"),
    ("the first over it", MELLUM, experts.BATCHED_ROWS_MOST + 1, "sorted"),
    ("the first row count at the hit share's floor", MELLUM, HIT_EDGE, "batched"),
    ("the last under it", MELLUM, HIT_EDGE - 1, "sorted"),
    ("a quarter held: the routed experts count, not the held", spec_of(16, 4, 4, 8), 16, "batched"),
    ("Command A+ decode, 32 slots on this chip alone: 87.3 % expected",
     spec_of(128, 16, 8), 32, "sorted"),
    ("Command A+ decode, 32 slots on each of 8 ranks: 32 x 8 rows' picks",
     dataclasses.replace(spec_of(128, 16, 8), ranks=8), 32, "batched"),
    ("Command A+ admission, bucket 4,096, on each of 8 ranks",
     dataclasses.replace(spec_of(128, 16, 8), ranks=8), 4096, "sorted"),
])
def test_the_rule_of_the_form(what, spec, rows, form):
    assert experts.experts_form(spec, rows) == form, what


def test_the_rules_constants_are_the_sweeps():
    assert (experts.HIT_SHARE_FLOOR, experts.BATCHED_ROWS_MOST) == (0.9, 128)
    share = lambda rows: 1.0 - (1.0 - 8 / 64) ** rows    # noqa: E731
    assert share(HIT_EDGE - 1) < experts.HIT_SHARE_FLOOR <= share(HIT_EDGE)


@pytest.mark.parametrize("rows,scope", [(16, "experts_batched"), (4, "experts")])
def test_the_scope_says_which_form_ran(rows, scope):
    from deeplearning4j_tpu.nn.conf import LayerType, NeuralNetConfiguration

    conf = NeuralNetConfiguration(layer_type=LayerType.MOE, n_in=N, n_out=N,
                                  layer_spec=spec_of(8, 8, 2))
    params = jax.eval_shape(lambda k: experts.MoELayer.init(k, conf), jax.random.PRNGKey(0))
    text = jax.jit(lambda p, x: experts.MoELayer.apply(p, conf, x)).lower(
        params, jax.ShapeDtypeStruct((rows, N), jnp.float32)).as_text(debug_info=True)
    other = {"experts_batched": "/experts/", "experts": "/experts_batched/"}[scope]
    assert f"/{scope}/" in text and other not in text
    assert ("ragged_dot" in text) == (scope == "experts")
    assert experts.MoELayer.product_form(conf, rows) == {
        "experts_batched": "batched", "experts": "sorted"}[scope]
