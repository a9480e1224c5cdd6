"""The expert layer that Ling-3.0-flash's language model brought (`moe`: one
chip's held experts of a wider router) against the plain reference of
`benchmark/families/ling3`, at tiny widths on the CPU: the ranks' parts, every
pick computed in both branches, the grouped routing and its bias."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import reference as bench_reference
from deeplearning4j_tpu.nn.layers import experts as experts_mod
from ling3_model import SEED, TIGHT, f32, rows      # noqa: F401  (f32: a fixture)


# --------------------------------------------------------------- (d) experts

def test_the_four_chips_parts_add_up_to_the_uncut_layer(f32):
    """Each rank's routed part, the shared expert counted once, against the
    reference holding every expert."""
    i = f32.layer("moe")
    u = rows((40, f32.sizes["d"]), seed=6)
    whole_cfg = {**f32.cfg, "num_experts": f32.sizes["experts_routed"]}
    key = bench_reference.base_key(SEED)

    def parts(cfg):
        w = f32.ref.layer_weights(cfg, key, i, "moe")
        w = {k: v.astype(jnp.float32) for k, v in w.items()}
        return f32.ref.moe_parts(w, u, f32.ref.sizes(cfg))

    whole, shared = parts(whole_cfg)
    total = shared
    for rank in range(4):
        cfg = {**f32.cfg, "deployment": {"rank": rank}}
        routed, also_shared = parts(cfg)
        np.testing.assert_array_equal(np.asarray(also_shared), np.asarray(shared))
        total = total + routed
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole + shared),
                               atol=TIGHT)


@pytest.mark.parametrize("rank", [0, 3])
@pytest.mark.parametrize("n_rows", [1, 1024])
def test_every_pick_of_a_held_expert_is_computed(f32, n_rows, rank):
    i = f32.layer("moe")
    cfg = {**f32.cfg, "deployment": {"rank": rank}}
    conf = f32.fam.program.build_conf(cfg).conf(i)
    weights = f32.ref.layer_weights(cfg, bench_reference.base_key(SEED), i, "moe")
    params = f32.fam.program.to_program([weights])[0]
    x = rows((n_rows, f32.sizes["d"]), seed=8)
    got, counts = jax.jit(lambda p, v: experts_mod.MoELayer.apply(p, conf, v))(params, x)
    want = f32.ref.moe(weights, x[None], f32.ref.sizes(cfg))[0]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=TIGHT)
    spec = conf.layer_spec
    u = f32.ref.rms_norm(x, weights["ln"], spec.eps)
    ids, _ = f32.ref.route(jax.nn.sigmoid(u @ weights["Wr"]), weights["b"],
                           f32.ref.sizes(cfg))
    mine = (np.asarray(ids) >= spec.first_held) & (
        np.asarray(ids) < spec.first_held + spec.n_held)
    assert int(counts[0]) == mine.sum()                 # none dropped
    assert int(counts[1]) == len(set(np.asarray(ids)[mine].tolist()))


def test_uneven_routing_takes_the_wide_branch_and_drops_nothing(f32):
    """All the picks on this rank's experts: more than the 3/8 of the rows
    that the narrow branch holds."""
    i = f32.layer("moe")
    spec = f32.conf.conf(i).layer_spec
    u = rows((64, f32.sizes["d"]), seed=2)
    ids = jnp.tile(jnp.arange(spec.top_k, dtype=jnp.int32), (64, 1))
    w = jnp.full((64, spec.top_k), 0.125, jnp.float32)
    p = f32.params[i]
    got, counts = jax.jit(lambda q: experts_mod.held_experts(
        q, spec, jnp.float32, u, ids, w))(p)
    want = jax.jit(lambda q: sum(
        0.125 * experts_mod.swiglu(u, q["Wgu"][e], q["Wd"][e], jnp.float32)
        for e in range(spec.top_k)))(p)
    assert int(counts[0]) == 64 * spec.top_k
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=TIGHT)


def test_at_most_topk_group_groups_are_chosen(f32):
    spec = f32.conf.conf(f32.layer("moe")).layer_spec
    scores = jax.nn.sigmoid(rows((256, spec.n_routed), seed=1))
    ids, w = experts_mod.route(scores, jnp.zeros((spec.n_routed,)), spec)
    groups = np.asarray(ids) // (spec.n_routed // spec.n_group)
    assert max(len(set(g)) for g in groups.tolist()) <= spec.topk_group
    assert all(len(set(r)) == spec.top_k for r in np.asarray(ids).tolist())
    np.testing.assert_allclose(np.asarray(w).sum(-1), spec.routed_scaling, rtol=1e-5)


def test_the_routers_bias_moves_the_choice_and_not_the_weights(f32):
    spec = f32.conf.conf(f32.layer("moe")).layer_spec
    scores = jax.nn.sigmoid(rows((64, spec.n_routed), seed=2))
    plain, _ = experts_mod.route(scores, jnp.zeros((spec.n_routed,)), spec)
    bias = jnp.zeros((spec.n_routed,)).at[5].set(10.0)
    ids, w = experts_mod.route(scores, bias, spec)
    assert (np.asarray(ids) == 5).any(axis=1).all()          # chosen everywhere
    assert not (np.asarray(plain) == 5).any(axis=1).all()
    picked = np.take_along_axis(np.asarray(scores), np.asarray(ids), axis=1)
    np.testing.assert_allclose(                             # weights: scores alone
        np.asarray(w), spec.routed_scaling * picked / picked.sum(-1, keepdims=True),
        rtol=1e-5)
