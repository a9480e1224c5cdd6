"""Expert-parallel MoE vs the dense single-device reference."""

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.parallel.expert import (init_moe_params, moe_ffn,
                                                moe_ffn_dense)
from deeplearning4j_tpu.parallel.mesh import make_mesh

T, D, H, E = 64, 8, 16, 8


def _setup(seed=0):
    kp, kx = jax.random.split(jax.random.PRNGKey(seed))
    params = init_moe_params(kp, D, H, E)
    x = jax.random.normal(kx, (T, D), jnp.float32)
    return params, x


def test_dense_moe_routes_and_transforms():
    params, x = _setup()
    y, aux = jax.jit(lambda p, v: moe_ffn_dense(p, v, capacity_factor=8.0))(
        params, x)
    assert y.shape == x.shape
    assert float(aux) > 0
    assert not np.allclose(np.asarray(y), np.asarray(x))  # experts acted


def test_ep_matches_dense_with_ample_capacity():
    mesh = make_mesh({"ep": 8})
    params, x = _setup(1)
    # capacity high enough that neither variant drops any token
    y_dense, aux_dense = jax.jit(
        lambda p, v: moe_ffn_dense(p, v, capacity_factor=float(E)))(params, x)
    y_ep, aux_ep = jax.jit(
        lambda p, v: moe_ffn(p, v, mesh, capacity_factor=float(E)))(params, x)
    np.testing.assert_allclose(np.asarray(y_ep), np.asarray(y_dense),
                               rtol=2e-4, atol=2e-4)
    # Aux loss must equal the DENSE global statistic, not a mean of
    # per-shard products (the r3 MULTICHIP failure mode).
    np.testing.assert_allclose(float(aux_ep), float(aux_dense), rtol=1e-5)


def test_ep_full_loss_and_grads_match_dense():
    """(y, aux) AND router/W1/W2/b1/b2 grads must match dense at 1e-4."""
    mesh = make_mesh({"ep": 8})
    params, x = _setup(4)

    def make_loss(fn):
        def loss(p):
            y, aux = fn(p)
            return jnp.mean(y ** 2) + 0.01 * aux
        return loss

    loss_ep = make_loss(lambda p: moe_ffn(p, x, mesh,
                                          capacity_factor=float(E)))
    loss_de = make_loss(lambda p: moe_ffn_dense(p, x,
                                                capacity_factor=float(E)))
    v_ep, g_ep = jax.jit(jax.value_and_grad(loss_ep))(params)
    v_de, g_de = jax.jit(jax.value_and_grad(loss_de))(params)
    np.testing.assert_allclose(float(v_ep), float(v_de), rtol=1e-4)
    for k in ("router", "W1", "b1", "W2", "b2"):
        np.testing.assert_allclose(
            np.asarray(g_ep[k]), np.asarray(g_de[k]),
            rtol=1e-4, atol=1e-6, err_msg=f"grad mismatch for {k}")


def test_ep_capacity_drops_fall_through_residual():
    mesh = make_mesh({"ep": 8})
    params, x = _setup(2)
    # capacity 1 forces drops: dropped tokens must equal their input
    y, _ = jax.jit(lambda p, v: moe_ffn(p, v, mesh, capacity_factor=0.01))(
        params, x)
    diff = np.abs(np.asarray(y) - np.asarray(x)).sum(axis=1)
    assert (diff < 1e-6).any(), "expected some tokens to ride the residual"


def test_ep_grads_flow_and_aux_loss_balances():
    mesh = make_mesh({"ep": 8})
    params, x = _setup(3)

    def loss(p):
        y, aux = moe_ffn(p, x, mesh, capacity_factor=float(E))
        return jnp.mean(y ** 2) + 0.01 * aux

    g = jax.jit(jax.grad(loss))(params)
    for k in ("router", "W1", "W2"):
        assert np.isfinite(np.asarray(g[k])).all()
        assert float(jnp.abs(g[k]).sum()) > 0, f"zero grad for {k}"
