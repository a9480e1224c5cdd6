"""Pipeline parallelism: forward equivalence and training on the 8-dev mesh."""

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.parallel.mesh import make_mesh
from deeplearning4j_tpu.parallel.pipeline import (make_pipeline_train_step,
                                                  pipeline_apply)

D = 16


def _stage(params, x):
    return jnp.tanh(x @ params["W"] + params["b"])


def _stack_params(key, n_stages):
    ks = jax.random.split(key, n_stages)
    return {
        "W": jnp.stack([jax.random.normal(k, (D, D)) * 0.3 for k in ks]),
        "b": jnp.zeros((n_stages, D)),
    }


def _sequential(params, x):
    for s in range(params["W"].shape[0]):
        x = _stage({"W": params["W"][s], "b": params["b"][s]}, x)
    return x


def test_pipeline_matches_sequential():
    mesh = make_mesh({"pp": 4})
    params = _stack_params(jax.random.PRNGKey(0), 4)
    x = jax.random.normal(jax.random.PRNGKey(1), (6, 5, D))  # 6 microbatches
    out = pipeline_apply(_stage, params, x, mesh, axis="pp")
    ref = jnp.stack([_sequential(params, x[i]) for i in range(6)])
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_pipeline_grads_match_sequential():
    mesh = make_mesh({"pp": 4})
    params = _stack_params(jax.random.PRNGKey(2), 4)
    x = jax.random.normal(jax.random.PRNGKey(3), (4, 3, D))
    y = jax.random.normal(jax.random.PRNGKey(4), (4, 3, D))

    def loss_pipe(p):
        return jnp.mean((pipeline_apply(_stage, p, x, mesh) - y) ** 2)

    def loss_seq(p):
        out = jnp.stack([_sequential(p, x[i]) for i in range(4)])
        return jnp.mean((out - y) ** 2)

    g_pipe = jax.jit(jax.grad(loss_pipe))(params)
    g_seq = jax.jit(jax.grad(loss_seq))(params)
    for k in g_pipe:
        np.testing.assert_allclose(np.asarray(g_pipe[k]),
                                   np.asarray(g_seq[k]),
                                   rtol=1e-4, atol=1e-5)


def test_pipeline_training_reduces_loss():
    mesh = make_mesh({"pp": 8})
    params = _stack_params(jax.random.PRNGKey(5), 8)
    x = jax.random.normal(jax.random.PRNGKey(6), (8, 4, D))
    y = 0.5 * x  # learnable target

    step = make_pipeline_train_step(
        _stage, lambda out, tgt: jnp.mean((out - tgt) ** 2), mesh, lr=0.3)
    params, loss0 = step(params, x, y)
    for _ in range(30):
        params, loss = step(params, x, y)
    assert float(loss) < float(loss0) * 0.5, (float(loss0), float(loss))
