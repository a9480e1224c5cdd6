"""Grouped-query attention's two options of Qwen3-Next's full layers
(`GQASpec.out_gate`, `GQASpec.rotary_dim`) and the expert layer's gated
shared expert (`MoESpec.shared_gate`), at toy widths on the CPU in float32:
the layer against the plain reference's gated attention
(`benchmark/families/qwen3_next/reference.py::attention`), a prefill then
decode through the table against the whole forward, rotary over part of a
head, and a conf without the options serialising as it did before them.
The tolerance is float32's: a block of queries and the decode step's read
sum in another order than the reference's whole scores."""

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.families.qwen3_next import reference
from deeplearning4j_tpu.nn.conf import (GQASpec, LayerType, MoESpec,
                                        NeuralNetConfiguration)
from deeplearning4j_tpu.nn.layers.gqa import GQALayer

N = 32
SPEC = GQASpec(n_heads=4, n_kv_heads=2, head_dim=16, rope_theta=1e4, qk_norm=True,
               rotary_dim=4, out_gate=True)
CONF = NeuralNetConfiguration(layer_type=LayerType.GQA, n_in=N, n_out=N,
                              weight_init="normalized", layer_spec=SPEC)
TIGHT = 2e-5


def params(seed: int = 0):
    p = GQALayer.init(jax.random.PRNGKey(seed), CONF)
    ks = jax.random.split(jax.random.PRNGKey(seed + 1), 3)
    return dict(p, ln=1.0 + 0.1 * jax.random.normal(ks[0], (N,)),
                q_norm=1.0 + 0.1 * jax.random.normal(ks[1], (16,)),
                k_norm=1.0 + 0.1 * jax.random.normal(ks[2], (16,)))


def test_the_gated_layer_is_the_references_and_decodes_as_it_prefills():
    """The layer (a query and a gate of 16 a head side by side in Wq) over
    20 tokens is the reference's gated attention; a prompt of 13 then 7
    tokens decoded through the table of 24 cells is the same."""
    p = params()
    assert p["Wq"].shape == (N, 4 * 2 * 16)
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 20, N))
    s = {"heads": 4, "kv_heads": 2, "head_dim": 16, "rotary_dim": 4, "theta": 1e4,
         "eps": 1e-6}
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda p, x: reference.attention(p, x, s))(p, x)
    whole = jax.jit(lambda p, x: GQALayer.forward(p, CONF, x))(p, x)
    np.testing.assert_allclose(whole, want, atol=TIGHT)

    length = jnp.asarray([13, 13], jnp.int32)
    hidden, state = jax.jit(lambda p, x, s, n: GQALayer.prefill(p, CONF, x, s, n))(
        p, x[:, :13], GQALayer.init_state(CONF, 2, 24), length)
    np.testing.assert_allclose(hidden, want[:, :13], atol=TIGHT)
    step = jax.jit(lambda p, x, s, q: GQALayer.decode_step(p, CONF, x, s, q))
    for t in range(13, 20):
        h, state = step(p, x[:, t], state, jnp.full((2,), t, jnp.int32))
        np.testing.assert_allclose(h, want[:, t], atol=TIGHT)


@pytest.mark.parametrize("option", ["out_gate", "rotary_dim"])
def test_each_option_changes_the_layer(option):
    """Taking either option away changes what the attention adds to the
    stream by a tenth of it at least: the gate is not the identity, and
    rotating the whole head is not rotating its first 4 dimensions."""
    p, x = params(), jax.random.normal(jax.random.PRNGKey(6), (1, 12, N))
    off = {"out_gate": False, "rotary_dim": 0}[option]
    plain = CONF.replace(layer_spec=dataclasses.replace(SPEC, **{option: off}))
    if option == "out_gate":
        p_plain = dict(p, Wq=p["Wq"].reshape(N, 4, 32)[..., :16].reshape(N, 64))
    else:
        p_plain = p
    a = jax.jit(lambda p, x: GQALayer.forward(p, CONF, x))(p, x) - x    # the attention's part
    b = jax.jit(lambda p, x: GQALayer.forward(p, plain, x))(p_plain, x) - x
    assert np.max(np.abs(np.asarray(a - b))) > 0.1 * np.max(np.abs(np.asarray(a)))


def test_partial_rotary_turns_the_first_dimensions_alone():
    """Under `rotary_dim` 4 a query's dimensions 4 to 15 are the same at
    every position, and its first 4 turn as a rotary of 4 dimensions."""
    from deeplearning4j_tpu.nn.layers.rms import rope

    p = params()
    u = jnp.broadcast_to(jax.random.normal(jax.random.PRNGKey(2), (N,)), (6, N))
    pos = jnp.arange(6) * 37
    q, k, _, gate = jax.jit(lambda p, u, pos: GQALayer._project(p, CONF, u, pos))(p, u, pos)
    q = q.reshape(6, 4, 16)
    np.testing.assert_allclose(q[:, :, 4:], jnp.broadcast_to(q[:1, :, 4:], q[:, :, 4:].shape),
                               atol=1e-6)
    q0 = jnp.broadcast_to(q[:1, :, :4], (6, 4, 4))
    np.testing.assert_allclose(q[:, :, :4], rope(q0, pos[:, None], 1e4), atol=1e-5)
    assert gate.shape == (6, 64) and k.shape == (6, 2, 16)


def test_a_conf_without_the_options_serialises_as_before():
    """At their defaults `rotary_dim`, `out_gate` and `shared_gate` are left
    out of a conf's JSON (its fingerprint, and every cache key made from it,
    are what they were before the fields); set, they round-trip."""
    plain = NeuralNetConfiguration(layer_type=LayerType.GQA, n_in=N, n_out=N,
                                   layer_spec=GQASpec(n_heads=4, n_kv_heads=2, head_dim=16))
    experts = NeuralNetConfiguration(layer_type=LayerType.MOE, n_in=N, n_out=N,
                                     layer_spec=MoESpec(n_routed=8, n_held=4, hidden=8,
                                                        shared_hidden=8))
    for conf, keys in ((plain, ("rotary_dim", "out_gate")), (experts, ("shared_gate",))):
        spec = json.loads(conf.to_json())["layer_spec"]
        assert not set(keys) & set(spec)
        assert NeuralNetConfiguration.from_json(conf.to_json()) == conf
    gated = experts.replace(layer_spec=dataclasses.replace(experts.layer_spec,
                                                           shared_gate=True))
    for conf in (CONF, gated):
        assert NeuralNetConfiguration.from_json(conf.to_json()) == conf
    assert json.loads(CONF.to_json())["layer_spec"]["rotary_dim"] == 4
    assert json.loads(gated.to_json())["layer_spec"]["shared_gate"] is True
