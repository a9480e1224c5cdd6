"""The decode protocol of `nn/layers/__init__.py`, layer type by layer type:
every type a generative stack may hold says what it keeps between tokens
(`init_state`, `prefill`, `decode_step`, `CARRY`), and `nn/decode.py` walks a
stack by calling that and nothing else.  The last case puts a layer class of
its own into the registry and runs it through the walkers untouched."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.nn import decode, layers
from deeplearning4j_tpu.nn.conf import (Activation, GQASpec, KDASpec, LayerType,
                                        LossFunction, MLASpec, MoESpec,
                                        MultiLayerConfiguration,
                                        NeuralNetConfiguration, SwiGLUSpec)
from deeplearning4j_tpu.nn.layers import get_layer
from deeplearning4j_tpu.nn.multilayer import init_params

N = 8           # width of the stream, and the one-hot vocabulary
MAX_SEQ = 8
BASE = NeuralNetConfiguration(n_in=N, n_out=N, weight_init="normalized",
                              activation=Activation.TANH)
SPECS = {
    LayerType.ATTENTION: dict(n_heads=2, causal=True),
    LayerType.KDA: dict(layer_spec=KDASpec(n_heads=2, head_dim=4)),
    LayerType.MLA: dict(layer_spec=MLASpec(
        n_heads=2, kv_lora_rank=4, qk_nope_head_dim=4, qk_rope_head_dim=2,
        v_head_dim=4)),
    # a ring of 4 cells: the two decoded tokens wrap into cells 0 and 1
    LayerType.GQA: dict(layer_spec=GQASpec(n_heads=4, n_kv_heads=2, head_dim=4,
                                           window=4, qk_norm=True)),
    LayerType.SWIGLU: dict(layer_spec=SwiGLUSpec(hidden=16)),
    LayerType.MOE: dict(layer_spec=MoESpec(
        n_routed=8, n_held=4, hidden=8, shared_hidden=8, top_k=2, n_group=2)),
}


def layer_conf(kind):
    return BASE.replace(layer_type=kind, **SPECS.get(kind, {}))


def stack(kind):
    """One hidden layer of `kind` over one-hot ids, and the head."""
    return MultiLayerConfiguration(confs=(
        layer_conf(kind),
        BASE.replace(layer_type=LayerType.OUTPUT, activation=Activation.SOFTMAX,
                     loss_function=LossFunction.MCXENT)))


def greedy(logp, keys, temps):
    return jnp.argmax(logp, axis=-1).astype(jnp.int32), keys


def row_of(state, r):
    return jax.tree_util.tree_map(lambda a: np.asarray(a[r]), state)


def same(a, b) -> bool:
    return all(np.array_equal(x, y) for x, y in zip(
        jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)))


def cell0(leaf):
    """Position 0's cell of one row's table: [positions, n], or with the K/V
    heads before the positions [G, positions, h]."""
    return leaf[:, :1] if leaf.ndim == 3 else leaf[:1]


def block_then_step(conf, params):
    """Two rows from a zero state: a block of three steps in which row 1's
    budget is one token, and one plain step.  Returns (the block's tokens,
    row 1 after the block, row 1 after the step)."""
    tok, pos = jnp.asarray([1, 2], jnp.int32), jnp.zeros((2,), jnp.int32)
    keys = jnp.zeros((2, 2), jnp.uint32)
    # traced anew at every call: a case changes a class's CARRY in between
    toks, _, _, *_, after = jax.jit(lambda p, s: decode.decode_block(
        conf, p, s, tok, pos, keys, jnp.zeros((2,)),
        jnp.asarray([3, 1], jnp.int32), 3, greedy))(
            params, decode.init_state(conf, 2, MAX_SEQ))
    _, stepped = jax.jit(lambda p, s: decode.decode_step(conf, p, s, tok, pos))(
        params, decode.init_state(conf, 2, MAX_SEQ))
    return np.asarray(toks), row_of(after, 1), row_of(stepped, 1)


@pytest.mark.parametrize("kind", decode.GENERATIVE_HIDDEN, ids=str)
def test_a_layer_type_keeps_the_protocol(kind, monkeypatch):
    impl, c = get_layer(kind), layer_conf(kind)
    params = impl.init(jax.random.PRNGKey(3), c)
    x = 0.5 * jax.random.normal(jax.random.PRNGKey(4), (2, 6, N), jnp.float32)
    whole = jax.jit(lambda p, v: impl.forward(p, c, v))(params, x)

    # a prefix through `prefill`, the rest a token at a time: the whole
    length = jnp.full((2,), 4, jnp.int32)
    hidden, state = jax.jit(lambda p, v, s, n: impl.prefill(p, c, v, s, n))(
        params, x[:, :4], impl.init_state(c, 2, MAX_SEQ), length)
    np.testing.assert_allclose(hidden, whole[:, :4], atol=2e-5)
    one = jax.jit(lambda p, v, s, q: impl.decode_step(p, c, v, s, q))
    for t in (4, 5):
        h, state = one(params, x[:, t], state, jnp.full((2,), t, jnp.int32))
        np.testing.assert_allclose(h, whole[:, t], atol=2e-5)

    # a slot's fresh row is what the class says a state of one row is
    conf = stack(kind)
    table = decode.init_state(conf, 3, MAX_SEQ)
    zero, fresh = decode.zero_row(table), decode.init_state(conf, 1, MAX_SEQ)
    assert (jax.tree_util.tree_structure(zero)
            == jax.tree_util.tree_structure(fresh))
    for a, b in zip(jax.tree_util.tree_leaves(zero),
                    jax.tree_util.tree_leaves(fresh)):
        assert a.shape == b.shape and a.dtype == b.dtype
        assert np.array_equal(a, b)
    assert sorted(table[0]) == sorted(impl.init_state(c, 3, MAX_SEQ))

    # a finished row under `decode_block` keeps its state iff CARRY
    net_params = init_params(conf, jax.random.PRNGKey(5))
    toks, held, stepped = block_then_step(conf, net_params)
    assert toks[0, 1] >= 0 and list(toks[1:, 1]) == [decode.BLOCK_SENTINEL] * 2
    if impl.CARRY:
        assert held[0] and same(held, stepped)
        monkeypatch.setattr(impl, "CARRY", False)       # and only for that
        assert not same(block_then_step(conf, net_params)[1], stepped)
    elif held[0]:
        # a table: the row goes on writing the cell at its frozen position
        assert not same(held, stepped)
        assert all(np.array_equal(cell0(a), cell0(b)) for a, b in zip(
            jax.tree_util.tree_leaves(held), jax.tree_util.tree_leaves(stepped)))
    else:
        assert held[0] == {} and stepped[0] == {}


class RunningMean:
    """A layer type of this file: the stream plus the mean of all it has
    seen, which is its state (`{"sum": [B, n], "seen": [B, 1]}`, a carry).
    It cannot page and cannot be verified in a chunk."""

    CARRY = True

    @staticmethod
    def init(key, conf):
        return {"w": jnp.full((conf.n_in,), 0.5, jnp.float32)}

    @staticmethod
    def init_state(conf, batch, max_seq):
        return {"sum": jnp.zeros((batch, conf.n_in), jnp.float32),
                "seen": jnp.zeros((batch, 1), jnp.float32)}

    @staticmethod
    def forward(params, conf, x, key=None, training=False):
        seen = jnp.arange(1, x.shape[1] + 1, dtype=jnp.float32)[None, :, None]
        return x + params["w"] * jnp.cumsum(x, axis=1) / seen

    @staticmethod
    def prefill(params, conf, x, state, length):
        real = (jnp.arange(x.shape[1])[None, :] < length[:, None])[..., None]
        total = state["sum"] + jnp.sum(jnp.where(real, x, 0.0), axis=1)
        return (RunningMean.forward(params, conf, x),
                {"sum": total, "seen": state["seen"] + length[:, None]})

    @staticmethod
    def decode_step(params, conf, x, state, pos):
        total, seen = state["sum"] + x, state["seen"] + 1.0
        return x + params["w"] * total / seen, {"sum": total, "seen": seen}


def test_a_new_kind_of_state_is_a_layer_file_and_a_registry_line(monkeypatch):
    """Registered under a type the walkers already admit, a class they have
    never seen goes through `init_state`, `prefill`, `decode_step` and
    `decode_block`; nothing of `nn/decode.py` names it."""
    kind = LayerType.TRANSFORMER_FFN
    monkeypatch.setitem(layers._REGISTRY, kind, RunningMean)
    conf = stack(kind)
    params = init_params(conf, jax.random.PRNGKey(6))
    state = decode.init_state(conf, 2, MAX_SEQ)
    assert sorted(state[0]) == ["seen", "sum"] and state[1] == {}

    ids = jnp.asarray([[1, 5, 2, 7, 3, 0], [4, 4, 6, 1, 0, 2]], jnp.int32)
    x = jax.nn.one_hot(ids, N, dtype=jnp.float32)
    head = get_layer(LayerType.OUTPUT)
    want = jnp.log(jnp.clip(head.forward(
        params[1], conf.conf(1),
        RunningMean.forward(params[0], conf.conf(0), x).reshape(12, N)),
        1e-9, 1.0)).reshape(2, 6, N)

    # a prompt padded to its bucket of 5, rows of 4 and 3 real tokens
    length = jnp.asarray([4, 3], jnp.int32)
    logp, state = decode.prefill(conf, params, state, ids[:, :5], length)
    np.testing.assert_allclose(logp, want[jnp.arange(2), length - 1], atol=1e-5)
    np.testing.assert_allclose(state[0]["seen"][:, 0], [4.0, 3.0])
    logp, state = decode.decode_step(
        conf, params, state, ids[jnp.arange(2), length], length)
    np.testing.assert_allclose(logp, want[jnp.arange(2), length], atol=1e-5)

    toks, held, stepped = block_then_step(conf, params)
    assert list(toks[1:, 1]) == [decode.BLOCK_SENTINEL] * 2
    assert same(held, stepped) and float(held[0]["seen"][0]) == 1.0

    # what the class does not declare, the walkers refuse by its type's name
    assert decode.dense_only(conf) == ["transformer_ffn"]
    assert not decode.has_experts(conf)
    with pytest.raises(ValueError, match="dense slot table only"):
        decode.init_paged_state(conf, 2, 4, 4)
    with pytest.raises(ValueError, match="a verify chunk cannot hold"):
        decode.verify_chunk(conf, params, state, ids[:, :2], length)
