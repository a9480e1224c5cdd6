"""A generation program takes its weight operands in the compute dtype, cast
once a params tree (`nn.decode.operand_tree`, `InferCache._operand_params`):
GPT-2 at the `cerebras-gpt-1.3b` rehearsal's sizes, float32 masters and
bfloat16 products, gives the masters' logits and tokens bit for bit from
half the bytes; the sparse families, whose trees are bfloat16 already, are
handed their own tree."""

import weakref

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import families, program, run
from deeplearning4j_tpu.nn import decode
from deeplearning4j_tpu.nn.layers import get_layer
from deeplearning4j_tpu.nn.layers.base import DenseLayer
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.optimize.infer_cache import InferCache
from deeplearning4j_tpu.serving.batcher import ContinuousBatcher

SEED = 2305843009213693951      # larger than 32 signed bits hold
NARROW = {"Wqkv", "Wo", "W1", "W2"}
SPARSE = ["serve-ling3-decode-64", "serve-mellum2-decode-64", "serve-dots3-long-64",
          "serve-lfm2-rag-64", "serve-commanda-long-32"]


def _cell(workload):
    cfg = run.load_cell(workload, rehearse=True).cfg
    return cfg, families.of(cfg).program.build_conf(cfg)


@pytest.fixture(scope="module")
def gpt2():
    cfg, conf = _cell("serve-1b3-decode")
    return conf, program.program_weights(cfg, SEED)


def _declared(conf, params) -> list:
    """The leaves of `params` that their layers declare operands."""
    out = []
    for i, layer in enumerate(params):
        for path in getattr(get_layer(conf.conf(i).layer_type), "OPERANDS", ()):
            node = layer
            for key in ((path,) if isinstance(path, str) else path):
                node = node.get(key) if isinstance(node, dict) else None
            if node is not None:
                out.append(node)
    return out


def _greedy(conf, params, prompts, n_new, max_seq=64):
    """Prompts -> (the logits of every step, the greedy tokens, the state)
    through `decode.prefill` and `decode.decode_step` in one batch."""
    width = max(len(p) for p in prompts)
    ids = np.zeros((len(prompts), width), np.int32)
    for r, p in enumerate(prompts):
        ids[r, :len(p)] = p
    pos = jnp.asarray([len(p) for p in prompts], jnp.int32)
    logp, state = jax.jit(lambda p, s: decode.prefill(conf, p, s, jnp.asarray(ids), pos))(
        params, decode.init_state(conf, len(prompts), max_seq))
    step = jax.jit(lambda p, s, t, q: decode.decode_step(conf, p, s, t, q))
    logits, toks = [logp], [jnp.argmax(logp, -1).astype(jnp.int32)]
    for _ in range(n_new - 1):
        logp, state = step(params, state, toks[-1], pos)
        logits.append(logp)
        toks.append(jnp.argmax(logp, -1).astype(jnp.int32))
        pos = pos + 1
    return np.stack(logits), np.stack(toks).T, state


def test_gpt2s_prefill_and_decode_give_the_masters_logits_and_tokens(gpt2):
    conf, masters = gpt2
    operands, narrowed = decode.operand_tree(conf, masters)
    assert narrowed and operands is not masters
    prompts = [[5, 17, 300, 2, 9, 44, 401, 7, 8, 1, 12], [33, 250, 6]]
    want_logits, want_toks, want_state = _greedy(conf, masters, prompts, 6)
    got_logits, got_toks, got_state = _greedy(conf, operands, prompts, 6)
    np.testing.assert_array_equal(got_logits, want_logits)
    np.testing.assert_array_equal(got_toks, want_toks)
    for a, b in zip(jax.tree_util.tree_leaves(got_state), jax.tree_util.tree_leaves(want_state)):
        np.testing.assert_array_equal(a, b)


def test_only_the_declared_operands_narrow(gpt2):
    """`Wqkv`, `Wo`, `W1`, `W2` and the head's `W` in bfloat16; the embedding's
    `W` and `P`, every bias and every LayerNorm leaf the master itself."""
    conf, masters = gpt2
    operands, narrowed = decode.operand_tree(conf, masters)
    last = conf.n_layers - 1
    seen = 0
    for i, (m, o) in enumerate(zip(masters, operands)):
        assert set(m) == set(o)
        for name in m:
            declared = (name in NARROW and 0 < i < last) or (i == last and name == "W")
            if declared:
                assert o[name].dtype == jnp.bfloat16 and m[name].dtype == jnp.float32
                np.testing.assert_array_equal(o[name], m[name].astype(jnp.bfloat16))
                seen += 1
            else:
                assert o[name] is m[name], (i, name)
    assert seen == len(narrowed) == len(_declared(conf, masters)) == 2 * (conf.n_layers - 2) + 1
    assert {"W", "P"} <= set(operands[0]) and operands[0]["W"].dtype == jnp.float32


def test_the_heads_bias_is_added_in_float32(gpt2):
    """The head's product comes back in float32 before its bias, whose values
    off bfloat16's grid survive: the operand head's pre-activations are the
    masters'."""
    conf, masters = gpt2
    last = conf.n_layers - 1
    head = dict(masters[last])
    head["b"] = (1.0 + jnp.arange(head["b"].shape[0], dtype=jnp.float32) * 2.0 ** -12)
    narrow = {**head, "W": head["W"].astype(jnp.bfloat16)}
    x = jax.random.normal(jax.random.PRNGKey(1), (3, head["W"].shape[0]), jnp.float32)
    got = DenseLayer.preout(narrow, conf.conf(last), x)
    want = DenseLayer.preout(head, conf.conf(last), x)
    assert got.dtype == jnp.float32
    np.testing.assert_array_equal(got, want)
    bias_in_bf16 = head["b"].astype(jnp.bfloat16).astype(jnp.float32)
    assert not np.array_equal(bias_in_bf16, head["b"])


@pytest.mark.parametrize("workload", SPARSE)
def test_a_bfloat16_tree_is_handed_through_as_it_is(workload):
    """The sparse families hold their weights in bfloat16 (`flags.param_dtype`):
    their layers declare operands, none is wider than the compute dtype, and
    the rule hands back the tree itself, under the masters' key."""
    cfg, conf = _cell(workload)
    params = program.program_weights(cfg, SEED)
    declared = _declared(conf, params)
    assert declared and all(leaf.dtype == jnp.bfloat16 for leaf in declared)
    tree, narrowed = decode.operand_tree(conf, params)
    assert tree is params and narrowed == []
    ic = InferCache()
    tree, key = ic._operand_params(conf, params)
    assert tree is params and key == ()
    assert ic.operand_bytes_narrowed == 0 and ic.operand_casts == 0


def test_a_tree_is_cast_once_and_a_new_tree_again(gpt2):
    conf, masters = gpt2
    ic = InferCache()
    first, key = ic._operand_params(conf, masters)
    again, key2 = ic._operand_params(conf, masters)
    assert again is first and key2 == key and ic.operand_casts == 1
    narrowed = decode.operand_tree(conf, masters)[1]
    assert key == tuple((tuple(a.shape), "bfloat16") for a in narrowed)
    assert ic.operand_bytes_narrowed == sum(a.size * 2 for a in narrowed)
    retrained = tuple(dict(layer) for layer in masters)
    retrained[1]["Wo"] = masters[1]["Wo"] + 1.0
    fresh, _ = ic._operand_params(conf, retrained)
    assert fresh is not first and ic.operand_casts == 2
    np.testing.assert_array_equal(fresh[1]["Wo"], retrained[1]["Wo"].astype(jnp.bfloat16))
    assert ic._operand_params(conf, retrained)[0] is fresh and ic.operand_casts == 2


def test_the_cast_tree_does_not_outlive_its_masters(gpt2):
    """The cache holds the cast tree beside the masters it was cast from, and
    lets go of both once the net's params are dropped (a harness that drops
    them before its reference runs frees both), or replaced."""
    conf, masters = gpt2
    net = MultiLayerNetwork(conf, seed=0)
    net.params = jax.tree_util.tree_map(jnp.copy, masters)
    tree, _ = net.infer_cache._operand_params(conf, net.params)
    cast, master = weakref.ref(tree[1]["Wqkv"]), weakref.ref(net.params[1]["Wqkv"])
    del tree
    assert cast() is not None
    net.params = None
    assert net.infer_cache._operands[0] is None and cast() is None and master() is None
    net.params = masters
    net.infer_cache._operand_params(conf, masters)
    net.params = tuple(masters)
    assert net.infer_cache._operands[0] is None


def _serve(conf, params, prompts, mesh=None):
    """Warm a net of `params` (under a serve mesh of `mesh`) and serve
    `prompts` greedily through a two-slot batcher: (the streams' tokens,
    `stats()`, the program keys)."""
    net = MultiLayerNetwork(conf, seed=0)
    net.params = params
    if mesh:
        net.set_serve_mesh(spec=mesh)
    net.warmup_generate(slots=2, max_seq=32, prompt_buckets=(16,))
    compiled = net.infer_cache.stats.misses
    batcher = ContinuousBatcher(net, n_slots=2, max_seq=32, prompt_buckets=(16,)).start()
    try:
        streams = [batcher.submit(p, max_new_tokens=6, temperature=0.0) for p in prompts]
        got = [list(s.tokens(timeout=120)) for s in streams]
        stats = batcher.stats()
    finally:
        batcher.stop()
    assert stats["fresh_compiles"] == compiled
    return got, stats, list(net.infer_cache._programs), net.infer_cache


def test_the_batcher_serves_the_operand_tree_and_counts_it(gpt2, monkeypatch):
    """Warm-up and serving take one cast between them, the programs are keyed
    by the narrowed leaves, and the streams are the tokens the same batcher
    serves from the masters."""
    conf, masters = gpt2
    prompts = [[5, 17, 300, 2, 9], [33, 250, 6, 1, 1, 8, 90, 411, 3]]
    got, stats, keys, _ = _serve(conf, masters, prompts)
    narrowed = decode.operand_tree(conf, masters)[1]
    assert stats["operand_casts_total"] == 1
    assert stats["operand_bytes_narrowed"] == sum(a.size * 2 for a in narrowed)
    signature = tuple((tuple(a.shape), "bfloat16") for a in narrowed)
    assert {k[0] for k in keys} == {"decode", "prefill-slot"}
    assert all(k[2][-len(signature):] == signature for k in keys)
    monkeypatch.setattr(InferCache, "_operand_params", lambda self, conf, params: (params, ()))
    want, plain, plain_keys, _ = _serve(conf, masters, prompts)
    assert got == want and all(len(t) == 6 for t in got)
    assert plain["operand_casts_total"] == 0 and plain["operand_bytes_narrowed"] == 0
    assert [k[2][:-len(signature)] for k in keys] == [k[2] for k in plain_keys]


@pytest.mark.skipif(jax.device_count() < 2, reason="needs two host devices")
def test_under_a_model_axis_the_operand_tree_is_placed_as_the_masters(gpt2, monkeypatch):
    """A plan with a `model` axis shards the operand tree leaf by leaf as it
    shards the masters (`ShardPlan.param_shardings` reads names and shapes),
    and the streams are the masters' through the same sharded programs."""
    conf, masters = gpt2
    prompts = [[5, 17, 300, 2, 9], [33, 250, 6, 1, 1, 8, 90, 411, 3]]
    got, stats, _, ic = _serve(conf, masters, prompts, mesh="batch=1,model=2")
    assert stats["operand_casts_total"] == 1
    operands, placed = ic._operands[2], ic._placed_params[1]
    want = ic.plan.param_shardings(masters)
    split = 0
    for o, p, w in zip(jax.tree_util.tree_leaves(operands), jax.tree_util.tree_leaves(placed),
                       jax.tree_util.tree_leaves(want)):
        assert p.dtype == o.dtype and p.sharding == w
        split += p.dtype == jnp.bfloat16 and w.spec != jax.sharding.PartitionSpec()
    assert split >= 4
    monkeypatch.setattr(InferCache, "_operand_params", lambda self, conf, params: (params, ()))
    assert _serve(conf, masters, prompts, mesh="batch=1,model=2")[0] == got
