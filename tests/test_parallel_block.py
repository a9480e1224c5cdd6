"""Command A+'s layer types at tiny widths on the CPU: the parallel block
(attention and experts off one LayerNorm) whole, prefilled and stepped; the
rotary variants (interleaved pairs, none); the prefill's block of queries;
the tied LayerNorm head; the typed settings' round trip; the scopes; and a
ring's live cells counted on the `decode` spans and in `stats()` against a
hand count.  The family's model against its reference is
`test_benchmark_family_cohere2.py`."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import families, program as bench_program
from deeplearning4j_tpu.nn import decode
from deeplearning4j_tpu.nn.conf import (GQASpec, HeadSpec, LayerType, MoESpec,
                                        MultiLayerConfiguration,
                                        NeuralNetConfiguration, ParallelSpec)
from deeplearning4j_tpu.nn.layers import gqa, rms
from deeplearning4j_tpu.nn.layers.experts import MoELayer
from deeplearning4j_tpu.nn.layers.output import OutputLayer
from deeplearning4j_tpu.nn.layers.parallel import ParallelLayer

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEED = 2147483693
TIGHT = 2e-5        # float32: the order of float32 sums


def tiny() -> dict:
    with open(os.path.join(ROOT, "benchmark", "configs", "command-a-plus-ep8.json")) as f:
        cfg = json.load(f)
    return {**cfg, **cfg["rehearse"], "flags": {"param_dtype": "float32",
                                                "compute_dtype": "float32"}}


@pytest.fixture(scope="module")
def model():
    cfg = tiny()
    conf = families.of(cfg).program.build_conf(cfg)
    return conf, bench_program.program_weights(cfg, SEED)


@pytest.mark.parametrize("layer", [1, 4], ids=["window", "full"])
def test_a_block_whole_is_its_prefill_and_its_steps(model, layer, monkeypatch):
    """`forward` over 20 positions (the window of 8 passed) in blocks of 8
    queries is its two branches off one LayerNorm, and `prefill` of 12 then
    8 `decode_step`s through the ring or the table."""
    conf, params = model
    c, p = conf.conf(layer), params[layer]
    monkeypatch.setattr(gqa, "q_block", lambda n_heads: 8)
    attn, ffn = (c.replace(layer_spec=c.layer_spec.attn),
                 c.replace(layer_spec=c.layer_spec.ffn))

    def whole_and_branches(q, v):
        u = rms.layer_norm(v, q["ln"], None, c.layer_spec.eps)
        a, _ = gqa.GQALayer.branch_prefill(q["attn"], attn, u,
                                           jnp.broadcast_to(jnp.arange(20), (2, 20)),
                                           gqa.GQALayer.init_state(attn, 2, 20), None)
        return ParallelLayer.forward(q, c, v), v + a + MoELayer.branch(q["ffn"], ffn, u)[0]

    x = jax.random.normal(jax.random.PRNGKey(1), (2, 20, c.n_in), jnp.float32)
    whole, summed = jax.jit(whole_and_branches)(p, x)
    np.testing.assert_allclose(summed, whole, atol=TIGHT)

    hidden, state = jax.jit(lambda q, v, s, n: ParallelLayer.prefill(q, c, v, s, n))(
        p, x[:, :12], ParallelLayer.init_state(c, 2, 24), jnp.full((2,), 12, jnp.int32))
    np.testing.assert_allclose(hidden, whole[:, :12], atol=TIGHT)
    cells = 8 if layer == 1 else 24
    assert state["k"].shape == (2, 2, cells, 16)
    step = jax.jit(lambda q, v, s, t: ParallelLayer.counted_step(q, c, v, s, t))
    for t in range(12, 20):
        h, state, counts = step(p, x[:, t], state, jnp.full((2,), t, jnp.int32))
        np.testing.assert_allclose(h, whole[:, t], atol=TIGHT)
        assert counts.shape == (2,) and 0 <= int(counts[1]) <= 4


def test_interleaved_rotary_is_rotate_half_on_a_permuted_head():
    """Dimensions (2m, 2m + 1) turned together are dimensions (m, m + n/2)
    of the head with its even dimensions first."""
    x = jax.random.normal(jax.random.PRNGKey(2), (5, 3, 16), jnp.float32)
    at = jnp.arange(5)[:, None] * 7
    perm = np.concatenate([np.arange(0, 16, 2), np.arange(1, 16, 2)])
    pairs, half = jax.jit(lambda v: (rms.rope(v, at, 50000.0, pairs=True),
                                     rms.rope(v[..., perm], at, 50000.0)))(x)
    np.testing.assert_allclose(pairs[..., perm], half, atol=1e-6)
    # and the turn is GPT-J's: (x0 cos - x1 sin, x1 cos + x0 sin) at angle p
    ang = 7.0 * 2
    np.testing.assert_allclose(pairs[2, 1, :2], [
        x[2, 1, 0] * np.cos(ang) - x[2, 1, 1] * np.sin(ang),
        x[2, 1, 1] * np.cos(ang) + x[2, 1, 0] * np.sin(ang)], atol=1e-5)


def test_a_layer_without_rotary_scores_the_same_at_every_position(model):
    """The full layer rotates nothing: one token's query against one key
    reads the same score wherever the two stand; the window layer's does
    not."""
    conf, params = model
    u = jax.random.normal(jax.random.PRNGKey(3), (2, 64), jnp.float32)

    def score(layer, p_query, p_key):
        c = conf.conf(layer)
        attn = c.replace(layer_spec=c.layer_spec.attn)
        q, *_ = gqa.GQALayer._project(params[layer]["attn"], attn, u[:1],
                                      jnp.asarray([p_query]))
        _, k, *_ = gqa.GQALayer._project(params[layer]["attn"], attn, u[1:],
                                         jnp.asarray([p_key]))
        return np.asarray(jnp.einsum("bgrh,bgh->bgr", q, k))

    np.testing.assert_allclose(score(4, 9, 3), score(4, 40, 1), atol=1e-6)
    assert np.max(np.abs(score(1, 9, 3) - score(1, 40, 1))) > 1e-3
    np.testing.assert_allclose(score(1, 9, 3), score(1, 15, 9), atol=1e-5)   # relative


def test_the_block_of_queries_bounds_heads_times_queries():
    """At 32 heads a block keeps today's 1,024 queries (Mellum's and LFM2's
    programs are the parent's); at 128, 256."""
    assert (gqa.q_block(32), gqa.q_block(128), gqa.q_block(4)) == (1024, 256, 8192)


def test_a_tied_layer_norm_head_reads_the_embeddings_table(model):
    conf, params = model
    head = conf.conf(conf.n_layers - 1)
    assert set(OutputLayer.init(jax.random.PRNGKey(0), head)) == {"norm"}
    got = OutputLayer.head_params(conf, params)
    assert got["E"] is params[0]["W"]
    x = jax.random.normal(jax.random.PRNGKey(4), (3, 64), jnp.float32)
    u = rms.layer_norm(x, params[-1]["norm"], None, head.layer_spec.eps)
    want = jax.nn.softmax(jnp.matmul(u, params[0]["W"].T, precision="highest"), axis=-1)
    np.testing.assert_allclose(OutputLayer.forward(got, head, x), want, atol=1e-6)
    # the untied head of a conf without the new fields is the same object
    plain = MultiLayerConfiguration(confs=(head.replace(layer_spec=HeadSpec()),) * 2)
    assert OutputLayer.head_params(plain, ({"W": 1}, {"W": 2})) == {"W": 2}


def test_a_tied_heads_loss_reads_the_embeddings_table(model):
    """`network_loss` and `network_rowwise_loss` hand the head the table as
    `feed_forward` does: an embedding and a tied head score the ids' one-hot
    labels by the softmax of LN(E[id]) . E^T.  A tied head behind a first
    layer that is no EMBEDDING is refused."""
    from deeplearning4j_tpu.nd import losses
    from deeplearning4j_tpu.nn.multilayer import network_loss, network_rowwise_loss

    conf, params = model
    stack = MultiLayerConfiguration(confs=(conf.conf(0), conf.conf(conf.n_layers - 1)))
    tree = (params[0], params[-1])
    ids = jnp.asarray([3, 17, 200])
    labels = jax.nn.one_hot(jnp.asarray([5, 17, 9]), 512)
    eps = stack.conf(1).layer_spec.eps
    u = rms.layer_norm(params[0]["W"][ids], params[-1]["norm"], None, eps)
    probs = jax.nn.softmax(jnp.matmul(u, params[0]["W"].T, precision="highest"), axis=-1)
    np.testing.assert_allclose(network_loss(stack, tree, ids, labels, training=False),
                               losses.score(labels, "mcxent", probs, 0.0, 0.0), rtol=1e-5)
    np.testing.assert_allclose(
        network_rowwise_loss(stack, tree, ids, labels, training=False),
        losses.get_rowwise("mcxent")(labels, probs), rtol=1e-5)
    odd = MultiLayerConfiguration(confs=(conf.conf(1), conf.conf(conf.n_layers - 1)))
    with pytest.raises(ValueError, match="EMBEDDING"):
        OutputLayer.head_params(odd, (params[1], params[-1]))


def test_the_typed_settings_round_trip_and_older_ones_serialise_as_before():
    spec = ParallelSpec(attn=GQASpec(n_heads=8, n_kv_heads=2, head_dim=16, window=8,
                                     rotary="pairs"),
                        ffn=MoESpec(n_routed=32, n_held=4, hidden=32, shared_hidden=128,
                                    router_bias=False, shared_scale=0.25, ranks=8),
                        eps=1e-5)
    c = NeuralNetConfiguration(layer_type=LayerType.PARALLEL, n_in=64, n_out=64,
                               layer_spec=spec)
    again = NeuralNetConfiguration.from_json(c.to_json())
    assert again == c and hash(again) == hash(c)
    d = c.to_dict()["layer_spec"]
    assert d["kind"] == "ParallelSpec" and d["attn"]["kind"] == "GQASpec"
    head = NeuralNetConfiguration(layer_type=LayerType.OUTPUT,
                                  layer_spec=HeadSpec(norm="layer", tied=True))
    assert NeuralNetConfiguration.from_json(head.to_json()) == head
    # the fields at their defaults are left out: a conf from before them
    # serialises, and so fingerprints, as it did
    for old in (GQASpec(4, 2, 8, yarn=(1.0, 2.0, 3.0, 4.0, 5.0)),
                MoESpec(n_routed=8, n_held=8, hidden=4, shared_hidden=4), HeadSpec()):
        d = NeuralNetConfiguration(layer_spec=old).to_dict()["layer_spec"]
        assert not {"rotary", "shared_scale", "ranks", "norm", "tied"} & set(d)
        assert NeuralNetConfiguration.from_dict({"layer_spec": d}).layer_spec == old


def test_the_blocks_scopes_tell_its_parts_apart(model):
    conf, params = model
    state = decode.init_state(conf, 2, 24)
    ids = jnp.zeros((2,), jnp.int32)
    text = jax.jit(lambda p, s, t, q: decode.decode_step(conf, p, s, t, q)).lower(
        params, state, ids, ids).as_text(debug_info=True)
    for part in ("ln", "qkv", "rope", "kv_write", "kv_read", "proj", "router", "experts",
                 "shared", "combine"):
        assert f"L1.parallel_window/{part}" in text, part
    assert "L4.parallel_full/qkv" in text and "L4.parallel_full/rope" not in text
    assert "L5.output/ln" in text and "L5.output/head" in text


def test_a_rings_live_cells_are_counted_on_the_spans_and_in_stats(model):
    """One request in one slot, 5 tokens in and 10 out, through rings of 8 in
    three layers: its 9 decode steps at positions 5 to 13 need
    min(p + 1, 8) = 6, 7, 8, ... cells of each ring, 207 in all, of the
    ring's 8 a step and slot that every step reads.  (dots3's indexed tables,
    which need their picks but are read whole, count as none:
    `test_dots3_serving.py`.)"""
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.serving.batcher import ContinuousBatcher
    from deeplearning4j_tpu.utils import profiling

    conf, params = model
    net = MultiLayerNetwork(conf)
    net.params = params
    profiling.clear()
    batcher = ContinuousBatcher(net, n_slots=1, max_seq=24, prompt_buckets=(8,)).start()
    try:
        tokens = list(batcher.submit(np.arange(5, dtype=np.int32) + 3,
                                     max_new_tokens=10).tokens(timeout=120))
        stats = batcher.stats()
    finally:
        batcher.stop()
    assert len(tokens) == 10
    spans = [s for s in profiling.spans() if s.name == "decode"
             and "ring_cells_spanned" in s.attrs]
    live = sum(s.attrs["ring_cells_live"] for s in spans)
    spanned = sum(s.attrs["ring_cells_spanned"] for s in spans)
    steps = sum(s.attrs["steps"] for s in spans)
    assert live == 3 * (6 + 7 + 7 * 8) == 207
    assert spanned == steps * 3 * 8 and steps >= 9
    assert (stats["ring_cells_live_total"], stats["ring_cells_spanned_total"]) == (live, spanned)
