"""dots3-note-prev's layer types at tiny widths on the CPU against the plain
reference of `benchmark/families/dots3_note`: latent attention with a
low-rank query in two geometries, the indexer's exact choice, the ring of
latents, the head-wise gates, the eight ranks' shares of an expert layer,
and the whole tiny model through `prefill` and `decode_step` with
`index_topk` 8 and a window of 5 under contexts of 40, so that the selection
and the ring's wrap both act.  The batcher, spans and counters are in
`test_dots3_serving.py`."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import reference as bench_reference
from deeplearning4j_tpu.nn import decode
from deeplearning4j_tpu.nn.layers import mla
from deeplearning4j_tpu.nn.layers.experts import MoELayer
from deeplearning4j_tpu.nn.layers.mla import MLALayer
from dots3_model import LOOSE, SEED, TIGHT, Model, bf16, f32, rows     # noqa: F401

FULL, WINDOW = ("full", 1), ("window", 0)   # layer 0's FFN is dense: take layer 1


@pytest.fixture
def small_blocks(monkeypatch):
    """Blocks of 8 queries of 4 heads and 16 of 2: a sequence of 40 is five
    blocks of a full layer, of which four are past `index_topk`, and three
    of a window layer, each against a band of at most 20 keys."""
    monkeypatch.setattr(mla, "SCORE_CELLS", 32)


def reference_layer(m: Model, i: int, kind: str, x):
    with jax.default_matmul_precision("highest"):
        return m.ref.attention(
            {k: v.astype(jnp.float32) for k, v in m.weights[i].items()}, x,
            m.sizes, kind)


@pytest.mark.parametrize("kind", [FULL, WINDOW], ids=["full", "window"])
@pytest.mark.parametrize("dtype", ["f32", "bf16"])
def test_an_attention_layer_is_the_references(request, small_blocks, dtype, kind):
    m = request.getfixturevalue(dtype)
    i = m.layer(*kind)
    x = rows((2, 40, m.sizes["d"]), seed=3)
    want = reference_layer(m, i, kind[0], x)
    got = jax.jit(lambda p, v: MLALayer.forward(p, m.conf.conf(i), v))(m.params[i], x)
    # the layer adds some 3e-3 to a stream of 1: in bfloat16 a product's
    # rounding is 4e-3 of that, and a pick that falls the other way at the
    # selection's edge swaps one of 8 positions
    assert float(jnp.max(jnp.abs(got - want))) < (TIGHT if dtype == "f32" else 2e-3)
    spec = m.conf.conf(i).layer_spec
    assert (spec.window, spec.index_topk) == ((0, 8) if kind[0] == "full" else (5, 0))
    assert spec.q_lora_rank == 32 and spec.gate and spec.lora_rescale


def test_blocks_of_queries_change_nothing(f32, monkeypatch):
    x = rows((1, 24, f32.sizes["d"]), seed=4)
    for kind in (FULL, WINDOW):
        i = f32.layer(*kind)
        forward = lambda: jax.jit(lambda p, v: MLALayer.forward(     # noqa: E731
            p, f32.conf.conf(i), v))(f32.params[i], x)      # traced anew a call
        whole = forward()
        monkeypatch.setattr(mla, "SCORE_CELLS", 32)
        np.testing.assert_allclose(forward(), whole, atol=TIGHT)
        monkeypatch.setattr(mla, "SCORE_CELLS", 32768)
    assert (mla.q_block(128), mla.q_block(64), mla.q_block(32)) == (256, 512, 1024)


# ------------------------------------------------------------ the selection

def top_k_mask(scores, valid, k):
    """What `jax.lax.top_k` picks among the valid scores, as a mask."""
    masked = np.where(valid, scores, -np.inf)
    _, ids = jax.lax.top_k(jnp.asarray(masked), min(k, scores.shape[-1]))
    mask = np.zeros(scores.shape, bool)
    np.put_along_axis(mask, np.asarray(ids), True, axis=-1)
    return mask & valid


@pytest.mark.parametrize("case", ["spread", "ties", "few", "signs"])
def test_the_choice_is_top_ks_without_a_sort(case):
    rng = np.random.default_rng(5)
    scores = rng.normal(size=(6, 300)).astype(np.float32)
    valid = np.arange(300)[None, :] <= np.asarray([299, 150, 40, 7, 0, 299])[:, None]
    if case == "ties":          # a handful of values: the k-th is shared
        scores = rng.integers(-2, 3, size=(6, 300)).astype(np.float32)
    elif case == "few":         # fewer valid than k in every row
        valid = np.arange(300)[None, :] < np.asarray([3, 1, 31, 0, 32, 17])[:, None]
    elif case == "signs":       # zeros of both signs are one value
        scores = np.where(rng.random((6, 300)) < 0.5, 0.0, -0.0).astype(np.float32)
        scores[:, ::7] = rng.normal(size=(6, 43)).astype(np.float32)
    got = np.asarray(jax.jit(lambda s, v: mla.select_top(s, v, 32))(scores, valid))
    np.testing.assert_array_equal(got, top_k_mask(scores + 0.0, valid, 32))
    assert (got.sum(-1) == np.minimum(valid.sum(-1), 32)).all()


def test_ties_go_to_the_lower_position():
    scores = np.zeros((1, 12), np.float32)
    scores[0, [2, 9]] = 1.0
    valid = np.ones((1, 12), bool)
    got = np.asarray(mla.select_top(scores, valid, 5))[0]
    assert list(np.flatnonzero(got)) == [0, 1, 2, 3, 9]     # 2 and 9, then the first zeros
    # the reference's own choice: position t of 12 keeps 5 of the t + 1 before it
    qi = jnp.ones((12, 1, 2), jnp.float32)
    ki = jnp.zeros((12, 2), jnp.float32).at[jnp.asarray([2, 9])].set(1.0)
    from benchmark.families.dots3_note import reference

    mask = np.asarray(reference.picked(qi, ki, jnp.ones((12, 1)), jnp.arange(12), 5))
    assert list(np.flatnonzero(mask[11])) == [0, 1, 2, 3, 9]
    assert list(np.flatnonzero(mask[3])) == [0, 1, 2, 3]     # all, while t < 5
    assert list(np.flatnonzero(mask[8])) == [0, 1, 2, 3, 4]


def test_an_indexer_that_keeps_every_position_is_no_indexer(f32):
    """`index_topk` at or past the table's length: the step reads every cell,
    and the layer is the same layer without its indexer."""
    i = f32.layer(*FULL)
    conf = f32.conf.conf(i)
    keeps_all = conf.replace(layer_spec=dataclasses.replace(conf.layer_spec, index_topk=64))
    none = conf.replace(layer_spec=dataclasses.replace(
        conf.layer_spec, index_topk=0, index_n_heads=0, index_head_dim=0))
    p = f32.params[i]
    bare = {k: v for k, v in p.items() if k not in ("Wiq", "Wik", "ik_g", "ik_b", "Wiw")}
    assert sorted(MLALayer.init(jax.random.PRNGKey(0), none)) == sorted(bare)
    assert (MLALayer.selects(keeps_all, 40), MLALayer.selects(conf, 40)) == (0, 8)
    x = rows((2, 24, f32.sizes["d"]), seed=8)
    forward = lambda w, c: jax.jit(lambda w, v: MLALayer.forward(w, c, v))(w, x)  # noqa: E731
    np.testing.assert_array_equal(forward(p, keeps_all), forward(bare, none))
    assert float(jnp.max(jnp.abs(forward(p, conf) - forward(bare, none)))) > 1e-4
    # and token by token through the cache
    state, plain = MLALayer.init_state(keeps_all, 2, 40), MLALayer.init_state(none, 2, 40)
    step = jax.jit(lambda w, c, v, st, q: MLALayer.decode_step(w, c, v, st, q),
                   static_argnums=1)
    for t in range(6):
        pos = jnp.full((2,), t, jnp.int32)
        a, state = step(p, keeps_all, x[:, t], state, pos)
        b, plain = step(bare, none, x[:, t], plain, pos)
        # one product over the joint cell against two over its halves
        np.testing.assert_allclose(a, b, atol=1e-6)
    assert sorted(state) == ["ckr", "ki"] and sorted(plain) == ["c", "kr"]


@pytest.mark.parametrize("what", ["index scores", "softmax"])
def test_a_bfloat16_index_score_or_softmax_shows_in_float32(f32, what, monkeypatch):
    """In float32 the full layer is the reference's to rounding.  With the
    index scores rounded to bfloat16 some query keeps another position, and
    with the softmax's weights rounded every sum moves: either is off by
    more than ten times the bound."""
    i = f32.layer(*FULL)
    conf = f32.conf.conf(i)
    # at the init's spread the layer adds 3e-3 to a stream of 1 and its
    # softmax is all but even; with five of its matrices eight times as
    # large it adds 0.25, and 512 queries meet a near tie at the 8th place
    p = {k: v * (8.0 if k in ("Wqb", "Wkvb", "Wiq", "Wik", "Wo") else 1.0)
         for k, v in f32.params[i].items()}
    x = rows((4, 128, f32.sizes["d"]), seed=9)
    with jax.default_matmul_precision("highest"):
        want = f32.ref.attention(p, x, f32.sizes, "full")

    def gap():
        return float(jnp.max(jnp.abs(MLALayer.forward(p, conf, x) - want)))

    assert gap() < TIGHT
    if what == "index scores":
        scores = MLALayer._index_scores
        monkeypatch.setattr(MLALayer, "_index_scores", staticmethod(
            lambda *a: scores(*a).astype(jnp.bfloat16).astype(jnp.float32)))
    else:
        softmax = jax.nn.softmax
        monkeypatch.setattr(jax.nn, "softmax", lambda *a, **k: softmax(*a, **k).astype(
            jnp.bfloat16).astype(jnp.float32))
    assert gap() > 10 * TIGHT


def test_the_rescale_and_the_gates_are_in_the_layer(f32):
    """Each option moves the layer's output: none is a name alone."""
    i = f32.layer(*WINDOW)
    conf, p = f32.conf.conf(i), f32.params[i]
    x = rows((1, 12, f32.sizes["d"]), seed=10)
    whole = MLALayer.forward(p, conf, x)
    for off in ({"lora_rescale": False}, {"gate": False}, {"window": 0}):
        other = conf.replace(layer_spec=dataclasses.replace(conf.layer_spec, **off))
        assert float(jnp.max(jnp.abs(MLALayer.forward(p, other, x) - whole))) > 1e-4, off
    s = conf.layer_spec
    assert (conf.n_in / s.q_lora_rank, conf.n_in / s.kv_lora_rank) == (2.0, 64 / 48)
    with pytest.raises(ValueError, match="low-rank query"):
        MLALayer.init(jax.random.PRNGKey(0), conf.replace(
            layer_spec=dataclasses.replace(s, index_topk=4, index_n_heads=2,
                                           index_head_dim=8)))


# ------------------------------------------------------------ the kernel

@pytest.mark.parametrize("dtype", ["f32", "bf16"])
def test_a_decode_step_through_the_kernel_is_the_masked_reads(request, dtype, monkeypatch):
    """An indexed layer's step on the chip's path (the kernel interpreted, in
    blocks of 16 of a table of 64) against the step off the chip, from one
    state: rows past `index_topk`, under it, and at a block's edge."""
    from deeplearning4j_tpu.nd import pallas_kernels

    m = request.getfixturevalue(dtype)
    i = m.layer(*FULL)
    conf, p = m.conf.conf(i), m.params[i]
    x = rows((3, 40, m.sizes["d"]), seed=15)
    _, state = MLALayer.prefill(p, conf, x, MLALayer.init_state(conf, 3, 64),
                                jnp.asarray([40, 6, 16], jnp.int32))
    pos = jnp.asarray([40, 6, 16], jnp.int32)
    step = lambda: jax.jit(lambda p, v, st, q: MLALayer.decode_step(  # noqa: E731
        p, conf, v, st, q))(p, x[:, -1], state, pos)        # traced anew a call
    off, off_state = step()
    monkeypatch.setattr(mla, "is_tpu", lambda: True)
    monkeypatch.setattr(pallas_kernels, "DSA_BLOCK", 16)
    on, on_state = step()
    # a table of no more cells than the layer picks has no choice to walk
    assert MLALayer.decode_kernel(conf, 64) and not MLALayer.decode_kernel(conf, 8)
    jax.tree_util.tree_map(np.testing.assert_array_equal, on_state, off_state)
    np.testing.assert_allclose(on, off, atol=TIGHT if dtype == "f32" else 2e-3)


# ----------------------------------------------------- the ranks' shares

def test_the_eight_chips_parts_add_up_to_the_uncut_layer(f32):
    """Each rank's routed part, from the reference and from the program, the
    shared expert counted once, against the reference holding every expert
    (4 held of 32 here, as 32 of 256 at the published sizes)."""
    i = f32.layer("moe")
    s = f32.sizes
    x = rows((40, s["d"]), seed=6)
    base = {**f32.cfg, "n_routed_experts": 4}
    key = bench_reference.base_key(SEED)

    def parts(cfg):
        w = f32.ref.layer_weights(cfg, key, i, "moe")
        w = {k: v.astype(jnp.float32) for k, v in w.items()}
        with jax.default_matmul_precision("highest"):
            return f32.ref.moe_parts(w, f32.ref.rms_norm(x, w["ln"], s["eps"]),
                                     f32.ref.sizes(cfg))

    whole, shared = parts({**base, "n_routed_experts": s["experts_routed"]})
    total, program_total = shared, -7.0 * shared
    for rank in range(8):
        cfg = {**base, "deployment": {"rank": rank}}
        assert f32.ref.sizes(cfg)["first_expert"] == 4 * rank
        routed, also_shared = parts(cfg)
        np.testing.assert_array_equal(np.asarray(also_shared), np.asarray(shared))
        total = total + routed
        conf = f32.fam.program.build_conf(cfg).conf(i)
        assert (conf.layer_spec.n_held, conf.layer_spec.first_held) == (4, 4 * rank)
        params = f32.fam.program.to_program(
            [f32.ref.layer_weights(cfg, key, i, "moe")])[0]
        out, _ = MoELayer.apply(params, conf, x[None])
        program_total = program_total + (out[0] - x)
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole + shared), atol=TIGHT)
    np.testing.assert_allclose(np.asarray(program_total), np.asarray(whole + shared),
                               atol=TIGHT)


# --------------------------------------------------- through the cache

@pytest.mark.parametrize("dtype", ["f32", "bf16"])
def test_prefill_past_topk_and_window_then_decoding_through_the_cache(request, dtype,
                                                                       small_blocks):
    """A prompt of 20 (bucket 24: rows of 20 and 13 real tokens) into tables
    whose layers keep 8 positions and rings of 5, then 20 tokens one at a
    time: the ring wraps eight times, every step selects, and every step's
    log-probabilities are those of the reference's one pass over all 40."""
    m = request.getfixturevalue(dtype)
    tol = TIGHT if dtype == "f32" else LOOSE
    ids = np.random.default_rng(0).integers(0, m.sizes["vocab"], (2, 40)).astype(np.int32)
    want = m.logp(ids)
    prompt = np.zeros((2, 24), np.int32)
    prompt[:, :20] = ids[:, :20]
    length = jnp.asarray([20, 13], jnp.int32)
    fill = jax.jit(lambda p, s, t, n: decode.prefill(m.conf, p, s, t, n))
    logp, state = fill(m.params, decode.init_state(m.conf, 2, 64), prompt, length)
    assert abs(np.asarray(logp)[0] - want[0, 19]).max() < tol
    assert abs(np.asarray(logp)[1] - want[1, 12]).max() < tol
    # padding is inert: other ids past a row's length change nothing a row
    # keeps, and the ring holds the last 5 real positions
    other = prompt.copy()
    other[0, 20:], other[1, 13:] = 7, 9
    logp2, state2 = fill(m.params, decode.init_state(m.conf, 2, 64), other, length)
    np.testing.assert_array_equal(logp, logp2)
    ring_at, table_at = m.layer(*WINDOW), m.layer(*FULL)
    np.testing.assert_array_equal(state[ring_at]["ckr"], state2[ring_at]["ckr"])
    for name in ("ckr", "ki"):
        np.testing.assert_array_equal(state[table_at][name][1, :13],
                                      state2[table_at][name][1, :13])
    step = jax.jit(lambda p, s, t, q: decode.decode_step(m.conf, p, s, t, q))
    pos = np.asarray([20, 13])
    for _ in range(20):
        logp, state = step(m.params, state, jnp.asarray(ids[np.arange(2), pos]),
                           jnp.asarray(pos, jnp.int32))
        for r in range(2):
            assert abs(np.asarray(logp)[r] - want[r, pos[r]]).max() < tol
        pos = pos + 1
    assert {k: v.shape for k, v in state[ring_at].items()} == {
        "ckr": (2, 5, 128)}
    assert {k: v.shape for k, v in state[table_at].items()} == {
        "ckr": (2, 64, 128), "ki": (2, 64, 16)}


def test_a_padded_prompt_leaves_the_ring_the_last_real_positions(f32):
    """Row 1 has 13 real tokens of a bucket of 24: cell c of the ring of 5
    holds position 12 - (12 - c) % 5, what a prompt of exactly 13 leaves."""
    i = f32.layer(*WINDOW)
    conf, p = f32.conf.conf(i), f32.params[i]
    x = rows((1, 24, f32.sizes["d"]), seed=11)
    _, padded = MLALayer.prefill(p, conf, x, MLALayer.init_state(conf, 1, 64),
                                 jnp.asarray([13], jnp.int32))
    _, exact = MLALayer.prefill(p, conf, x[:, :13], MLALayer.init_state(conf, 1, 64),
                                jnp.asarray([13], jnp.int32))
    np.testing.assert_allclose(padded["ckr"], exact["ckr"], atol=1e-6)
    _, _, c, kr, _, _ = MLALayer._project(p, conf, x, jnp.arange(24)[None])
    held = [12 - (12 - cell) % 5 for cell in range(5)]
    assert held == [10, 11, 12, 8, 9]
    np.testing.assert_allclose(padded["ckr"][0, :, :48], c[0, np.asarray(held)], atol=1e-6)
    np.testing.assert_allclose(padded["ckr"][0, :, 48:56], kr[0, np.asarray(held)], atol=1e-6)
