"""The Pallas kernels of the main path, compiled for a described TPU v5e.

Interpret mode, which every other kernel test runs in, cannot see what the
chip's compiler refuses: a slice off the lane tiling, a kernel that wants
more fast memory than it may have.  The compiler is installed here and
compiles for a chip that is described and not attached, so these tests ask
it, at real widths, at about two seconds a case and no chip time.  Nothing
runs: a compile that passes says nothing about results or speed.

One process at a time may load the TPU's library and it keeps it until it
exits, so the topology is described inside a fixture (never while a module
is imported), every compile happens in this process, and all such tests
live in this one file.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from deeplearning4j_tpu.nd import pallas_kernels as pk
from deeplearning4j_tpu.nd import platform
from deeplearning4j_tpu.nn.conf import LayerType, NeuralNetConfiguration
from deeplearning4j_tpu.nn.layers.lstm import LSTMLayer

B, S, H, HD = 4, 512, 16, 128  # the flagship's attention shape


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no compiler here: nothing to ask
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """Sharding on the first described chip, with JAX's persistent cache
    off around the module: an entry written for a described chip cannot be
    read back without one, and the next compile would warn."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, one_chip, *shapes):
    """Compile for the described chip; returns the program text."""
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
            for shape, dtype in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def _flash(block_skip, fused_bwd=False):
    bq, bk = pk.pick_attention_blocks(S, HD)
    return lambda q, k, v: pk.flash_attention(
        q, k, v, True, bq, bk, False, block_skip=block_skip,
        fused_bwd=fused_bwd)


@pytest.mark.parametrize("block_skip", [True, False],
                         ids=["block-skip", "no-skip"])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
def test_flash_forward_compiles(one_chip, dtype, block_skip):
    qkv = [((B, S, H, HD), dtype)] * 3
    assert "tpu_custom_call" in _compile(_flash(block_skip), one_chip, *qkv)


@pytest.mark.parametrize("block_skip", [True, False],
                         ids=["block-skip", "no-skip"])
def test_flash_fused_backward_compiles(one_chip, block_skip):
    attend = _flash(block_skip, fused_bwd=True)
    grad = jax.grad(lambda q, k, v: attend(q, k, v).astype(jnp.float32).sum(),
                    argnums=(0, 1, 2))
    text = _compile(grad, one_chip, *[((B, S, H, HD), jnp.bfloat16)] * 3)
    # forward with residuals, delta, dK/dV and dQ: four kernels, and none
    # of them gave way to the jax-level recompute
    assert text.count("tpu_custom_call") >= 4


def _lstm_shapes(batch, hidden, dtype):
    return [((batch, hidden), dtype)] * 3 + [((hidden, 4 * hidden), dtype)] * 2 \
        + [((4 * hidden,), dtype)]


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
def test_fused_lstm_cell_h256_compiles(one_chip, dtype):
    text = _compile(lambda *a: pk.fused_lstm_step(*a, False), one_chip,
                    *_lstm_shapes(256, 256, dtype))
    assert "tpu_custom_call" in text


def test_fused_lstm_cell_h1024_pinned_says_why_not(one_chip):
    """The cell has no grid: at H=1024 it wants ~41 MB of a 16 MB scope and
    the compiler refuses it.  Pinned, it must say so itself, with the bytes
    and the limit, before the compiler is asked."""
    with pytest.raises(ValueError) as err:
        _compile(lambda *a: pk.fused_lstm_step(*a, False), one_chip,
                 *_lstm_shapes(256, 1024, jnp.float32))
    need = pk.fused_lstm_vmem_bytes(256, 1024, 1024, jnp.float32)
    assert str(need) in str(err.value)
    assert str(pk.VMEM_LIMIT_BYTES) in str(err.value)
    assert need > pk.VMEM_LIMIT_BYTES


@pytest.mark.parametrize("hidden,kernels", [(256, True), (1024, False)],
                         ids=["h256-fused", "h1024-scan"])
def test_lstm_auto_dispatch_compiles_at_every_width(one_chip, monkeypatch,
                                                    hidden, kernels):
    """`lstm_impl="auto"` on a TPU takes the Pallas cell where it fits and
    the scan path where it does not; either way the layer compiles."""
    monkeypatch.setattr(platform, "is_tpu", lambda: True)
    monkeypatch.setattr(pk, "is_tpu", lambda: True)
    conf = NeuralNetConfiguration(layer_type=LayerType.LSTM, n_in=hidden,
                                  n_out=hidden)
    assert conf.lstm_impl == "auto"
    text = _compile(
        lambda w, b, x: LSTMLayer.forward({"W": w, "b": b}, conf, x),
        one_chip, ((2 * hidden, 4 * hidden), jnp.float32),
        ((4 * hidden,), jnp.float32), ((64, 8, hidden), jnp.float32))
    assert ("tpu_custom_call" in text) == kernels


def test_scatter_add_rows_lane_width(one_chip):
    """Row widths that tile the 128 lanes compile; others are refused with
    a reason before the compiler's own `Slice shape ... must be aligned`."""
    def scatter(table, idx, upd):
        return pk.scatter_add_rows(table, idx, upd, interpret=False)

    def shapes(width):
        return [((1000, width), jnp.float32), ((64,), jnp.int32),
                ((64, width), jnp.float32)]

    assert "tpu_custom_call" in _compile(scatter, one_chip, *shapes(128))
    with pytest.raises(ValueError, match="multiple of the 128-lane"):
        _compile(scatter, one_chip, *shapes(100))


# -- the layer types of PR 28 at Ling-3.0-flash's widths: plain XLA, no kernel
# of this repo's, but each leans on something only the chip's compiler can
# refuse (a grouped matrix product, a triangular solve, a 5-D reduction)

def _ling3_conf(layer_type, spec):
    return NeuralNetConfiguration(layer_type=layer_type, n_in=2560, n_out=2560,
                                  dtype="bfloat16", compute_dtype="bfloat16",
                                  layer_spec=spec)


def _compiled_layer(fn, one_chip, impl, conf, *args):
    """`fn(params, *args)` compiled with `impl.init`'s shapes for params."""
    shaped = lambda tree: jax.tree_util.tree_map(  # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), tree)
    params = shaped(jax.eval_shape(lambda k: impl.init(k, conf), jax.random.PRNGKey(0)))
    return jax.jit(fn).lower(params, *[shaped(a) for a in args]).compile()


def _compile_layer(fn, one_chip, impl, conf, *args):
    """The same, as the program's text."""
    return _compiled_layer(fn, one_chip, impl, conf, *args).as_text()


@pytest.mark.parametrize("rows", [64, 1024], ids=["decode-64", "prefill-1024"])
def test_expert_layer_is_a_grouped_product_on_the_chip(one_chip, rows):
    from deeplearning4j_tpu.nn.conf import MoESpec
    from deeplearning4j_tpu.nn.layers.experts import MoELayer

    conf = _ling3_conf(LayerType.MOE, MoESpec(
        n_routed=512, n_held=128, hidden=768, shared_hidden=768, top_k=8,
        n_group=8, topk_group=4, routed_scaling=2.5))
    text = _compile_layer(lambda p, x: MoELayer.apply(p, conf, x), one_chip,
                          MoELayer, conf,
                          jax.ShapeDtypeStruct((rows, 2560), jnp.float32))
    # XLA's own grouped kernel, in both branches: over 3/8 of the picks and
    # over all of them; never a product over every expert for every row (64
    # rows are expected to hit 63.5 % of the experts: the sorted form)
    assert MoELayer.product_form(conf, rows) == "sorted"
    assert text.count("ragged_dot_tiling") >= 4 and "conditional" in text


def test_kda_prefill_and_decode_compile(one_chip):
    from deeplearning4j_tpu.nn.conf import KDASpec
    from deeplearning4j_tpu.nn.layers.kda import KDALayer

    conf = _ling3_conf(LayerType.KDA, KDASpec(n_heads=32, head_dim=128))
    state = lambda b: jax.eval_shape(lambda: KDALayer.init_state(conf, b, 0))  # noqa: E731
    text = _compile_layer(
        lambda p, x, s, n: KDALayer.prefill(p, conf, x, s, n), one_chip, KDALayer,
        conf, jax.ShapeDtypeStruct((1, 1024, 2560), jnp.bfloat16), state(1),
        jax.ShapeDtypeStruct((1,), jnp.int32))
    assert "while" in text          # a loop over chunks, not over tokens
    _compile_layer(
        lambda p, x, s, q: KDALayer.decode_step(p, conf, x, s, q), one_chip,
        KDALayer, conf, jax.ShapeDtypeStruct((64, 2560), jnp.float32), state(64),
        jax.ShapeDtypeStruct((64,), jnp.int32))


def test_mla_absorbed_decode_compiles(one_chip):
    from deeplearning4j_tpu.nn.conf import MLASpec
    from deeplearning4j_tpu.nn.layers.mla import MLALayer

    conf = _ling3_conf(LayerType.MLA, MLASpec(
        n_heads=32, kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
        v_head_dim=128, rope_theta=6e6))
    _compile_layer(
        lambda p, x, s, q: MLALayer.decode_step(p, conf, x, s, q), one_chip,
        MLALayer, conf, jax.ShapeDtypeStruct((64, 2560), jnp.float32),
        jax.eval_shape(lambda: MLALayer.init_state(conf, 64, 2048)),
        jax.ShapeDtypeStruct((64,), jnp.int32))


# -- the layer type of PR 32 at Mellum2's widths and the cell's shapes: plain
# XLA again; what only the chip's compiler can say is whether the ring and
# the table are read where they lie (K/V heads before positions: no copy of
# a whole table a step) and whether a block of 1,024 queries fits

def _mellum_conf(layer_type, spec):
    return NeuralNetConfiguration(layer_type=layer_type, n_in=2304, n_out=2304,
                                  dtype="bfloat16", compute_dtype="bfloat16",
                                  layer_spec=spec)


def _gqa_conf(kind):
    from deeplearning4j_tpu.nn.conf import GQASpec

    yarn = (16.0, 8192.0, 32.0, 1.0, 1.2772588722239782)
    return _mellum_conf(LayerType.GQA, GQASpec(
        n_heads=32, n_kv_heads=4, head_dim=128, rope_theta=500000.0, qk_norm=True,
        window=1024 if kind == "window" else 0, yarn=None if kind == "window" else yarn))


@pytest.mark.parametrize("kind", ["window", "full"])
def test_gqa_decode_reads_its_state_where_it_lies(one_chip, kind):
    import re

    from deeplearning4j_tpu.nn.layers.gqa import GQALayer

    conf = _gqa_conf(kind)
    state = jax.eval_shape(lambda: GQALayer.init_state(conf, 64, 9216))
    cells = 1024 if kind == "window" else 9216
    assert state["k"].shape == (64, 4, cells, 128)
    shaped = lambda tree: jax.tree_util.tree_map(  # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), tree)
    params = shaped(jax.eval_shape(lambda k: GQALayer.init(k, conf), jax.random.PRNGKey(0)))
    # the state donated, as the decode programs of the cache have it
    text = jax.jit(lambda p, x, s, q: GQALayer.decode_step(p, conf, x, s, q),
                   donate_argnums=(2,)).lower(
        params, shaped(jax.ShapeDtypeStruct((64, 2304), jnp.float32)), shaped(state),
        shaped(jax.ShapeDtypeStruct((64,), jnp.int32))).compile().as_text()
    # written with the heads' axis inside the scatter's window, the compiler
    # copied each table into a position-major layout and back, every step
    assert not re.search(rf"= bf16\[64,4,{cells},128\]\S* (copy|transpose)\(", text)


@pytest.mark.parametrize("kind", ["window", "full"])
def test_gqa_prefill_of_8192_compiles_in_blocks(one_chip, kind):
    from deeplearning4j_tpu.nn.layers.gqa import GQALayer

    conf = _gqa_conf(kind)
    text = _compile_layer(
        lambda p, x, s, n: GQALayer.prefill(p, conf, x, s, n), one_chip, GQALayer,
        conf, jax.ShapeDtypeStruct((1, 8192, 2304), jnp.float32),
        jax.eval_shape(lambda: GQALayer.init_state(conf, 1, 9216)),
        jax.ShapeDtypeStruct((1,), jnp.int32))
    # no block's scores span the whole prompt twice over: [.., 8192, 8192] never
    assert "8192,8192]" not in text


@pytest.mark.parametrize("rows", [64, 1024], ids=["decode-64", "prefill-1024"])
def test_expert_layer_that_holds_every_expert_runs_one_branch(one_chip, rows):
    from deeplearning4j_tpu.nn.conf import MoESpec
    from deeplearning4j_tpu.nn.layers.experts import MoELayer

    conf = _mellum_conf(LayerType.MOE, MoESpec(
        n_routed=64, n_held=64, hidden=896, shared_hidden=0, top_k=8,
        score="softmax", router_bias=False))
    compiled = _compiled_layer(lambda p, x: MoELayer.apply(p, conf, x), one_chip,
                               MoELayer, conf,
                               jax.ShapeDtypeStruct((rows, 2304), jnp.float32))
    text = compiled.as_text()
    assert " conditional(" not in text
    if rows == 64:
        # the cell's decode step, 8 picks a row over 64 experts: every expert
        # over all 64 rows as one batched product (PR 33), no grouped kernel
        # paying a 512-row tile a group, and the only sort is the router's
        # top-k; the float32 intermediates (29 + 38 MB) are the temporaries
        assert MoELayer.product_form(conf, rows) == "batched"
        assert "ragged" not in text
        assert all("/router/" in line for line in text.splitlines() if " sort(" in line)
        assert compiled.memory_analysis().temp_size_in_bytes < 0.2 * (1 << 30)
    else:
        assert MoELayer.product_form(conf, rows) == "sorted"
        assert text.count("ragged_dot_tiling") >= 2

