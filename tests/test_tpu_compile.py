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



# -- the layer types of LFM2-24B-A2B at its widths and the cell's shapes:
# whether a table of heads 64 wide lies in memory at its own bytes and is
# read where it lies, and whether an admission of 4,096 tokens fits beside
# 64 slots

def _lfm2_conf(layer_type, spec):
    return NeuralNetConfiguration(layer_type=layer_type, n_in=2048, n_out=2048,
                                  dtype="bfloat16", compute_dtype="bfloat16",
                                  layer_spec=spec)


def _lfm2_gqa():
    from deeplearning4j_tpu.nn.conf import GQASpec

    return _lfm2_conf(LayerType.GQA, GQASpec(n_heads=32, n_kv_heads=8, head_dim=64,
                                             rope_theta=1e6, qk_norm=True, eps=1e-5))


def test_a_table_of_64_wide_heads_is_read_unpadded_where_it_lies(one_chip):
    import re

    from deeplearning4j_tpu.nn.layers.gqa import GQALayer

    conf = _lfm2_gqa()
    state = jax.eval_shape(lambda: GQALayer.init_state(conf, 64, 6144))
    assert state["k"].shape == (64, 8, 6144, 64)
    shaped = lambda tree: jax.tree_util.tree_map(  # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), tree)
    params = shaped(jax.eval_shape(lambda k: GQALayer.init(k, conf), jax.random.PRNGKey(0)))
    compiled = jax.jit(lambda p, x, s, q: GQALayer.decode_step(p, conf, x, s, q),
                       donate_argnums=(2,)).lower(
        params, shaped(jax.ShapeDtypeStruct((64, 2048), jnp.float32)), shaped(state),
        shaped(jax.ShapeDtypeStruct((64,), jnp.int32))).compile()
    text = compiled.as_text()
    assert not re.search(r"= bf16\[64,8,6144,64\]\S* (copy|transpose)\(", text)
    # every argument at its own bytes: K and V are 2,048 bytes a position
    # (the compiler lays the table out positions innermost, so the heads'
    # 64 lanes are not padded to 128)
    table = 64 * 6144 * 2048
    others = sum(a.size * a.dtype.itemsize for a in jax.tree_util.tree_leaves(params))
    others += 64 * 2048 * 4 + 64 * 4
    mem = compiled.memory_analysis()
    assert table <= mem.argument_size_in_bytes - others < 1.01 * table
    assert mem.temp_size_in_bytes < 0.05 * table


def test_an_admission_of_4096_fits_beside_64_slots(one_chip):
    """An attention layer's prefill of one row of 4,096 tokens, the layer
    kind whose admission needs the most temporaries (compiled alone for the chip:
    0.50 GiB against 0.36 for the dense FFN, 0.25 for the routed one, 0.09
    for the short convolution; the whole admission 0.84), beside the weights
    of the cell's ten layers (10.8 GB), the table of 64 slots (1.61 GB) and
    the stream, fits the chip's 15.75 GiB with room; its scores are formed a
    block of 1,024 queries at a time."""
    from deeplearning4j_tpu.nn.layers.gqa import GQALayer

    gqa = _lfm2_gqa()
    compiled = _compiled_layer(
        lambda p, v, s, q: GQALayer.prefill(p, gqa, v, s, q), one_chip, GQALayer, gqa,
        jax.ShapeDtypeStruct((1, 4096, 2048), jnp.float32),
        jax.eval_shape(lambda: GQALayer.init_state(gqa, 1, 6144)),
        jax.ShapeDtypeStruct((1,), jnp.int32))
    assert "4096,4096]" not in compiled.as_text()
    temps = compiled.memory_analysis().temp_size_in_bytes
    assert temps < 0.6 * (1 << 30)
    weights = 2 * 5_401_307_904
    table = 64 * (2 * 6144 * 2048 + 8 * 2 * 2048 * 2)
    stream = 3 * 4096 * 2048 * 4        # the layer's input, output and residual
    assert weights + table + temps + stream < 0.9 * 15.75 * (1 << 30)


@pytest.mark.parametrize("cell", ["mellum", "lfm2"])
def test_a_full_layers_decode_walks_its_table_where_it_lies(one_chip, cell, monkeypatch):
    """The chip's path (the platform patched): a full layer's decode step
    attends through `dl4j_gqa_decode` at the cell's slots and table, and
    neither the write of a row's cell nor the kernel's walk copies or
    transposes the table (Mellum's `[64, 4, 9216, 128]`, laid out as it
    reads; LFM2's `[64, 8, 6144, 64]`, laid out positions innermost, which the
    kernel is handed as `[64, 8, 64, 6144]`: a bitcast).  The kernel keeps
    its blocks in fast memory: the step's temporaries are not a twentieth of
    the table."""
    import re

    from deeplearning4j_tpu.nn.layers import gqa
    from deeplearning4j_tpu.nn.layers.gqa import GQALayer

    monkeypatch.setattr(gqa, "is_tpu", lambda: True)
    monkeypatch.setattr(pk, "is_tpu", lambda: True)
    conf, max_seq = (_gqa_conf("full"), 9216) if cell == "mellum" else (_lfm2_gqa(), 6144)
    s = conf.layer_spec
    assert GQALayer.decode_kernel(conf, max_seq) and GQALayer.kv_read_block(conf, max_seq) == 512
    state = jax.eval_shape(lambda: GQALayer.init_state(conf, 64, max_seq))
    shaped = lambda tree: jax.tree_util.tree_map(  # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), tree)
    params = shaped(jax.eval_shape(lambda k: GQALayer.init(k, conf), jax.random.PRNGKey(0)))
    compiled = jax.jit(lambda p, x, st, q: GQALayer.decode_step(p, conf, x, st, q),
                       donate_argnums=(2,)).lower(
        params, shaped(jax.ShapeDtypeStruct((64, conf.n_in), jnp.float32)), shaped(state),
        shaped(jax.ShapeDtypeStruct((64,), jnp.int32))).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "dl4j_gqa_decode" in text
    table = rf"bf16\[64,{s.n_kv_heads},({max_seq},{s.head_dim}|{s.head_dim},{max_seq})\]"
    assert not re.search(rf"= {table}\S* (copy|transpose)\(", text)
    assert compiled.memory_analysis().temp_size_in_bytes < 0.05 * state["k"].size * 2


def _lfm2_stack(dtype):
    """LFM2's stack cut to one short convolution and one attention layer,
    each with its dense FFN, at every published width: what the batcher's
    decode program does around the table besides the attention layer."""
    import json

    from benchmark.families.lfm2_moe.program import build_conf

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs", "lfm2-24b-a2b-pp10.json")) as f:
        cfg = json.load(f)
    return build_conf({**cfg, "num_hidden_layers": 2, "layer_types": ["conv", "full_attention"],
                       "flags": {"param_dtype": dtype, "compute_dtype": dtype}})


def _table_copies(text, dtype, cells):
    """The copies and transposes in `text` of an array of `cells` elements of
    `dtype` (a table, whatever its shape and layout)."""
    import math
    import re

    return sum(math.prod(int(d) for d in dims.split(",")) == cells for dims in re.findall(
        rf"= {dtype}\[([\d,]+)\]\S* (?:copy|transpose)\(", text))


@pytest.mark.parametrize("slots,dtype", [(16, "bfloat16"), (64, "float32")],
                         ids=["16-slots-bf16", "64-slots-f32"])
def test_a_whole_decode_program_walks_a_64_wide_table_where_it_lies(one_chip, monkeypatch,
                                                                     slots, dtype):
    """The batcher's decode program of a stack with 64-wide heads, its state
    donated, at another slot count than the cell's and with float32 tables:
    the kernel's `[slots, 8, 64, 6144]` view of a table is a bitcast, so the
    program copies a table no more often than the masked read's does (in
    float32 at 64 slots never; at 16 slots the write of a row's cell copies
    each table out of the positions-innermost layout and back, on both
    paths)."""
    from deeplearning4j_tpu.nn import decode
    from deeplearning4j_tpu.nn.layers import gqa
    from deeplearning4j_tpu.nn.multilayer import init_params
    from deeplearning4j_tpu.optimize.infer_cache import _decode_program

    monkeypatch.setattr(pk, "is_tpu", lambda: True)
    conf = _lfm2_stack(dtype)
    shaped = lambda tree: jax.tree_util.tree_map(  # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), tree)
    params = shaped(jax.eval_shape(lambda k: init_params(conf, k), jax.random.PRNGKey(0)))
    state = shaped(jax.eval_shape(lambda: decode.init_state(conf, slots, 6144)))
    rows = lambda dt, *shape: shaped(jax.ShapeDtypeStruct((slots,) + shape, dt))  # noqa: E731

    def compiled(chip):
        monkeypatch.setattr(gqa, "is_tpu", lambda: chip)
        assert decode.kernel_layers(conf, 6144) == chip
        return jax.jit(_decode_program(conf), donate_argnums=(1,)).lower(
            params, state, rows(jnp.int32), rows(jnp.int32), rows(jnp.uint32, 2),
            rows(jnp.float32)).compile()

    walked = compiled(True)
    assert "dl4j_gqa_decode" in walked.as_text()
    short, cells = ("bf16" if dtype == "bfloat16" else "f32"), slots * 8 * 6144 * 64
    copies = _table_copies(walked.as_text(), short, cells)
    if slots == 64:
        assert copies == 0
    else:
        masked = compiled(False)
        assert copies == _table_copies(masked.as_text(), short, cells) == 4
        assert (walked.memory_analysis().temp_size_in_bytes
                <= masked.memory_analysis().temp_size_in_bytes)


@pytest.mark.parametrize("kernel", ["gqa", "dsa"])
def test_a_decode_kernel_runs_whole_on_each_chip_of_a_model_axis(topo, one_chip, monkeypatch,
                                                                  kernel):
    """Under a plan with a `model` axis over the described four chips (the
    weights split by head, the tables whole on every chip) a decode kernel
    is no call XLA has to partition: it runs in a `shard_map` in which each
    chip holds its arguments whole, and no table is gathered.  Mellum's full
    layer at the cell's slots and table; `dsa_decode` at dots3's latent
    table."""
    import re

    import numpy as np
    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    from deeplearning4j_tpu.nn.layers import gqa
    from deeplearning4j_tpu.nn.layers.gqa import GQALayer
    from deeplearning4j_tpu.parallel.plan import ShardPlan

    monkeypatch.setattr(gqa, "is_tpu", lambda: True)
    monkeypatch.setattr(pk, "is_tpu", lambda: True)
    plan = ShardPlan(mesh=Mesh(np.asarray(topo.devices).reshape(1, 4), ("batch", "model")))
    whole = NamedSharding(plan.mesh, P())
    placed = lambda tree, shardings: jax.tree_util.tree_map(  # noqa: E731
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s), tree, shardings)
    if kernel == "gqa":
        conf = _gqa_conf("full")
        params = jax.eval_shape(lambda k: GQALayer.init(k, conf), jax.random.PRNGKey(0))
        state = jax.eval_shape(lambda: GQALayer.init_state(conf, 64, 9216))
        table = r"bf16\[64,4,9216,128\]"
        compiled = jax.jit(lambda p, x, st, q: GQALayer.decode_step(p, conf, x, st, q),
                           donate_argnums=(2,)).lower(
            placed(params, plan.param_shardings(params)),
            jax.ShapeDtypeStruct((64, 2304), jnp.float32, sharding=whole),
            placed(state, plan.state_shardings(state)),
            jax.ShapeDtypeStruct((64,), jnp.int32, sharding=whole)).compile()
    else:
        heads = NamedSharding(plan.mesh, P(None, "model", None))
        table = r"bf16\[64,10240,640\]"
        compiled = jax.jit(lambda q, t, m, p: pk.dsa_decode(q, t, m, p, 0.07)).lower(
            jax.ShapeDtypeStruct((64, 128, 640), jnp.bfloat16, sharding=heads),
            jax.ShapeDtypeStruct((64, 10240, 640), jnp.bfloat16, sharding=whole),
            jax.ShapeDtypeStruct((64, 10240), jnp.bool_, sharding=whole),
            jax.ShapeDtypeStruct((64,), jnp.int32, sharding=whole)).compile()
    text = compiled.as_text()
    assert f"dl4j_{kernel}_decode" in text
    assert not re.search(rf"= {table}\S* (all-gather|copy|transpose)", text)


def test_a_decode_kernel_of_16_query_heads_a_kv_head_compiles(one_chip):
    """`dl4j_gqa_decode` at Command A+'s full layer: 128 query heads over 8
    K/V heads of 128 (a group of 16), 32 rows of a table of 10,240 cells
    walked in blocks of 512, the blocks in fast memory."""
    text = _compile(lambda q, k, v, p: pk.gqa_decode(q, k, v, p), one_chip,
                    ((32, 8, 16, 128), jnp.bfloat16), ((32, 8, 10240, 128), jnp.bfloat16),
                    ((32, 8, 10240, 128), jnp.bfloat16), ((32,), jnp.int32))
    assert "dl4j_gqa_decode" in text and pk.walk_block(10240, 8 * 128 * 2) == 512


def _commanda_stack():
    """Command A+'s stack cut to one window layer and its full layer, at
    every published width and this rank's 16 experts and 32,768 ids."""
    import json

    from benchmark.families.cohere2_moe.program import build_conf

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs", "command-a-plus-ep8.json")) as f:
        cfg = json.load(f)
    return build_conf({**cfg, "num_hidden_layers": 2,
                       "layer_types": ["sliding_attention", "full_attention"]})


def test_the_parallel_blocks_decode_program_at_32_slots(one_chip, monkeypatch):
    """The batcher's decode program of the parallel stack at the cell's 32
    slots and 10,240 positions, its state donated, on the chip's path: the
    full layer walks its table through `dl4j_gqa_decode`, neither the ring
    (`[32, 8, 4096, 128]`) nor the table (`[32, 8, 10240, 128]`) is copied,
    and the step's temporaries are under a tenth of a GiB."""
    from deeplearning4j_tpu.nn import decode
    from deeplearning4j_tpu.nn.layers import gqa
    from deeplearning4j_tpu.nn.multilayer import init_params
    from deeplearning4j_tpu.optimize.infer_cache import _decode_program

    monkeypatch.setattr(pk, "is_tpu", lambda: True)
    monkeypatch.setattr(gqa, "is_tpu", lambda: True)
    conf = _commanda_stack()
    assert decode.kernel_layers(conf, 10240) == 1
    shaped = lambda tree: jax.tree_util.tree_map(  # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), tree)
    params = shaped(jax.eval_shape(lambda k: init_params(conf, k), jax.random.PRNGKey(0)))
    state = shaped(jax.eval_shape(lambda: decode.init_state(conf, 32, 10240)))
    assert [lay["k"].shape for lay in state if lay] == [(32, 8, 4096, 128), (32, 8, 10240, 128)]
    rows = lambda dt, *shape: shaped(jax.ShapeDtypeStruct((32,) + shape, dt))  # noqa: E731
    compiled = jax.jit(_decode_program(conf), donate_argnums=(1,)).lower(
        params, state, rows(jnp.int32), rows(jnp.int32), rows(jnp.uint32, 2),
        rows(jnp.float32)).compile()
    text = compiled.as_text()
    assert "dl4j_gqa_decode" in text
    for cells in (32 * 8 * 4096 * 128, 32 * 8 * 10240 * 128):
        assert _table_copies(text, "bf16", cells) == 0
    assert compiled.memory_analysis().temp_size_in_bytes < 0.1 * (1 << 30)


def _qwen3next_stack():
    """Qwen3-Next's stack cut to one Gated DeltaNet layer and one gated full
    layer, each with its experts, at every published width and this rank's
    64 experts and 18,992 ids."""
    import json

    from benchmark.families.qwen3_next.program import build_conf

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs", "qwen3-next-80b-a3b-ep8.json")) as f:
        cfg = json.load(f)
    return build_conf({**cfg, "num_hidden_layers": 2, "full_attention_interval": 2})


def test_the_delta_rules_decode_program_at_64_slots(one_chip, monkeypatch):
    """The batcher's decode program of Qwen3-Next's stack at the cell's 64
    slots and 2,048 positions, its state donated, on the chip's path: the
    full layer walks its table of heads of 256 through `dl4j_gqa_decode`,
    neither the delta rule's state (`f32[64, 32, 128, 128]`) nor the
    table (`bf16[64, 2, 2048, 256]`) is copied, and the step's temporaries
    are under a tenth of a GiB."""
    from deeplearning4j_tpu.nn import decode
    from deeplearning4j_tpu.nn.layers import gqa
    from deeplearning4j_tpu.nn.multilayer import init_params
    from deeplearning4j_tpu.optimize.infer_cache import _decode_program

    monkeypatch.setattr(pk, "is_tpu", lambda: True)
    monkeypatch.setattr(gqa, "is_tpu", lambda: True)
    conf = _qwen3next_stack()
    assert [str(c.layer_type) for c in conf.confs] == [
        "embedding", "gdn", "moe", "gqa", "moe", "output"]
    assert decode.kernel_layers(conf, 2048) == 1
    shaped = lambda tree: jax.tree_util.tree_map(  # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), tree)
    params = shaped(jax.eval_shape(lambda k: init_params(conf, k), jax.random.PRNGKey(0)))
    state = shaped(jax.eval_shape(lambda: decode.init_state(conf, 64, 2048)))
    assert (state[1]["S"].shape, state[3]["k"].shape) == ((64, 32, 128, 128),
                                                           (64, 2, 2048, 256))
    rows = lambda dt, *shape: shaped(jax.ShapeDtypeStruct((64,) + shape, dt))  # noqa: E731
    compiled = jax.jit(_decode_program(conf), donate_argnums=(1,)).lower(
        params, state, rows(jnp.int32), rows(jnp.int32), rows(jnp.uint32, 2),
        rows(jnp.float32)).compile()
    text = compiled.as_text()
    assert "dl4j_gqa_decode" in text
    assert _table_copies(text, "f32", 64 * 32 * 128 * 128) == 0
    assert _table_copies(text, "bf16", 64 * 2 * 2048 * 256) == 0
    assert compiled.memory_analysis().temp_size_in_bytes < 0.1 * (1 << 30)


def _gpt2_block():
    """GPT-2 1.3B cut to one block, at every published width: 16 heads of
    128, d 2,048, 50,257 ids."""
    import json

    from benchmark.families.gpt2.program import build_conf

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs", "cerebras-gpt-1.3b.json")) as f:
        cfg = json.load(f)
    return build_conf({**cfg, "n_layer": 1})


def test_gpt2s_decode_walks_its_table_where_it_lies(one_chip, monkeypatch):
    """GPT-2's decode at the cell's 8 slots and 2,048 positions, on the
    chip's path: the attention layer's step, its state donated, attends
    through `dl4j_gqa_decode` in blocks of 256 (a block of 512 of 16 heads
    of 128 would take all the fast memory a kernel may hold), writes a row's
    cell where it lies, and holds no temporary a twentieth of a table; and
    the batcher's whole decode program copies and transposes neither
    `[8, 16, 2048, 128]` table."""
    from deeplearning4j_tpu.nn import decode
    from deeplearning4j_tpu.nn.layers import gqa
    from deeplearning4j_tpu.nn.layers.attention import MultiHeadAttentionLayer as MHA
    from deeplearning4j_tpu.nn.multilayer import init_params
    from deeplearning4j_tpu.optimize.infer_cache import _decode_program

    monkeypatch.setattr(pk, "is_tpu", lambda: True)
    monkeypatch.setattr(gqa, "is_tpu", lambda: True)
    conf = _gpt2_block()
    c = conf.conf(1)
    assert MHA.decode_kernel(c, 2048) and MHA.kv_read_block(c, 2048) == 256
    assert decode.kernel_layers(conf, 2048) == 1
    shaped = lambda tree: jax.tree_util.tree_map(  # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), tree)
    state = shaped(jax.eval_shape(lambda: MHA.init_state(c, 8, 2048)))
    table = 8 * 16 * 2048 * 128
    assert state["k"].shape == (8, 16, 2048, 128)
    params = shaped(jax.eval_shape(lambda k: MHA.init(k, c), jax.random.PRNGKey(0)))
    step = jax.jit(lambda p, x, st, q: MHA.decode_step(p, c, x, st, q),
                   donate_argnums=(2,)).lower(
        params, shaped(jax.ShapeDtypeStruct((8, 2048), jnp.float32)), state,
        shaped(jax.ShapeDtypeStruct((8,), jnp.int32))).compile()
    assert "dl4j_gqa_decode" in step.as_text()
    assert _table_copies(step.as_text(), "bf16", table) == 0
    memory = step.memory_analysis()
    assert memory.alias_size_in_bytes == 2 * table * 2
    assert memory.temp_size_in_bytes < 0.05 * table * 2
    params = shaped(jax.eval_shape(lambda k: init_params(conf, k), jax.random.PRNGKey(0)))
    rows = lambda dt, *shape: shaped(jax.ShapeDtypeStruct((8,) + shape, dt))  # noqa: E731
    program = jax.jit(_decode_program(conf), donate_argnums=(1,)).lower(
        params, shaped(jax.eval_shape(lambda: decode.init_state(conf, 8, 2048))),
        rows(jnp.int32), rows(jnp.int32), rows(jnp.uint32, 2), rows(jnp.float32)).compile()
    assert "dl4j_gqa_decode" in program.as_text()
    assert _table_copies(program.as_text(), "bf16", table) == 0


def _weight_converts(text, shapes) -> int:
    """The converts in `text` whose result is a bfloat16 array of one of
    `shapes`: a weight leaf cast inside the program."""
    import re

    dims = "|".join(",".join(map(str, s)) for s in shapes)
    return len(re.findall(rf"= bf16\[(?:{dims})\]\S* convert\(", text))


def test_gpt2s_decode_reads_its_weight_operands_in_bfloat16(one_chip, monkeypatch):
    """GPT-2 1.3B's decode program at every published width (one block of 24)
    on the chip's path, compiled from the float32 masters and from the operand
    tree (`nn.decode.operand_tree`): its arguments fall by the narrowed
    operands' bytes (306.5 MB here, 2.62 GB at the cell's 24 blocks), and the
    casts of the weight leaves that the masters' program makes (the head's and
    each block's four) are gone from it."""
    from deeplearning4j_tpu.nn import decode
    from deeplearning4j_tpu.nn.layers import gqa
    from deeplearning4j_tpu.nn.multilayer import init_params
    from deeplearning4j_tpu.optimize.infer_cache import _decode_program

    monkeypatch.setattr(pk, "is_tpu", lambda: True)
    monkeypatch.setattr(gqa, "is_tpu", lambda: True)
    conf = _gpt2_block()
    shaped = lambda tree: jax.tree_util.tree_map(  # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), tree)
    masters = shaped(jax.eval_shape(lambda k: init_params(conf, k), jax.random.PRNGKey(0)))
    operands, narrowed = shaped(jax.eval_shape(lambda p: decode.operand_tree(conf, p), masters))
    shapes = {a.shape for a in narrowed}
    assert shapes == {(2048, 6144), (2048, 2048), (2048, 8192), (8192, 2048), (2048, 50257)}
    assert all(a.dtype == jnp.bfloat16 for a in narrowed)
    state = shaped(jax.eval_shape(lambda: decode.init_state(conf, 8, 2048)))
    rows = lambda dt, *shape: shaped(jax.ShapeDtypeStruct((8,) + shape, dt))  # noqa: E731

    def compiled(params):
        return jax.jit(_decode_program(conf), donate_argnums=(1,)).lower(
            params, state, rows(jnp.int32), rows(jnp.int32), rows(jnp.uint32, 2),
            rows(jnp.float32)).compile()

    before, after = compiled(masters), compiled(operands)
    fell = (before.memory_analysis().argument_size_in_bytes
            - after.memory_analysis().argument_size_in_bytes)
    narrow_bytes = sum(a.size * 2 for a in narrowed)
    assert narrow_bytes == 306_515_968
    assert abs(fell - narrow_bytes) < 0.001 * narrow_bytes
    assert _weight_converts(before.as_text(), shapes) >= len(narrowed)
    assert _weight_converts(after.as_text(), shapes) == 0
    assert "dl4j_gqa_decode" in after.as_text()


def test_the_training_steps_text_is_the_parents():
    """`train-590m-2k`'s data-parallel step at its real widths and batch,
    lowered (not compiled): the text it had before generation programs took
    narrowed operands (commit 97a9b27), since `mixed_matmul` returns a float32
    product from float32 weights as it did."""
    import hashlib

    from benchmark import families, run
    from deeplearning4j_tpu.nn.multilayer import init_params
    from deeplearning4j_tpu.optimize.updater import init_updater
    from deeplearning4j_tpu.parallel.data_parallel import TrainState, make_dp_train_step
    from deeplearning4j_tpu.parallel.mesh import make_mesh

    cell = run.load_cell("train-590m-2k", rehearse=False)
    conf = families.of(cell.cfg).program.build_conf(cell.cfg)

    def state(key):
        params = init_params(conf, key)
        return TrainState(params, init_updater(params), jnp.asarray(0, jnp.int32))

    rows, seq = cell.mix["rows"], cell.mix["seq"]
    text = make_dp_train_step(conf, make_mesh({"dp": 1})).lower(
        jax.eval_shape(state, jax.random.PRNGKey(0)),
        jax.ShapeDtypeStruct((rows, seq), jnp.int32),
        jax.ShapeDtypeStruct((rows * seq,), jnp.int32),
        jax.eval_shape(lambda: jax.random.PRNGKey(0))).as_text()
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == "37239e68ca97f6d8"
