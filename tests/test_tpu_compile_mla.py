"""The options `MLASpec` gained in PR 34, at dots3-note-prev's widths and the
cell's shapes (64 slots of 10,240 positions; buckets of 4,096 and 8,192),
compiled for a described v5e as `test_tpu_compile.py` compiles the older
layer types (its fixtures; a compile, never a run; a file of its own so that
neither is a twentieth of the suite).  Plain XLA again; what only the chip's
compiler can say is whether the picked cells are gathered and the state
written where the tables lie (no copy of a whole table a step), what the
exact choice costs in temporaries, and whether a block of queries fits.  The
whole 8,192 bucket's admission takes minutes to compile: `python3 -m
benchmark.rehearse --workload serve-dots3-long-64` does it (PERF.md 6, PR 34:
3.31 GiB of temporaries)."""

import jax
import jax.numpy as jnp
import pytest

from deeplearning4j_tpu.nn.conf import LayerType, NeuralNetConfiguration
from test_tpu_compile import _compiled_layer, one_chip, topo     # noqa: F401  (fixtures)


def _dots3_mla_conf(kind):
    from deeplearning4j_tpu.nn.conf import MLASpec

    spec = (MLASpec(n_heads=128, kv_lora_rank=512, qk_nope_head_dim=128,
                    qk_rope_head_dim=64, v_head_dim=128, rope_theta=8e7, eps=1e-5,
                    q_lora_rank=1024, lora_rescale=True, gate=True, index_n_heads=64,
                    index_head_dim=128, index_topk=2048) if kind == "full" else
            MLASpec(n_heads=64, kv_lora_rank=1024, qk_nope_head_dim=192,
                    qk_rope_head_dim=64, v_head_dim=128, rope_theta=5e4, eps=1e-5,
                    q_lora_rank=1024, lora_rescale=True, gate=True, window=513))
    return NeuralNetConfiguration(layer_type=LayerType.MLA, n_in=5120, n_out=5120,
                                  dtype="bfloat16", compute_dtype="bfloat16",
                                  layer_spec=spec)


@pytest.mark.parametrize("kind", ["full", "window"])
def test_mla_decode_reads_and_writes_its_state_where_it_lies(one_chip, kind):
    import re

    from deeplearning4j_tpu.nn.layers.mla import MLALayer

    conf = _dots3_mla_conf(kind)
    state = jax.eval_shape(lambda: MLALayer.init_state(conf, 64, 10240))
    cells = 10240 if kind == "full" else 513
    assert {k: v.shape for k, v in state.items()} == {
        "ckr": (64, cells, 640 if kind == "full" else 1152),
        **({"ki": (64, 10240, 128)} if kind == "full" else {})}
    shaped = lambda tree: jax.tree_util.tree_map(  # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), tree)
    params = shaped(jax.eval_shape(lambda k: MLALayer.init(k, conf), jax.random.PRNGKey(0)))
    # the state donated, as the decode programs of the cache have it
    compiled = jax.jit(lambda p, x, s, q: MLALayer.decode_step(p, conf, x, s, q),
                       donate_argnums=(2,)).lower(
        params, shaped(jax.ShapeDtypeStruct((64, 5120), jnp.float32)), shaped(state),
        shaped(jax.ShapeDtypeStruct((64,), jnp.int32))).compile()
    text = compiled.as_text()
    temporaries = compiled.memory_analysis().temp_size_in_bytes / 2 ** 30
    if kind == "full":
        # neither the two writes of one cell a row nor the gather of 2,048 of
        # 10,240 cells copies or transposes a table.  With the latent and the
        # rotary key in tables of their own, the chip kept the one 64 wide
        # with its positions innermost ({1,2,0}) and copied it whole, twice a
        # step; side by side in rows of 576 it did the same to the joint
        # table; in rows of 640, whole 128s, it copies none
        assert not re.search(rf"= bf16\[64,{cells},\d+\]\S* (copy|transpose)\(", text)
        # the index scores [64, 64, 10240] in float32 (0.16 GiB), the picked
        # cells (0.16), `compact`'s one-hot products: under the 0.63 GiB of
        # the whole step (PERF.md 6, PR 34).  The choice is exact: no sort
        # (`jax.lax.top_k` of 2,048 is one on this chip), nothing approximate
        assert temporaries < 0.7
        assert "sort(" not in text and "approx" not in text.lower()
    else:
        # a ring is 76 MB; alone the layer's program copies it once into the
        # layout its products want, within the whole step it does not
        assert temporaries < 0.2


@pytest.mark.parametrize("kind,bucket", [("full", 4096), ("window", 8192)])
def test_mla_prefill_compiles_in_blocks(one_chip, kind, bucket):
    """A full layer at the cell's smaller bucket (16 blocks of 256 queries,
    8 of them under the indexer's choice; the 8,192 bucket is 32 and takes
    over a minute here), a window layer at the larger."""
    from deeplearning4j_tpu.nn.layers.mla import MLALayer

    conf = _dots3_mla_conf(kind)
    compiled = _compiled_layer(
        lambda p, x, s, n: MLALayer.prefill(p, conf, x, s, n), one_chip, MLALayer,
        conf, jax.ShapeDtypeStruct((1, bucket, 5120), jnp.float32),
        jax.eval_shape(lambda: MLALayer.init_state(conf, 1, 10240)),
        jax.ShapeDtypeStruct((1,), jnp.int32))
    text = compiled.as_text()
    # no block's scores span the whole prompt twice over (a window layer's 64
    # heads of 128 make its output [8192, 8192] itself), and the choice among
    # the keys is no sort
    assert f",{bucket},{bucket}]" not in text and "sort(" not in text
    temporaries = compiled.memory_analysis().temp_size_in_bytes / 2 ** 30
    # a block of 256 queries of 128 heads against 4,096 keys is 0.5 GiB in
    # float32; beside 10.2 GB of weights and slots (9.5 GiB) the chip's
    # 15.75 GiB leave 6, and the whole 8,192 bucket's admission took 3.31
    assert temporaries < 2.0
