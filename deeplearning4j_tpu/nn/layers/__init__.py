"""Layer implementations + registry.

Parity with reference `nn/layers/*` + `nn/layers/factory/LayerFactories.java:32-47`
(layer class -> factory dispatch).  TPU-native design: a layer is a pair of
pure functions
    init(key, conf)                  -> params (dict pytree of jnp arrays)
    forward(params, conf, x, key=None, training=False) -> activations
registered by `LayerType`.  Pretrainable layers additionally expose
    pretrain_grad_and_score(params, conf, x, key) -> (grads, score)
replacing the reference's `Model.gradientAndScore` contract
(`nn/api/Model.java`) used by layer-wise pretraining.

A layer type that can stand in a generative stack (`nn.decode.
GENERATIVE_HIDDEN`) says itself what it keeps between tokens.  `nn/decode.py`
walks the layers and calls, never asking which type it has before it:
    init_state(conf, batch, max_seq)            -> state, a dict of arrays
                                                   with the rows on axis 0
    prefill(params, conf, x, state, length)     -> (hidden [B, T, n], state)
    decode_step(params, conf, x, state, pos)    -> (hidden [B, n], state)
    CARRY   does the state advance with every token (a recurrent carry: a
            fused K-step block must hold a finished row's still), or is it
            a table written at `pos` (rewriting a cell changes nothing)?
What only some types can do, a class declares by having it:
    init_paged_state(conf, batch, n_pages, page_size) -> state
            its state can live in the page pool; `decode_step`,
            `verify_chunk` and `counted_step` then take a `page_table=None`
            argument
    verify_chunk(params, conf, x, state, pos)   -> (hidden [B, K, n], state,
            carries)   K tokens a row in one pass; `carries` the state after
            each of them where it is a carry ({} otherwise), to roll back to
    counted_step(params, conf, x, state, pos)   -> (hidden, state, counts)
            a step that counts its expert picks ([2] int32)
    kv_cells(conf, max_seq), kv_cells_read(conf, max_seq)   -> int, or one
            a table of its state
            its state holds one cell a position: the most cells of a row
            that a step needs (a table's length, a ring's, the positions a
            step picks), and `decode_step` reads so many for every row of
            the slot table: the batcher counts the cells a step needs
            beside those the layer says its read covers
    selects(conf, max_seq)                      -> int
            its decode step picks so many of a row's cached positions and
            attends to those alone (0: to all): the batcher counts the
            positions cached beside those picked
A type with a state but without `init_paged_state` and `verify_chunk` lives
in the dense slot table only (`nn.decode.dense_only`).  A type with no state
gets all of it from `base.StatelessDecode`.  A new kind of state is a layer
file, its `LayerType` in `nn/conf.py` and a line of the registry below.
"""

from deeplearning4j_tpu.nn.conf import LayerType
from deeplearning4j_tpu.nn.layers import (base, output, autoencoder, rbm, lstm,
                                          conv, attention, experts, gqa, kda,
                                          mla)

_REGISTRY = {
    LayerType.DENSE: base.DenseLayer,
    LayerType.OUTPUT: output.OutputLayer,
    LayerType.AUTOENCODER: autoencoder.AutoEncoder,
    # recursive AE over tree structures is future scope; until then the
    # flat denoising AE provides the pretrain contract for this type
    LayerType.RECURSIVE_AUTOENCODER: autoencoder.AutoEncoder,
    LayerType.RBM: rbm.RBM,
    LayerType.LSTM: lstm.LSTMLayer,
    LayerType.GRAVES_LSTM: lstm.GravesLSTMLayer,
    LayerType.CONVOLUTION: conv.ConvolutionLayer,
    LayerType.SUBSAMPLING: conv.SubsamplingLayer,
    LayerType.BATCH_NORM: base.BatchNormLayer,
    LayerType.EMBEDDING: base.EmbeddingLayer,
    LayerType.ATTENTION: attention.MultiHeadAttentionLayer,
    LayerType.TRANSFORMER_FFN: attention.TransformerFFNLayer,
    LayerType.KDA: kda.KDALayer,
    LayerType.MLA: mla.MLALayer,
    LayerType.GQA: gqa.GQALayer,
    LayerType.SWIGLU: experts.SwiGLULayer,
    LayerType.MOE: experts.MoELayer,
}


def get_layer(layer_type):
    """Layer factory dispatch (parity: `LayerFactories.getFactory`)."""
    return _REGISTRY[LayerType(str(layer_type).lower())]


def register_layer(layer_type, impl) -> None:
    _REGISTRY[LayerType(str(layer_type).lower())] = impl
