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
"""

from deeplearning4j_tpu.nn.conf import LayerType
from deeplearning4j_tpu.nn.layers import (base, output, autoencoder, rbm, lstm,
                                          conv, attention, experts, kda, mla)

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
    LayerType.SWIGLU: experts.SwiGLULayer,
    LayerType.MOE: experts.MoELayer,
}


def get_layer(layer_type):
    """Layer factory dispatch (parity: `LayerFactories.getFactory`)."""
    return _REGISTRY[LayerType(str(layer_type).lower())]


def register_layer(layer_type, impl) -> None:
    _REGISTRY[LayerType(str(layer_type).lower())] = impl
