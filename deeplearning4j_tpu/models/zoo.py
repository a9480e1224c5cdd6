"""Canonical model configurations — the BASELINE.json benchmark set.

These are *configs*, not classes: the reference expressed LeNet/DBN/LSTM
as `MultiLayerConfiguration`s over its layer enum (e.g. the DBN-on-Iris
builder in `MultiLayerTest.java:55-110`); same idea here.  BASELINE.json
configs: LeNet-5 MNIST, char-LSTM (PTB-style), VGG-style CIFAR-10,
Word2Vec (see models/word2vec.py), data-parallel MLP.
"""

from __future__ import annotations

from deeplearning4j_tpu.nn.conf import (Activation, LayerType, LossFunction,
                                        MultiLayerConfiguration,
                                        NeuralNetConfiguration,
                                        OptimizationAlgorithm, PoolingType,
                                        WeightInit)

SGD = OptimizationAlgorithm.ITERATION_GRADIENT_DESCENT


def _base(lr=0.1, iters=1, **kw):
    return NeuralNetConfiguration(
        optimization_algo=SGD, lr=lr, num_iterations=iters,
        activation=Activation.RELU, weight_init=WeightInit.VI,
        use_adagrad=False, momentum=0.9, **kw)


def lenet5(lr: float = 0.05, iterations: int = 1,
           dtype: str = "float32") -> MultiLayerConfiguration:
    """LeNet-5 on MNIST (BASELINE configs[0]): 1x28x28 -> conv20@5x5 ->
    pool2 -> conv50@5x5 -> pool2 -> dense500 -> softmax10."""
    b = _base(lr=lr, iters=iterations, dtype=dtype)
    confs = (
        b.replace(layer_type=LayerType.CONVOLUTION, n_channels=1, n_out=20,
                  kernel_size=(5, 5), stride=(1, 1)),
        b.replace(layer_type=LayerType.SUBSAMPLING, kernel_size=(2, 2),
                  stride=(2, 2), pooling=PoolingType.MAX),
        b.replace(layer_type=LayerType.CONVOLUTION, n_channels=20, n_out=50,
                  kernel_size=(5, 5), stride=(1, 1)),
        b.replace(layer_type=LayerType.SUBSAMPLING, kernel_size=(2, 2),
                  stride=(2, 2), pooling=PoolingType.MAX),
        b.replace(layer_type=LayerType.DENSE, n_in=50 * 4 * 4, n_out=500),
        b.replace(layer_type=LayerType.OUTPUT, n_in=500, n_out=10,
                  activation=Activation.SOFTMAX,
                  loss_function=LossFunction.MCXENT),
    )
    return MultiLayerConfiguration(
        confs=confs, pretrain=False, backprop=True,
        input_preprocessors=((0, "ff_to_conv:1:28:28"), (4, "conv_to_ff")))


def mlp(n_in: int, hidden, n_out: int, lr: float = 0.1,
        iterations: int = 1) -> MultiLayerConfiguration:
    """Plain MLP (the data-parallel benchmark model, BASELINE configs[4])."""
    b = _base(lr=lr, iters=iterations)
    dims = [n_in] + list(hidden) + [n_out]
    confs = []
    for i in range(len(dims) - 1):
        last = i == len(dims) - 2
        confs.append(b.replace(
            layer_type=LayerType.OUTPUT if last else LayerType.DENSE,
            n_in=dims[i], n_out=dims[i + 1],
            activation=Activation.SOFTMAX if last else Activation.RELU,
            loss_function=LossFunction.MCXENT))
    return MultiLayerConfiguration(confs=tuple(confs), backprop=True)


def dbn(n_in: int, hidden, n_out: int, lr: float = 0.05,
        iterations: int = 30, k: int = 1,
        finetune_iterations: int = 60) -> MultiLayerConfiguration:
    """Deep belief net — the reference's signature 2015 workflow
    (`MultiLayerTest.java` DBN-on-Iris/LFW pattern): a stack of sigmoid
    RBMs greedily pretrained with CD-k, then an output layer finetuned
    with conjugate gradient.  Features should be scaled into [0, 1] for
    the binary visible units."""
    b = _base(lr=lr, iters=iterations).replace(
        layer_type=LayerType.RBM, activation=Activation.SIGMOID, k=k)
    dims = [n_in] + list(hidden)
    confs = [b.replace(n_in=dims[i], n_out=dims[i + 1])
             for i in range(len(dims) - 1)]
    confs.append(b.replace(
        layer_type=LayerType.OUTPUT, n_in=dims[-1], n_out=n_out,
        activation=Activation.SOFTMAX, loss_function=LossFunction.MCXENT,
        lr=2 * lr, num_iterations=finetune_iterations,
        optimization_algo=OptimizationAlgorithm.CONJUGATE_GRADIENT))
    return MultiLayerConfiguration(confs=tuple(confs), pretrain=True,
                                   backprop=True)


def deep_autoencoder(n_in: int = 784, hidden=(400, 200, 100, 50, 25, 6),
                     lr: float = 0.05, iterations: int = 30,
                     finetune_iterations: int = 60,
                     corruption: float = 0.3) -> MultiLayerConfiguration:
    """Hinton-style deep autoencoder — the reference's Curves workflow
    (`CurvesDataFetcher.java` + stacked `AutoEncoder.java` pretraining):
    a denoising-AE encoder stack greedily pretrained layer by layer, a
    mirrored sigmoid decoder, and a RECONSTRUCTION_CROSSENTROPY output
    finetuned end-to-end against the inputs (fit(x, x)).  After
    pretraining, `unroll_autoencoder_stack` copies the encoder weights
    transposed into the decoder (Hinton's unrolling) — use
    `fit_deep_autoencoder` to get pretrain -> unroll -> finetune in one
    call."""
    if not hidden:
        raise ValueError("deep_autoencoder needs at least one hidden size")
    b = _base(lr=lr, iters=iterations).replace(
        activation=Activation.SIGMOID)
    dims = [n_in] + list(hidden)
    confs = [b.replace(layer_type=LayerType.AUTOENCODER, n_in=dims[i],
                       n_out=dims[i + 1], corruption_level=corruption)
             for i in range(len(dims) - 1)]
    # mirrored decoder: plain sigmoid dense layers back up the stack
    rev = list(reversed(dims))
    confs += [b.replace(layer_type=LayerType.DENSE, n_in=rev[i],
                        n_out=rev[i + 1])
              for i in range(len(rev) - 2)]
    confs.append(b.replace(
        layer_type=LayerType.OUTPUT, n_in=rev[-2], n_out=n_in,
        activation=Activation.SIGMOID,
        loss_function=LossFunction.RECONSTRUCTION_CROSSENTROPY,
        num_iterations=finetune_iterations,
        optimization_algo=OptimizationAlgorithm.CONJUGATE_GRADIENT))
    return MultiLayerConfiguration(confs=tuple(confs), pretrain=True,
                                   backprop=True)


def unroll_autoencoder_stack(conf: MultiLayerConfiguration, params):
    """Hinton's unrolling for a `deep_autoencoder` net: decoder layer p
    mirrors encoder AE layer L-1-p, so its weights become the PRETRAINED
    encoder weights transposed (W_dec = W_enc.T) and its bias the
    encoder's visible bias vb — instead of leaving the decoder at random
    init, which forces finetuning to train a deep random decoder through
    the bottleneck."""
    n_enc = sum(1 for c in conf.confs
                if LayerType(str(c.layer_type)) == LayerType.AUTOENCODER)
    params = list(params)
    for p in range(n_enc):  # decoder positions, incl. the OUTPUT layer
        enc = dict(params[n_enc - 1 - p])
        dec_idx = n_enc + p
        dec = dict(params[dec_idx])
        dec["W"] = enc["W"].T
        dec["b"] = enc["vb"]
        params[dec_idx] = dec
    return tuple(params)


def fit_deep_autoencoder(net, x):
    """pretrain (greedy AE stack) -> unroll decoder -> reconstruction
    finetune; `net` wraps a `deep_autoencoder` configuration."""
    import jax.numpy as jnp

    x = jnp.asarray(x)
    net.pretrain(x)
    net.params = unroll_autoencoder_stack(net.conf, net.params)
    net.finetune(x, x)
    return net


def char_lstm(vocab: int, hidden: int = 256, n_layers: int = 1,
              lr: float = 0.1, iterations: int = 1,
              sparse_labels: bool = False,
              embed: int = 0) -> MultiLayerConfiguration:
    """char-LSTM (BASELINE configs[1]; reference `LSTM.java:53` is a
    1-layer karpathy char-LSTM with fused iFog gates + decoder).

    `sparse_labels=True` declares that training feeds int class-id targets
    (shape [batch*seq]) instead of one-hot rows — the mcxent gather path,
    bitwise-identical loss without the [rows, vocab] one-hot gemm.

    `embed > 0` prepends an EMBEDDING layer (vocab -> embed, no positional
    table — the LSTM carries order) so the net consumes int char ids
    [batch, seq] directly: the input one-hot [B, S, vocab] materialization
    and its gemm against the first LSTM's W become a table gather."""
    b = _base(lr=lr, iters=iterations)
    confs = []
    if embed > 0:
        confs.append(b.replace(layer_type=LayerType.EMBEDDING, n_in=vocab,
                               n_out=embed))
    for i in range(n_layers):
        confs.append(b.replace(layer_type=LayerType.LSTM,
                               n_in=(embed if embed > 0 else vocab)
                               if i == 0 else hidden,
                               n_out=hidden,
                               activation=Activation.TANH))
    confs.append(b.replace(layer_type=LayerType.OUTPUT, n_in=hidden,
                           n_out=vocab, activation=Activation.SOFTMAX,
                           loss_function=LossFunction.MCXENT,
                           sparse_labels=sparse_labels))
    return MultiLayerConfiguration(
        confs=tuple(confs), backprop=True,
        # output layer consumes per-timestep features
        input_preprocessors=((len(confs) - 1, "rnn_to_ff"),))


def vgg_cifar10(lr: float = 0.05, iterations: int = 1,
                width: int = 64) -> MultiLayerConfiguration:
    """VGG-style ConvNet for CIFAR-10 (BASELINE configs[2]) — conv-conv-pool
    x3 + batchnorm + dense head.  Exceeds the reference, whose conv layer was
    stubbed (`ConvolutionLayer.java:95-233`)."""
    b = _base(lr=lr, iters=iterations)

    def conv(cin, cout):
        return b.replace(layer_type=LayerType.CONVOLUTION, n_channels=cin,
                         n_out=cout, kernel_size=(3, 3), stride=(1, 1),
                         padding=(1, 1))

    def bn(c):
        return b.replace(layer_type=LayerType.BATCH_NORM, n_in=c, n_out=c)

    def pool():
        return b.replace(layer_type=LayerType.SUBSAMPLING, kernel_size=(2, 2),
                         stride=(2, 2), pooling=PoolingType.MAX)

    w = width
    confs = (
        conv(3, w), bn(w), pool(),
        conv(w, 2 * w), bn(2 * w), pool(),
        conv(2 * w, 4 * w), bn(4 * w), pool(),
        b.replace(layer_type=LayerType.DENSE, n_in=4 * w * 4 * 4, n_out=256),
        b.replace(layer_type=LayerType.OUTPUT, n_in=256, n_out=10,
                  activation=Activation.SOFTMAX,
                  loss_function=LossFunction.MCXENT),
    )
    return MultiLayerConfiguration(
        confs=confs, backprop=True,
        input_preprocessors=((0, "ff_to_conv:3:32:32"),
                             (9, "conv_to_ff")))


def char_transformer(vocab: int, d_model: int = 128, n_blocks: int = 2,
                     n_heads: int = 4, max_seq_len: int = 256,
                     lr: float = 1e-3, iterations: int = 1,
                     updater: str = "adam", sparse_labels: bool = False,
                     fused_updater: bool = False,
                     attention_block_skip: bool = False,
                     attention_fused_bwd: bool = False
                     ) -> MultiLayerConfiguration:
    """Decoder-only char transformer LM (new scope — the reference's only
    sequence model is the scalar-loop LSTM).  Embedding (+ learned
    positions) -> n_blocks x [causal MHA, FFN] -> per-token softmax.
    Trains with Adam by default (the flagship wants it; plain SGD+momentum
    trains transformers poorly).

    The keyword flags are the MFU-campaign hot-path switches (all
    value-preserving; see tests/test_mfu_paths.py): `sparse_labels` trains
    against int class-id targets via the mcxent gather path,
    `fused_updater` is accepted and does nothing (the updater has one
    layout since PR 31; the benchmark's configurations still pass the key),
    `attention_block_skip` drops mask arithmetic on fully-causal flash
    tiles, and `attention_fused_bwd` replaces the flash backward's forward
    recompute with fused Pallas dK/dV + dQ kernels over saved logsumexp
    residuals (allclose rather than bitwise; training-only — never an
    infer-cache key)."""
    b = _base(lr=lr, iters=iterations, updater=updater,
              fused_updater=fused_updater)
    confs = [b.replace(layer_type=LayerType.EMBEDDING, n_in=vocab,
                       n_out=d_model, max_seq_len=max_seq_len)]
    for _ in range(n_blocks):
        confs.append(b.replace(layer_type=LayerType.ATTENTION, n_in=d_model,
                               n_out=d_model, n_heads=n_heads, causal=True,
                               attention_block_skip=attention_block_skip,
                               attention_fused_bwd=attention_fused_bwd))
        confs.append(b.replace(layer_type=LayerType.TRANSFORMER_FFN,
                               n_in=d_model, n_out=d_model))
    confs.append(b.replace(layer_type=LayerType.OUTPUT, n_in=d_model,
                           n_out=vocab, activation=Activation.SOFTMAX,
                           loss_function=LossFunction.MCXENT,
                           sparse_labels=sparse_labels))
    return MultiLayerConfiguration(
        confs=tuple(confs), backprop=True,
        input_preprocessors=((2 * n_blocks + 1, "rnn_to_ff"),))


# -- serve-precision eval slice ----------------------------------------------

#: Declared per-model error budgets for the low-precision serving
#: policies (optimize/quantize.py): softmax heads budget the top-1
#: disagreement vs the f32 reference, the reconstruction head budgets
#: relative output MSE.  `quantize.error_budget_report` measures every
#: model/policy pair against these in tier-1 (deterministic on CPU) —
#: a quantization regression fails the build before it ships.
PRECISION_ERROR_BUDGETS = {
    "lenet5": {
        "bf16": {"top1_delta": 0.05, "rel_mse": 5e-4},
        "int8": {"top1_delta": 0.10, "rel_mse": 5e-3},
    },
    "char_lstm": {
        "bf16": {"top1_delta": 0.05, "rel_mse": 5e-4},
        "int8": {"top1_delta": 0.10, "rel_mse": 5e-3},
    },
    "char_transformer": {
        "bf16": {"top1_delta": 0.08, "rel_mse": 1e-3},
        "int8": {"top1_delta": 0.15, "rel_mse": 1e-2},
    },
    "deep_autoencoder": {
        "bf16": {"rel_mse": 5e-4},
        "int8": {"rel_mse": 5e-3},
    },
}


def precision_eval_confs(small: bool = True):
    """The four-model zoo slice the precision eval harness runs —
    LeNet (conv), char-LSTM (recurrent), charTransformer (attention),
    deep-AE (reconstruction) — sized for CPU tier-1 when `small`."""
    if small:
        return {
            "lenet5": lenet5(),
            "char_lstm": char_lstm(24, hidden=24, n_layers=1),
            "char_transformer": char_transformer(
                24, d_model=16, n_blocks=1, n_heads=2, max_seq_len=16),
            "deep_autoencoder": deep_autoencoder(n_in=32, hidden=(16, 8)),
        }
    return {
        "lenet5": lenet5(),
        "char_lstm": char_lstm(64, hidden=256, n_layers=1),
        "char_transformer": char_transformer(64),
        "deep_autoencoder": deep_autoencoder(),
    }
