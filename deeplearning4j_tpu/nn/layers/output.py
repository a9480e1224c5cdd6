"""Output layer — softmax (or configured activation) head + loss scoring.

Parity: reference `OutputLayer.java:54-356` — softmax output (:337-345),
per-loss-function scoring (:77-90).  The reference hand-derives weight
gradients per loss case (:126-158); here the gradient is `jax.grad` of
`loss(...)` end-to-end, which covers every registered loss identically.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nd import losses as L
from deeplearning4j_tpu.nd.ops import activate
from deeplearning4j_tpu.nn.layers.base import DenseLayer, compute_dtype
from deeplearning4j_tpu.nn.layers.rms import (F32, layer_norm, mm, precision_of,
                                              rms_norm)
from deeplearning4j_tpu.utils.profiling import scope


class OutputLayer(DenseLayer):
    @staticmethod
    def init(key, conf):
        spec = conf.layer_spec
        if spec is not None and spec.tied:
            # the matrix is the embedding's table: the head holds its norm
            return {"norm": jnp.ones((conf.n_in,), jnp.dtype(conf.dtype))}
        params = DenseLayer.init(key, conf)
        if spec is None:
            return params
        # a language model's head (`HeadSpec`): its norm, a matrix, no bias
        return {"W": params["W"],
                "norm": jnp.ones((conf.n_in,), params["W"].dtype)}

    @staticmethod
    def forward(params, conf, x, key=None, training=False):
        if conf.layer_spec is not None:
            return OutputLayer._lm_head(params, conf, x)
        with scope("head"):
            return OutputLayer._head(params, conf, x, key, training)

    @staticmethod
    def _lm_head(params, conf, x):
        """Under a `HeadSpec`: its norm, then logits in float32 from weights
        of any type (a tied head's are the table `E` [vocab, n] that
        `head_params` hands it, read as it lies: x . E^T), then softmax."""
        spec, cd = conf.layer_spec, compute_dtype(conf)
        with scope("ln"):
            if spec.norm == "layer":
                x = layer_norm(x, params["norm"], None, spec.eps)
            else:
                x = rms_norm(x, params["norm"], spec.eps)
        with scope("head"):
            if spec.tied:
                logits = jax.lax.dot_general(
                    x.astype(cd), params["E"].astype(cd),
                    (((x.ndim - 1,), (1,)), ((), ())),
                    precision=precision_of(cd), preferred_element_type=F32)
            else:
                logits = mm(x, params["W"], cd)
            return activate("softmax", logits)

    @staticmethod
    def head_params(conf, params) -> dict:
        """The head's parameters as `forward` takes them, from the whole
        stack's `params`: its own, and under a tied `HeadSpec` the first
        layer's (the EMBEDDING's) table beside them as `E`, the one array
        and no copy."""
        last = conf.n_layers - 1
        if not getattr(conf.conf(last).layer_spec, "tied", False):
            return params[last]
        from deeplearning4j_tpu.nn.conf import LayerType

        if LayerType(str(conf.conf(0).layer_type)) != LayerType.EMBEDDING:
            raise ValueError("a tied head reads the first layer's table, and "
                             f"the first layer is {conf.conf(0).layer_type}, "
                             "not an EMBEDDING")
        return {**params[last], "E": params[0]["W"]}

    @staticmethod
    def _head(params, conf, x, key, training):
        # input dropout / dropconnect apply here exactly as in DenseLayer
        # (the reference's OutputLayer inherits BaseLayer's dropout path)
        kdrop = kdc = None
        if key is not None:
            kdrop, kdc = jax.random.split(key)
        if training and conf.dropout > 0.0 and kdrop is not None:
            from deeplearning4j_tpu.nd import random as ndr
            x = x * ndr.dropout_mask(kdrop, 1.0 - conf.dropout, x.shape,
                                     x.dtype)
        z = OutputLayer.preout(params, conf, x, kdc, training)
        loss = str(conf.loss_function).lower()
        # The head must match the loss (the reference's OutputLayer is a
        # softmax head; hidden-layer activations leaking into the output of a
        # classifier would let cross-entropy collapse degenerately): softmax
        # for multiclass CE, sigmoid for binary CE, linear for regression.
        if loss in ("mcxent", "negativeloglikelihood", "expll"):
            return activate("softmax", z)
        if loss in ("xent", "rmse_xent", "reconstruction_crossentropy"):
            return activate("sigmoid", z)
        # regression losses honor the configured activation (sigmoid head on
        # MSE is the reference's bounded-regression/AE-finetune shape)
        return activate(conf.activation, z)

    @staticmethod
    def loss(params, conf, x, labels, key=None, training=False):
        out = OutputLayer.forward(params, conf, x, key, training)
        with scope("loss"):
            # a tied head's table is the embedding's, whose term the stack
            # counts with that layer
            l2n = (jnp.sum(params["W"].astype(jnp.float32) ** 2)
                   if "W" in params else 0.0)
            l2 = conf.l2 if conf.use_regularization else 0.0
            s = L.score(labels, conf.loss_function, out, l2, l2n)
            if conf.use_regularization and conf.l1 and "W" in params:
                s = s + conf.l1 * jnp.sum(
                    jnp.abs(params["W"].astype(jnp.float32)))
        return s

    @staticmethod
    def score(params, conf, examples, labels):
        """F1 of the layer's classifications on (examples, labels) —
        reference `OutputLayer.score(INDArray, INDArray)` (:183-188: build
        an Evaluation over labelProbabilities, return eval.f1()). Scale 0-1,
        higher is better — distinct from `loss`, which is the training
        objective (lower is better)."""
        from deeplearning4j_tpu.evaluation import Evaluation

        probs = OutputLayer.forward(params, conf, examples)
        ev = Evaluation()
        ev.eval(labels, probs)
        return float(ev.f1())

    @staticmethod
    def rowwise_loss(params, conf, x, labels, key=None, training=False):
        """Per-example loss vector, WITHOUT regularization terms (the caller
        owns those — they must be counted once per step, not per example).
        Backs sample-weighted / pad-masked training on remainder batches."""
        out = OutputLayer.forward(params, conf, x, key, training)
        with scope("loss"):
            return L.get_rowwise(conf.loss_function)(labels, out)
