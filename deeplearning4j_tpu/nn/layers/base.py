"""Dense / feed-forward layers.

Parity: reference `BaseLayer.java:46-408` — param table {"W","b"},
`activate() = f(x.W + b)` (:211-219), dropout (:250-262), dropconnect;
`merge` (parameter averaging, :271-273) is subsumed by pytree arithmetic in
`parallel/averaging.py`.  Plus BatchNorm and Embedding layers (capability the
reference's config enum gestures at via BASELINE config[2] "ConvolutionLayer
+ BatchNorm").
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.nd import random as ndr
from deeplearning4j_tpu.nd.ops import activate
from deeplearning4j_tpu.nn.weights import init_weights


def _dtype(conf):
    return jnp.dtype(conf.dtype)


def compute_dtype(conf):
    cd = getattr(conf, "compute_dtype", "")
    return jnp.dtype(cd) if cd else jnp.dtype(conf.dtype)


def mixed_matmul(x, W, conf, master=None):
    """x @ W with operands in conf.compute_dtype — bf16 feeds the MXU at
    full rate while params stay f32 (TPU bf16 matmuls accumulate in f32 on
    the MXU).  The product comes back in the masters' dtype: W's own, or,
    where W is an operand held narrow beside wider masters
    (`nn.decode.operand_tree`), the wider `master` dtype the caller names
    (its bias's), so that every sum after it is the master tree's."""
    cd = compute_dtype(conf)
    out = W.dtype if master is None else jnp.promote_types(W.dtype, master)
    return (x.astype(cd) @ W.astype(cd)).astype(out)


def rows_broadcast(v, n_rows, dtype=None):
    """Broadcast a feature vector v[F] over n_rows rows as `ones @ v[None]`
    (a rank-1 gemm) rather than a plain numpy-style broadcast.

    Value-identical (1.0 * v_j is exact), but the TRANSPOSE — the batch-dim
    reduction autodiff emits for the broadcast's backward pass — lowers as a
    gemm contraction, which XLA evaluates bit-identically whatever the batch
    size.  A plain broadcast transposes to `reduce_sum` over the batch dim,
    whose pairwise-split strategy is shape-dependent: a remainder batch
    zero-padded into a larger bucket would then drift from the unpadded run
    by ~1 ulp in bias / BN-affine gradients, breaking the step cache's
    bit-for-bit padding guarantee."""
    dt = dtype or v.dtype
    return jnp.ones((n_rows, 1), dt) @ v[None, :].astype(dt)


class StatelessDecode:
    """The decode protocol (`nn/layers/__init__.py`) of a layer type that
    keeps nothing between tokens: `{}` for a state, and one token's row, a
    chunk's rows and a prompt's all go through `forward` as a sequence's
    would.  With nothing to keep it can page and be verified in a chunk."""

    CARRY = False

    @staticmethod
    def init_state(conf, batch: int, max_seq: int) -> dict:
        return {}

    @staticmethod
    def init_paged_state(conf, batch: int, n_pages: int, page_size: int) -> dict:
        return {}

    @classmethod
    def prefill(cls, params, conf, x, state, length):
        return cls.forward(params, conf, x), state

    @classmethod
    def decode_step(cls, params, conf, x, state, pos, page_table=None):
        return cls.forward(params, conf, x), state

    @classmethod
    def verify_chunk(cls, params, conf, x, state, pos, page_table=None):
        return cls.forward(params, conf, x), state, {}


class DenseLayer:
    """f(x.W + b) with optional dropout/dropconnect."""

    #: the leaves a generation program reads only as a weight product's
    #: operand, which `nn.decode.operand_tree` may hold in the compute dtype
    OPERANDS = ("W",)

    @staticmethod
    def init(key, conf):
        kw, _ = jax.random.split(key)
        dist = conf.dist.sampler() if conf.dist is not None else None
        return {
            "W": init_weights(kw, (conf.n_in, conf.n_out), conf.weight_init,
                              dist, _dtype(conf)),
            "b": jnp.zeros((conf.n_out,), _dtype(conf)),
        }

    @staticmethod
    def preout(params, conf, x, key=None, training=False):
        W = params["W"]
        if training and conf.drop_connect and key is not None:
            W = W * ndr.dropout_mask(key, 0.5, W.shape, W.dtype)
        z = mixed_matmul(x, W, conf, params["b"].dtype)
        if z.ndim == 2:  # gemm-broadcast the bias: pad-invariant bias grad
            return z + rows_broadcast(params["b"], z.shape[0], z.dtype)
        return z + params["b"]

    @staticmethod
    def forward(params, conf, x, key=None, training=False):
        kdrop = kdc = None
        if key is not None:
            kdrop, kdc = jax.random.split(key)
        if training and conf.dropout > 0.0 and kdrop is not None:
            x = x * ndr.dropout_mask(kdrop, 1.0 - conf.dropout, x.shape, x.dtype)
        z = DenseLayer.preout(params, conf, x, kdc, training)
        return activate(conf.activation, z)


class BatchNormLayer:
    """Batch normalization over the feature axis.

    Stateless-from-jit design: running stats live in params under "ema_*" and
    are updated outside jit by the training loop (or folded in via
    `forward(..., training=True)` which normalizes with batch stats).
    """

    @staticmethod
    def init(key, conf):
        n = conf.n_out or conf.n_in
        d = _dtype(conf)
        return {
            "gamma": jnp.ones((n,), d),
            "beta": jnp.zeros((n,), d),
            # bias-corrected running stats: raw EMA accumulators plus the
            # total EMA weight (1 - m^k); inference divides by ema_w so one
            # training batch already yields exact stats and the estimate is
            # never dominated by whichever batch came last
            "ema_mean": jnp.zeros((n,), d),
            "ema_var": jnp.zeros((n,), d),
            "ema_w": jnp.zeros((), d),
        }

    @staticmethod
    def _feature_axes(x):
        """Reduction axes: channel axis is 1 for NCHW conv outputs, -1 for
        dense features."""
        return (0, 2, 3) if x.ndim == 4 else tuple(range(x.ndim - 1))

    @staticmethod
    def moments(x, row_weights=None):
        """Raw batch moments (s1, s2, cnt) in f32, optionally row-weighted
        (pad rows of a masked remainder batch weigh 0 and are excluded).
        mean = s1/cnt, var = s2/cnt - mean^2.  Kept as raw sums so dp
        shards can psum them into GLOBAL-batch statistics."""
        axes = BatchNormLayer._feature_axes(x)
        xf = x.astype(jnp.float32)
        if x.ndim == 2:
            # express the batch-dim reductions as gemm contractions so the
            # moments (and their grads) are bit-invariant to zero-pad rows
            # — see `rows_broadcast` for why reduce_sum is not
            if row_weights is None:
                w1 = jnp.ones((1, x.shape[0]), jnp.float32)
            else:
                w1 = row_weights.reshape(1, -1).astype(jnp.float32)
            s1 = (w1 @ xf)[0]
            s2 = (w1 @ (xf * xf))[0]
            cnt = (w1 @ jnp.ones((x.shape[0], 1), jnp.float32))[0, 0]
            return s1, s2, cnt
        if x.ndim == 4:
            # NCHW conv activations: same gemm-contraction trick, with
            # channels as gemm rows and the flattened (n, h, w) positions
            # as the contraction axis.  n is the SLOWEST-varying column
            # index, so a batch zero-padded into a larger bucket only
            # appends trailing zero-weight columns — the contraction (and
            # its grads) stays bit-identical to the unpadded run.
            n, c = x.shape[0], x.shape[1]
            hw = x.shape[2] * x.shape[3]
            if row_weights is None:
                wv = jnp.ones((n * hw, 1), jnp.float32)
            else:
                # per-column weight = the column's batch-row weight,
                # expanded over h*w via an exact rank-1 product
                wv = (row_weights.reshape(-1, 1).astype(jnp.float32)
                      @ jnp.ones((1, hw), jnp.float32)).reshape(-1, 1)
            cols = xf.transpose(1, 0, 2, 3).reshape(c, n * hw)
            s1 = (cols @ wv)[:, 0]
            s2 = ((xf * xf).transpose(1, 0, 2, 3).reshape(c, n * hw)
                  @ wv)[:, 0]
            cnt = (jnp.ones((1, n * hw), jnp.float32) @ wv)[0, 0]
            return s1, s2, cnt
        if row_weights is None:
            cnt = jnp.asarray(float(np.prod([x.shape[a] for a in axes])),
                              jnp.float32)
            s1 = jnp.sum(xf, axis=axes)
            s2 = jnp.sum(xf * xf, axis=axes)
        else:
            w = row_weights.reshape((x.shape[0],) + (1,) * (x.ndim - 1))
            w = w.astype(jnp.float32)
            per_row = float(np.prod([x.shape[a] for a in axes if a != 0])
                            or 1.0)
            cnt = jnp.sum(w) * per_row
            s1 = jnp.sum(xf * w, axis=axes)
            s2 = jnp.sum(xf * xf * w, axis=axes)
        return s1, s2, cnt

    @staticmethod
    def stats_of(s1, s2, cnt):
        """(mean, var) from raw moments."""
        cnt = jnp.maximum(cnt, 1.0)
        mean = s1 / cnt
        var = jnp.maximum(s2 / cnt - mean * mean, 0.0)
        return mean, var

    @staticmethod
    def weighted_batch_stats(x, row_weights):
        """Batch mean/var over real rows only (pad rows weigh 0) — the
        masked remainder-batch path must not let zero padding skew the
        statistics the real rows are normalized with."""
        mean, var = BatchNormLayer.stats_of(
            *BatchNormLayer.moments(x, row_weights))
        return mean.astype(x.dtype), var.astype(x.dtype)

    @staticmethod
    def apply_stats(params, x, mean, var):
        """Normalize x with the given stats + the layer's affine."""
        eps = 1e-5
        if x.ndim == 4:
            # gemm-broadcast each per-channel vector over the (n, h, w)
            # positions: value-identical to a plain broadcast, but the
            # backward-pass batch reduction lowers as a gemm contraction
            # with trailing pad columns — pad-invariant gamma/beta (and
            # upstream mean/var) grads, mirroring the 2-D branch below
            n, h, w = x.shape[0], x.shape[2], x.shape[3]

            def bc(v):
                return (rows_broadcast(v, n * h * w, x.dtype)
                        .reshape(n, h, w, -1).transpose(0, 3, 1, 2))

            mean, var = bc(mean), bc(var)
            gamma, beta = bc(params["gamma"]), bc(params["beta"])
        elif x.ndim == 2:
            # gemm-broadcast every feature vector (pad-invariant grads for
            # gamma/beta and for whatever feeds mean/var — see rows_broadcast)
            n = x.shape[0]
            mean = rows_broadcast(mean, n, x.dtype)
            var = rows_broadcast(var, n, x.dtype)
            gamma = rows_broadcast(params["gamma"], n, x.dtype)
            beta = rows_broadcast(params["beta"], n, x.dtype)
        else:
            gamma, beta = params["gamma"], params["beta"]
        xn = (x - mean) / jnp.sqrt(var + eps)
        return xn * gamma + beta

    @staticmethod
    def forward(params, conf, x, key=None, training=False, row_weights=None):
        axes = BatchNormLayer._feature_axes(x)
        if training and row_weights is not None:
            mean, var = BatchNormLayer.weighted_batch_stats(x, row_weights)
        elif training:
            mean = jnp.mean(x, axis=axes)
            var = jnp.var(x, axis=axes)
        else:
            mean, var = params["ema_mean"], params["ema_var"]
            if "ema_w" in params:  # bias-corrected running estimate
                ema_w = params["ema_w"]
                denom = jnp.maximum(ema_w, 1e-8)
                mean = mean / denom
                # untrained (ema_w == 0): identity-ish normalization
                var = jnp.where(ema_w > 0, var / denom, jnp.ones_like(var))
        return BatchNormLayer.apply_stats(params, x, mean, var)


class EmbeddingLayer:
    """Integer ids -> embedding rows (gather; MXU-friendly one-hot matmul for
    tiny vocabularies is not worth it — XLA lowers gather well on TPU).

    With conf.max_seq_len > 0 a learned positional table is added over the
    sequence axis (transformer-LM input embedding)."""

    OPERANDS = ()       # `W` and `P` are gathered, not multiplied

    @staticmethod
    def init(key, conf):
        dist = conf.dist.sampler() if conf.dist is not None else None
        kw, kp = jax.random.split(key)
        params = {
            "W": init_weights(kw, (conf.n_in, conf.n_out), conf.weight_init,
                              dist, _dtype(conf)),
        }
        if conf.max_seq_len > 0:
            params["P"] = 0.02 * jax.random.normal(
                kp, (conf.max_seq_len, conf.n_out), _dtype(conf))
        return params

    @staticmethod
    def forward(params, conf, x, key=None, training=False):
        e = params["W"][x.astype(jnp.int32)]
        if "P" in params and e.ndim >= 2:
            s = e.shape[-2]
            if s > params["P"].shape[0]:
                raise ValueError(
                    f"sequence length {s} exceeds max_seq_len "
                    f"{params['P'].shape[0]}")
            e = e + params["P"][:s]
        return e
