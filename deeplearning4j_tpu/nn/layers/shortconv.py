"""The gated short convolution that stands in place of attention in LFM2's
convolution layers.  `conf.layer_spec` is a `ShortConvSpec`; the layer is
residual, [.., n_in], under an RMSNorm, and has no bias.

With u = RMSNorm(x) and d = n_in:
    [B; C; x~] = u W_in        W_in [d, 3d], split in three
    z_t = B_t * x~_t
    c_t = sum_{j<K} w_j * z_{t-K+1+j}     depthwise and causal, K taps
    o_t = (C_t * c_t) W_out    W_out [d, d]
No activation.  The products take operands in the compute dtype; z is kept
in it too (it is what the state carries, so a prompt and a decoded token
see the same inputs), and the convolution's sums and the gates are float32.

Decode state, a row a slot: {"conv": [B, K-1, d]} in the compute dtype, the
last K-1 values of z.  It is a carry: a prompt padded to its bucket leaves it
as it stood after token `length - 1`, and a decode step reads and writes it
whole.  It holds no position, so neither `init_paged_state` nor
`verify_chunk`: the state lives in the dense slot table only.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn.layers.base import compute_dtype
from deeplearning4j_tpu.nn.layers.rms import (F32, causal_conv, conv_tail,
                                              initializer, mm, pre_norm)
from deeplearning4j_tpu.utils.profiling import scope


class ShortConvLayer:
    CARRY = True        # advances with every token, read whole a step
    OPERANDS = ("in_proj", "out_proj")

    @staticmethod
    def init(key, conf):
        d, n = jnp.dtype(conf.dtype), conf.n_in
        ks = jax.random.split(key, 3)
        w = initializer(conf)
        return {"ln": jnp.ones((n,), d),
                "in_proj": w(ks[0], (n, 3 * n)),
                "conv": w(ks[1], (conf.layer_spec.kernel, n)),
                "out_proj": w(ks[2], (n, n))}

    @staticmethod
    def init_state(conf, batch: int, max_seq: int) -> dict:
        return {"conv": jnp.zeros((batch, conf.layer_spec.kernel - 1, conf.n_in),
                                  compute_dtype(conf))}

    @staticmethod
    def _gates(params, conf, x):
        """x [..., n] -> (C [..., n] float32, z = B * x~ [..., n] in the
        compute dtype)."""
        cd = compute_dtype(conf)
        u = pre_norm(params, x, conf.layer_spec.eps)
        with scope("in_proj"):
            bcx = mm(u, params["in_proj"], cd)
            b, c, xt = jnp.split(bcx, 3, axis=-1)
            return c, (b * xt).astype(cd)

    @staticmethod
    def _output(params, conf, x, c, y):
        with scope("out_proj"):
            return x.astype(F32) + mm(c * y, params["out_proj"], compute_dtype(conf))

    @staticmethod
    def prefill(params, conf, x, state, length):
        """x [B, T, n], zero-padded past each row's `length` [B]: (hidden
        [B, T, n], the tail after token `length - 1`)."""
        c, z = ShortConvLayer._gates(params, conf, x)
        with scope("conv"):
            y, seen = causal_conv(state["conv"], z, params["conv"])
            tail = conv_tail(seen, conf.layer_spec.kernel, length)
        return ShortConvLayer._output(params, conf, x, c, y), {"conv": tail}

    @staticmethod
    def decode_step(params, conf, x, state, pos):
        """One token a row: x [B, n] -> (hidden [B, n], the tail with this
        token's z shifted in)."""
        c, z = ShortConvLayer._gates(params, conf, x)
        with scope("conv"):
            y, seen = causal_conv(state["conv"], z, params["conv"])
        return (ShortConvLayer._output(params, conf, x, c, y),
                {"conv": conv_tail(seen, conf.layer_spec.kernel)})

    @staticmethod
    def forward(params, conf, x, key=None, training=False):
        """The whole sequence from a zero tail (no cache kept)."""
        b, t = x.shape[0], x.shape[1]
        out, _ = ShortConvLayer.prefill(params, conf, x,
                                        ShortConvLayer.init_state(conf, b, t),
                                        jnp.full((b,), t, jnp.int32))
        return out
