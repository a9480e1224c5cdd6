"""Gated DeltaNet (arXiv:2412.06464) as Qwen3-Next runs it: linear attention
whose state is a matrix a value head, decayed by one number a head and
corrected by the delta rule.  `conf.layer_spec` is a `GDNSpec`; the layer is
residual, [.., n_in].

With u = RMSNorm(x), Hk key heads and Hv = r Hk value heads:
[q~, k~, v~, z] = Wqkvz u (q~, k~ in [Hk, dk], v~, z in [Hv, dv]) and [b, a]
= Wba u (Hv each); q~, k~, v~ through a depthwise causal convolution of
`conv_kernel` taps and SiLU; q = l2norm(q~) / sqrt(dk), k = l2norm(k~), head
by head, key head j serving value heads j r .. j r + r - 1; beta =
sigmoid(b); g = -exp(A_log) * softplus(a + dt_bias), a number a value head.
    S' = exp(g_t) S_{t-1}
    S_t = S' + beta_t k_t (v_t - k_t^T S')^T          o_t = S_t^T q_t
The output is RMSNorm(o_t) a value head, times silu(z), then Wo.  No rotary
positions: the decay orders the tokens.  The recurrence is `kda.py`'s, with
a decay of one number where KDA has one a channel (`kda.delta_step`,
`kda.chunked_delta_rule`).

Decode state, a row a slot: {"S": [B, Hv, dk, dv] float32, "conv": [B, K-1,
2 Hk dk + Hv dv] compute dtype}, the last K-1 inputs of the convolution.  It
is a carry, as KDA's: padded prompt positions get g = 0 and beta = 0, under
which the recurrence is the identity, and a decode step reads and writes it
whole.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn.layers.base import compute_dtype
from deeplearning4j_tpu.nn.layers.kda import chunked_delta_rule, delta_step
from deeplearning4j_tpu.nn.layers.rms import (F32, causal_conv, conv_tail,
                                              initializer, l2norm, mm, pre_norm,
                                              rms_norm)
from deeplearning4j_tpu.utils.profiling import scope


def _widths(conf):
    """spec, the convolution's channels (q, k and v) and the value heads'
    width (v and z each)."""
    s = conf.layer_spec
    return s, 2 * s.n_k_heads * s.k_dim + s.n_v_heads * s.v_dim, s.n_v_heads * s.v_dim


class GDNLayer:
    CARRY = True        # advances with every token, read whole a step
    OPERANDS = ("Wqkvz", "Wba", "Wo")

    @staticmethod
    def init(key, conf):
        s, c, vz = _widths(conf)
        d, n = jnp.dtype(conf.dtype), conf.n_in
        ks = jax.random.split(key, 4)
        w = initializer(conf)
        return {
            "ln": jnp.ones((n,), d),
            "Wqkvz": w(ks[0], (n, c + vz)),
            "conv": w(ks[1], (s.conv_kernel, c)),
            "Wba": w(ks[2], (n, 2 * s.n_v_heads)),
            "A_log": jnp.zeros((s.n_v_heads,), d),
            "dt_bias": jnp.full((s.n_v_heads,), -4.0, d),
            "o_norm": jnp.ones((s.v_dim,), d),
            "Wo": w(ks[3], (vz, n)),
        }

    @staticmethod
    def init_state(conf, batch: int, max_seq: int) -> dict:
        s, c, _ = _widths(conf)
        return {"S": jnp.zeros((batch, s.n_v_heads, s.k_dim, s.v_dim), F32),
                "conv": jnp.zeros((batch, s.conv_kernel - 1, c), compute_dtype(conf))}

    @staticmethod
    def _project(params, conf, x):
        """x [..., n] -> (qkv~ [..., c] in the compute dtype, which is what
        the convolution's cache keeps; z [..., Hv, dv]; g [..., Hv, 1];
        beta [..., Hv])."""
        s, c, _ = _widths(conf)
        cd = compute_dtype(conf)
        u = pre_norm(params, x, s.eps)
        with scope("qkvz"):
            qkvz = mm(u, params["Wqkvz"], cd)
        z = qkvz[..., c:].reshape(qkvz.shape[:-1] + (s.n_v_heads, s.v_dim))
        with scope("gate"):
            ba = mm(u, params["Wba"], cd)
            beta = jax.nn.sigmoid(ba[..., :s.n_v_heads])
            g = -jnp.exp(params["A_log"].astype(F32)) * jax.nn.softplus(
                ba[..., s.n_v_heads:] + params["dt_bias"].astype(F32))
        return qkvz[..., :c].astype(cd), z, g[..., None], beta

    @staticmethod
    def _heads(conf, y):
        """The convolution's output [..., c] -> q, k [..., Hv, dk] (each key
        head repeated for the value heads it serves), v [..., Hv, dv]."""
        s, _, _ = _widths(conf)
        qk = s.n_k_heads * s.k_dim
        r = s.n_v_heads // s.n_k_heads
        q, k = (y[..., i * qk:(i + 1) * qk].reshape(y.shape[:-1] + (s.n_k_heads, s.k_dim))
                for i in range(2))
        v = y[..., 2 * qk:].reshape(y.shape[:-1] + (s.n_v_heads, s.v_dim))
        q, k = l2norm(q) / math.sqrt(s.k_dim), l2norm(k)
        return jnp.repeat(q, r, axis=-2), jnp.repeat(k, r, axis=-2), v

    @staticmethod
    def _output(params, conf, x, o, z):
        """o, z [..., Hv, dv] -> the block's output."""
        s, _, vz = _widths(conf)
        with scope("norm_gate"):
            o = rms_norm(o, params["o_norm"], s.eps) * jax.nn.silu(z)
        with scope("proj"):
            out = mm(o.reshape(o.shape[:-2] + (vz,)), params["Wo"], compute_dtype(conf))
        return x.astype(F32) + out

    @staticmethod
    def prefill(params, conf, x, state, length):
        """x [B, T, n], zero-padded past each row's `length` [B]; `state`
        the rows' state before the prompt.  Returns (hidden [B, T, n],
        state after token `length - 1`)."""
        t = x.shape[1]
        qkv, z, g, beta = GDNLayer._project(params, conf, x)
        with scope("conv"):
            y, seen = causal_conv(state["conv"], qkv, params["conv"])
            y = jax.nn.silu(y)
            conv = conv_tail(seen, conf.layer_spec.conv_kernel, length)
        q, k, v = GDNLayer._heads(conf, y)
        real = (jnp.arange(t)[None, :] < length[:, None])[..., None]
        g = jnp.where(real[..., None], g, 0.0)
        beta = jnp.where(real, beta, 0.0)
        with scope("state_update"):
            o, s = chunked_delta_rule(q, k, v, g, beta, state["S"])
        return (GDNLayer._output(params, conf, x, o, z),
                {"S": s, "conv": conv.astype(state["conv"].dtype)})

    @staticmethod
    def decode_step(params, conf, x, state, pos):
        """One token a row: x [B, n] -> (hidden [B, n], advanced state)."""
        qkv, z, g, beta = GDNLayer._project(params, conf, x)
        with scope("conv"):
            y, seen = causal_conv(state["conv"], qkv, params["conv"])
            y = jax.nn.silu(y)
        q, k, v = GDNLayer._heads(conf, y)
        o, s = delta_step(q, k, v, g, beta, state["S"])
        return (GDNLayer._output(params, conf, x, o, z),
                {"S": s, "conv": conv_tail(seen, conf.layer_spec.conv_kernel)})

    @staticmethod
    def forward(params, conf, x, key=None, training=False):
        """The whole sequence from a zero state (no cache kept)."""
        b, t = x.shape[0], x.shape[1]
        out, _ = GDNLayer.prefill(params, conf, x,
                                  GDNLayer.init_state(conf, b, t),
                                  jnp.full((b,), t, jnp.int32))
        return out
