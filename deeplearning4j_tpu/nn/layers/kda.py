"""Kimi Delta Attention (arXiv:2510.26692): linear attention whose state is
a matrix a head, decayed a channel at a time and corrected by the delta
rule.  `conf.layer_spec` is a `KDASpec`; the layer is residual, [.., n_in].

With u = RMSNorm(x): q~, k~, v~ = Wqkv u, each through a depthwise causal
convolution of `conv_kernel` taps and SiLU; q = l2norm(q~) / sqrt(dk), k =
l2norm(k~), head by head; g = gate_lower_bound * sigmoid(exp(A_log_h) *
(Wa u + dt_bias)), a number a channel; beta = sigmoid(.), a number a head.
    S' = diag(exp(g_t)) S_{t-1}
    S_t = S' + beta_t k_t (v_t - k_t^T S')^T          o_t = S_t^T q_t
The output is RMSNorm(o_t) head by head, times a sigmoid gate a head, then
Wo.  No rotary positions: the decay orders the tokens.

Decode state, a row a slot: {"S": [B, H, dk, dv] float32, "conv": [B, K-1,
3 H dk] compute dtype}, the last K-1 inputs of the convolution.  It is a
carry: a prompt padded to its bucket leaves both as they were at `length -
1` (padded positions get g = 0 and beta = 0, under which the recurrence is
the identity), and `nn.decode.decode_block` keeps a finished row's.

`prefill` runs the recurrence in chunks of `CHUNK` tokens.  Inside a chunk,
with G_t the running sum of g from the chunk's start and S_0 the state
before it, S_t = diag(e^{G_t}) S_0 + sum_{s<=t} diag(e^{G_t - G_s}) k_s w_s^T,
where the corrected values w solve the unit lower-triangular system
    (I + diag(beta) M) W = diag(beta) (V - (e^G * K) S_0),
    M[t, s] = sum_i k_t[i] k_s[i] e^{G_t[i] - G_s[i]}   (s < t),
and o_t = S_0^T (e^{G_t} * q_t) + sum_{s<=t} A[t, s] w_s with A as M but
from q_t and with its diagonal.  Every exponent is a difference G_t - G_s
with s <= t, so none is positive and nothing overflows however fast a
channel decays; the state's arithmetic is float32 throughout.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn.layers.base import compute_dtype
from deeplearning4j_tpu.nn.layers.rms import (F32, initializer, l2norm, mm,
                                              pre_norm, rms_norm)
from deeplearning4j_tpu.utils.profiling import scope

CHUNK = 64
_HI = jax.lax.Precision.HIGHEST


def _widths(conf):
    spec = conf.layer_spec
    return spec, spec.n_heads, spec.head_dim, spec.n_heads * spec.head_dim


class KDALayer:
    CARRY = True        # a finished row's state must not advance

    @staticmethod
    def init(key, conf):
        spec, h, dk, c = _widths(conf)
        d, n = jnp.dtype(conf.dtype), conf.n_in
        ks = jax.random.split(key, 5)
        w = initializer(conf)
        return {
            "ln": jnp.ones((n,), d),
            "Wqkv": w(ks[0], (n, 3 * c)),
            "conv": w(ks[1], (spec.conv_kernel, 3 * c)),
            "Wa": w(ks[2], (n, c)),
            "A_log": jnp.zeros((h,), d),
            "dt_bias": jnp.full((c,), -4.0, d),
            "Wbg": w(ks[3], (n, 2 * h)),
            "o_norm": jnp.ones((dk,), d),
            "Wo": w(ks[4], (c, n)),
        }

    @staticmethod
    def init_state(conf, batch: int, max_seq: int) -> dict:
        spec, h, dk, c = _widths(conf)
        return {"S": jnp.zeros((batch, h, dk, dk), F32),
                "conv": jnp.zeros((batch, spec.conv_kernel - 1, 3 * c),
                                  compute_dtype(conf))}

    @staticmethod
    def _project(params, conf, x):
        """x [..., n] -> (qkv~ [..., 3c] in the compute dtype, which is what
        the convolution's cache keeps; g [..., h, dk]; beta, gate [..., h])."""
        spec, h, dk, c = _widths(conf)
        cd = compute_dtype(conf)
        u = pre_norm(params, x, spec.eps)
        with scope("qkv"):
            qkv = mm(u, params["Wqkv"], cd).astype(cd)
        with scope("gate"):
            a = mm(u, params["Wa"], cd) + params["dt_bias"].astype(F32)
            rate = jnp.exp(params["A_log"].astype(F32))[:, None]
            g = spec.gate_lower_bound * jax.nn.sigmoid(
                rate * a.reshape(a.shape[:-1] + (h, dk)))
            bg = jax.nn.sigmoid(mm(u, params["Wbg"], cd))
        return qkv, g, bg[..., :h], bg[..., h:]

    @staticmethod
    def _heads(conf, y):
        """The convolution's output [..., 3c] -> q, k, v [..., h, dk]."""
        _, h, dk, c = _widths(conf)
        q, k, v = (y[..., i * c:(i + 1) * c].reshape(y.shape[:-1] + (h, dk))
                   for i in range(3))
        return l2norm(q) / math.sqrt(dk), l2norm(k), v

    @staticmethod
    def _output(params, conf, x, o, gate):
        """o [..., h, dv], gate [..., h] -> the block's output."""
        spec, h, dk, c = _widths(conf)
        with scope("gate"):
            o = rms_norm(o, params["o_norm"], spec.eps) * gate[..., None]
        with scope("proj"):
            out = mm(o.reshape(o.shape[:-2] + (c,)), params["Wo"],
                     compute_dtype(conf))
        return x.astype(F32) + out

    @staticmethod
    def prefill(params, conf, x, state, length):
        """x [B, T, n], zero-padded past each row's `length` [B]; `state`
        the rows' state before the prompt.  Returns (hidden [B, T, n],
        state after token `length - 1`)."""
        spec = conf.layer_spec
        kk = spec.conv_kernel
        t = x.shape[1]
        qkv, g, beta, gate = KDALayer._project(params, conf, x)
        with scope("conv"):
            seen = jnp.concatenate([state["conv"], qkv], axis=1)   # [B, K-1+T, 3c]
            w = params["conv"].astype(F32)
            y = jax.nn.silu(sum(seen[:, j: j + t].astype(F32) * w[j]
                                for j in range(kk)))
            # the inputs of tokens length-K+1 .. length-1 sit at seen[length:]
            conv = jax.vmap(lambda row, n: jax.lax.dynamic_slice_in_dim(
                row, n, kk - 1, axis=0))(seen, length)
        q, k, v = KDALayer._heads(conf, y)
        real = (jnp.arange(t)[None, :] < length[:, None])
        g = jnp.where(real[..., None, None], g, 0.0)
        beta = jnp.where(real[..., None], beta, 0.0)
        with scope("state_update"):
            o, s = chunked_delta_rule(q, k, v, g, beta, state["S"])
        return (KDALayer._output(params, conf, x, o, gate),
                {"S": s, "conv": conv.astype(state["conv"].dtype)})

    @staticmethod
    def decode_step(params, conf, x, state, pos):
        """One token a row: x [B, n] -> (hidden [B, n], advanced state)."""
        qkv, g, beta, gate = KDALayer._project(params, conf, x)
        with scope("conv"):
            seen = jnp.concatenate([state["conv"], qkv[:, None]], axis=1)
            w = params["conv"].astype(F32)
            y = jax.nn.silu(jnp.sum(seen.astype(F32) * w[None], axis=1))
        q, k, v = KDALayer._heads(conf, y)
        with scope("state_update"):
            s = jnp.exp(g)[..., None] * state["S"]
            ks = jnp.sum(k[..., None] * s, axis=-2)
            s = s + (beta[..., None] * k)[..., None] * (v - ks)[..., None, :]
        with scope("state_read"):
            o = jnp.sum(q[..., None] * s, axis=-2)
        return (KDALayer._output(params, conf, x, o, gate),
                {"S": s, "conv": seen[:, 1:]})

    @staticmethod
    def forward(params, conf, x, key=None, training=False):
        """The whole sequence from a zero state (no cache kept)."""
        b, t = x.shape[0], x.shape[1]
        out, _ = KDALayer.prefill(params, conf, x,
                                  KDALayer.init_state(conf, b, t),
                                  jnp.full((b,), t, jnp.int32))
        return out


def chunked_delta_rule(q, k, v, g, beta, state, chunk: int = CHUNK):
    """The recurrence of the module's docstring over q, k, v, g [B, T, H, dk]
    and beta [B, T, H] from `state` [B, H, dk, dv], `chunk` tokens at a
    time.  Returns (o [B, T, H, dv], the state after token T - 1).  A
    position with g = 0 and beta = 0 leaves the state as it was."""
    b, t, h, dk = q.shape
    c = min(chunk, t)
    pad = -t % c
    if pad:
        q, k, v, g = (jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0)))
                      for a in (q, k, v, g))
        beta = jnp.pad(beta, ((0, 0), (0, pad), (0, 0)))
    n = (t + pad) // c

    def chunks(a):                      # [B, T, H, ...] -> [n, B, H, c, ...]
        a = a.reshape((b, n, c) + a.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(a, 3, 2), 1, 0)

    lower = jnp.tril(jnp.ones((c, c), bool))
    strict = jnp.tril(jnp.ones((c, c), bool), -1)

    def one(s0, xs):
        qc, kc, vc, gc, bc = xs                          # [B, H, c, dk]; bc [B, H, c]
        big = jnp.cumsum(gc, axis=2)                     # G_t
        diff = big[:, :, :, None, :] - big[:, :, None, :, :]
        decay = jnp.exp(jnp.where(lower[:, :, None], diff, -jnp.inf))
        m = jnp.sum(kc[:, :, :, None, :] * kc[:, :, None, :, :] * decay, axis=-1)
        a = jnp.sum(qc[:, :, :, None, :] * kc[:, :, None, :, :] * decay, axis=-1)
        grown = jnp.exp(big)
        rhs = bc[..., None] * (vc - jnp.einsum(
            "bhci,bhij->bhcj", kc * grown, s0, precision=_HI))
        system = (jnp.eye(c, dtype=F32)
                  + bc[..., None] * jnp.where(strict, m, 0.0))
        w = jax.scipy.linalg.solve_triangular(system, rhs, lower=True,
                                              unit_diagonal=True)
        o = (jnp.einsum("bhci,bhij->bhcj", qc * grown, s0, precision=_HI)
             + jnp.einsum("bhts,bhsj->bhtj", a, w, precision=_HI))
        last = big[:, :, -1:, :]
        s1 = (jnp.exp(last[:, :, 0, :])[..., None] * s0
              + jnp.einsum("bhci,bhcj->bhij", kc * jnp.exp(last - big), w,
                           precision=_HI))
        return s1, o

    state, o = jax.lax.scan(one, state.astype(F32),
                            tuple(chunks(a.astype(F32)) for a in (q, k, v, g))
                            + (jnp.moveaxis(beta.astype(F32).reshape(b, n, c, h),
                                            (1, 3), (0, 2)),))
    # [n, B, H, c, dv] -> [B, T, H, dv]
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 1), 2, 3).reshape(b, n * c, h, -1)
    return o[:, :t], state
