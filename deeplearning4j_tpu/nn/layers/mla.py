"""Multi-head latent attention (arXiv:2405.04434): every head's keys and
values come from one latent vector a token, which is all the cache keeps.
`conf.layer_spec` is an `MLASpec`; the layer is residual, [.., n_in].

With u = RMSNorm(x): q = Wq u in [H, nope + rope]; [c~; kr~] = Wkva u; c =
RMSNorm(c~), the latent; kr = RoPE(kr~), one rotary key for all heads;
[k_nope; v] = Wkvb c a head.  Scores are (q_nope . k_nope + RoPE(q_rope) .
kr) / sqrt(nope + rope), causal softmax, then Wo.

Decode state, a row a slot: {"c": [B, max_S, rank], "kr": [B, max_S, rope]},
both in the compute dtype.  One cache serves two ways.  `prefill`
materialises keys and values from the latents it has just written and
attends as any attention does.  `decode_step` absorbs Wkvb instead: the
query goes through the key half of Wkvb into the latent's space, scores are
taken against `c` and `kr` as cached, the softmax's weights sum the latents,
and the value half of Wkvb is applied after the sum: no key or value of a
cached position is ever formed.  Both attend to the latents as rounded to
the cache's type, so the two agree to rounding.

A padded prompt needs no care: positions past a row's length are written,
never attended to by a real one, and overwritten before they are read.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn.layers.base import compute_dtype
from deeplearning4j_tpu.nn.layers.rms import (F32, initializer, mm, pre_norm,
                                              precision_of, rms_norm, rope)
from deeplearning4j_tpu.utils.profiling import scope


class MLALayer:
    CARRY = False       # a finished row rewrites one cell with what it holds

    @staticmethod
    def init(key, conf):
        s = conf.layer_spec
        d, n, h = jnp.dtype(conf.dtype), conf.n_in, s.n_heads
        ks = jax.random.split(key, 4)
        w = initializer(conf)
        return {
            "ln": jnp.ones((n,), d),
            "Wq": w(ks[0], (n, h * (s.qk_nope_head_dim + s.qk_rope_head_dim))),
            "Wkva": w(ks[1], (n, s.kv_lora_rank + s.qk_rope_head_dim)),
            "c_norm": jnp.ones((s.kv_lora_rank,), d),
            "Wkvb": w(ks[2], (s.kv_lora_rank,
                              h * (s.qk_nope_head_dim + s.v_head_dim))),
            "Wo": w(ks[3], (h * s.v_head_dim, n)),
        }

    @staticmethod
    def init_state(conf, batch: int, max_seq: int) -> dict:
        s, cd = conf.layer_spec, compute_dtype(conf)
        return {"c": jnp.zeros((batch, max_seq, s.kv_lora_rank), cd),
                "kr": jnp.zeros((batch, max_seq, s.qk_rope_head_dim), cd)}

    @staticmethod
    def _project(params, conf, x, positions):
        """x [..., n] at `positions` [...] -> q_nope [..., H, nope], q_rope
        [..., H, rope] (rotated), and in the cache's type the latent c
        [..., rank] and the rotary key kr [..., rope]."""
        s, cd = conf.layer_spec, compute_dtype(conf)
        u = pre_norm(params, x, s.eps)
        with scope("qkv"):
            q = mm(u, params["Wq"], cd)
            q = q.reshape(q.shape[:-1] + (s.n_heads, -1))
            kva = mm(u, params["Wkva"], cd)
            c = rms_norm(kva[..., :s.kv_lora_rank], params["c_norm"], s.eps)
        with scope("rope"):
            q_rope = rope(q[..., s.qk_nope_head_dim:], positions[..., None],
                          s.rope_theta)
            kr = rope(kva[..., s.kv_lora_rank:], positions, s.rope_theta)
        return q[..., :s.qk_nope_head_dim], q_rope, c.astype(cd), kr.astype(cd)

    @staticmethod
    def _halves(params, conf):
        """Wkvb as (key half, value half), each [rank, H, .]."""
        s = conf.layer_spec
        w = params["Wkvb"].reshape(s.kv_lora_rank, s.n_heads, -1)
        return w[..., :s.qk_nope_head_dim], w[..., s.qk_nope_head_dim:]

    @staticmethod
    def prefill(params, conf, x, state, length):
        s, cd = conf.layer_spec, compute_dtype(conf)
        b, t, _ = x.shape
        hi = precision_of(cd)
        q_nope, q_rope, c, kr = MLALayer._project(
            params, conf, x, jnp.broadcast_to(jnp.arange(t), (b, t)))
        with scope("latent_write"):
            state = {"c": jax.lax.dynamic_update_slice(state["c"], c, (0, 0, 0)),
                     "kr": jax.lax.dynamic_update_slice(state["kr"], kr, (0, 0, 0))}
        w_k, w_v = MLALayer._halves(params, conf)
        with scope("qkv"):          # keys and values of the prompt, materialised
            k_nope = jnp.einsum("bsr,rhn->bshn", c, w_k.astype(cd), precision=hi,
                                preferred_element_type=F32).astype(cd)
            v = jnp.einsum("bsr,rhv->bshv", c, w_v.astype(cd), precision=hi,
                           preferred_element_type=F32).astype(cd)
        with scope("scores"):
            sc = (jnp.einsum("bqhn,bkhn->bhqk", q_nope.astype(cd), k_nope,
                             precision=hi, preferred_element_type=F32)
                  + jnp.einsum("bqhe,bke->bhqk", q_rope.astype(cd), kr,
                               precision=hi, preferred_element_type=F32))
            sc = sc / math.sqrt(s.qk_nope_head_dim + s.qk_rope_head_dim)
            causal = jnp.tril(jnp.ones((t, t), bool))
            p = jax.nn.softmax(jnp.where(causal, sc, -1e30), axis=-1)
        with scope("attend"):
            o = jnp.einsum("bhqk,bkhv->bqhv", p.astype(cd), v, precision=hi,
                           preferred_element_type=F32)
        with scope("proj"):
            out = mm(o.reshape(b, t, -1), params["Wo"], cd)
        return x.astype(F32) + out, state

    @staticmethod
    def decode_step(params, conf, x, state, pos):
        s, cd = conf.layer_spec, compute_dtype(conf)
        b = x.shape[0]
        hi = precision_of(cd)
        q_nope, q_rope, c, kr = MLALayer._project(params, conf, x, pos)
        with scope("latent_write"):
            rows = jnp.arange(b)
            c_all = state["c"].at[rows, pos].set(c)
            kr_all = state["kr"].at[rows, pos].set(kr)
        w_k, w_v = MLALayer._halves(params, conf)
        with scope("absorb"):
            q_lat = jnp.einsum("bhn,rhn->bhr", q_nope.astype(cd), w_k.astype(cd),
                               precision=hi, preferred_element_type=F32)
        with scope("scores"):
            sc = (jnp.einsum("bhr,bsr->bhs", q_lat.astype(cd), c_all,
                             precision=hi, preferred_element_type=F32)
                  + jnp.einsum("bhe,bse->bhs", q_rope.astype(cd), kr_all,
                               precision=hi, preferred_element_type=F32))
            sc = sc / math.sqrt(s.qk_nope_head_dim + s.qk_rope_head_dim)
            seen = jnp.arange(c_all.shape[1])[None, :] <= pos[:, None]
            p = jax.nn.softmax(jnp.where(seen[:, None, :], sc, -1e30), axis=-1)
        with scope("attend"):
            lat = jnp.einsum("bhs,bsr->bhr", p.astype(cd), c_all, precision=hi,
                             preferred_element_type=F32)
        with scope("absorb"):
            o = jnp.einsum("bhr,rhv->bhv", lat.astype(cd), w_v.astype(cd),
                           precision=hi, preferred_element_type=F32)
        with scope("proj"):
            out = mm(o.reshape(b, -1), params["Wo"], cd)
        return x.astype(F32) + out, {"c": c_all, "kr": kr_all}

    @staticmethod
    def forward(params, conf, x, key=None, training=False):
        """The whole sequence, materialised; the cache it fills is dropped."""
        b, t = x.shape[0], x.shape[1]
        out, _ = MLALayer.prefill(params, conf, x,
                                  MLALayer.init_state(conf, b, t), None)
        return out
