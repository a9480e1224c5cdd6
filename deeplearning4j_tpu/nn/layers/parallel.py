"""Attention and an expert FFN side by side off one norm: LayerType.PARALLEL,
`conf.layer_spec` a `ParallelSpec`.  The layer is residual over [.., n_in]:

    u = LayerNorm(x)    (a weight, no bias, float32)
    x + Attn(u) + MoE(u)

Attn is `gqa.GQALayer`'s branch under the spec's `attn` (a `GQASpec`), MoE
`experts.MoELayer`'s under its `ffn` (a `MoESpec`): each from the normed
input to its output, with no norm and no residual of its own.  The
parameters are `{"ln", "attn": {...}, "ffn": {...}}`, each branch's leaves
as its own layer type holds them but for its unused norm.

The decode state is the attention's, and every question about it
(`init_state`, `kv_cells`, `kv_cells_read`, `kv_read_block`,
`kv_cell_bytes`) is answered by the mixer, which also chooses on its own
whether its step walks a table through `dl4j_gqa_decode`; the counts of the
experts' picks (`counted_step`) and the form of their product
(`product_form`) by the FFN.  Scopes: `ln`, then the mixer's (`qkv`,
`rope`, `kv_write`, `kv_read` or `gqa_attend`, `scores`, `attend`, `proj`)
and the FFN's (`router`, `dispatch`, `experts` or `experts_batched`,
`shared`, `combine`), all inside `L<i>.parallel_window` or
`L<i>.parallel_full`.  Like `gqa`, no paged state and no verify chunk.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn.layers.experts import MoELayer
from deeplearning4j_tpu.nn.layers.gqa import GQALayer
from deeplearning4j_tpu.nn.layers.rms import F32, layer_norm
from deeplearning4j_tpu.utils.profiling import scope


def _attn(conf):
    return conf.replace(layer_spec=conf.layer_spec.attn)


def _ffn(conf):
    return conf.replace(layer_spec=conf.layer_spec.ffn)


def _norm(params, conf, x):
    with scope("ln"):
        return layer_norm(x, params["ln"], None, conf.layer_spec.eps)


class ParallelLayer:
    CARRY = False       # the attention's table or ring
    #: each branch's operands, by their path in the layer's params
    OPERANDS = (tuple(("attn", name) for name in GQALayer.OPERANDS)
                + tuple(("ffn", name) for name in MoELayer.OPERANDS))

    @staticmethod
    def init(key, conf):
        ka, kf = jax.random.split(key)

        def own(params):
            return {k: v for k, v in params.items() if k != "ln"}

        return {"ln": jnp.ones((conf.n_in,), jnp.dtype(conf.dtype)),
                "attn": own(GQALayer.init(ka, _attn(conf))),
                "ffn": own(MoELayer.init(kf, _ffn(conf)))}

    @staticmethod
    def kv_cells(conf, max_seq: int) -> int:
        return GQALayer.kv_cells(_attn(conf), max_seq)

    @staticmethod
    def kv_cells_read(conf, max_seq: int) -> int:
        return GQALayer.kv_cells_read(_attn(conf), max_seq)

    @staticmethod
    def kv_read_block(conf, max_seq: int) -> int:
        return GQALayer.kv_read_block(_attn(conf), max_seq)

    @staticmethod
    def kv_cell_bytes(conf, max_seq: int) -> int:
        return GQALayer.kv_cell_bytes(_attn(conf), max_seq)

    @staticmethod
    def init_state(conf, batch: int, max_seq: int) -> dict:
        return GQALayer.init_state(_attn(conf), batch, max_seq)

    @staticmethod
    def product_form(conf, rows: int) -> str:
        return MoELayer.product_form(_ffn(conf), rows)

    @staticmethod
    def prefill(params, conf, x, state, length):
        b, t, _ = x.shape
        at = jnp.broadcast_to(jnp.arange(t), (b, t))
        u = _norm(params, conf, x)
        a, state = GQALayer.branch_prefill(params["attn"], _attn(conf), u, at,
                                           state, length)
        f, _ = MoELayer.branch(params["ffn"], _ffn(conf), u)
        return x.astype(F32) + a + f, state

    @staticmethod
    def counted_step(params, conf, x, state, pos):
        """`decode_step` with the experts' counts: (hidden, state, counts)."""
        u = _norm(params, conf, x)
        a, state = GQALayer.branch_step(params["attn"], _attn(conf), u, state, pos)
        f, counts = MoELayer.branch(params["ffn"], _ffn(conf), u)
        return x.astype(F32) + a + f, state, counts

    @staticmethod
    def decode_step(params, conf, x, state, pos):
        x, state, _ = ParallelLayer.counted_step(params, conf, x, state, pos)
        return x, state

    @staticmethod
    def forward(params, conf, x, key=None, training=False):
        """The whole sequence; the state it fills is dropped."""
        b, t = x.shape[0], x.shape[1]
        out, _ = ParallelLayer.prefill(params, conf, x,
                                       ParallelLayer.init_state(conf, b, t), None)
        return out
