"""The plain reference of Command A+'s language model: weights from a seed,
and its layers in straightforward float32 `jax.numpy`, with no cache, no
kernels and no batching tricks.  Nothing here imports the program.

d = `hidden_size`, H = `num_attention_heads` query heads over G =
`num_key_value_heads` K/V heads of h = `head_dim`, no bias anywhere.

  x_0 = E[id]                                  (no scale)
  every layer i (`use_parallel_block`):
    u = LN_i(x)                                (a LayerNorm with a weight and
                                                no bias, eps `layer_norm_eps`)
    x <- x + Attn_i(u) + MoE_i(u)
  logits = logit_scale * LN_f(x) E^T           (`tie_word_embeddings`: the
                                                embedding's own table)

Attention: q = u Wq in [H, h], k = u Wk and v = u Wv in [G, h], no QK-norm;
  query head j reads K/V head j // (H / G); scores q . k / sqrt(h), float32
  softmax, then Wo over the heads side by side.  Layer i attends over a
  window where `layer_types[i]` is `sliding_attention`: q and k rotated in
  interleaved pairs over all h dimensions (`position_embedding_type`
  `rope_gptj`, `rotary_pct` 1: dimensions 2m and 2m + 1 turn by
  p * theta^(-2m/h)), and a token sees itself and the `sliding_window` - 1
  positions before it.  Where it is `full_attention` nothing is rotated
  (NoPE) and a token sees every position up to its own.  The [T, T] scores
  are formed a block of query rows at a time against every key, under the
  full mask: there is no cache and no band.
Experts: s = sigmoid(u Wr) over all published `num_experts` in float32; T the
  `num_experts_per_tok` largest (one group, no bias); w_e = s_e / sum_T s
  (`norm_topk_prob`); E_e(u) = (silu(u G_e) * u U_e) D_e;
  MoE(u) = sum_{e in T, e held} w_e E_e(u) + (1/S) sum_{j<S} S_j(u), the S =
  `num_shared_experts` shared experts averaged
  (`shared_expert_combination_strategy` `average`).  Every held expert runs
  over every row, one at a time, weighted by the row's weight for it.

This chip is a rank of an expert-parallel group (`deployment`): it holds
`num_experts` of the published count from `rank * num_experts` on, and
`vocab_size` ids of the published vocabulary, the embedding's first rows.
What the absent experts would add is left out.

The weights are a function of (configuration, seed, layer, leaf) alone, an
expert's of its published id, and of the published depth through the scale
of the matrices that write the residual stream: the layers of a cut in depth
are the first layers of the uncut model (`uncut`).  The embedding is drawn at
the slice's own shape.  Weights are drawn in float32 and, where
`flags.param_dtype` says bfloat16, rounded to it once, here: the program and
this reference then hold the same numbers, and this reference computes with
them in float32.  Every product with a weight, and attention's scores and
values, go through the shared `_mm`, whose `precision="int8"` is the control
of `correct`; the router's logits stay float32 `highest`.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import Frozen, _mm, base_key

_W_STD = 0.02      # every matrix; those that write the residual stream are
_G_STD = 0.02      # scaled by 1/sqrt(2 L), L the published depth.  Norm
                   # weights lie about 1, so that a path that drops one shows
_HI = jax.lax.Precision.HIGHEST
_HEAD_INDEX = 1 << 20   # the head's key: the same in the cut and the uncut

#: the published switches this reference writes down; any other value refuses
SWITCHES = {
    "use_parallel_block": True, "tie_word_embeddings": True, "use_qk_norm": False,
    "attention_bias": False, "position_embedding_type": "rope_gptj", "rotary_pct": 1,
    "expert_selection_fn": "sigmoid", "norm_topk_prob": True,
    "shared_expert_combination_strategy": "average", "first_k_dense_replace": 0,
    "use_gated_activation": True, "hidden_act": "silu",
}


def sizes(cfg: dict) -> dict:
    held = int(cfg["num_experts"])
    published = cfg.get("published", {})
    return {
        "d": int(cfg["hidden_size"]), "heads": int(cfg["num_attention_heads"]),
        "kv_heads": int(cfg["num_key_value_heads"]), "head_dim": int(cfg["head_dim"]),
        "layers": int(cfg["num_hidden_layers"]),
        "depth_published": int(published.get("num_hidden_layers", cfg["num_hidden_layers"])),
        "vocab": int(cfg["vocab_size"]),
        "positions": int(cfg["max_position_embeddings"]),
        "expert_ffn": int(cfg["intermediate_size"]),
        "experts_held": held,
        "experts_routed": int(published.get("num_experts", held)),
        "first_expert": held * int(cfg.get("deployment", {}).get("rank", 0)),
        "top_k": int(cfg["num_experts_per_tok"]),
        "shared": int(cfg["num_shared_experts"]),
        "window": int(cfg["sliding_window"]),
        "theta": float(cfg["rope_parameters"]["rope_theta"]),
        "eps": float(cfg["layer_norm_eps"]),
        "logit_scale": float(cfg["logit_scale"]),
    }


def layer_kinds(cfg: dict) -> list:
    """embed, then window | full a parallel block, then head (the final norm;
    its matrix is the embedding's)."""
    for key, value in SWITCHES.items():
        if cfg[key] != value:
            raise ValueError(f"{key} {cfg[key]!r}: only the published switches "
                             f"are written down here")
    if cfg["rope_parameters"]["rope_type"] != "default":
        raise ValueError("only plain rotary is written down here")
    names = {"sliding_attention": "window", "full_attention": "full"}
    return (["embed"] + [names[k] for k in cfg["layer_types"][: int(cfg["num_hidden_layers"])]]
            + ["head"])


def _leaf_specs(cfg: dict, kind: str) -> dict:
    """name -> (shape, spread, centre) of one layer of `kind`.  The routed
    experts' leaves lead with the expert axis, the shared experts' with
    theirs."""
    s = sizes(cfg)
    d, h, f = s["d"], s["head_dim"], s["expert_ffn"]
    resid = _W_STD / math.sqrt(2.0 * s["depth_published"])
    ln = ((d,), _G_STD, 1.0)
    if kind == "embed":
        return {"W": ((s["vocab"], d), _W_STD, 0.0)}
    if kind in ("window", "full"):
        e, n = s["experts_held"], s["shared"]
        return {"ln": ln,
                "Wq": ((d, s["heads"] * h), _W_STD, 0.0),
                "Wk": ((d, s["kv_heads"] * h), _W_STD, 0.0),
                "Wv": ((d, s["kv_heads"] * h), _W_STD, 0.0),
                "Wo": ((s["heads"] * h, d), resid, 0.0),
                "Wr": ((d, s["experts_routed"]), _W_STD, 0.0),
                "Wgate": ((e, d, f), _W_STD, 0.0), "Wup": ((e, d, f), _W_STD, 0.0),
                "Wdown": ((e, f, d), resid, 0.0),
                "sWgate": ((n, d, f), _W_STD, 0.0), "sWup": ((n, d, f), _W_STD, 0.0),
                "sWdown": ((n, f, d), resid, 0.0)}
    if kind == "head":
        return {"norm": ln}
    raise ValueError(f"no layer kind {kind!r}")


_EXPERT_LEAVES = ("Wgate", "Wup", "Wdown")


def layer_weights(cfg: dict, key, index: int, kind: str) -> dict:
    """One layer's leaves, uniform with the stated spread about the centre,
    in `flags.param_dtype`.  A routed expert's leaves come from its
    published id."""
    lk = jax.random.fold_in(key, _HEAD_INDEX if kind == "head" else index)
    dtype = jnp.dtype(cfg["flags"]["param_dtype"])
    first = sizes(cfg)["first_expert"]
    out = {}
    for j, (name, (shape, std, centre)) in enumerate(
            sorted(_leaf_specs(cfg, kind).items())):
        a = std * math.sqrt(3.0)
        jk = jax.random.fold_in(lk, j)
        if name in _EXPERT_LEAVES:
            leaf = jax.vmap(lambda e: jax.random.uniform(
                jax.random.fold_in(jk, e), shape[1:], jnp.float32, -a, a))(
                    first + jnp.arange(shape[0]))
        else:
            leaf = jax.random.uniform(jk, shape, jnp.float32, -a, a)
        out[name] = (centre + leaf).astype(dtype)
    return out


def model_weights(cfg: dict, key) -> list:
    """Every layer's leaves, as a list in layer order.  Jit it."""
    return [layer_weights(cfg, key, i, kind)
            for i, kind in enumerate(layer_kinds(cfg))]


def count_params(cfg: dict) -> dict:
    """Parameters by role, from shapes.  `all` is every leaf held here.
    `always` are the matrices every token passes outside the routed experts
    (attention, the shared experts, the router) and `head` the table the
    head reads (the embedding's, counted once in `all`); `expert` is one
    routed expert's, `expert_layers` the layers that have them, `attention`
    one layer's four attention matrices, `active` what a token reads of the
    model: everything but the routed experts, and `num_experts_per_tok` of
    them a layer."""
    s = sizes(cfg)
    total = always = 0
    for kind in layer_kinds(cfg):
        for name, (shape, _, _) in _leaf_specs(cfg, kind).items():
            n = math.prod(shape)
            total += n
            if kind in ("window", "full") and name not in _EXPERT_LEAVES and name != "ln":
                always += n
    expert = 3 * s["d"] * s["expert_ffn"]
    return {"all": total, "always": always, "head": s["vocab"] * s["d"],
            "expert": expert, "expert_layers": s["layers"],
            "attention": 2 * s["d"] * s["head_dim"] * (s["heads"] + s["kv_heads"]),
            "active": total - s["layers"] * (s["experts_held"] - s["top_k"]) * expert}


def uncut(cfg: dict) -> dict:
    """The configuration as published: every key of `published` back in its
    place."""
    pub = cfg["published"]
    return {**cfg, **{k: v for k, v in pub.items() if k in cfg}}


def leaf_names(cfg: dict) -> list:
    """`layer.leaf` for every leaf, in the order `leaf_norms` reports them."""
    return [f"{i}.{kind}.{name}"
            for i, kind in enumerate(layer_kinds(cfg))
            for name in sorted(_leaf_specs(cfg, kind))]


# ---------------------------------------------------------------- forward

def layer_norm(x, g, eps: float):
    """A LayerNorm with a weight and no bias."""
    x = x - jnp.mean(x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * g


def rope_pairs(x, positions, theta: float):
    """Interleaved rotary over the whole last axis of x [S, heads, n]:
    dimensions 2m and 2m + 1 turn together by `positions * theta^(-2m/n)`;
    `positions` [S]."""
    n = x.shape[-1]
    inv = theta ** (-2.0 * np.arange(n // 2, dtype=np.float64) / n)
    ang = positions.astype(jnp.float32)[:, None] * jnp.asarray(inv, jnp.float32)[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                     axis=-1).reshape(x.shape)


_Q_ROWS = 64        # query rows a block of scores: [G, H/G, 64, T] at a time;
                    # at 128 heads and 10,240 keys 0.34 GB in float32


def attention(w: dict, u, s: dict, kind: str, precision: str = "f32"):
    """One layer's attention over one row's normed input u [T, d], T a
    multiple of `_Q_ROWS` or less than it: Wo's output [T, d]."""
    t = u.shape[0]
    g, h = s["kv_heads"], s["head_dim"]
    r = s["heads"] // g
    pos = jnp.arange(t)

    def proj(n, heads):
        return _mm("sd,de->se", u, w[n], -1, 0, precision).reshape(t, heads, h)

    q, k, v = proj("Wq", s["heads"]), proj("Wk", g), proj("Wv", g)
    if kind == "window":
        q, k = rope_pairs(q, pos, s["theta"]), rope_pairs(k, pos, s["theta"])
    q = q.reshape(t, g, r, h)

    def rows(start):
        """Query rows [start, start + block) against every key."""
        block = min(_Q_ROWS, t)
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, axis=0)
        qp = start + jnp.arange(block)[:, None]
        seen = pos[None, :] <= qp
        if kind == "window":
            seen = seen & (pos[None, :] > qp - s["window"])
        sc = _mm("qgrh,kgh->grqk", qb, k, -1, -1, precision) / math.sqrt(h)
        p = jax.nn.softmax(jnp.where(seen, sc, -1e30), axis=-1)
        return _mm("grqk,kgh->qgrh", p, v, -1, 0, precision).reshape(block, -1)

    if t <= _Q_ROWS:
        o = rows(0)
    else:
        o = jax.lax.map(rows, jnp.arange(0, t, _Q_ROWS)).reshape(t, -1)
    return _mm("se,ed->sd", o, w["Wo"], -1, 0, precision)


def _swiglu(u, wg, wu, wd, precision: str):
    a = (jax.nn.silu(_mm("td,df->tf", u, wg, -1, 0, precision))
         * _mm("td,df->tf", u, wu, -1, 0, precision))
    return _mm("tf,fd->td", a, wd, -1, 0, precision)


def route(scores, s: dict):
    """The choice: sigmoid `scores` [T, routed] in float32 -> (ids [T,
    top_k], weights [T, top_k]): the top k, their scores renormalised."""
    picked, ids = jax.lax.top_k(scores, s["top_k"])
    return ids, picked / jnp.sum(picked, axis=-1, keepdims=True)


def moe_parts(w: dict, u, s: dict, precision: str = "f32"):
    """(what the held experts give, what the shared experts give, averaged)
    for rows u [T, d]: every held expert over every row, one expert at a
    time, weighted by the row's routing weight for it (0 where it was not
    picked).  Every leaf is upcast as its turn comes."""
    f32 = lambda a: a.astype(jnp.float32)   # noqa: E731
    scores = jax.nn.sigmoid(jnp.einsum("td,de->te", u, f32(w["Wr"]), precision=_HI))
    ids, weights = route(scores, s)
    local = ids - s["first_expert"]                                    # [T, top_k]

    def one(acc, xs):
        e, wg, wu, wd = xs
        mine = jnp.sum(jnp.where(local == e, weights, 0.0), axis=-1)   # [T]
        return acc + mine[:, None] * _swiglu(u, f32(wg), f32(wu), f32(wd), precision), None

    routed, _ = jax.lax.scan(
        one, jnp.zeros_like(u),
        (jnp.arange(s["experts_held"]), w["Wgate"], w["Wup"], w["Wdown"]))
    shared, _ = jax.lax.scan(
        lambda acc, xs: (acc + _swiglu(u, *map(f32, xs), precision), None),
        jnp.zeros_like(u), (w["sWgate"], w["sWup"], w["sWdown"]))
    return routed, shared / s["shared"]


def block(w: dict, x, s: dict, kind: str, precision: str = "f32"):
    """One parallel block over one row x [T, d]; the norm's and attention's
    leaves float32 already."""
    u = layer_norm(x, w["ln"], s["eps"])
    routed, shared = moe_parts(w, u, s, precision)
    return x + attention(w, u, s, kind, precision) + routed + shared


def head_logits(w: dict, table, x, s: dict, precision: str = "f32"):
    """Logits [B, S, V] of x [B, S, d] against the embedding's table [V, d]."""
    u = layer_norm(x, w["norm"].astype(jnp.float32), s["eps"])
    return s["logit_scale"] * _mm("bsd,vd->bsv", u, table.astype(jnp.float32), -1, 1,
                                  precision)


def apply_layer(kind: str, w: dict, x, cfg: dict, precision: str = "f32"):
    s = sizes(cfg)
    if kind == "embed":
        return w["W"].astype(jnp.float32)[x]
    # the experts' leaves are upcast one expert at a time (`moe_parts`); the
    # blocks run a row at a time: at 10,240 positions one row's queries of
    # 128 heads are 0.7 GB in float32
    w = {**w, **{k: w[k].astype(jnp.float32) for k in ("ln", "Wq", "Wk", "Wv", "Wo")}}
    return jax.lax.map(lambda row: block(w, row, s, kind, precision), x)


# ---------------------------------------------------------------- serving

_PAD = 128          # lengths stay on a grid (the Ling family's lesson: at a
                    # length off it the TPU's compiler once made NaN)
_HEAD_ROWS = 128    # positions a call of the head: [6, 128, 32768] float32
                    # are 0.1 GB; the whole logits of 6 rows of 10,240
                    # positions are 8.1 GB, and they are kept on the host


def teacher_forced_logits(cfg: dict, seed: int, ids, precisions=("f32",)):
    """Teacher-forced logits of `ids` [B, S], one layer's weights alive at a
    time.  Returns {precision: logits [B, S, V]}; position t holds the
    next-token logits after consuming ids[:, :t+1].

    The ids are padded with zeros to a whole number of `_PAD` positions and
    the hidden rows cut back to S before the head: nothing here looks ahead,
    so no position sees the padding.  The head reads the embedding's table,
    drawn again for it.  The logits are kept in host memory (JAX's CPU
    device): the head runs `_HEAD_ROWS` positions at a time and each piece
    is written into its place there, one at a time."""
    key, frozen = base_key(seed), Frozen(cfg)
    ids = jnp.asarray(ids, jnp.int32)
    rows, length = ids.shape
    ids = jnp.pad(ids, ((0, 0), (0, -length % _PAD)))
    xs = {p: ids for p in precisions}
    kinds = layer_kinds(cfg)
    host = jax.devices("cpu")[0]
    with jax.default_matmul_precision("highest"):
        for i, kind in enumerate(kinds[:-1]):
            w = _layer_jit(kind)(frozen, key, i)
            for p in precisions:
                xs[p] = _apply_jit(kind, p)(w, xs[p], frozen)
            del w
        w = _layer_jit("head")(frozen, key, len(kinds) - 1)
        table = _layer_jit("embed")(frozen, key, 0)["W"]
        out = {}
        for p in precisions:
            hidden = xs.pop(p)[:, :length]
            with jax.default_device(host):
                logits = jnp.zeros((rows, length, sizes(cfg)["vocab"]), jnp.float32)
            for start in range(0, length, _HEAD_ROWS):
                piece = _head_jit(p)(w, table, hidden[:, start: start + _HEAD_ROWS], frozen)
                logits = _place(logits, jax.device_put(piece, host), start)
                # one piece alive at a time: dispatch is asynchronous, and
                # the pieces in flight would be a second copy of the whole
                logits.block_until_ready()
            out[p] = logits
    return out


@functools.partial(jax.jit, donate_argnums=0)
def _place(whole, piece, start):
    """`piece` into `whole` from position `start` on, in place."""
    return jax.lax.dynamic_update_slice(whole, piece, (0, start, 0))


@functools.lru_cache(maxsize=None)
def _layer_jit(kind: str):
    return jax.jit(lambda cfg, key, i: layer_weights(cfg, key, i, kind),
                   static_argnums=0)


@functools.lru_cache(maxsize=None)
def _apply_jit(kind: str, precision: str):
    return jax.jit(lambda w, x, cfg: apply_layer(kind, w, x, cfg, precision),
                   static_argnums=2)


@functools.lru_cache(maxsize=None)
def _head_jit(precision: str):
    return jax.jit(lambda w, table, x, cfg: head_logits(w, table, x, sizes(cfg), precision),
                   static_argnums=3)


def first_steps(cfg: dict, seed: int, batches, precision: str = "f32", rows=None):
    raise NotImplementedError(
        "this configuration serves only: trained at 16 bytes a parameter even "
        "the floors' cut (4 layers, 8 routed experts, an eighth of the "
        "vocabulary) is 50 GB, and the parallel block has no checked backward "
        "pass (configs/command-a-plus-ep8.json)")
