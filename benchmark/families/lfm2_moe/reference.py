"""The plain reference of LFM2-24B-A2B's language model: weights from a seed,
and its layers in straightforward float32 `jax.numpy`, with no cache, no
kernels and no batching tricks.  Nothing here imports the program.

d = `hidden_size`, no bias anywhere, RMSNorm eps `norm_eps`, head untied.
Decoder layer i is `h = x + Mixer_i(RMSNorm(x))`, `y = h + FFN_i(RMSNorm(h))`,
and a final RMSNorm stands before the head.  `Mixer_i` is attention where
`layer_types[i]` is `full_attention`, else the short convolution; `FFN_i` is
dense SwiGLU of width `intermediate_size` for i < `num_dense_layers`, else
routed.

Short convolution, with u = RMSNorm(x) [T, d] and K = `conv_L_cache`:
  [B; C; x~] = u W_in (W_in [d, 3d], split in three); z_t = B_t * x~_t;
  c_t = sum_{j<K} w_j * z_{t-K+1+j}, depthwise and causal, z before the
  first token zero; o_t = (C_t * c_t) W_out.  No activation.  Written as a
  loop over the K taps, each a shifted copy of z.
Attention, with u = RMSNorm(x): q = Wq u in [H, h], k = Wk u, v = Wv u in
  [G, h], H = `num_attention_heads` over G = `num_key_value_heads` heads of
  h = d / H; an RMSNorm over h on each head of q and of k (its weight a
  head dimension); rotate-half rotary positions over all h dimensions at
  `rope_parameters.rope_theta`; query head j reads K/V head j // (H / G);
  scores q . k / sqrt(h), float32 softmax over the keys t' <= t, then Wo.
  The [T, T] scores are formed a block of query rows at a time, against
  every key, under the full mask: there is no cache.
Routed FFN, with u = RMSNorm(h): s = sigmoid(Wr u) over all `num_experts` in
  float32; T = the `num_experts_per_tok` largest of s + b, where the bias b
  (`use_expert_bias`) enters the choice alone; w_e = s_e / sum_T s
  (`norm_topk_prob`, `routed_scaling_factor` 1); y = h + sum_{e in T} w_e
  Wdown_e(silu(Wgate_e u) * Wup_e u).  No shared expert.  Every expert runs
  over every row, one at a time, weighted by the row's weight for it.

The weights are a function of (configuration, seed, layer, leaf) alone, and
of the published depth through the scale of the matrices that write the
residual stream: the layers of a cut in depth are the first layers of the
uncut model (`uncut`).  They are drawn in float32 and, where
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
_CONV_STD = 0.1    # a tap sees 3 inputs, not d: wider, so that the mixer's
                   # output is of the stream's order and a tap out of place shows
_B_STD = 0.015     # the router's bias: seeded, not zero, and about the gap
                   # between a token's 4th and 5th score (0.013 in the median)
_HI = jax.lax.Precision.HIGHEST
_HEAD_INDEX = 1 << 20   # the head's key: the same in the cut and the uncut


def sizes(cfg: dict) -> dict:
    d, heads = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    return {
        "d": d, "heads": heads, "kv_heads": int(cfg["num_key_value_heads"]),
        "head_dim": d // heads,
        "layers": int(cfg["num_hidden_layers"]),
        "depth_published": int(cfg.get("published", {}).get(
            "num_hidden_layers", cfg["num_hidden_layers"])),
        "dense_layers": int(cfg["num_dense_layers"]),
        "vocab": int(cfg["vocab_size"]),
        "positions": int(cfg["max_position_embeddings"]),
        "ffn": int(cfg["intermediate_size"]),
        "expert_ffn": int(cfg["moe_intermediate_size"]),
        "experts_held": int(cfg["num_experts"]), "top_k": int(cfg["num_experts_per_tok"]),
        "scaling": float(cfg["routed_scaling_factor"]),
        "conv_k": int(cfg["conv_L_cache"]),
        "theta": float(cfg["rope_parameters"]["rope_theta"]),
        "eps": float(cfg["norm_eps"]),
    }


def layer_kinds(cfg: dict) -> list:
    """embed, then (conv | attn, dense | moe) a decoder layer, then head (the
    final norm is the head's)."""
    if cfg["conv_bias"] or not cfg["use_expert_bias"] or cfg["rope_parameters"][
            "rope_type"] != "default" or not cfg["norm_topk_prob"]:
        raise ValueError("only the published switches are written down here")
    mixers = {"conv": "conv", "full_attention": "attn"}
    dense = int(cfg["num_dense_layers"])
    out = ["embed"]
    for i, kind in enumerate(cfg["layer_types"][: int(cfg["num_hidden_layers"])]):
        out += [mixers[kind], "dense" if i < dense else "moe"]
    return out + ["head"]


def _leaf_specs(cfg: dict, kind: str) -> dict:
    """name -> (shape, spread, centre) of one layer of `kind`.  The experts'
    leaves lead with the expert axis."""
    s = sizes(cfg)
    d, h = s["d"], s["head_dim"]
    resid = _W_STD / math.sqrt(2.0 * s["depth_published"])
    ln = ((d,), _G_STD, 1.0)
    if kind == "embed":
        return {"W": ((s["vocab"], d), _W_STD, 0.0)}
    if kind == "conv":
        return {"in_proj": ((d, 3 * d), _W_STD, 0.0),
                "conv": ((s["conv_k"], d), _CONV_STD, 0.0),
                "out_proj": ((d, d), resid, 0.0), "ln": ln}
    if kind == "attn":
        return {"Wq": ((d, s["heads"] * h), _W_STD, 0.0),
                "Wk": ((d, s["kv_heads"] * h), _W_STD, 0.0),
                "Wv": ((d, s["kv_heads"] * h), _W_STD, 0.0),
                "q_norm": ((h,), _G_STD, 1.0), "k_norm": ((h,), _G_STD, 1.0),
                "Wo": ((s["heads"] * h, d), resid, 0.0), "ln": ln}
    if kind == "dense":
        f = s["ffn"]
        return {"Wgate": ((d, f), _W_STD, 0.0), "Wup": ((d, f), _W_STD, 0.0),
                "Wdown": ((f, d), resid, 0.0), "ln": ln}
    if kind == "moe":
        e, f = s["experts_held"], s["expert_ffn"]
        return {"Wr": ((d, e), _W_STD, 0.0), "rb": ((e,), _B_STD, 0.0),
                "Wgate": ((e, d, f), _W_STD, 0.0), "Wup": ((e, d, f), _W_STD, 0.0),
                "Wdown": ((e, f, d), resid, 0.0), "ln": ln}
    if kind == "head":
        return {"W": ((d, s["vocab"]), _W_STD, 0.0), "norm": ln}
    raise ValueError(f"no layer kind {kind!r}")


_EXPERT_LEAVES = ("Wgate", "Wup", "Wdown")


def layer_weights(cfg: dict, key, index: int, kind: str) -> dict:
    """One layer's leaves, uniform with the stated spread about the centre,
    in `flags.param_dtype`.  An expert's leaves come from its id."""
    lk = jax.random.fold_in(key, _HEAD_INDEX if kind == "head" else index)
    dtype = jnp.dtype(cfg["flags"]["param_dtype"])
    out = {}
    for j, (name, (shape, std, centre)) in enumerate(
            sorted(_leaf_specs(cfg, kind).items())):
        a = std * math.sqrt(3.0)
        jk = jax.random.fold_in(lk, j)
        if kind == "moe" and name in _EXPERT_LEAVES:
            leaf = jax.vmap(lambda e: jax.random.uniform(
                jax.random.fold_in(jk, e), shape[1:], jnp.float32, -a, a))(
                    jnp.arange(shape[0]))
        else:
            leaf = jax.random.uniform(jk, shape, jnp.float32, -a, a)
        out[name] = (centre + leaf).astype(dtype)
    return out


def model_weights(cfg: dict, key) -> list:
    """Every layer's leaves, as a list in layer order.  Jit it."""
    return [layer_weights(cfg, key, i, kind)
            for i, kind in enumerate(layer_kinds(cfg))]


def count_params(cfg: dict) -> dict:
    """Parameters by role, from shapes.  `all` is every leaf.  `always` are
    the matrices every token passes (the mixers, the dense FFNs, the router,
    the head), `expert` one routed expert's, `expert_layers` the layers that
    have them, `conv` and `attention` one mixer of each kind (`_mixer`); the
    table and the vectors are the rest."""
    total = always = 0
    kinds = layer_kinds(cfg)
    for kind in kinds:
        for name, (shape, _, _) in _leaf_specs(cfg, kind).items():
            n = math.prod(shape)
            total += n
            if kind != "embed" and len(shape) == 2 and name != "conv":
                always += n
    s = sizes(cfg)
    return {"all": total, "always": always,
            "expert": 3 * s["d"] * s["expert_ffn"],
            "expert_layers": kinds.count("moe"),
            "conv": _mixer(cfg, "conv"), "attention": _mixer(cfg, "attn")}


def _mixer(cfg: dict, kind: str) -> int:
    """One mixer's parameters, its input norm left out."""
    return sum(math.prod(shape) for name, (shape, _, _) in
               _leaf_specs(cfg, kind).items() if name != "ln")


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

def _f32(w: dict) -> dict:
    return {k: v.astype(jnp.float32) for k, v in w.items()}


def rms_norm(x, g, eps: float):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * g


def short_conv(w: dict, x, s: dict, precision: str = "f32"):
    """One convolution layer's mixer over x [B, T, d]."""
    t, k = x.shape[1], s["conv_k"]
    u = rms_norm(x, w["ln"], s["eps"])
    b, c, xt = jnp.split(_mm("bsd,de->bse", u, w["in_proj"], -1, 0, precision), 3, axis=-1)
    z = b * xt
    conv = jnp.zeros_like(z)
    for j in range(k):          # tap j reads z_{t-K+1+j}: z shifted K-1-j on
        shift = k - 1 - j
        conv = conv + w["conv"][j] * jnp.pad(z, ((0, 0), (shift, 0), (0, 0)))[:, :t]
    return x + _mm("bsd,de->bse", c * conv, w["out_proj"], -1, 0, precision)


def apply_rope(x, positions, theta: float):
    """Rotate-half over the whole last axis of x [B, S, heads, n];
    `positions` [S]."""
    n = x.shape[-1]
    inv = theta ** (-2.0 * np.arange(n // 2, dtype=np.float64) / n)
    ang = positions.astype(jnp.float32)[:, None] * jnp.asarray(inv, jnp.float32)[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], axis=-1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], axis=-1)[:, None, :]
    half = jnp.concatenate([-x[..., n // 2:], x[..., : n // 2]], axis=-1)
    return x * cos + half * sin


_Q_ROWS = 128       # query rows a block of scores: [B, H, 128, T] at a time


def attention(w: dict, x, s: dict, precision: str = "f32"):
    """One attention layer's mixer over x [B, T, d]; T a multiple of
    `_Q_ROWS` or less than it."""
    b, t, _ = x.shape
    g, h = s["kv_heads"], s["head_dim"]
    r = s["heads"] // g
    pos = jnp.arange(t)
    u = rms_norm(x, w["ln"], s["eps"])

    def proj(n, heads):
        return _mm("bsd,de->bse", u, w[n], -1, 0, precision).reshape(b, t, heads, h)

    q = rms_norm(proj("Wq", s["heads"]), w["q_norm"], s["eps"])
    k = rms_norm(proj("Wk", g), w["k_norm"], s["eps"])
    v = proj("Wv", g)
    q = apply_rope(q, pos, s["theta"]).reshape(b, t, g, r, h)
    k = apply_rope(k, pos, s["theta"])

    def rows(start):
        """Query rows [start, start + block) against every key."""
        block = min(_Q_ROWS, t)
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, axis=1)
        seen = pos[None, :] <= start + jnp.arange(block)[:, None]
        sc = _mm("bqgrh,bkgh->bgrqk", qb, k, -1, -1, precision) / math.sqrt(h)
        p = jax.nn.softmax(jnp.where(seen, sc, -1e30), axis=-1)
        return _mm("bgrqk,bkgh->bqgrh", p, v, -1, 1, precision).reshape(b, block, -1)

    if t <= _Q_ROWS:
        o = rows(0)
    else:
        o = jax.lax.map(rows, jnp.arange(0, t, _Q_ROWS))        # [T/block, B, block, H h]
        o = jnp.moveaxis(o, 0, 1).reshape(b, t, -1)
    return x + _mm("bse,ed->bsd", o, w["Wo"], -1, 0, precision)


def _swiglu(u, wg, wu, wd, precision: str):
    a = (jax.nn.silu(_mm("td,df->tf", u, wg, -1, 0, precision))
         * _mm("td,df->tf", u, wu, -1, 0, precision))
    return _mm("tf,fd->td", a, wd, -1, 0, precision)


def dense(w: dict, x, s: dict, precision: str = "f32"):
    b, t, d = x.shape
    u = rms_norm(x, w["ln"], s["eps"]).reshape(b * t, d)
    return x + _swiglu(u, w["Wgate"], w["Wup"], w["Wdown"], precision).reshape(b, t, d)


def route(logits, bias, s: dict):
    """Router logits [T, experts] in float32 -> (ids [T, top_k], weights
    [T, top_k]): sigmoid scores, the top k of scores + bias, the weights
    from the scores alone, renormalised over the k."""
    scores = jax.nn.sigmoid(logits)
    ids = jax.lax.top_k(scores + bias, s["top_k"])[1]
    picked = jnp.take_along_axis(scores, ids, axis=-1)
    return ids, s["scaling"] * picked / jnp.sum(picked, axis=-1, keepdims=True)


def dense_weights(ids, weights, experts: int):
    """[T, experts]: a row's weight for every expert, 0 where not picked."""
    return jnp.sum(jax.nn.one_hot(ids, experts, dtype=weights.dtype)
                   * weights[..., None], axis=1)


def moe(w: dict, x, s: dict, precision: str = "f32"):
    """Every expert over every row, one at a time, weighted by the row's
    routing weight for it.  The leaves without an expert axis are float32
    already; an expert's are upcast as its turn comes."""
    b, t, d = x.shape
    small = {k: v.astype(jnp.float32) for k, v in w.items() if v.ndim < 3}
    u = rms_norm(x, small["ln"], s["eps"]).reshape(b * t, d)
    ids, weights = route(jnp.einsum("td,de->te", u, small["Wr"], precision=_HI),
                         small["rb"], s)
    mine = dense_weights(ids, weights, s["experts_held"])                 # [T, experts]

    def one(acc, xs):
        e, wg, wu, wd = xs
        return acc + mine[:, e][:, None] * _swiglu(
            u, wg.astype(jnp.float32), wu.astype(jnp.float32),
            wd.astype(jnp.float32), precision), None

    routed, _ = jax.lax.scan(
        one, jnp.zeros_like(u),
        (jnp.arange(s["experts_held"]), w["Wgate"], w["Wup"], w["Wdown"]))
    return x + routed.reshape(b, t, d)


def head_logits(w: dict, x, s: dict, precision: str = "f32"):
    return _mm("bsd,dv->bsv", rms_norm(x, w["norm"], s["eps"]), w["W"], -1, 0, precision)


def apply_layer(kind: str, w: dict, x, cfg: dict, precision: str = "f32"):
    s = sizes(cfg)
    if kind == "embed":
        return w["W"].astype(jnp.float32)[x]
    if kind == "moe":                   # upcasts its experts one at a time
        return moe(w, x, s, precision)
    return {"conv": short_conv, "attn": attention, "dense": dense,
            "head": head_logits}[kind](_f32(w), x, s, precision)


# ---------------------------------------------------------------- serving

_PAD = 128          # lengths stay on a grid (the Ling family's lesson: at a
                    # length off it the TPU's compiler once made NaN)
_HEAD_ROWS = 128    # positions a call of the head: [6, 128, 65536] float32
                    # are 0.2 GB; the whole logits of 6 rows of 6,144
                    # positions are 9.7 GB, and they are kept on the host


def teacher_forced_logits(cfg: dict, seed: int, ids, precisions=("f32",)):
    """Teacher-forced logits of `ids` [B, S], one layer's weights alive at a
    time.  Returns {precision: logits [B, S, V]}; position t holds the
    next-token logits after consuming ids[:, :t+1].

    The ids are padded with zeros to a whole number of `_PAD` positions and
    the hidden rows cut back to S before the head: nothing here looks ahead,
    so no position sees the padding.  The logits are kept in host memory
    (JAX's CPU device): the head runs `_HEAD_ROWS` positions at a time and
    each piece is written into its place there, one at a time."""
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
        out = {}
        for p in precisions:
            hidden = xs.pop(p)[:, :length]
            with jax.default_device(host):
                logits = jnp.zeros((rows, length, sizes(cfg)["vocab"]), jnp.float32)
            for start in range(0, length, _HEAD_ROWS):
                piece = _apply_jit("head", p)(w, hidden[:, start: start + _HEAD_ROWS], frozen)
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


def first_steps(cfg: dict, seed: int, batches, precision: str = "f32", rows=None):
    raise NotImplementedError(
        "this configuration serves only: trained at 16 bytes a parameter one "
        "chip holds 8 of its 64 experts at the floor of depth, and the short "
        "convolution, grouped attention and expert layers have no checked "
        "backward pass (configs/lfm2-24b-a2b-pp10.json)")
