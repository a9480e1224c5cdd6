"""The plain reference of Mellum2-12B-A2.5B's language model: weights from a
seed, and its layers in straightforward float32 `jax.numpy`, with no cache,
no kernels and no batching tricks.  Nothing here imports the program.

d = `hidden_size`, H = `num_attention_heads` query heads over G =
`num_key_value_heads` K/V heads of h = `head_dim`, no bias anywhere, head
untied.  A block is `y = x + Attn(RMSNorm(x))`, `z = y + MoE(RMSNorm(y))`,
and a final RMSNorm stands before the head.  Layer `l` attends over a
window where `layer_types[l]` is `sliding_attention` and over everything
before it where it is `full_attention`; every FFN is routed
(`mlp_layer_types` all `sparse`).

Attention, with u = RMSNorm(x): q = Wq u in [H, h], k = Wk u, v = Wv u in
  [G, h]; q and k through an RMSNorm over h with a weight a head dimension
  (assumed: the configuration file says why); rotate-half rotary positions
  over all h dimensions with inverse frequencies `inv_i = theta^(-2i/h)` on
  window layers and, on full layers, YaRN's (arXiv:2309.00071): with
  dim(r) = h ln(original / (2 pi r)) / (2 ln theta), low = max(floor(dim(
  beta_fast)), 0), high = min(ceil(dim(beta_slow)), h - 1), ramp_i =
  clip((i - low) / (high - low), 0, 1), inv_i = (1 - ramp_i) theta^(-2i/h) +
  ramp_i theta^(-2i/h) / factor, and cos and sin both times
  `attention_factor`.  Query head j reads K/V head j // (H / G).  Scores
  q . k / sqrt(h), float32 softmax over the keys t' <= t (full) or t -
  `sliding_window` < t' <= t (window), then Wo.  The [T, T] scores are
  formed a block of query rows at a time, against every key, under the full
  mask: there is no cache and no band.
Routed FFN, with u = RMSNorm(y): p = softmax over all `num_experts` of Wr u
  in float32; T = the `num_experts_per_tok` largest; w_e = p_e / sum_T p
  (`norm_topk_prob`); z = y + sum_{e in T} w_e Wdown_e(silu(Wgate_e u) *
  Wup_e u).  No bias, no groups, no shared expert.  Every expert runs over
  every row, one expert at a time, weighted by the row's weight for it.

The weights are a function of (configuration, seed, layer, leaf) alone,
and of the published depth through the scale of the two matrices that write
the residual stream: the layers of a cut in depth are the first layers of
the uncut model (`uncut`).  They are drawn in float32 and, where
`flags.param_dtype` says bfloat16, rounded to it once, here: the program and
this reference then hold the same numbers, and this reference computes with
them in float32.  Every product with a weight, and attention's scores and
values, go through the shared `_mm`, whose `precision="int8"` is the
control of `correct`; the router's logits stay float32 `highest`.

The init, and what was chosen for the routing (PR 28's lesson: a seed draws
the weights, so it draws the routing): every matrix is uniform about zero
and every path to the router is odd in its inputs (no convolution, no
activation with a mean), so the normed stream has no direction that all
tokens share, the router's softmax is near even, and 64 rows' 512 picks miss
one of 64 experts with probability (7/8)^64 = 0.02 %: every seed reads every
expert every step.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import Frozen, _mm, base_key

_W_STD = 0.02      # every matrix; the two that write the residual stream
_G_STD = 0.02      # are scaled by 1/sqrt(2 L), L the published depth.  Norm
                   # weights lie about 1, so that a path that drops one shows
_HI = jax.lax.Precision.HIGHEST
_HEAD_INDEX = 1 << 20   # the head's key: the same in the cut and the uncut


def sizes(cfg: dict) -> dict:
    rp = cfg["rope_parameters"]
    return {
        "d": int(cfg["hidden_size"]), "heads": int(cfg["num_attention_heads"]),
        "kv_heads": int(cfg["num_key_value_heads"]), "head_dim": int(cfg["head_dim"]),
        "layers": int(cfg["num_hidden_layers"]),
        "depth_published": int(cfg.get("published", {}).get(
            "num_hidden_layers", cfg["num_hidden_layers"])),
        "vocab": int(cfg["vocab_size"]),
        "positions": int(cfg["max_position_embeddings"]),
        "expert_ffn": int(cfg["moe_intermediate_size"]),
        "experts_held": int(cfg["num_experts"]), "top_k": int(cfg["num_experts_per_tok"]),
        "window": int(cfg["sliding_window"]),
        "eps": float(cfg["rms_norm_eps"]),
        "rope": {"window": _rope_of(rp["sliding_attention"]),
                 "full": _rope_of(rp["full_attention"])},
    }


def _rope_of(p: dict) -> dict:
    """(theta, and YaRN's five numbers or None) of one section of
    `rope_parameters`."""
    if p["rope_type"] == "default":
        return {"theta": float(p["rope_theta"]), "yarn": None}
    if p["rope_type"] != "yarn":
        raise ValueError(f"no rope_type {p['rope_type']!r}")
    return {"theta": float(p["rope_theta"]),
            "yarn": (float(p["factor"]), float(p["original_max_position_embeddings"]),
                     float(p["beta_fast"]), float(p["beta_slow"]),
                     float(p["attention_factor"]))}


def layer_kinds(cfg: dict) -> list:
    """embed, then (window | full, moe) a block, then head (the final norm
    is the head's)."""
    names = {"sliding_attention": "window", "full_attention": "full"}
    if set(cfg["mlp_layer_types"]) != {"sparse"}:
        raise ValueError("a dense FFN layer is not written down here")
    out = ["embed"]
    for kind in cfg["layer_types"][: int(cfg["num_hidden_layers"])]:
        out += [names[kind], "moe"]
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
    if kind in ("window", "full"):
        return {"Wq": ((d, s["heads"] * h), _W_STD, 0.0),
                "Wk": ((d, s["kv_heads"] * h), _W_STD, 0.0),
                "Wv": ((d, s["kv_heads"] * h), _W_STD, 0.0),
                "q_norm": ((h,), _G_STD, 1.0), "k_norm": ((h,), _G_STD, 1.0),
                "Wo": ((s["heads"] * h, d), resid, 0.0), "ln": ln}
    if kind == "moe":
        e, f = s["experts_held"], s["expert_ffn"]
        return {"Wr": ((d, e), _W_STD, 0.0),
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
    the matrices every token passes (attention, router, head), `expert` one
    routed expert's, `expert_layers` the layers that have them, `attention`
    one attention layer's four matrices; the table and the vectors are the
    rest."""
    total = always = 0
    for kind in layer_kinds(cfg):
        for name, (shape, _, _) in _leaf_specs(cfg, kind).items():
            n = math.prod(shape)
            total += n
            if kind != "embed" and len(shape) == 2:
                always += n
    s = sizes(cfg)
    return {"all": total, "always": always,
            "expert": 3 * s["d"] * s["expert_ffn"],
            "expert_layers": s["layers"],
            "attention": 2 * s["d"] * s["head_dim"] * (s["heads"] + s["kv_heads"])}


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


def rope_frequencies(n: int, rope: dict):
    """(the n/2 inverse frequencies, the factor on cos and sin) of a head of
    `n` dimensions: plain, or YaRN's where `rope["yarn"]` gives (factor,
    original, beta_fast, beta_slow, attention_factor)."""
    i = np.arange(n // 2, dtype=np.float64)
    plain = rope["theta"] ** (-2.0 * i / n)
    if rope["yarn"] is None:
        return plain, 1.0
    factor, original, beta_fast, beta_slow, attention_factor = rope["yarn"]

    def dim(r):
        return n * math.log(original / (2.0 * math.pi * r)) / (2.0 * math.log(rope["theta"]))

    low = max(math.floor(dim(beta_fast)), 0)
    high = min(math.ceil(dim(beta_slow)), n - 1)
    ramp = np.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    return (1.0 - ramp) * plain + ramp * plain / factor, attention_factor


def apply_rope(x, positions, rope: dict):
    """Rotate-half over the whole last axis of x [B, S, heads, n];
    `positions` [S]."""
    n = x.shape[-1]
    inv, scale = rope_frequencies(n, rope)
    ang = positions.astype(jnp.float32)[:, None] * jnp.asarray(inv, jnp.float32)[None, :]
    cos = (jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], axis=-1) * scale)[:, None, :]
    sin = (jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], axis=-1) * scale)[:, None, :]
    half = jnp.concatenate([-x[..., n // 2:], x[..., : n // 2]], axis=-1)
    return x * cos + half * sin


_Q_ROWS = 128       # query rows a block of scores: [B, H, 128, T] at a time


def attention(w: dict, x, s: dict, kind: str, precision: str = "f32"):
    """One attention layer over x [B, T, d]; T a multiple of `_Q_ROWS` or
    less than it."""
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
    q = apply_rope(q, pos, s["rope"][kind]).reshape(b, t, g, r, h)
    k = apply_rope(k, pos, s["rope"][kind])

    def rows(start):
        """Query rows [start, start + block) against every key."""
        block = min(_Q_ROWS, t)
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, axis=1)
        qp = start + jnp.arange(block)[:, None]
        seen = pos[None, :] <= qp
        if kind == "window":
            seen = seen & (pos[None, :] > qp - s["window"])
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


def route(logits, s: dict):
    """Router logits [T, experts] in float32 -> (ids [T, top_k], weights
    [T, top_k]): softmax over all experts, the top k, renormalised."""
    p = jax.nn.softmax(logits, axis=-1)
    picked, ids = jax.lax.top_k(p, s["top_k"])
    return ids, picked / jnp.sum(picked, axis=-1, keepdims=True)


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
    ids, weights = route(jnp.einsum("td,de->te", u, small["Wr"], precision=_HI), s)
    mine = dense_weights(ids, weights, s["experts_held"])            # [T, experts]

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
    if kind == "head":
        return head_logits(_f32(w), x, s, precision)
    return attention(_f32(w), x, s, kind, precision)


# ---------------------------------------------------------------- serving

_PAD = 128          # lengths stay on a grid (the Ling family's lesson: at a
                    # length off it the TPU's compiler once made NaN)
_HEAD_ROWS = 128    # positions a call of the head: [6, 128, 98304] float32
                    # are 0.3 GB, on the device and twice on their way into
                    # the host's array.  The chip's runtime holds 13 to 17 GB
                    # of a one-chip machine's 40 GiB, the whole logits 20.5:
                    # at 512 positions a piece the process met the limit


def teacher_forced_logits(cfg: dict, seed: int, ids, precisions=("f32",)):
    """Teacher-forced logits of `ids` [B, S], one layer's weights alive at a
    time.  Returns {precision: logits [B, S, V]}; position t holds the
    next-token logits after consuming ids[:, :t+1].

    The ids are padded with zeros to a whole number of `_PAD` positions and
    the logits cut back: nothing here looks ahead, so no position sees the
    padding.  The logits are kept in host memory (JAX's CPU device): at 6
    rows of 8,704 positions and 98,304 ids they are 20.5 GB of float32,
    more than the chip has, so the head runs `_HEAD_ROWS` positions at a
    time and each piece is written into its place there, one at a time."""
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
            with jax.default_device(host):
                logits = jnp.zeros((rows, length, sizes(cfg)["vocab"]), jnp.float32)
            for start in range(0, length, _HEAD_ROWS):
                piece = _apply_jit("head", p)(
                    w, xs[p][:, start: start + _HEAD_ROWS], frozen)
                logits = _place(logits, jax.device_put(
                    piece[:, : length - start], host), start)
                # one piece alive at a time: dispatch is asynchronous, and
                # the pieces in flight would be a second copy of the whole
                logits.block_until_ready()
            out[p] = logits
            del xs[p]
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
        "chip holds a quarter of its experts and of its vocabulary at the "
        "floor of 4 layers, and the expert layer has no checked backward "
        "pass (configs/mellum2-12b-a2.5b-pp8.json)")
