"""The plain reference of dots3-note-prev's language model: weights from a
seed, and its layers in straightforward float32 `jax.numpy`, with no cache,
no absorption, no kernels and no batching tricks.  Nothing here imports the
program.

d = `hidden_size`, no bias anywhere but in the indexer's LayerNorm, head
untied.  A block is `y = x + Attn(RMSNorm(x))`, `z = y + FFN(RMSNorm(y))`,
and a final RMSNorm stands before the head.  Layer `l` is latent attention
in one of two geometries: `full_attention` (the unprefixed keys: H heads,
`q_lora_rank`, `kv_lora_rank`, `qk_nope_head_dim`, `qk_rope_head_dim`,
`v_head_dim`, `rope_theta`) with an indexer, or `sliding_attention` (the
`swa_` keys) over a window.  Its FFN is dense SwiGLU for `l <
first_k_dense_replace`, else routed.

Latent attention, with u = RMSNorm(x), eps `rms_norm_eps`:
  cq = a_q RMSNorm(Wqa u) in R^q_rank; q = Wqb cq as [H, nope + rope], its
  rope part rotated; [c~; kr~] = Wkva u; c = a_kv RMSNorm(c~); kr =
  RoPE(kr~), one rotary key for all heads; [k_nope; v]_h = Wkvb_h c;
  scores (q_nope . k_nope + q_rope . kr) / sqrt(nope + rope); a float32
  softmax over the keys the token may see; head h's output times g_h, g =
  sigmoid(Wg u) in R^H (`attention_gate_type` headwise); then Wo.
  `apply_mla_qkv_lora_rescale`: a_q = sqrt(d / q_rank), a_kv = sqrt(d /
  kv_rank) (assumed: the configuration file says why).
  A window layer's token t sees t - `sliding_window_size` < s <= t.
  A full layer's token sees the `index_topk` positions s <= t of largest
  index score (all of them while t < index_topk; ties to the lower
  position): qi = Wiq cq as [J, n] (`index_n_heads`, `index_head_dim`), ki =
  LayerNorm(Wik u) in R^n with a weight and a bias, both rotated over their
  first `qk_rope_head_dim` dimensions; w = Wiw u in R^J; I[t, s] = sum_j
  w[t, j] relu(qi[t, j] . ki[s]) / sqrt(J n).  The choice is `jax.lax.top_k`
  over the scores with s > t at -inf, made into a mask.
  Rotate-half pairing, no `rope_scaling`.  A row at a time, and the [T, T]
  scores a block of query rows at a time against every key under the full
  mask: there is no cache and no band.
Routed FFN (routing of arXiv:2412.19437, `topk_method` noaux_tc, one group):
  s = sigmoid(Wr u) over all the published experts in float32; for the
  choice only s' = s + b; T = the `num_experts_per_tok` largest s'; w_e =
  `routed_scaling_factor` s_e / sum_T s (`norm_topk_prob`); z = y + sum_{e
  in T, e held} w_e E_e(u) + E_shared(u), E(u) = Wdown(silu(Wgate u) * Wup
  u).  `n_routed_experts` experts are held, from `deployment.rank *
  n_routed_experts` on; `published.n_routed_experts` is the router's width.
  What the absent experts would add is left out.

The weights are a function of (configuration, seed, layer, leaf) alone, an
expert's also of its published id and of nothing else, so that the shares
of a layer add up to the uncut layer; the two matrices that write the
residual stream are scaled by the published depth, so that the layers of a
cut in depth are the first layers of the uncut model (`uncut`).  They are
drawn in float32 and, where `flags.param_dtype` says bfloat16, rounded to it
once, here: the program and this reference then hold the same numbers, and
this reference computes with them in float32.  Every product with a weight,
and attention's scores and values, go through the shared `_mm`, whose
`precision="int8"` is the control of `correct`; the router's logits and the
index scores' product stay float32 `highest`: each feeds a choice.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmark.reference import Frozen, _mm, base_key

_W_STD = 0.02      # every matrix; the two that write the residual stream
_G_STD = 0.02      # are scaled by 1/sqrt(2 L), L the published depth.  Norm
                   # weights lie about 1 (the LayerNorm's bias about 0), so
                   # that a path that drops one shows
_B_STD = 0.005     # the router's bias: seeded, not zero, and about the gap
                   # between the 8th and the 9th of a token's 256 scores, so
                   # that it settles near ties and does not do the routing
_HI = jax.lax.Precision.HIGHEST
_HEAD_INDEX = 1 << 20   # the head's key: the same in the cut and the uncut


def sizes(cfg: dict) -> dict:
    held = int(cfg["n_routed_experts"])
    d = int(cfg["hidden_size"])

    def geometry(pre: str, heads: str) -> dict:
        return {"heads": int(cfg[heads]), "q_rank": int(cfg[pre + "q_lora_rank"]),
                "kv_rank": int(cfg[pre + "kv_lora_rank"]),
                "nope": int(cfg[pre + "qk_nope_head_dim"]),
                "rope": int(cfg[pre + "qk_rope_head_dim"]),
                "v_dim": int(cfg[pre + "v_head_dim"]),
                "theta": float(cfg[pre + "rope_theta"])}

    return {
        "d": d, "layers": int(cfg["num_hidden_layers"]),
        "depth_published": int(cfg.get("published", {}).get(
            "num_hidden_layers", cfg["num_hidden_layers"])),
        "dense_layers": int(cfg["first_k_dense_replace"]),
        "vocab": int(cfg["vocab_size"]),
        "positions": int(cfg["max_position_embeddings"]),
        "eps": float(cfg["rms_norm_eps"]),
        "rescale": bool(cfg["apply_mla_qkv_lora_rescale"]),
        "full": geometry("", "num_attention_heads"),
        "window": {**geometry("swa_", "swa_num_attention_heads"),
                   "span": int(cfg["sliding_window_size"])},
        "index_heads": int(cfg["index_n_heads"]),
        "index_dim": int(cfg["index_head_dim"]),
        "index_topk": int(cfg["index_topk"]),
        "ffn": int(cfg["intermediate_size"]),
        "expert_ffn": int(cfg["moe_intermediate_size"]),
        "shared_ffn": int(cfg["moe_intermediate_size"]) * int(cfg["n_shared_experts"]),
        "experts_held": held,
        "experts_routed": int(cfg.get("published", {}).get("n_routed_experts", held)),
        "first_expert": held * int(cfg.get("deployment", {}).get("rank", 0)),
        "top_k": int(cfg["num_experts_per_tok"]),
        "routed_scaling": float(cfg["routed_scaling_factor"]),
    }


def layer_kinds(cfg: dict) -> list:
    """embed, then (full | window, swiglu | moe) a block, then head (the
    final norm is the head's)."""
    names = {"sliding_attention": "window", "full_attention": "full"}
    s = sizes(cfg)
    if (cfg["attention_gate_type"], cfg["swa_attention_gate_type"]) != (
            "headwise", "headwise") or cfg["scoring_func"] != "sigmoid":
        raise ValueError("only head-wise gates and sigmoid scores are written "
                         "down here")
    out = ["embed"]
    for i, kind in enumerate(cfg["layer_types"][: s["layers"]]):
        out += [names[kind], "swiglu" if i < s["dense_layers"] else "moe"]
    return out + ["head"]


def _leaf_specs(cfg: dict, kind: str) -> dict:
    """name -> (shape, spread, centre) of one layer of `kind`.  The leaves of
    the held experts lead with the expert axis."""
    s = sizes(cfg)
    d = s["d"]
    resid = _W_STD / math.sqrt(2.0 * s["depth_published"])
    ln = ((d,), _G_STD, 1.0)
    if kind == "embed":
        return {"W": ((s["vocab"], d), _W_STD, 0.0)}
    if kind in ("full", "window"):
        g = s[kind]
        h = g["heads"]
        out = {"Wqa": ((d, g["q_rank"]), _W_STD, 0.0),
               "q_norm": ((g["q_rank"],), _G_STD, 1.0),
               "Wqb": ((g["q_rank"], h * (g["nope"] + g["rope"])), _W_STD, 0.0),
               "Wkva": ((d, g["kv_rank"] + g["rope"]), _W_STD, 0.0),
               "c_norm": ((g["kv_rank"],), _G_STD, 1.0),
               "Wkvb": ((g["kv_rank"], h * (g["nope"] + g["v_dim"])), _W_STD, 0.0),
               "Wg": ((d, h), _W_STD, 0.0),
               "Wo": ((h * g["v_dim"], d), resid, 0.0), "ln": ln}
        if kind == "full":
            j, n = s["index_heads"], s["index_dim"]
            out.update({"Wiq": ((g["q_rank"], j * n), _W_STD, 0.0),
                        "Wik": ((d, n), _W_STD, 0.0),
                        "ik_g": ((n,), _G_STD, 1.0), "ik_b": ((n,), _G_STD, 0.0),
                        "Wiw": ((d, j), _W_STD, 0.0)})
        return out
    if kind == "swiglu":
        f = s["ffn"]
        return {"Wgate": ((d, f), _W_STD, 0.0), "Wup": ((d, f), _W_STD, 0.0),
                "Wdown": ((f, d), resid, 0.0), "ln": ln}
    if kind == "moe":
        e, f, fs = s["experts_held"], s["expert_ffn"], s["shared_ffn"]
        return {"Wr": ((d, s["experts_routed"]), _W_STD, 0.0),
                "b": ((s["experts_routed"],), _B_STD, 0.0),
                "Wgate": ((e, d, f), _W_STD, 0.0), "Wup": ((e, d, f), _W_STD, 0.0),
                "Wdown": ((e, f, d), resid, 0.0),
                "sWgate": ((d, fs), _W_STD, 0.0), "sWup": ((d, fs), _W_STD, 0.0),
                "sWdown": ((fs, d), resid, 0.0), "ln": ln}
    if kind == "head":
        return {"W": ((d, s["vocab"]), _W_STD, 0.0), "norm": ln}
    raise ValueError(f"no layer kind {kind!r}")


_EXPERT_LEAVES = ("Wgate", "Wup", "Wdown")


def layer_weights(cfg: dict, key, index: int, kind: str) -> dict:
    """One layer's leaves, uniform with the stated spread about the centre,
    in `flags.param_dtype`.  An expert's leaves come from its published id."""
    lk = jax.random.fold_in(key, _HEAD_INDEX if kind == "head" else index)
    dtype = jnp.dtype(cfg["flags"]["param_dtype"])
    first = sizes(cfg)["first_expert"]
    out = {}
    for j, (name, (shape, std, centre)) in enumerate(
            sorted(_leaf_specs(cfg, kind).items())):
        a = std * math.sqrt(3.0)
        jk = jax.random.fold_in(lk, j)
        if kind == "moe" and name in _EXPERT_LEAVES:
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
    `always` are the matrices every token passes (attention with its indexer
    and gate, dense and shared FFN, router, head), `expert` one routed
    expert's, `expert_layers` the layers that have them, `full` and `window`
    one attention layer's matrices of each geometry; the table and the
    vectors are the rest."""
    total = always = 0
    attention = {"full": 0, "window": 0}
    kinds = layer_kinds(cfg)
    for kind in kinds:
        for name, (shape, _, _) in _leaf_specs(cfg, kind).items():
            n = math.prod(shape)
            total += n
            if kind != "embed" and len(shape) == 2:
                always += n
    for kind in attention:
        attention[kind] = sum(math.prod(shape) for shape, _, _ in
                              _leaf_specs(cfg, kind).values() if len(shape) == 2)
    s = sizes(cfg)
    return {"all": total, "always": always,
            "expert": 3 * s["d"] * s["expert_ffn"],
            "expert_layers": kinds.count("moe"), **attention}


def uncut(cfg: dict) -> dict:
    """The configuration as published: every key of `published` back in its
    place, every expert and the whole vocabulary on one rank."""
    pub = cfg["published"]
    return {**cfg, **{k: v for k, v in pub.items() if k in cfg},
            "deployment": {**cfg.get("deployment", {}), "rank": 0}}


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


def layer_norm(x, g, b, eps: float):
    x = x - jnp.mean(x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * g + b


def rope(x, positions, theta: float):
    """Rotate-half RoPE over the whole last axis of x [S, (H,) n];
    `positions` [S]."""
    n = x.shape[-1]
    inv = theta ** (-jnp.arange(0, n, 2, dtype=jnp.float32) / n)
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]      # [S, n/2]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], axis=-1)
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], axis=-1)
    if x.ndim == 3:                                                    # [S, H, n]
        cos, sin = cos[:, None, :], sin[:, None, :]
    half = jnp.concatenate([-x[..., n // 2:], x[..., : n // 2]], axis=-1)
    return x * cos + half * sin


def _rope_first(x, positions, theta: float, n: int):
    """RoPE over the first `n` dimensions of the last axis, the rest as is."""
    return jnp.concatenate([rope(x[..., :n], positions, theta), x[..., n:]], axis=-1)


_Q_ROWS = 128       # query rows a block: scores [H, 128, T] at a time


def index_scores(w: dict, u, cq, s: dict, precision: str = "f32"):
    """(qi [T, J, n], ki [T, n], w [T, J]) of one row: the indexer's
    queries, keys and head weights, the weights scaled by 1 / sqrt(J n)."""
    t = u.shape[0]
    g, j, n = s["full"], s["index_heads"], s["index_dim"]
    pos = jnp.arange(t)
    qi = _mm("sr,re->se", cq, w["Wiq"], -1, 0, precision).reshape(t, j, n)
    ki = layer_norm(_mm("sd,de->se", u, w["Wik"], -1, 0, precision),
                    w["ik_g"], w["ik_b"], s["eps"])
    wi = _mm("sd,dj->sj", u, w["Wiw"], -1, 0, precision) / math.sqrt(j * n)
    return (_rope_first(qi, pos, g["theta"], g["rope"]),
            _rope_first(ki, pos, g["theta"], g["rope"]), wi)


def picked(qi, ki, wi, rows, topk: int):
    """The mask [R, T] of the positions the queries at `rows` [R] attend to:
    the `topk` positions s <= t of largest I[t, s], by `jax.lax.top_k` (ties
    to the lower position), all of them while there are at most `topk`."""
    t = ki.shape[0]
    dots = jnp.einsum("qjn,kn->qjk", qi, ki, precision=_HI)
    scores = jnp.sum(jax.nn.relu(dots) * wi[..., None], axis=1)      # [R, T]
    earlier = jnp.arange(t)[None, :] <= rows[:, None]
    _, ids = jax.lax.top_k(jnp.where(earlier, scores, -jnp.inf), min(topk, t))
    mask = jnp.zeros(scores.shape, bool).at[
        jnp.arange(rows.shape[0])[:, None], ids].set(True)
    return mask & earlier


def _attention_row(w: dict, x, s: dict, kind: str, precision: str):
    """One row x [T, d]; T a multiple of `_Q_ROWS` or less than it."""
    t, d = x.shape
    g = s[kind]
    h, nope, rp, vd, r = g["heads"], g["nope"], g["rope"], g["v_dim"], g["kv_rank"]
    a_q = math.sqrt(d / g["q_rank"]) if s["rescale"] else 1.0
    a_kv = math.sqrt(d / r) if s["rescale"] else 1.0
    pos = jnp.arange(t)
    u = rms_norm(x, w["ln"], s["eps"])
    cq = a_q * rms_norm(_mm("sd,dr->sr", u, w["Wqa"], -1, 0, precision),
                        w["q_norm"], s["eps"])
    q = _mm("sr,re->se", cq, w["Wqb"], -1, 0, precision).reshape(t, h, nope + rp)
    q_nope, q_rope = q[..., :nope], rope(q[..., nope:], pos, g["theta"])
    kva = _mm("sd,de->se", u, w["Wkva"], -1, 0, precision)
    c = a_kv * rms_norm(kva[..., :r], w["c_norm"], s["eps"])
    kr = rope(kva[..., r:], pos, g["theta"])                           # [T, rope]
    kv = _mm("sr,re->se", c, w["Wkvb"], -1, 0, precision).reshape(t, h, nope + vd)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    gate = jax.nn.sigmoid(_mm("sd,dh->sh", u, w["Wg"], -1, 0, precision))
    if kind == "full":
        qi, ki, wi = index_scores(w, u, cq, s, precision)
    block = min(_Q_ROWS, t)

    def rows(start):
        """Query rows [start, start + block) against every key."""
        at = start + jnp.arange(block)
        cut = lambda a: jax.lax.dynamic_slice_in_dim(a, start, block, axis=0)  # noqa: E731
        if kind == "full":
            seen = picked(cut(qi), ki, cut(wi), at, s["index_topk"])
        else:
            seen = ((pos[None, :] <= at[:, None])
                    & (pos[None, :] > at[:, None] - g["span"]))
        sc = (_mm("qhn,khn->hqk", cut(q_nope), k_nope, -1, -1, precision)
              + _mm("qhe,ke->hqk", cut(q_rope), kr, -1, -1, precision)
              ) / math.sqrt(nope + rp)
        p = jax.nn.softmax(jnp.where(seen[None], sc, -1e30), axis=-1)
        return _mm("hqk,khv->qhv", p, v, -1, 0, precision)            # [block, H, v]

    if t <= _Q_ROWS:
        o = rows(0)
    else:
        o = jax.lax.map(rows, jnp.arange(0, t, _Q_ROWS)).reshape(t, h, vd)
    o = (o * gate[..., None]).reshape(t, h * vd)
    return x + _mm("se,ed->sd", o, w["Wo"], -1, 0, precision)


def attention(w: dict, x, s: dict, kind: str, precision: str = "f32"):
    """One attention layer over x [B, T, d], a row at a time."""
    return jax.lax.map(lambda row: _attention_row(w, row, s, kind, precision), x)


def _swiglu(u, wg, wu, wd, precision: str):
    a = (jax.nn.silu(_mm("td,df->tf", u, wg, -1, 0, precision))
         * _mm("td,df->tf", u, wu, -1, 0, precision))
    return _mm("tf,fd->td", a, wd, -1, 0, precision)


def swiglu(w: dict, x, s: dict, precision: str = "f32"):
    """The dense FFN, a row at a time ([T, 13,824] float32 three times over
    is 1.7 GB a row of 10,240)."""
    def one(row):
        u = rms_norm(row, w["ln"], s["eps"])
        return row + _swiglu(u, w["Wgate"], w["Wup"], w["Wdown"], precision)

    return jax.lax.map(one, x)


def route(scores, bias, s: dict):
    """The choice: `scores` [T, routed] in float32 -> (ids [T, top_k], weights
    [T, top_k]).  The bias enters the choice and not the weights."""
    ids = jax.lax.top_k(scores + bias, s["top_k"])[1]
    picked_scores = jnp.take_along_axis(scores, ids, axis=-1)
    return ids, s["routed_scaling"] * picked_scores / jnp.sum(
        picked_scores, axis=-1, keepdims=True)


def moe_parts(w: dict, u, s: dict, precision: str = "f32"):
    """(what the held experts give, what the shared expert gives) for rows
    u [T, d]: every held expert over every row, one expert at a time, weighted
    by the row's routing weight for it (0 where it was not picked).  The
    leaves without an expert axis are float32 already; an expert's are
    upcast as its turn comes."""
    scores = jax.nn.sigmoid(jnp.einsum("td,de->te", u, w["Wr"], precision=_HI))
    ids, weights = route(scores, w["b"], s)
    local = ids - s["first_expert"]                                    # [T, top_k]

    def one(acc, xs):
        e, wg, wu, wd = xs
        mine = jnp.sum(jnp.where(local == e, weights, 0.0), axis=-1)   # [T]
        return acc + mine[:, None] * _swiglu(
            u, wg.astype(jnp.float32), wu.astype(jnp.float32),
            wd.astype(jnp.float32), precision), None

    routed, _ = jax.lax.scan(
        one, jnp.zeros_like(u),
        (jnp.arange(s["experts_held"]), w["Wgate"], w["Wup"], w["Wdown"]))
    return routed, _swiglu(u, w["sWgate"], w["sWup"], w["sWdown"], precision)


def moe(w: dict, x, s: dict, precision: str = "f32"):
    small = {k: v.astype(jnp.float32) for k, v in w.items() if v.ndim < 3}

    def one(row):
        u = rms_norm(row, small["ln"], s["eps"])
        routed, shared = moe_parts({**w, **small}, u, s, precision)
        return row + routed + shared

    return jax.lax.map(one, x)


def head_logits(w: dict, x, s: dict, precision: str = "f32"):
    return _mm("bsd,dv->bsv", rms_norm(x, w["norm"], s["eps"]), w["W"], -1, 0, precision)


def apply_layer(kind: str, w: dict, x, cfg: dict, precision: str = "f32"):
    s = sizes(cfg)
    if kind == "embed":
        return w["W"].astype(jnp.float32)[x]
    if kind == "moe":                   # upcasts its experts one at a time
        return moe(w, x, s, precision)
    if kind in ("full", "window"):
        return attention(_f32(w), x, s, kind, precision)
    return {"swiglu": swiglu, "head": head_logits}[kind](_f32(w), x, s, precision)


# ---------------------------------------------------------------- serving

_PAD = 128          # lengths stay on a grid (the Ling family's lesson: at a
                    # length off it the TPU's compiler once made NaN)


def teacher_forced_logits(cfg: dict, seed: int, ids, precisions=("f32",)):
    """Teacher-forced logits of `ids` [B, S], one layer's weights alive at a
    time.  Returns {precision: logits [B, S, V]}; position t holds the
    next-token logits after consuming ids[:, :t+1].

    The ids are padded with zeros to a whole number of `_PAD` positions and
    the hidden rows cut back before the head: nothing here looks ahead, so no
    position sees the padding.  Every layer but the head runs a row at a time: at 10,240
    positions one row's queries, keys and values of 128 heads are 2.6 GB in
    float32, and a block's scores 0.7."""
    key, frozen = base_key(seed), Frozen(cfg)
    ids = jnp.asarray(ids, jnp.int32)
    length = ids.shape[1]
    ids = jnp.pad(ids, ((0, 0), (0, -length % _PAD)))
    xs = {p: ids for p in precisions}
    with jax.default_matmul_precision("highest"):
        for i, kind in enumerate(layer_kinds(cfg)):
            w = _layer_jit(kind)(frozen, key, i)
            for p in precisions:
                if kind == "head":      # cut back before the logits are made:
                    xs[p] = xs[p][:, :length]   # a slice of them is a copy of them
                xs[p] = _apply_jit(kind, p)(w, xs[p], frozen)
            del w
    return xs


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
        "this configuration serves only: trained at 16 bytes a parameter no "
        "cut within the floors fits one chip (1 dense + 4 expert layers with "
        "8 experts held and an eighth of the vocabulary are 29 GB), and the "
        "new layers have no checked backward pass "
        "(configs/dots3-note-prev-ep8.json)")
