"""The plain reference of Ling-3.0-flash-VL's language model: weights from a
seed, and its layers in straightforward float32 `jax.numpy`, with no cache,
no kernels and no batching tricks.  Nothing here imports the program.

d = `hidden_size`, H = `num_attention_heads`, no bias anywhere, head untied.
A block is `h = x + Attn(RMSNorm(x))`, `y = h + FFN(RMSNorm(h))`, and a final
RMSNorm stands before the head.  Layer `i` is MLA where `(i + 1) %
layer_group_size == 0`, else KDA; its FFN is dense SwiGLU for `i <
first_k_dense_replace`, else routed.  The vision tower is absent.

KDA (Kimi Delta Attention, arXiv:2510.26692), with `u = RMSNorm(x)`:
  q~, k~, v~ = Wq u, Wk u, Wv u, each through a depthwise causal convolution
  over time of `short_conv_kernel_size` taps and SiLU;
  q = l2norm_head(q~) / sqrt(dk), k = l2norm_head(k~);
  g_t = kda_lower_bound * sigmoid(exp(A_log_h) * (Wa u + dt_bias)), a number
  a channel, a_t = exp(g_t); beta_t = sigmoid(Wb u), a number a head;
  S' = diag(a_t) S_{t-1}; S_t = S' + beta_t k_t (v_t - k_t^T S')^T;
  o_t = S_t^T q_t, then RMSNorm head by head, times sigmoid(Wg u)_h, then Wo.
  It runs here as a scan over the tokens, from a zero state.
MLA (arXiv:2405.04434, no low-rank query): q = Wq u in [H, nope + rope];
  [c~; kr~] = Wkva u; c = RMSNorm(c~); kr = RoPE(kr~), one for all heads;
  [k_nope; v] = Wkvb c; scores (q_nope . k_nope + RoPE(q_rope) . kr) /
  sqrt(nope + rope), causal softmax, Wo.  Keys and values are materialised.
Routed FFN (routing of arXiv:2412.19437): s = sigmoid(Wr u) over all the
  published experts; for the choice only s' = s + b; a group scores the sum
  of its two largest s'; the `topk_group` best groups stay; T = the
  `num_experts_per_tok` largest s' among them; w_e = routed_scaling_factor *
  s_e / sum_{T} s; y = sum_{e in T, e held} w_e E_e(u) + E_shared(u), E(u) =
  Wdown(silu(Wgate u) * Wup u).  `num_experts` experts are held, from
  `deployment.rank * num_experts` on; `published.num_experts` is the router's
  width.  What the absent experts would add is left out.

The weights are a function of (configuration, seed, layer, leaf) alone, an
expert's also of its published id and of nothing else, so that the shares
of a layer add up to the uncut layer.  They are drawn in float32 and, where
`flags.param_dtype` says bfloat16, rounded to it once, here: the program and
this reference then hold the same numbers, and this reference computes with
them in float32.  Every product with a weight, and MLA's scores and values,
go through the shared `_mm`, whose `precision="int8"` is the control of
`correct`; the KDA state's own arithmetic stays float32.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmark.reference import Frozen, _mm, base_key

_W_STD = 0.02      # every matrix; the two that write the residual stream
_G_STD = 0.02      # are scaled by 1/sqrt(2L).  Norm weights lie about 1,
                   # so that a path that drops one shows
_CONV_STD = 0.03   # the convolution's taps: small, so that SiLU sees inputs
                   # of some 0.06 and stays near its linear part.  At 0.3
                   # its positive mean gave q, k and v of every token a common
                   # direction, a third of the normed stream's norm was the
                   # same for all tokens, and the router sent them to the
                   # same experts (65 to 71 of 128 hit by 64 tokens, where
                   # even routing hits 81, and a rank's share of the picks
                   # swung by seed); at 0.03 it is a twentieth and 75 to 85
                   # (CPU, float32, 512 tokens, seed 5200000029, PR 28).  A
                   # trained router's bias does that balancing; a seed's
                   # cannot
_B_STD = 0.005     # the router's bias: seeded, not zero, and about the gap
                   # between the 8th and the 9th score of a token (0.0065),
                   # so that it settles near ties and does not do the routing
_HI = jax.lax.Precision.HIGHEST


def sizes(cfg: dict) -> dict:
    held = int(cfg["num_experts"])
    return {
        "d": int(cfg["hidden_size"]), "heads": int(cfg["num_attention_heads"]),
        "layers": int(cfg["num_hidden_layers"]),
        "dense_layers": int(cfg["first_k_dense_replace"]),
        "period": int(cfg["layer_group_size"]),
        "vocab": int(cfg["vocab_size"]),
        "positions": int(cfg["max_position_embeddings"]),
        "ffn": int(cfg["intermediate_size"]),
        "expert_ffn": int(cfg["moe_intermediate_size"]),
        "shared_ffn": int(cfg["moe_shared_expert_intermediate_size"]),
        "experts_held": held,
        "experts_routed": int(cfg.get("published", {}).get("num_experts", held)),
        "first_expert": held * int(cfg.get("deployment", {}).get("rank", 0)),
        "top_k": int(cfg["num_experts_per_tok"]),
        "n_group": int(cfg["n_group"]), "topk_group": int(cfg["topk_group"]),
        "routed_scaling": float(cfg["routed_scaling_factor"]),
        "kda_dim": int(cfg["head_dim"]),
        "conv": int(cfg["short_conv_kernel_size"]),
        "kda_lower_bound": float(cfg["kda_lower_bound"]),
        "kv_rank": int(cfg["kv_lora_rank"]),
        "nope": int(cfg["qk_nope_head_dim"]), "rope": int(cfg["qk_rope_head_dim"]),
        "v_dim": int(cfg["v_head_dim"]), "rope_theta": float(cfg["rope_theta"]),
        "eps": float(cfg["rms_norm_eps"]),
    }


def layer_kinds(cfg: dict) -> list:
    """embed, then (kda | mla, swiglu | moe) a block, then head (the final
    norm is the head's)."""
    s = sizes(cfg)
    out = ["embed"]
    for i in range(s["layers"]):
        out.append("mla" if (i + 1) % s["period"] == 0 else "kda")
        out.append("swiglu" if i < s["dense_layers"] else "moe")
    return out + ["head"]


def _leaf_specs(cfg: dict, kind: str) -> dict:
    """name -> (shape, spread, centre) of one layer of `kind`.  The leaves of
    the held experts lead with the expert axis."""
    s = sizes(cfg)
    d, h = s["d"], s["heads"]
    resid = _W_STD / math.sqrt(2.0 * s["layers"])
    ln = ((d,), _G_STD, 1.0)
    if kind == "embed":
        return {"W": ((s["vocab"], d), _W_STD, 0.0)}
    if kind == "kda":
        c = h * s["kda_dim"]
        out = {n: ((d, c), _W_STD, 0.0) for n in ("Wq", "Wk", "Wv", "Wa")}
        out.update({n: ((c, s["conv"]), _CONV_STD, 0.0)
                    for n in ("conv_q", "conv_k", "conv_v")})
        out.update({"A_log": ((h,), 0.3, 0.0), "dt_bias": ((c,), 1.0, -4.0),
                    "Wb": ((d, h), _W_STD, 0.0), "Wg": ((d, h), _W_STD, 0.0),
                    "o_norm": ((s["kda_dim"],), _G_STD, 1.0),
                    "Wo": ((c, d), resid, 0.0), "ln": ln})
        return out
    if kind == "mla":
        return {"Wq": ((d, h * (s["nope"] + s["rope"])), _W_STD, 0.0),
                "Wkva": ((d, s["kv_rank"] + s["rope"]), _W_STD, 0.0),
                "c_norm": ((s["kv_rank"],), _G_STD, 1.0),
                "Wkvb": ((s["kv_rank"], h * (s["nope"] + s["v_dim"])), _W_STD, 0.0),
                "Wo": ((h * s["v_dim"], d), resid, 0.0), "ln": ln}
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
    lk = jax.random.fold_in(key, index)
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
    `always` are the matrices every token passes (attention, dense and
    shared FFN, router, head), `expert` one routed expert's, `expert_layers`
    the layers that have them; the table and the vectors are the rest."""
    total = always = 0
    for kind in layer_kinds(cfg):
        for name, (shape, _, _) in _leaf_specs(cfg, kind).items():
            n = math.prod(shape)
            total += n
            if kind != "embed" and len(shape) == 2 and not name.startswith("conv"):
                always += n
    s = sizes(cfg)
    return {"all": total, "always": always,
            "expert": 3 * s["d"] * s["expert_ffn"],
            "expert_layers": s["layers"] - s["dense_layers"]}


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


def l2norm(x, eps: float = 1e-6):
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True) + eps)


def rope(x, positions, theta: float):
    """Rotate-half RoPE over the whole last axis of x [..., S, (H,) n];
    `positions` [S]."""
    n = x.shape[-1]
    inv = theta ** (-jnp.arange(0, n, 2, dtype=jnp.float32) / n)
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]      # [S, n/2]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], axis=-1)
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], axis=-1)
    if x.ndim == 4:                                                    # [B, S, H, n]
        cos, sin = cos[:, None, :], sin[:, None, :]
    half = jnp.concatenate([-x[..., n // 2:], x[..., : n // 2]], axis=-1)
    return x * cos + half * sin


def _conv_silu(x, w):
    """Depthwise causal convolution over time, then SiLU: x [B, S, C],
    w [C, K]; tap K-1 multiplies the current token."""
    k = w.shape[1]
    pad = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    y = sum(pad[:, j: j + x.shape[1]] * w[:, j] for j in range(k))
    return jax.nn.silu(y)


def kda_inputs(w: dict, x, s: dict, precision: str = "f32"):
    """What the recurrence consumes: q, k, v [B, S, H, dk], the log decay g
    [B, S, H, dk], beta [B, S, H]; and the output gate [B, S, H]."""
    b, t, _ = x.shape
    h, dk = s["heads"], s["kda_dim"]
    u = rms_norm(x, w["ln"], s["eps"])

    def proj(n):
        return _mm("bsd,de->bse", u, w[n], -1, 0, precision)

    q = _conv_silu(proj("Wq"), w["conv_q"]).reshape(b, t, h, dk)
    k = _conv_silu(proj("Wk"), w["conv_k"]).reshape(b, t, h, dk)
    v = _conv_silu(proj("Wv"), w["conv_v"]).reshape(b, t, h, dk)
    q, k = l2norm(q) / math.sqrt(dk), l2norm(k)
    g = s["kda_lower_bound"] * jax.nn.sigmoid(
        jnp.exp(w["A_log"])[:, None]
        * (proj("Wa") + w["dt_bias"]).reshape(b, t, h, dk))
    return q, k, v, g, jax.nn.sigmoid(proj("Wb")), jax.nn.sigmoid(proj("Wg"))


def kda_recurrence(q, k, v, g, beta, state):
    """The token recurrence from `state` [B, H, dk, dv]: returns the outputs
    [B, S, H, dv] and the state after the last token."""

    def step(st, xs):
        qt, kt, vt, gt, bt = xs
        st = jnp.exp(gt)[..., None] * st
        ks = jnp.einsum("bhi,bhij->bhj", kt, st, precision=_HI)
        st = st + (bt[..., None] * kt)[..., None] * (vt - ks)[..., None, :]
        return st, jnp.einsum("bhi,bhij->bhj", qt, st, precision=_HI)

    state, out = jax.lax.scan(
        step, state, tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta)))
    return jnp.moveaxis(out, 0, 1), state


def kda(w: dict, x, s: dict, precision: str = "f32"):
    b, t, d = x.shape
    h, dk = s["heads"], s["kda_dim"]
    q, k, v, g, beta, gate = kda_inputs(w, x, s, precision)
    o, _ = kda_recurrence(q, k, v, g, beta, jnp.zeros((b, h, dk, dk), jnp.float32))
    o = rms_norm(o, w["o_norm"], s["eps"]) * gate[..., None]
    return x + _mm("bse,ed->bsd", o.reshape(b, t, h * dk), w["Wo"], -1, 0, precision)


def mla(w: dict, x, s: dict, precision: str = "f32"):
    b, t, d = x.shape
    h, r, nope, rp, vd = s["heads"], s["kv_rank"], s["nope"], s["rope"], s["v_dim"]
    pos = jnp.arange(t)
    u = rms_norm(x, w["ln"], s["eps"])
    q = _mm("bsd,de->bse", u, w["Wq"], -1, 0, precision).reshape(b, t, h, nope + rp)
    q_nope, q_rope = q[..., :nope], rope(q[..., nope:], pos, s["rope_theta"])
    kva = _mm("bsd,de->bse", u, w["Wkva"], -1, 0, precision)
    c = rms_norm(kva[..., :r], w["c_norm"], s["eps"])
    kr = rope(kva[..., r:], pos, s["rope_theta"])                     # [B, S, rope]
    kv = _mm("bsr,re->bse", c, w["Wkvb"], -1, 0, precision).reshape(b, t, h, nope + vd)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    sc = (_mm("bqhn,bkhn->bhqk", q_nope, k_nope, -1, -1, precision)
          + _mm("bqhe,bke->bhqk", q_rope, kr, -1, -1, precision)) / math.sqrt(nope + rp)
    causal = jnp.tril(jnp.ones((t, t), bool))
    p = jax.nn.softmax(jnp.where(causal, sc, -1e30), axis=-1)
    o = _mm("bhqk,bkhv->bqhv", p, v, -1, 1, precision).reshape(b, t, h * vd)
    return x + _mm("bse,ed->bsd", o, w["Wo"], -1, 0, precision)


def _swiglu(u, wg, wu, wd, precision: str):
    a = (jax.nn.silu(_mm("td,df->tf", u, wg, -1, 0, precision))
         * _mm("td,df->tf", u, wu, -1, 0, precision))
    return _mm("tf,fd->td", a, wd, -1, 0, precision)


def swiglu(w: dict, x, s: dict, precision: str = "f32"):
    b, t, d = x.shape
    u = rms_norm(x, w["ln"], s["eps"]).reshape(b * t, d)
    return x + _swiglu(u, w["Wgate"], w["Wup"], w["Wdown"], precision).reshape(b, t, d)


def route(scores, bias, s: dict):
    """The choice: `scores` [T, routed] in float32 -> (ids [T, top_k], weights
    [T, top_k]).  The bias enters the choice and not the weights."""
    t, n = scores.shape
    sp = (scores + bias).reshape(t, s["n_group"], n // s["n_group"])
    group = jnp.sum(jax.lax.top_k(sp, 2)[0], axis=-1)                  # [T, groups]
    kept = jax.lax.top_k(group, s["topk_group"])[1]
    mask = jnp.sum(jax.nn.one_hot(kept, s["n_group"], dtype=jnp.int32), axis=1) > 0
    ids = jax.lax.top_k(jnp.where(mask[..., None], sp, -jnp.inf).reshape(t, n),
                        s["top_k"])[1]
    picked = jnp.take_along_axis(scores, ids, axis=-1)
    return ids, s["routed_scaling"] * picked / jnp.sum(picked, axis=-1, keepdims=True)


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
    b, t, d = x.shape
    small = {k: v.astype(jnp.float32) for k, v in w.items() if v.ndim < 3}
    u = rms_norm(x, small["ln"], s["eps"]).reshape(b * t, d)
    routed, shared = moe_parts({**w, **small}, u, s, precision)
    return x + (routed + shared).reshape(b, t, d)


def head_logits(w: dict, x, s: dict, precision: str = "f32"):
    return _mm("bsd,dv->bsv", rms_norm(x, w["norm"], s["eps"]), w["W"], -1, 0, precision)


def apply_layer(kind: str, w: dict, x, cfg: dict, precision: str = "f32"):
    s = sizes(cfg)
    if kind == "embed":
        return w["W"].astype(jnp.float32)[x]
    if kind == "moe":                   # upcasts its experts one at a time
        return moe(w, x, s, precision)
    f = {"kda": kda, "mla": mla, "swiglu": swiglu, "head": head_logits}[kind]
    return f(_f32(w), x, s, precision)


# ---------------------------------------------------------------- serving

def teacher_forced_logits(cfg: dict, seed: int, ids, precisions=("f32",)):
    """Teacher-forced logits of `ids` [B, S], one layer's weights alive at a
    time.  Returns {precision: logits [B, S, V]}; position t holds the
    next-token logits after consuming ids[:, :t+1].

    The ids are padded with zeros to a whole number of `_PAD` positions and
    the logits cut back: nothing here looks ahead, so no position sees the
    padding.  At 6 rows of 1726 the TPU's compiler turned the expert layer's
    output into NaN, every row from position 0 (seed 4300000007, PR 28; the
    same numbers at 1792 and at 2048 positions, and the layer's parts
    computed apart at 1726, were finite): lengths stay on the grid."""
    key, frozen = base_key(seed), Frozen(cfg)
    ids = jnp.asarray(ids, jnp.int32)
    length = ids.shape[1]
    ids = jnp.pad(ids, ((0, 0), (0, -length % _PAD)))
    xs = {p: ids for p in precisions}
    with jax.default_matmul_precision("highest"):
        for i, kind in enumerate(layer_kinds(cfg)):
            w = _layer_jit(kind)(frozen, key, i)
            for p in precisions:
                xs[p] = _apply_jit(kind, p)(w, xs[p], frozen)
            del w
    return {p: x[:, :length] for p, x in xs.items()}


_PAD = 128


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
        "this configuration serves only: at 16 bytes a parameter its floor "
        "cut does not train on one chip (configs/ling-3.0-flash-ep4.json)")
