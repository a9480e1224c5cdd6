"""The plain reference of Qwen3-Next's language model: weights from a seed,
and its layers in straightforward float32 `jax.numpy`, with no cache, no
kernels and no batching tricks.  Nothing here imports the program.

d = `hidden_size`, no bias anywhere, head untied, no multi-token-prediction
head.  A block is `h = x + Mixer(RMSNorm(x))`, `y = h + MoE(RMSNorm(h))`,
and a final RMSNorm stands before the head.  Layer i's mixer is full
attention where `(i + 1) % full_attention_interval == 0`, else Gated
DeltaNet.  Every norm is written with its effective scale, the `1 + w` of
Qwen3-Next's zero-centred RMSNorm: x * rsqrt(mean x^2 + eps) * scale.

Gated DeltaNet (arXiv:2412.06464), with u = RMSNorm(x), Hk =
`linear_num_key_heads` heads of dk = `linear_key_head_dim` for q and k, Hv
= `linear_num_value_heads` heads of dv = `linear_value_head_dim` for v and
z, r = Hv / Hk:
  q~, k~, v~, z = Wq u, Wk u, Wv u, Wz u; [q~; k~; v~] through one depthwise
  causal convolution over time of `linear_conv_kernel_dim` taps, then SiLU;
  q = l2norm_head(q~) / sqrt(dk), k = l2norm_head(k~); value head i reads
  key head i // r;
  beta = sigmoid(Wb u), g = -exp(A_log) * softplus(Wa u + dt_bias), a number
  a value head;
  S' = exp(g_t) S_{t-1}; S_t = S' + beta_t k_t (v_t - k_t^T S')^T;
  o_t = S_t^T q_t; then RMSNorm a value head (a weight of dv) times silu(z),
  then Wo.  It runs here as a scan over the tokens, from a zero state.
Gated attention: Wq u gives each of H = `num_attention_heads` heads a
  query and a gate of h = `head_dim` side by side; k = Wk u and v = Wv u in
  G = `num_key_value_heads` heads of h; RMSNorm over h on every head of q
  and of k; rotate-half rotary over the first `partial_rotary_factor` * h
  dimensions of q and k (m turns with m + R/2 by p * theta^(-2m/R), R the
  rotated width), the rest as they are; query head j reads K/V head j //
  (H / G); scores q . k / sqrt(h), causal float32 softmax, the values'
  sum, times sigmoid(gate) element by element, then Wo.
Routed FFN: p = softmax(Wr u) over all the published experts in float32; T
  = the `num_experts_per_tok` largest, no groups and no bias; w_e = p_e /
  sum_T p (`norm_topk_prob`); y = sum_{e in T, e held} w_e E_e(u) +
  sigmoid(u . w_sg) E_shared(u), E(u) = Wdown(silu(Wgate u) * Wup u).
  `num_experts` experts are held, from `deployment.rank * num_experts` on;
  `published.num_experts` is the router's width.  What the absent experts
  would add is left out.

The weights are a function of (configuration, seed, layer, leaf) alone, an
expert's also of its published id, and of the published depth through the
scale of the matrices that write the residual stream: the layers of a cut
in depth are the first layers of the uncut model, and the shares of a layer
add up to the uncut layer.  The embedding and the head are drawn at the
slice's own shape.  Weights are drawn in float32 and, where
`flags.param_dtype` says bfloat16, rounded to it once, here: the program and
this reference then hold the same numbers, and this reference computes with
them in float32.  Every product with a weight, and attention's scores and
values, go through the shared `_mm`, whose `precision="int8"` is the control
of `correct`; the delta rule's own arithmetic, the router's logits and the
shared expert's gate stay float32 `highest`.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmark.reference import Frozen, _mm, base_key

_W_STD = 0.02      # every matrix; those that write the residual stream are
_G_STD = 0.02      # scaled by 1/sqrt(2 L), L the published depth.  Norm
                   # scales lie about 1, so that a path that drops one shows
_CONV_STD = 0.03   # the convolution's taps: small, so that SiLU stays near
                   # its linear part and gives the tokens no common
                   # direction, which would send them all to the same experts
_HI = jax.lax.Precision.HIGHEST
_HEAD_INDEX = 1 << 20   # the head's key: the same in the cut and the uncut

#: the published switches this reference writes down; any other value refuses
SWITCHES = {
    "decoder_sparse_step": 1, "mlp_only_layers": [], "norm_topk_prob": True,
    "tie_word_embeddings": False, "use_sliding_window": False,
    "hidden_act": "silu", "rope_scaling": None,
}


def sizes(cfg: dict) -> dict:
    held = int(cfg["num_experts"])
    published = cfg.get("published", {})
    h = int(cfg["head_dim"])
    return {
        "d": int(cfg["hidden_size"]), "heads": int(cfg["num_attention_heads"]),
        "kv_heads": int(cfg["num_key_value_heads"]), "head_dim": h,
        "rotary_dim": int(round(h * float(cfg["partial_rotary_factor"]))),
        "theta": float(cfg["rope_theta"]),
        "k_heads": int(cfg["linear_num_key_heads"]),
        "v_heads": int(cfg["linear_num_value_heads"]),
        "k_dim": int(cfg["linear_key_head_dim"]),
        "v_dim": int(cfg["linear_value_head_dim"]),
        "conv": int(cfg["linear_conv_kernel_dim"]),
        "interval": int(cfg["full_attention_interval"]),
        "layers": int(cfg["num_hidden_layers"]),
        "depth_published": int(published.get("num_hidden_layers", cfg["num_hidden_layers"])),
        "vocab": int(cfg["vocab_size"]),
        "positions": int(cfg["max_position_embeddings"]),
        "expert_ffn": int(cfg["moe_intermediate_size"]),
        "shared_ffn": int(cfg["shared_expert_intermediate_size"]),
        "experts_held": held,
        "experts_routed": int(published.get("num_experts", held)),
        "first_expert": held * int(cfg.get("deployment", {}).get("rank", 0)),
        "ranks": int(cfg.get("deployment", {}).get("chips_per_layer", 1)),
        "top_k": int(cfg["num_experts_per_tok"]),
        "eps": float(cfg["rms_norm_eps"]),
    }


def layer_kinds(cfg: dict) -> list:
    """embed, then (gdn | attn, moe) a block, then head (the final norm is
    the head's)."""
    for key, value in SWITCHES.items():
        if cfg[key] != value:
            raise ValueError(f"{key} {cfg[key]!r}: only the published switches "
                             f"are written down here")
    s = sizes(cfg)
    out = ["embed"]
    for i in range(s["layers"]):
        out += ["attn" if (i + 1) % s["interval"] == 0 else "gdn", "moe"]
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
    if kind == "gdn":
        qk, vz, hv = s["k_heads"] * s["k_dim"], s["v_heads"] * s["v_dim"], s["v_heads"]
        return {"ln": ln,
                "Wq": ((d, qk), _W_STD, 0.0), "Wk": ((d, qk), _W_STD, 0.0),
                "Wv": ((d, vz), _W_STD, 0.0), "Wz": ((d, vz), _W_STD, 0.0),
                "Wb": ((d, hv), _W_STD, 0.0), "Wa": ((d, hv), _W_STD, 0.0),
                "conv1d": ((2 * qk + vz, s["conv"]), _CONV_STD, 0.0),
                "A_log": ((hv,), 0.3, 0.0), "dt_bias": ((hv,), 1.0, -4.0),
                "o_norm": ((s["v_dim"],), _G_STD, 1.0),
                "Wo": ((vz, d), resid, 0.0)}
    if kind == "attn":
        h, heads, g = s["head_dim"], s["heads"], s["kv_heads"]
        return {"ln": ln,
                "Wq": ((d, heads * 2 * h), _W_STD, 0.0),
                "Wk": ((d, g * h), _W_STD, 0.0), "Wv": ((d, g * h), _W_STD, 0.0),
                "q_norm": ((h,), _G_STD, 1.0), "k_norm": ((h,), _G_STD, 1.0),
                "Wo": ((heads * h, d), resid, 0.0)}
    if kind == "moe":
        e, f, fs = s["experts_held"], s["expert_ffn"], s["shared_ffn"]
        return {"ln": ln, "Wr": ((d, s["experts_routed"]), _W_STD, 0.0),
                "Wgate": ((e, d, f), _W_STD, 0.0), "Wup": ((e, d, f), _W_STD, 0.0),
                "Wdown": ((e, f, d), resid, 0.0),
                "sWgate": ((d, fs), _W_STD, 0.0), "sWup": ((d, fs), _W_STD, 0.0),
                "sWdown": ((fs, d), resid, 0.0), "Wsg": ((d, 1), _W_STD, 0.0)}
    if kind == "head":
        return {"W": ((d, s["vocab"]), _W_STD, 0.0), "norm": ln}
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
    `always` are the matrices every token passes outside the routed experts
    and the vocabulary (the mixers' projections, the router, the shared
    expert and its gate); `embed` and `head` the two vocabulary tables;
    `gdn` and `attn` one mixer of each kind, its norm excluded; `expert`
    one routed expert's; `expert_layers` the layers that have them;
    `active` what a token reads of the model: everything but the routed
    experts, and `num_experts_per_tok` of them a layer."""
    s = sizes(cfg)
    total = always = 0
    for kind in layer_kinds(cfg):
        for name, (shape, _, _) in _leaf_specs(cfg, kind).items():
            n = math.prod(shape)
            total += n
            if (kind in ("gdn", "attn", "moe") and len(shape) == 2
                    and name != "conv1d"):
                always += n

    def mixer(kind):
        return sum(math.prod(shape) for name, (shape, _, _)
                   in _leaf_specs(cfg, kind).items() if name != "ln")

    expert = 3 * s["d"] * s["expert_ffn"]
    return {"all": total, "always": always,
            "embed": s["vocab"] * s["d"], "head": s["d"] * s["vocab"],
            "gdn": mixer("gdn"), "attn": mixer("attn"),
            "expert": expert, "expert_layers": s["layers"],
            "active": total - s["layers"] * (s["experts_held"] - s["top_k"]) * expert}


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


def rope_partial(x, positions, theta: float, n: int):
    """Rotate-half rotary over the first `n` dimensions of x [B, S, H, h],
    the rest as they are; `positions` [S]."""
    inv = theta ** (-jnp.arange(0, n, 2, dtype=jnp.float32) / n)
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]      # [S, n/2]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], axis=-1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], axis=-1)[:, None, :]
    turn, rest = x[..., :n], x[..., n:]
    half = jnp.concatenate([-turn[..., n // 2:], turn[..., : n // 2]], axis=-1)
    return jnp.concatenate([turn * cos + half * sin, rest], axis=-1)


def _conv_silu(x, w):
    """Depthwise causal convolution over time, then SiLU: x [B, S, C],
    w [C, K]; tap K-1 multiplies the current token."""
    k = w.shape[1]
    pad = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    return jax.nn.silu(sum(pad[:, j: j + x.shape[1]] * w[:, j] for j in range(k)))


def gdn_inputs(w: dict, x, s: dict, precision: str = "f32"):
    """What the recurrence consumes: q, k [B, S, Hv, dk] (a key head
    repeated for each value head it serves), v [B, S, Hv, dv], the log
    decay g [B, S, Hv] and beta [B, S, Hv]; and z [B, S, Hv, dv]."""
    b, t, _ = x.shape
    hk, hv, dk, dv = s["k_heads"], s["v_heads"], s["k_dim"], s["v_dim"]
    u = rms_norm(x, w["ln"], s["eps"])

    def proj(n):
        return _mm("bsd,de->bse", u, w[n], -1, 0, precision)

    y = _conv_silu(jnp.concatenate([proj("Wq"), proj("Wk"), proj("Wv")], axis=-1),
                   w["conv1d"])
    q = y[..., :hk * dk].reshape(b, t, hk, dk)
    k = y[..., hk * dk: 2 * hk * dk].reshape(b, t, hk, dk)
    v = y[..., 2 * hk * dk:].reshape(b, t, hv, dv)
    q, k = l2norm(q) / math.sqrt(dk), l2norm(k)
    r = hv // hk
    q, k = jnp.repeat(q, r, axis=2), jnp.repeat(k, r, axis=2)
    g = -jnp.exp(w["A_log"]) * jax.nn.softplus(proj("Wa") + w["dt_bias"])
    beta = jax.nn.sigmoid(proj("Wb"))
    return q, k, v, g, beta, proj("Wz").reshape(b, t, hv, dv)


def gdn_recurrence(q, k, v, g, beta, state):
    """The token recurrence from `state` [B, Hv, dk, dv]: returns the
    outputs [B, S, Hv, dv] and the state after the last token."""

    def step(st, xs):
        qt, kt, vt, gt, bt = xs
        st = jnp.exp(gt)[..., None, None] * st
        ks = jnp.einsum("bhi,bhij->bhj", kt, st, precision=_HI)
        st = st + (bt[..., None] * kt)[..., None] * (vt - ks)[..., None, :]
        return st, jnp.einsum("bhi,bhij->bhj", qt, st, precision=_HI)

    state, out = jax.lax.scan(
        step, state, tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta)))
    return jnp.moveaxis(out, 0, 1), state


def gdn(w: dict, x, s: dict, precision: str = "f32"):
    b, t, _ = x.shape
    hv, dk, dv = s["v_heads"], s["k_dim"], s["v_dim"]
    q, k, v, g, beta, z = gdn_inputs(w, x, s, precision)
    o, _ = gdn_recurrence(q, k, v, g, beta, jnp.zeros((b, hv, dk, dv), jnp.float32))
    o = rms_norm(o, w["o_norm"], s["eps"]) * jax.nn.silu(z)
    return x + _mm("bse,ed->bsd", o.reshape(b, t, hv * dv), w["Wo"], -1, 0, precision)


def attention(w: dict, x, s: dict, precision: str = "f32"):
    b, t, _ = x.shape
    heads, g, h = s["heads"], s["kv_heads"], s["head_dim"]
    pos = jnp.arange(t)
    u = rms_norm(x, w["ln"], s["eps"])
    qg = _mm("bsd,de->bse", u, w["Wq"], -1, 0, precision).reshape(b, t, heads, 2 * h)
    q, gate = qg[..., :h], qg[..., h:]
    k = _mm("bsd,de->bse", u, w["Wk"], -1, 0, precision).reshape(b, t, g, h)
    v = _mm("bsd,de->bse", u, w["Wv"], -1, 0, precision).reshape(b, t, g, h)
    q = rope_partial(rms_norm(q, w["q_norm"], s["eps"]), pos, s["theta"], s["rotary_dim"])
    k = rope_partial(rms_norm(k, w["k_norm"], s["eps"]), pos, s["theta"], s["rotary_dim"])
    q = q.reshape(b, t, g, heads // g, h)
    sc = _mm("bqgrh,bkgh->bgrqk", q, k, -1, -1, precision) / math.sqrt(h)
    causal = jnp.tril(jnp.ones((t, t), bool))
    p = jax.nn.softmax(jnp.where(causal, sc, -1e30), axis=-1)
    o = _mm("bgrqk,bkgh->bqgrh", p, v, -1, 1, precision).reshape(b, t, heads, h)
    o = (o * jax.nn.sigmoid(gate)).reshape(b, t, heads * h)
    return x + _mm("bse,ed->bsd", o, w["Wo"], -1, 0, precision)


def _swiglu(u, wg, wu, wd, precision: str):
    a = (jax.nn.silu(_mm("td,df->tf", u, wg, -1, 0, precision))
         * _mm("td,df->tf", u, wu, -1, 0, precision))
    return _mm("tf,fd->td", a, wd, -1, 0, precision)


def route(probs, s: dict):
    """The choice: softmax `probs` [T, routed] in float32 -> (ids [T,
    top_k], weights [T, top_k]): the top k, renormalised over them."""
    picked, ids = jax.lax.top_k(probs, s["top_k"])
    return ids, picked / jnp.sum(picked, axis=-1, keepdims=True)


def moe_parts(w: dict, u, s: dict, precision: str = "f32"):
    """(what the held experts give, what the gated shared expert gives) for
    rows u [T, d]: every held expert over every row, one expert at a time,
    weighted by the row's routing weight for it (0 where it was not picked).
    The leaves without an expert axis are float32 already; an expert's are
    upcast as its turn comes."""
    probs = jax.nn.softmax(jnp.einsum("td,de->te", u, w["Wr"], precision=_HI), axis=-1)
    ids, weights = route(probs, s)
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
    gate = jax.nn.sigmoid(jnp.einsum("td,de->te", u, w["Wsg"], precision=_HI))
    return routed, gate * _swiglu(u, w["sWgate"], w["sWup"], w["sWdown"], precision)


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
    f = {"gdn": gdn, "attn": attention, "head": head_logits}[kind]
    return f(_f32(w), x, s, precision)


# ---------------------------------------------------------------- serving

_PAD = 128          # lengths stay on a grid, as every serving family's do


def teacher_forced_logits(cfg: dict, seed: int, ids, precisions=("f32",)):
    """Teacher-forced logits of `ids` [B, S], one layer's weights alive at a
    time.  Returns {precision: logits [B, S, V]}; position t holds the
    next-token logits after consuming ids[:, :t+1].  The ids are padded
    with zeros to a whole number of `_PAD` positions and the logits cut
    back: nothing here looks ahead, so no position sees the padding."""
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
        "this configuration serves only: no layer type of the serving "
        "families has a checked backward pass (configs/"
        "qwen3-next-80b-a3b-ep8.json)")
