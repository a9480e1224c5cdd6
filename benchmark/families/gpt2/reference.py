"""The plain reference: weights from a seed, and the GPT-2 block as the
program computes it, in straightforward float32 `jax.numpy`.

Pre-LN attention with biases, GELU (tanh form, `jax.nn.gelu`'s default,
which is what the program calls) FFN of 4x width, learned positions, no
final LayerNorm, untied head with a bias, softmax cross-entropy on class
ids, Adam without weight decay.  Nothing here imports the program.

The weights are a function of (configuration, seed, layer, leaf) alone, so
the harness can hand the program the whole model in one jitted call while
the reference makes the same numbers again one layer at a time, after the
program's copy is gone.

Every matrix product goes through the shared `_mm`, whose `precision="int8"`
is the control of `correct` (`benchmark/reference.py`).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmark.reference import (ADAM, Frozen, _mm, base_key, leaf_norms,
                                 projections)

_W_STD = 0.02      # GPT-2's; residual projections are scaled by 1/sqrt(2L)
_B_STD = 0.02      # biases and LayerNorm offsets are not zero, so a path
_G_STD = 0.02      # that drops one shows


def sizes(cfg: dict) -> dict:
    d = int(cfg["n_embd"])
    return {"d": d, "heads": int(cfg["n_head"]), "layers": int(cfg["n_layer"]),
            "vocab": int(cfg["vocab_size"]), "positions": int(cfg["n_positions"]),
            "ffn": int(cfg.get("n_inner") or 4 * d)}


def _leaf_specs(cfg: dict, kind: str) -> dict:
    """name -> (shape, spread, centre) of one layer of `kind`."""
    s = sizes(cfg)
    d, f, v = s["d"], s["ffn"], s["vocab"]
    resid = _W_STD / math.sqrt(2.0 * s["layers"])
    if kind == "embed":
        return {"wte": ((v, d), _W_STD, 0.0),
                "wpe": ((s["positions"], d), _W_STD / 2, 0.0)}
    if kind == "attn":
        out = {n: ((d, d), _W_STD, 0.0) for n in ("Wq", "Wk", "Wv")}
        out.update({n: ((d,), _B_STD, 0.0) for n in ("bq", "bk", "bv", "bo")})
        out.update({"Wo": ((d, d), resid, 0.0), "ln_g": ((d,), _G_STD, 1.0),
                    "ln_b": ((d,), _B_STD, 0.0)})
        return out
    if kind == "ffn":
        return {"W1": ((d, f), _W_STD, 0.0), "b1": ((f,), _B_STD, 0.0),
                "W2": ((f, d), resid, 0.0), "b2": ((d,), _B_STD, 0.0),
                "ln_g": ((d,), _G_STD, 1.0), "ln_b": ((d,), _B_STD, 0.0)}
    if kind == "head":
        return {"W": ((d, v), _W_STD, 0.0), "b": ((v,), _B_STD, 0.0)}
    raise ValueError(f"no layer kind {kind!r}")


def layer_kinds(cfg: dict) -> list:
    """The model's layers in order: embed, (attn, ffn) x n_layer, head."""
    return ["embed"] + ["attn", "ffn"] * sizes(cfg)["layers"] + ["head"]


def layer_weights(cfg: dict, key, index: int, kind: str) -> dict:
    """One layer's leaves, uniform with the stated spread about the centre."""
    lk = jax.random.fold_in(key, index)
    out = {}
    for j, (name, (shape, std, centre)) in enumerate(
            sorted(_leaf_specs(cfg, kind).items())):
        a = std * math.sqrt(3.0)
        out[name] = centre + jax.random.uniform(
            jax.random.fold_in(lk, j), shape, jnp.float32, -a, a)
    return out


def model_weights(cfg: dict, key) -> list:
    """Every layer's leaves, as a list in layer order.  Jit it."""
    return [layer_weights(cfg, key, i, kind)
            for i, kind in enumerate(layer_kinds(cfg))]


def count_params(cfg: dict) -> dict:
    """Parameters by role, from shapes: `matmul` are those a token passes
    through a matrix product (blocks and head), `all` adds the tables,
    biases and LayerNorms."""
    total = matmul = 0
    for kind in layer_kinds(cfg):
        for name, (shape, _, _) in _leaf_specs(cfg, kind).items():
            n = math.prod(shape)
            total += n
            if len(shape) == 2 and kind != "embed":
                matmul += n
    return {"all": total, "matmul": matmul}


# ---------------------------------------------------------------- forward

def _layer_norm(x, g, b, eps: float = 1e-5):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * g + b


def embed(w: dict, ids):
    """ids [B, S] -> [B, S, d]: token rows plus the first S position rows."""
    return w["wte"][ids] + w["wpe"][: ids.shape[1]]


def attention(w: dict, x, heads: int, precision: str = "f32"):
    b, s, d = x.shape
    hd = d // heads
    xn = _layer_norm(x, w["ln_g"], w["ln_b"])

    def proj(wn, bn):
        return (_mm("bsd,de->bse", xn, w[wn], -1, 0, precision)
                + w[bn]).reshape(b, s, heads, hd)

    q, k, v = proj("Wq", "bq"), proj("Wk", "bk"), proj("Wv", "bv")
    sc = _mm("bqhd,bkhd->bhqk", q, k, -1, -1, precision) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((s, s), bool))
    p = jax.nn.softmax(jnp.where(causal, sc, -1e30), axis=-1)
    o = _mm("bhqk,bkhd->bqhd", p, v, -1, 1, precision).reshape(b, s, d)
    return x + _mm("bsd,de->bse", o, w["Wo"], -1, 0, precision) + w["bo"]


def ffn(w: dict, x, precision: str = "f32"):
    xn = _layer_norm(x, w["ln_g"], w["ln_b"])
    h = jax.nn.gelu(_mm("bsd,df->bsf", xn, w["W1"], -1, 0, precision)
                    + w["b1"], approximate=True)
    return x + _mm("bsf,fd->bsd", h, w["W2"], -1, 0, precision) + w["b2"]


def head_logits(w: dict, x, precision: str = "f32"):
    return _mm("bsd,dv->bsv", x, w["W"], -1, 0, precision) + w["b"]


def apply_layer(kind: str, w: dict, x, heads: int, precision: str = "f32"):
    if kind == "embed":
        return embed(w, x)
    if kind == "attn":
        return attention(w, x, heads, precision)
    if kind == "ffn":
        return ffn(w, x, precision)
    return head_logits(w, x, precision)


# ---------------------------------------------------------------- serving

def teacher_forced_logits(cfg: dict, seed: int, ids, precisions=("f32",)):
    """Teacher-forced logits of `ids` [B, S], one layer's weights alive at a
    time.  Returns {precision: logits [B, S, V]}; position t holds the
    next-token logits after consuming ids[:, :t+1]."""
    heads = sizes(cfg)["heads"]
    key = base_key(seed)
    xs = {p: jnp.asarray(ids, jnp.int32) for p in precisions}
    for i, kind in enumerate(layer_kinds(cfg)):
        w = _layer_jit(kind)(Frozen(cfg), key, i)
        for p in precisions:
            xs[p] = _apply_jit(kind, heads, p)(w, xs[p])
        del w
    return xs


@functools.lru_cache(maxsize=None)
def _layer_jit(kind: str):
    return jax.jit(lambda cfg, key, i: layer_weights(cfg, key, i, kind),
                   static_argnums=0)


@functools.lru_cache(maxsize=None)
def _apply_jit(kind: str, heads: int, precision: str):
    return jax.jit(lambda w, x: apply_layer(kind, w, x, heads, precision))


# --------------------------------------------------------------- training

def loss_fn(weights: list, cfg: dict, x, y, precision: str = "f32"):
    """Mean next-token cross-entropy of rows x [B, S] against y [B, S]."""
    heads = sizes(cfg)["heads"]
    kinds = layer_kinds(cfg)
    h = x
    for kind, w in zip(kinds, weights):
        f = functools.partial(apply_layer, kind, heads=heads, precision=precision)
        h = jax.checkpoint(f)(w, h) if kind in ("attn", "ffn") else f(w, h)
    logp = jax.nn.log_softmax(h, axis=-1)
    picked = jnp.take_along_axis(logp, y[..., None], axis=-1)[..., 0]
    return -jnp.mean(picked)


def train_step(weights, m, v, t, x, y, cfg: dict, precision: str = "f32",
               rows=None):
    """One Adam step on the batch's mean loss, one row at a time so that the
    activations of one row are all that is alive.  `rows` limits the batch
    to its first rows (the half-batch fault of the tests).  Returns
    (weights, m, v, loss, grads)."""
    n = x.shape[0] if rows is None else int(rows)
    grad = jax.value_and_grad(loss_fn)
    loss = 0.0
    grads = jax.tree_util.tree_map(jnp.zeros_like, weights)
    for r in range(n):
        l, g = grad(weights, cfg, x[r:r + 1], y[r:r + 1], precision)
        loss = loss + l / n
        grads = jax.tree_util.tree_map(lambda a, b: a + b / n, grads, g)
    b1, b2 = ADAM["beta1"], ADAM["beta2"]
    m = jax.tree_util.tree_map(lambda a, g: b1 * a + (1 - b1) * g, m, grads)
    v = jax.tree_util.tree_map(lambda a, g: b2 * a + (1 - b2) * g * g, v, grads)
    c1, c2 = 1.0 - b1 ** t, 1.0 - b2 ** t
    weights = jax.tree_util.tree_map(
        lambda p, a, b: p - ADAM["lr"] * (a / c1) / (jnp.sqrt(b / c2) + ADAM["eps"]),
        weights, m, v)
    return weights, m, v, loss, grads


def first_steps(cfg: dict, seed: int, batches, precision: str = "f32",
                rows=None) -> dict:
    """Follow the first `len(batches)` steps from the seed's weights.
    Returns the numbers `correct` compares: each step's loss, the norm and
    the seed's projection of every leaf of the first gradient, and the norm
    of every leaf's change after the last step."""
    with jax.default_matmul_precision("highest"):
        w0 = jax.jit(model_weights, static_argnums=0)(Frozen(cfg), base_key(seed))
        step = jax.jit(train_step, static_argnames=("cfg", "precision", "rows"),
                       donate_argnums=(0, 1, 2))
        w = jax.tree_util.tree_map(jnp.copy, w0)
        m = jax.tree_util.tree_map(jnp.zeros_like, w0)
        v = jax.tree_util.tree_map(jnp.zeros_like, w0)
        losses, grad_norms, grad_proj = [], None, None
        for t, (x, y) in enumerate(batches, 1):
            w, m, v, loss, g = step(w, m, v, float(t), jnp.asarray(x), jnp.asarray(y),
                                    cfg=Frozen(cfg), precision=precision, rows=rows)
            losses.append(float(loss))
            if t == 1:
                grad_norms = [float(n) for n in jax.jit(leaf_norms)(g)]
                grad_proj = [[float(x) for x in n] for n in
                             jax.jit(projections)(base_key(seed), g)]
            del g
        change = jax.jit(lambda a, b: leaf_norms(
            jax.tree_util.tree_map(jnp.subtract, a, b)))(w, w0)
        return {"losses": losses, "grad_norms": grad_norms,
                "grad_projections": grad_proj, "change_norms": [float(n) for n in change]}


def leaf_names(cfg: dict) -> list:
    """`layer.leaf` for every leaf, in the order `leaf_norms` reports them."""
    return [f"{i}.{kind}.{name}"
            for i, kind in enumerate(layer_kinds(cfg))
            for name in sorted(_leaf_specs(cfg, kind))]
