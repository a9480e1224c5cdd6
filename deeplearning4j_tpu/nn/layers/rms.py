"""What the RMSNorm-family layer types share (`kda`, `mla`, `swiglu`, `moe`,
and the OUTPUT layer under a `HeadSpec`): the norms, rotary positions, and
the one matrix product they all take.

Their precision contract: parameters are kept in `conf.dtype`, matrix
products take operands in `compute_dtype(conf)` and accumulate in float32,
and everything between the products (the residual stream, norms, softmax,
router scores, the KDA state) is float32.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.nn.weights import init_weights
from deeplearning4j_tpu.utils.profiling import scope

F32 = jnp.float32


def initializer(conf):
    """`w(key, shape)`: a leaf in `conf.dtype` by the conf's weight init."""
    dist = conf.dist.sampler() if conf.dist is not None else None
    dtype = jnp.dtype(conf.dtype)
    return lambda key, shape: init_weights(key, shape, conf.weight_init, dist,
                                           dtype)


def precision_of(cd):
    """float32 operands ask for float32 products (the TPU's default would
    round them to bfloat16); narrower operands take the MXU's own."""
    return jax.lax.Precision.HIGHEST if jnp.dtype(cd) == F32 else None


def mm(x, w, cd):
    """x @ w with operands in `cd`, accumulated and returned in float32."""
    return jnp.matmul(x.astype(cd), w.astype(cd), precision=precision_of(cd),
                      preferred_element_type=F32)


def rms_norm(x, g, eps: float):
    x = x.astype(F32)
    return (x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                              + eps) * g.astype(F32))


def layer_norm(x, g, b, eps: float):
    """LayerNorm with a weight and a bias, in float32."""
    x = x.astype(F32)
    x = x - jnp.mean(x, axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                              + eps) * g.astype(F32) + b.astype(F32))


def pre_norm(params, x, eps: float):
    """The block's input norm, under the scope `ln`."""
    with scope("ln"):
        return rms_norm(x, params["ln"], eps)


def l2norm(x, eps: float = 1e-6):
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True) + eps)


def yarn_frequencies(n: int, theta: float, yarn):
    """The n/2 inverse frequencies of a rotary embedding stretched by YaRN
    (arXiv:2309.00071) and the factor cos and sin are scaled by.  `yarn` is
    `(factor, original_max, beta_fast, beta_slow, attention_factor)`: a
    dimension that turns more than `beta_fast` times within the original
    context keeps its frequency, one that turns less than `beta_slow` times
    gets `1 / factor` of it, and those between are blended linearly."""
    factor, original, beta_fast, beta_slow, attention_factor = yarn

    def turns_at(r):        # the (fractional) dimension that turns r times
        return n * math.log(original / (2.0 * math.pi * r)) / (2.0 * math.log(theta))

    low = max(math.floor(turns_at(beta_fast)), 0)
    high = min(math.ceil(turns_at(beta_slow)), n - 1)
    i = np.arange(n // 2)           # NumPy on the host: a constant of the trace
    ramp = np.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    plain = float(theta) ** (-2.0 * i / n)
    inv = (1.0 - ramp) * plain + ramp * plain / factor
    return jnp.asarray(inv, F32), float(attention_factor)


def rope(x, positions, theta: float, yarn=None):
    """Rotate-half rotary embedding over the whole last axis of `x`;
    `positions` broadcasts against x's leading axes (x [..., n], positions
    [...]).  With `yarn` the frequencies and the scale of cos and sin are
    `yarn_frequencies`'."""
    n = x.shape[-1]
    if yarn is None:
        inv, scale = theta ** (-jnp.arange(0, n, 2, dtype=F32) / n), None
    else:
        inv, scale = yarn_frequencies(n, theta, yarn)
    ang = positions.astype(F32)[..., None] * inv
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], axis=-1)
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], axis=-1)
    if scale is not None:
        cos, sin = cos * scale, sin * scale
    half = jnp.concatenate([-x[..., n // 2:], x[..., : n // 2]], axis=-1)
    return x * cos + half * sin


def rope_first(x, positions, theta: float, n: int):
    """`rope` over the first `n` dimensions of the last axis, the rest as
    they are."""
    return jnp.concatenate([rope(x[..., :n], positions, theta), x[..., n:]],
                           axis=-1)


def swiglu(u, w_gate_up, w_down, cd):
    """down(silu(gate u) * up u), gate and up being the two halves of one
    matrix's columns."""
    h = mm(u, w_gate_up, cd)
    f = h.shape[-1] // 2
    return mm(jax.nn.silu(h[..., :f]) * h[..., f:], w_down, cd)
