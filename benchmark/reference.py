"""What every family's plain reference shares: the key of a seed, the int8
control's arithmetic, the optimizer's constants, and the readings of a tree
of leaves.  The model itself (its sizes, its weights from the seed, its
forward pass and its first steps) is a family's, `families/<model_type>/
reference.py`.  Nothing here, and nothing there, imports the program.

`precision="int8"` is the control of `correct`: every matmul operand is
rounded to 8-bit integers along its contraction axis (symmetric, absmax
over that axis), which is what a W8A8 path with an int8 K/V cache computes;
gradients pass straight through the rounding, and the cotangent that enters
the backward pass's matmuls is rounded the same way.  Every family's
reference multiplies through `_mm`, so that the control means one thing in
every cell.
"""

from __future__ import annotations

import json
import math

import jax
import jax.numpy as jnp


def base_key(seed: int):
    """A key for any whole number: the low 31 bits seed it, the rest fold in."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)


def _quant(x, axis: int):
    """Symmetric 8-bit rounding along `axis`; the gradient passes through."""
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale > 0, scale, 1.0)
    q = jnp.clip(jnp.round(x / scale), -127, 127) * scale
    return x + jax.lax.stop_gradient(q - x)


@jax.custom_vjp
def _round_cotangent(x):
    """The identity, whose cotangent is rounded to 8 bits along its last
    axis: the backward pass's matmuls then take int8 operands as well."""
    return x


_round_cotangent.defvjp(
    lambda x: (x, None),
    lambda _, g: (jax.lax.stop_gradient(_quant(g, -1)),))


def _mm(spec: str, a, b, a_axis: int, b_axis: int, precision: str):
    if precision == "int8":
        a, b = _quant(a, a_axis), _quant(b, b_axis)
    out = jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST)
    return _round_cotangent(out) if precision == "int8" else out


ADAM = {"lr": 1e-3, "beta1": 0.9, "beta2": 0.999, "eps": 1e-8}


def leaf_norms(tree) -> list:
    """The L2 norm of every leaf, in `jax.tree_util` order."""
    return [jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32))))
            for a in jax.tree_util.tree_leaves(tree)]


PROJECTIONS = 4


def projections(key, tree) -> list:
    """Each leaf's inner products with `PROJECTIONS` directions drawn from
    `key` (uniform, unit variance), in `jax.tree_util` order.  The key is an
    argument, so that one compiled program serves every seed.  Two
    gradients that differ by e in a leaf differ in each of these by about
    |e|, so the readings see an error of direction that the leaf's norm
    hides."""
    key = jax.random.fold_in(key, 0x5EED)
    a = math.sqrt(3.0)
    out = []
    for i, leaf in enumerate(jax.tree_util.tree_leaves(tree)):
        lk = jax.random.fold_in(key, i)
        out.append(jnp.stack([
            jnp.sum(leaf.astype(jnp.float32) * jax.random.uniform(
                jax.random.fold_in(lk, j), leaf.shape, jnp.float32, -a, a))
            for j in range(PROJECTIONS)]))
    return out


class Frozen(dict):
    """A configuration that jit can take as a static argument."""

    def __hash__(self):
        return hash(_freeze(self))

    def __eq__(self, other):
        return _freeze(self) == _freeze(other)


def _freeze(cfg: dict) -> str:
    return json.dumps(cfg, sort_keys=True)
