"""What every family's adapter shares: the seed's weights in the program's
layout, and the readings of a tree in that layout, leaf by leaf in the
reference's order.  The layout itself is the family's, `families/<model_type>/
program.py`: `build_conf`, `to_program`, `from_program`."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark import families, reference


def program_weights(cfg: dict, seed: int) -> tuple:
    """The whole model on the device, from the seed, in one jitted call."""
    fam, frozen = families.of(cfg), reference.Frozen(cfg)
    make = jax.jit(lambda key: fam.program.to_program(
        fam.reference.model_weights(frozen, key)))
    return make(reference.base_key(seed))


def norms_of(cfg: dict, tree) -> list:
    """Norm of every leaf of a program-layout tree, in reference order."""
    from_program = families.of(cfg).program.from_program
    return [float(n) for n in
            jax.jit(lambda t: reference.leaf_norms(from_program(t)))(tree)]


def projections_of(cfg: dict, seed: int, tree) -> list:
    """The seed's projections of every leaf of a program-layout tree."""
    from_program = families.of(cfg).program.from_program
    return [[float(x) for x in n] for n in jax.jit(
        lambda key, t: reference.projections(key, from_program(t)))(
            reference.base_key(seed), tree)]


def change_norms(cfg: dict, seed: int, params) -> list:
    """Norm of every leaf's change from the seed's weights."""
    fam, frozen = families.of(cfg), reference.Frozen(cfg)

    def f(key, now):
        then = fam.reference.model_weights(frozen, key)
        return reference.leaf_norms(jax.tree_util.tree_map(
            jnp.subtract, fam.program.from_program(now), then))

    return [float(n) for n in jax.jit(f)(reference.base_key(seed), params)]
