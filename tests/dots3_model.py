"""What the cases of `test_dots3_*.py` share: dots3-note-prev's language
model at tiny widths on the CPU, as the plain reference of
`benchmark/families/dots3_note` holds it and as the program does.  No test
file: nothing here is collected."""

import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import families, program as bench_program, reference as bench_reference

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEED = 2147483659
# float32 compute: what is left is the order of float32 sums (absorbed
# against materialised products, a gathered set of cells against a mask, a
# ring read in cell order)
TIGHT = 2e-5
# bfloat16 operands and cached latents: every product rounds to 8 bits of
# mantissa; over 5 blocks the log-probabilities of this tiny model move by
# some 1e-2, and a near tie at the selection's edge or in the router that
# falls the other way moves a token's by as much again
LOOSE = 6e-2

# Laid over the rehearsal's sizes: the whole period after the dense layer
# (full, full, window, window, window) cut to (full + dense, full + experts,
# window + experts): every layer kind once, what is compiled is 3 blocks.
SMALLER = {"num_hidden_layers": 3,
           "layer_types": ["full_attention", "full_attention", "sliding_attention"]}


def tiny(dtype: str, **over) -> dict:
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "dots3-note-prev-ep8.json")) as f:
        cfg = json.load(f)
    return {**cfg, **cfg["rehearse"], **SMALLER, **over,
            "flags": {"param_dtype": dtype, "compute_dtype": dtype}}


class Model:
    """The reference's weights and the program's copy of them are made when a
    case first asks: a file that needs one does not pay for the other."""

    def __init__(self, dtype: str, **over):
        self.cfg = tiny(dtype, **over)
        self.fam = families.of(self.cfg)
        self.ref = self.fam.reference
        self.sizes = self.ref.sizes(self.cfg)
        self.conf = self.fam.program.build_conf(self.cfg)
        self.kinds = self.ref.layer_kinds(self.cfg)

    @functools.cached_property
    def weights(self):
        return jax.jit(self.ref.model_weights, static_argnums=0)(
            bench_reference.Frozen(self.cfg), bench_reference.base_key(SEED))

    @functools.cached_property
    def params(self):
        return bench_program.program_weights(self.cfg, SEED)

    def logp(self, ids):
        """The reference's log-probabilities [B, S, V] of ids [B, S]."""
        logits = self.ref.teacher_forced_logits(self.cfg, SEED, ids)["f32"]
        return np.asarray(jax.nn.log_softmax(logits, axis=-1))

    def layer(self, kind: str, nth: int = 0) -> int:
        return [i for i, k in enumerate(self.kinds) if k == kind][nth]


@pytest.fixture(scope="module")
def f32():
    return Model("float32")


@pytest.fixture(scope="module")
def bf16():
    return Model("bfloat16")


def rows(shape, seed=0, scale=1.0):
    return scale * jax.random.normal(jax.random.PRNGKey(seed), shape, jnp.float32)
