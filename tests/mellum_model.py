"""What the cases of `test_mellum_*.py` share: Mellum2's language model at
tiny widths on the CPU, as the plain reference of `benchmark/families/mellum`
holds it and as the program does.  No test file: nothing here is collected."""

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
# float32 compute: what is left is the order of float32 sums (a block of
# queries against a band of keys, a ring read in cell order)
TIGHT = 2e-5
# bfloat16 operands and K/V cells: every product rounds to 8 bits of mantissa;
# over 8 blocks the log-probabilities of this tiny model move by some 1e-2
LOOSE = 6e-2

# Laid over the rehearsal's sizes: one period (window, window, window, full)
# in place of two; what is compiled is half as long, and every layer kind,
# the window of 8, YaRN and the 8 experts of which 2 are picked stay.
SMALLER = {"num_hidden_layers": 4}


def tiny(dtype: str, **over) -> dict:
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "mellum2-12b-a2.5b-pp8.json")) as f:
        cfg = json.load(f)
    cfg = {**cfg, **cfg["rehearse"], **SMALLER, **over,
           "flags": {"param_dtype": dtype, "compute_dtype": dtype}}
    n = cfg["num_hidden_layers"]
    return {**cfg, "layer_types": cfg["layer_types"][:n],
            "mlp_layer_types": cfg["mlp_layer_types"][:n]}


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

    def layer(self, kind: str) -> int:
        return self.kinds.index(kind)


@pytest.fixture(scope="module")
def f32():
    return Model("float32")


@pytest.fixture(scope="module")
def bf16():
    return Model("bfloat16")


def rows(shape, seed=0, scale=1.0):
    return scale * jax.random.normal(jax.random.PRNGKey(seed), shape, jnp.float32)
