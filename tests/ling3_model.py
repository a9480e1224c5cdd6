"""What the cases of `test_ling3_*.py` share: Ling-3.0-flash's language model
at tiny widths on the CPU, as the plain reference of `benchmark/families/ling3`
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
# float32 compute: what is left is the order of float32 sums (the chunked
# KDA prefill against the token scan, absorbed against materialised MLA)
TIGHT = 2e-5
# bfloat16 operands: every matmul rounds its operands to 8 bits of mantissa
# (relative 2^-9); over 14 layers of a residual stream the log-probabilities
# of this tiny model move by some 1e-2.  An int8 path moves them ten times
# further, a dropped layer by more than 1.
LOOSE = 6e-2


# Laid over the rehearsal's sizes: three blocks in place of seven, and a
# period of two, so that every layer kind still occurs and each attention
# kind stands before an expert layer: KDA + dense SwiGLU, MLA + experts,
# KDA + experts.  What is compiled is half as long; widths, the 8 held of
# 32 routed experts and the grouped routing are the rehearsal's.
SMALLER = {"num_hidden_layers": 3, "layer_group_size": 2}


def tiny(dtype: str) -> dict:
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "ling-3.0-flash-ep4.json")) as f:
        cfg = json.load(f)
    return {**cfg, **cfg["rehearse"], **SMALLER,
            "flags": {"param_dtype": dtype, "compute_dtype": dtype}}


class Model:
    """The reference's weights and the program's copy of them are made when a
    case first asks: a file that needs one does not pay for the other."""

    def __init__(self, dtype: str):
        self.cfg = tiny(dtype)
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
