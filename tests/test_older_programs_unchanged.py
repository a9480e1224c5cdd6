"""The programs of the serving families that were there before the parallel
block lower to the texts they had: the split of `gqa.py` and `experts.py`
into a residual layer and a branch it calls, the prefill's block of queries
from a rule on the heads (`gqa.q_block`), the rotary variants, the head's
norm and tie and the ring counts reach none of them; nor, in those and in
the parallel block's, do attention's output gate and rotary over part of a
head, the gated shared expert, or the delta rule's second gate shape
(`kda.delta_step`, `kda.chunked_delta_rule`).  Each family's decode
step (4 slots) and prefill (a bucket of 64) at its rehearsal's sizes on the
CPU, lowered and never compiled.  Handed their tree through the operand rule
(`nn.decode.operand_tree`) they lower to the same texts: their weights are
bfloat16 already, and the rule casts none."""

import hashlib

import jax
import jax.numpy as jnp
import pytest

from benchmark import families, run
from deeplearning4j_tpu.nn import decode
from deeplearning4j_tpu.nn.multilayer import init_params

# Lowered by this file's recipe on the commit before the parallel block
# (c78d4cb); Command A+'s on the commit before the delta rule with a decay a
# head, the output-gated attention and the gated shared expert (93eaf05)
PARENT_TEXTS = {
    "serve-mellum2-mixed-64": {"decode": "dcabf51f8a1da493", "prefill": "dfe511ace6729b69"},
    "serve-lfm2-rag-64": {"decode": "22d46f24df0b93b5", "prefill": "b4c428259b6e9873"},
    "serve-ling3-decode-64": {"decode": "d44e45361c0cef35", "prefill": "bcde2ee4dec71d98"},
    "serve-dots3-long-64": {"decode": "4862bcc0f5b66d21", "prefill": "07425eb35c1640ed"},
    "serve-commanda-long-32": {"decode": "9bc079f1cdf490bc", "prefill": "cf3f4c283b0b2d71"},
}


def lowered_texts(workload: str) -> dict:
    cfg = run.load_cell(workload, rehearse=True).cfg
    conf = families.of(cfg).program.build_conf(cfg)
    params = jax.eval_shape(lambda k: init_params(conf, k), jax.random.PRNGKey(0))
    tree, narrowed = decode.operand_tree(conf, params)
    assert tree is params and narrowed == []
    table = lambda rows: jax.eval_shape(lambda: decode.init_state(conf, rows, 96))  # noqa: E731
    ids = jax.ShapeDtypeStruct((4,), jnp.int32)
    texts = {"decode": jax.jit(lambda p, s, t, q: decode.decode_step(conf, p, s, t, q)).lower(
        params, table(4), ids, ids).as_text()}
    texts["prefill"] = jax.jit(lambda p, s, t, n: decode.prefill(conf, p, s, t, n)).lower(
        params, table(1), jax.ShapeDtypeStruct((1, 64), jnp.int32),
        jax.ShapeDtypeStruct((1,), jnp.int32)).as_text()
    return {k: hashlib.sha256(v.encode()).hexdigest()[:16] for k, v in texts.items()}


@pytest.mark.parametrize("workload", sorted(PARENT_TEXTS))
def test_an_older_familys_programs_lower_to_the_parents_texts(workload):
    assert lowered_texts(workload) == PARENT_TEXTS[workload]
