"""The contract the nine decode-family entries of `InferCache` share, one
case an entry: the key's layout and entry name (what `optimize/persist.py`
files a program under and `programs_summary` parses), the program's name
`jit_dl4j_<entry>` (what the benchmark's readers look for in a trace), the
state or table donated as argument 1 and last among the outputs."""

import jax
import jax.numpy as jnp
import pytest

from deeplearning4j_tpu.models.zoo import char_transformer
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.optimize.infer_cache import InferCache
from deeplearning4j_tpu.optimize.step_cache import arg_signature
from deeplearning4j_tpu.utils.profiling import program_name

SLOTS, MAX_SEQ, PAGE = 2, 8, 4


@pytest.fixture(scope="module")
def net():
    return MultiLayerNetwork(
        char_transformer(11, d_model=8, n_blocks=1, n_heads=2, max_seq_len=16),
        seed=0).init()


def calls(ic, conf, params):
    """entry name -> (the call, the arguments its key names before the
    state's leaves, the state or table it donates)."""
    tok = pos = jnp.zeros((SLOTS,), jnp.int32)
    keys = jnp.zeros((SLOTS, 2), jnp.uint32)
    temps = jnp.zeros((SLOTS,), jnp.float32)
    toks = jnp.zeros((SLOTS, 3), jnp.int32)
    prompt, length = jnp.zeros((1, 4), jnp.int32), jnp.ones((1,), jnp.int32)
    table = ic.init_decode_state(conf, SLOTS, MAX_SEQ)
    row = ic.init_decode_state(conf, 1, MAX_SEQ)
    pool = ic.init_paged_decode_state(conf, SLOTS, 5, PAGE)
    pages = jnp.zeros((SLOTS, MAX_SEQ // PAGE), jnp.int32)
    k1, t1 = keys[:1], temps[:1]
    return {
        "decode": (lambda **kw: ic.decode(
            conf, params, table, tok, pos, keys, temps, **kw),
            (tok, pos, keys, temps), table),
        "decode-paged": (lambda **kw: ic.decode(
            conf, params, pool, tok, pos, keys, temps, page_table=pages, **kw),
            (tok, pos, keys, temps, pages), pool),
        "verify": (lambda **kw: ic.verify(
            conf, params, table, toks, pos, keys, temps, **kw),
            (toks, pos, keys, temps), table),
        "verify-paged": (lambda **kw: ic.verify(
            conf, params, pool, toks, pos, keys, temps, page_table=pages, **kw),
            (toks, pos, keys, temps, pages), pool),
        "prefill": (lambda **kw: ic.prefill(
            conf, params, row, prompt, length, k1, t1, **kw),
            (prompt, length, k1, t1), row),
        "prefill-logp": (lambda **kw: ic.prefill_logp(
            conf, params, row, prompt, length, **kw), (prompt, length), row),
        "prefill-slot": (lambda **kw: ic.prefill_slot(
            conf, params, table, 1, prompt, length, k1, t1, **kw),
            (prompt, length, k1, t1), table),
        "prefill-logp-slot": (lambda **kw: ic.prefill_logp_slot(
            conf, params, table, 1, prompt, length, **kw),
            (prompt, length), table),
        "write-row": (lambda **kw: ic.write_row(conf, table, row, 1, **kw),
                      tuple(jax.tree_util.tree_leaves(row)), table),
    }


ENTRIES = ("decode", "decode-paged", "verify", "verify-paged", "prefill",
           "prefill-logp", "prefill-slot", "prefill-logp-slot", "write-row")


@pytest.mark.parametrize("entry", ENTRIES)
def test_a_decode_family_entry_keeps_the_contract(net, entry, monkeypatch):
    # the chip's donation (the CPU backend only warns that it cannot alias)
    monkeypatch.setattr(InferCache, "_decode_donate", lambda self: (1,))
    ic = InferCache()
    call, keyed, state = calls(ic, net.conf, net.params)[entry]
    assert call(compile_only=True) is None and ic.stats.steps == 0
    (key, rec), = ic._audit_records.items()

    # (entry, fingerprint, small arguments then the state's leaves, tag), and
    # nothing after them under the float32 policy
    assert key == (entry, ic._fingerprint(net.conf),
                   arg_signature(*keyed, *jax.tree_util.tree_leaves(state)),
                   InferCache.SINGLE)
    assert ic.programs_summary() == [{"entry": entry, "bucket": int(keyed[0].shape[0]),
                                      "sharding": "single", "policy": "f32"}]
    assert (ic._programs[key].as_text().split()[1].rstrip(",")
            == "jit_" + program_name(entry))

    # argument 1 is the state, donated, and the last output is its like
    assert rec["donate_argnums"] == (1,)
    like = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), state)
    assert rec["abstract"][1] == like
    out = jax.eval_shape(rec["build"](), *rec["abstract"])
    assert out[-1] == like

    # the call itself: a step counted, a hit, the new state where the old was
    got = call()
    assert ic.stats.steps == 1 and ic.stats.hits == 1 and ic.stats.misses == 1
    new = got if entry == "write-row" else got[-1]
    assert jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), new) == like
