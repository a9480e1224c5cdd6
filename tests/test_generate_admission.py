"""ISSUE 26 on the compiled decode path of `test_generate.py`: a dense
admission is one call of one compiled program that writes its row into the
slot table and no other, and says on its span which way it came in.  That it
compiles nothing once warm is in `test_generate_warm_admission.py`.

Tier-1: CPU-only, tiny models."""

import jax
import numpy as np
import pytest

from deeplearning4j_tpu.nn import decode as decode_mod
from deeplearning4j_tpu.serving.batcher import ContinuousBatcher
from deeplearning4j_tpu.utils import profiling
from generate_helpers import (_clean_faults, _compiled_tokens,   # noqa: F401
                              _draft_net, _drain, lstm_net, transformer_net)


# -- ISSUE 26: one compiled program per admission ----------------------------

_ADMIT_FLAGS = {"plain": {}, "prefix": {"prefix_cache": True},
                "draft": {"spec_k": 3}, "prefix+draft": {"prefix_cache": True,
                                                         "spec_k": 3}}


def _bucket_of(buckets, n):
    return next(b for b in buckets if b >= n)


@pytest.mark.parametrize("buckets", [(8,), (4, 8)])
@pytest.mark.parametrize("flags", sorted(_ADMIT_FLAGS))
@pytest.mark.parametrize("which", ["lstm", "transformer"])
def test_fused_admission_streams_equal_the_b1_prefill_path(
        which, flags, buckets, lstm_net, transformer_net):
    """Seven streams over three slots (every slot admitted into at least
    twice, both buckets, greedy and sampled, a repeated prompt for the
    prefix cache's `write_row` path): each stream's tokens are those of
    the parent's admission, a B=1 `prefill` and the decode step."""
    net = lstm_net if which == "lstm" else transformer_net
    kw = dict(_ADMIT_FLAGS[flags])
    if "spec_k" in kw:
        kw["draft_net"] = _draft_net()
    asks = [([1, 2, 3], 0.0, 0), ([4, 5, 6, 7, 2, 1], 0.8, 1),
            ([2, 2], 0.0, 2), ([1, 2, 3], 0.6, 3), ([7], 0.0, 4),
            ([4, 5, 6, 7, 2, 1], 0.0, 5), ([3, 1, 2, 5, 6], 1.1, 6)]
    refs = [_compiled_tokens(net, p, 6, temperature=t, rng_seed=s,
                             bucket=_bucket_of(buckets, len(p)))
            for p, t, s in asks]
    cb = ContinuousBatcher(net, n_slots=3, max_seq=16,
                           prompt_buckets=buckets, **kw)
    profiling.clear()
    try:
        streams = [cb.submit(p, max_new_tokens=6, temperature=t, rng_seed=s)
                   for p, t, s in asks]
        assert _drain(streams) == refs
    finally:
        cb.stop()
    admits = [s for s in profiling.spans() if s.name == "admit"]
    assert len(admits) == 7
    assert {a.attrs["slot"] for a in admits} == {0, 1, 2}
    assert {a.attrs["bucket"] for a in admits if "bucket" in a.attrs} \
        == set(buckets)
    paths = [a.attrs["path"] for a in admits]
    if "prefix_cache" in kw:
        assert paths.count("write_row") == 2       # the two repeated prompts
    assert paths.count("prefill_slot") == 7 - paths.count("write_row")


def _random_table(net, slots, max_seq, seed):
    """A slots-wide table with no zero in it, so that a write that strays
    shows in any row."""
    zero = net.infer_cache.init_decode_state(net.conf, slots, max_seq)
    leaves, tree = jax.tree_util.tree_flatten(zero)
    keys = jax.random.split(jax.random.PRNGKey(seed), max(1, len(leaves)))
    return jax.tree_util.tree_unflatten(tree, [
        (1.0 + jax.random.uniform(k, l.shape)).astype(l.dtype)
        for k, l in zip(keys, leaves)])


@pytest.mark.parametrize("slot", [0, 1, 2])
@pytest.mark.parametrize("entry", ["prefill_slot", "prefill_logp_slot",
                                   "write_row"])
@pytest.mark.parametrize("which", ["lstm", "transformer"])
def test_admission_writes_its_row_and_no_other(which, entry, slot, lstm_net,
                                               transformer_net):
    """Row `slot` of every leaf becomes the B=1 prefill's row, zeros past
    the prompt included; every other row of every leaf keeps its bits;
    first token and key are the B=1 program's."""
    net = lstm_net if which == "lstm" else transformer_net
    ic, conf, params = net.infer_cache, net.conf, net.params
    prompt = np.zeros((1, 8), np.int32)
    prompt[0, :5] = [3, 1, 4, 1, 5]
    length = np.asarray([5], np.int32)
    keys = np.asarray(jax.random.PRNGKey(7))[None]
    temps = np.asarray([0.9], np.float32)
    tok_ref, keys_ref, row_ref = ic.prefill(
        conf, params, ic.init_decode_state(conf, 1, 16), prompt, length,
        keys, temps)
    before = _random_table(net, 3, 16, seed=slot)
    if entry == "prefill_slot":
        tok, keys2, after = ic.prefill_slot(conf, params, before, slot,
                                            prompt, length, keys, temps)
        assert int(tok[0]) == int(tok_ref[0])
        np.testing.assert_array_equal(np.asarray(keys2), np.asarray(keys_ref))
    elif entry == "prefill_logp_slot":
        logp_ref, _ = ic.prefill_logp(
            conf, params, ic.init_decode_state(conf, 1, 16), prompt, length)
        logp, row, after = ic.prefill_logp_slot(conf, params, before, slot,
                                                prompt, length)
        np.testing.assert_array_equal(np.asarray(logp), np.asarray(logp_ref))
        for got, want in zip(jax.tree_util.tree_leaves(row),
                             jax.tree_util.tree_leaves(row_ref)):
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    else:
        host_row = jax.tree_util.tree_map(np.asarray, row_ref)
        after = ic.write_row(conf, before, host_row, slot)
    assert jax.tree_util.tree_structure(after) \
        == jax.tree_util.tree_structure(before)
    leaves = list(zip(jax.tree_util.tree_leaves(before),
                      jax.tree_util.tree_leaves(after),
                      jax.tree_util.tree_leaves(row_ref)))
    assert leaves
    for was, now, row in leaves:
        was, now, row = np.asarray(was), np.asarray(now), np.asarray(row)
        assert now.shape == was.shape and now.dtype == was.dtype
        np.testing.assert_array_equal(now[slot], row[0])
        others = [i for i in range(3) if i != slot]
        np.testing.assert_array_equal(now[others], was[others])


def test_zero_row_is_init_state_of_one_row(lstm_net, transformer_net):
    for net in (lstm_net, transformer_net):
        table = decode_mod.init_state(net.conf, 3, 16)
        got = decode_mod.zero_row(table)
        want = decode_mod.init_state(net.conf, 1, 16)
        assert jax.tree_util.tree_structure(got) \
            == jax.tree_util.tree_structure(want)
        for g, w in zip(jax.tree_util.tree_leaves(got),
                        jax.tree_util.tree_leaves(want)):
            assert g.shape == w.shape and g.dtype == w.dtype
            assert not np.asarray(g).any()


@pytest.mark.parametrize("path", ["prefill_slot", "write_row", "paged"])
def test_admit_span_says_which_way_in(path, lstm_net):
    """The `admit` span's `path` attr and its children on each path."""
    kw = {"prefill_slot": {}, "write_row": {"prefix_cache": True},
          "paged": {"page_size": 4}}[path]
    cb = ContinuousBatcher(lstm_net, n_slots=2, max_seq=16,
                           prompt_buckets=(8,), **kw)
    try:
        if path == "write_row":
            cb.generate([1, 2, 3], max_new_tokens=2)    # seeds the cache
        profiling.clear()
        assert len(cb.generate([1, 2, 3], max_new_tokens=2)) == 2
    finally:
        cb.stop()
    record = profiling.spans()
    admit, = [s for s in record if s.name == "admit"]
    assert admit.attrs["path"] == path
    kids = [s.name for s in record if s.parent == admit.sid]
    assert kids == {"prefill_slot": ["admit.prefill", "admit.deliver"],
                    "write_row": ["admit.scatter", "admit.deliver"],
                    "paged": ["admit.prefill", "admit.scatter",
                              "admit.deliver"]}[path]
