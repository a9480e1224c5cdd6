"""What ISSUE 16 put on the compiled decode path of `test_generate.py`: a paged
K/V cache, prefix caching and speculative decoding, each token for token
against the dense, cold, draft-free path, and each with its fault isolated
to one stream.

Tier-1: CPU-only, tiny models."""

import pytest

from deeplearning4j_tpu.models.zoo import char_lstm
from deeplearning4j_tpu.nn import decode as decode_mod
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.reliability import faults
from deeplearning4j_tpu.serving.batcher import ContinuousBatcher
from generate_helpers import (VOCAB, _clean_faults, _compiled_tokens,   # noqa: F401
                              _draft_net, _drain, lstm_net, transformer_net)


# -- ISSUE 16: paged KV cache -------------------------------------------------

@pytest.mark.parametrize("which", ["lstm", "transformer"])
def test_paged_decode_token_parity(which, lstm_net, transformer_net):
    """page_size > 0 reroutes decode through the shared physical page
    pool — and changes NOTHING about the tokens, on both generative
    architectures."""
    net = lstm_net if which == "lstm" else transformer_net
    refs = [_compiled_tokens(net, p, 6, temperature=t, rng_seed=i)
            for i, (p, t) in enumerate(
                [([1, 2, 3], 0.0), ([4, 5], 0.8)])]
    cb = ContinuousBatcher(net, n_slots=2, max_seq=16,
                           prompt_buckets=(8,), page_size=4)
    try:
        streams = [cb.submit(p, max_new_tokens=6, temperature=t,
                             rng_seed=i)
                   for i, (p, t) in enumerate(
                       [([1, 2, 3], 0.0), ([4, 5], 0.8)])]
        assert _drain(streams) == refs
        pages = cb.stats()["kv_pages"]
        assert pages["page_size"] == 4
        assert pages["live"] == 0  # all streams done -> all pages freed
        assert pages["free"] == pages["total"]
    finally:
        cb.stop()


def test_paged_pool_frees_and_reuses_pages(lstm_net):
    """Live pages track live tokens while streams run, return to the
    free list on completion, and the same pool serves stream after
    stream without leaking."""
    cb = ContinuousBatcher(lstm_net, n_slots=1, max_seq=16,
                           prompt_buckets=(8,), page_size=4)
    try:
        for _ in range(3):
            assert len(cb.generate([1, 2, 3], max_new_tokens=4)) == 4
            pages = cb.stats()["kv_pages"]
            assert pages["live"] == 0 and pages["free"] == pages["total"]
    finally:
        cb.stop()


def test_paged_overcommit_admits_more_slots_than_pages_queue_drains(lstm_net):
    """An overcommitted pool (fewer pages than slots x max pages) still
    completes EVERY stream: admissions that cannot get pages wait in
    the queue and drain as finished streams free theirs — queue-or-503,
    never a crash."""
    # 4 slots x 4 pages/slot = 16 pages fully provisioned; give it 6:
    # at most one full-length stream plus one short one hold pages at
    # once, the rest queue
    cb = ContinuousBatcher(lstm_net, n_slots=4, max_seq=16,
                           prompt_buckets=(8,), page_size=4, n_pages=6)
    try:
        streams = [cb.submit([i + 1], max_new_tokens=10)
                   for i in range(6)]
        toks = _drain(streams, timeout=120.0)
        assert all(len(t) == 10 for t in toks)
        st = cb.stats()
        assert st["streams"]["completed"] == 6
        assert st["streams"]["failed"] == 0
        assert st["kv_pages"]["total"] == 6
        assert st["kv_pages"]["live"] == 0
    finally:
        cb.stop()


def test_page_pool_too_small_for_one_stream_rejected_at_construction(lstm_net):
    with pytest.raises(ValueError):
        ContinuousBatcher(lstm_net, n_slots=1, max_seq=16,
                          prompt_buckets=(8,), page_size=4, n_pages=3,
                          auto_start=False)


def test_page_alloc_fault_fails_one_stream_neighbour_decodes_on(lstm_net):
    """Armed decode.page_alloc mid-decode: the slot that needed a fresh
    page ends its stream with the injected error; the neighbour keeps
    its pages and finishes; the failed slot's pages return to the
    pool."""
    cb = ContinuousBatcher(lstm_net, n_slots=2, max_seq=16,
                           prompt_buckets=(4,), page_size=4)
    try:
        # both admissions allocate once each (traversals 1-2); doomed
        # decodes past its first page boundary first (prompt 3 tokens +
        # 2 tokens -> pos 4 crosses into page 2 at traversal 3)
        faults.arm("decode.page_alloc", "raise", nth=3)
        doomed = cb.submit([1, 2, 3], max_new_tokens=10)
        ok = cb.submit([4], max_new_tokens=2)
        assert len(list(ok.tokens(timeout=30.0))) == 2
        with pytest.raises(faults.FaultInjected):
            list(doomed.tokens(timeout=30.0))
        faults.disarm()
        st = cb.stats()
        assert st["streams"]["failed"] == 1
        assert st["kv_pages"]["live"] == 0  # doomed's pages were freed
        # the pool still serves new streams
        assert len(cb.generate([5], max_new_tokens=3)) == 3
    finally:
        cb.stop()


# -- ISSUE 16: prefix caching -------------------------------------------------

def test_prefix_cache_exact_hit_token_identical_and_counted(lstm_net):
    """A repeated prompt skips prefill (hit counter moves) and the
    trajectory is token-identical to the cold stream — including under
    temperature, where the stream's OWN key must drive sampling."""
    ref_greedy = _compiled_tokens(lstm_net, [1, 2, 3], 6)
    ref_temp = _compiled_tokens(lstm_net, [1, 2, 3], 6, temperature=0.7,
                                rng_seed=9)
    cb = ContinuousBatcher(lstm_net, n_slots=2, max_seq=16,
                           prompt_buckets=(8,), prefix_cache=True)
    try:
        assert cb.generate([1, 2, 3], max_new_tokens=6) == ref_greedy
        assert cb.generate([1, 2, 3], max_new_tokens=6) == ref_greedy
        s = cb.submit([1, 2, 3], max_new_tokens=6, temperature=0.7,
                      rng_seed=9)
        assert list(s.tokens(timeout=30.0)) == ref_temp
        pc = cb.stats()["prefix_cache"]
        assert pc["misses"] == 1 and pc["hits"] == 2
    finally:
        cb.stop()


def test_prefix_cache_longest_match_parity(lstm_net):
    """prefix_match='longest': a longer prompt sharing a cached prefix
    enters decode at the match point and feeds the unmatched suffix
    through the table — tokens identical to a cold prefill of the full
    prompt."""
    ref = _compiled_tokens(lstm_net, [1, 2, 3, 4, 5, 6], 5, rng_seed=1)
    cb = ContinuousBatcher(lstm_net, n_slots=2, max_seq=16,
                           prompt_buckets=(8,), prefix_cache=True,
                           prefix_match="longest")
    try:
        cb.generate([1, 2, 3, 4], max_new_tokens=3)  # seeds the cache
        s = cb.submit([1, 2, 3, 4, 5, 6], max_new_tokens=5, rng_seed=1)
        assert list(s.tokens(timeout=30.0)) == ref
        pc = cb.stats()["prefix_cache"]
        assert pc["hits"] == 1 and pc["misses"] == 1
    finally:
        cb.stop()


def test_prefix_lookup_fault_falls_back_to_cold_prefill(lstm_net):
    """Armed generate.prefix_lookup (a corrupt/missing cache entry):
    the probe degrades to a counted miss and a cold prefill — the
    stream completes with the exact cold tokens, and a neighbour stream
    admitted in the same window is untouched."""
    ref = _compiled_tokens(lstm_net, [1, 2, 3], 6)
    cb = ContinuousBatcher(lstm_net, n_slots=2, max_seq=16,
                           prompt_buckets=(8,), prefix_cache=True)
    try:
        assert cb.generate([1, 2, 3], max_new_tokens=6) == ref
        faults.arm("generate.prefix_lookup", "raise", nth=1)
        a = cb.submit([1, 2, 3], max_new_tokens=6)      # probe blows up
        b = cb.submit([1, 2, 3], max_new_tokens=6)       # neighbour
        assert list(a.tokens(timeout=30.0)) == ref
        assert list(b.tokens(timeout=30.0)) == ref
        st = cb.stats()
        assert st["streams"]["failed"] == 0
        pc = st["prefix_cache"]
        assert pc["misses"] == 2  # the cold start + the faulted probe
        assert pc["hits"] == 1    # the neighbour probes clean and hits
    finally:
        cb.stop()


def test_prefix_cache_persists_through_disk_store(tmp_path, monkeypatch):
    """With a persistent program store attached, prefill state written
    by one batcher is a HIT for a fresh batcher over a fresh net — the
    restart story, same as compiled programs."""
    def fresh_net():
        net = MultiLayerNetwork(char_lstm(VOCAB, hidden=16, n_layers=2),
                                seed=0).init()
        net.set_compile_cache(str(tmp_path))
        return net

    ref = _compiled_tokens(fresh_net(), [1, 2, 3], 5)
    cb1 = ContinuousBatcher(fresh_net(), n_slots=1, max_seq=16,
                            prompt_buckets=(8,), prefix_cache=True)
    try:
        assert cb1.generate([1, 2, 3], max_new_tokens=5) == ref
    finally:
        cb1.stop()
    cb2 = ContinuousBatcher(fresh_net(), n_slots=1, max_seq=16,
                            prompt_buckets=(8,), prefix_cache=True)
    try:
        assert cb2.generate([1, 2, 3], max_new_tokens=5) == ref
        pc = cb2.stats()["prefix_cache"]
        assert pc["hits"] == 1 and pc["misses"] == 0
    finally:
        cb2.stop()


# -- ISSUE 16: speculative decoding -------------------------------------------

@pytest.mark.parametrize("which", ["lstm", "transformer"])
def test_spec_decode_greedy_parity_disagreeing_draft(which, lstm_net,
                                                     transformer_net):
    """Greedy speculative decode with a draft that frequently disagrees
    must still emit EXACTLY the sequential trajectory — acceptance cuts
    the chain where conditioning would diverge, and recurrent carries
    roll back to the accepted prefix."""
    net = lstm_net if which == "lstm" else transformer_net
    refs = [_compiled_tokens(net, p, 8, rng_seed=i)
            for i, p in enumerate([[1, 2, 3, 4], [5, 6, 7]])]
    cb = ContinuousBatcher(net, n_slots=2, max_seq=16,
                           prompt_buckets=(8,), draft_net=_draft_net(),
                           spec_k=3)
    try:
        streams = [cb.submit(p, max_new_tokens=8, rng_seed=i)
                   for i, p in enumerate([[1, 2, 3, 4], [5, 6, 7]])]
        assert _drain(streams) == refs
        spec = cb.stats()["speculative"]
        assert spec["rounds"] >= 1
        assert spec["accepted_hist"]["count"] >= 2
    finally:
        cb.stop()


def test_spec_decode_temperature_parity(lstm_net):
    """Sampled trajectories match sequential decode too: the verify
    step burns the exact key splits K sequential steps would, so
    acceptance never changes WHAT is sampled, only how many device
    calls produce it."""
    refs = [_compiled_tokens(lstm_net, [1, 2], 8, temperature=0.9,
                             rng_seed=s) for s in (3, 4)]
    cb = ContinuousBatcher(lstm_net, n_slots=2, max_seq=16,
                           prompt_buckets=(8,), draft_net=_draft_net(),
                           spec_k=3)
    try:
        streams = [cb.submit([1, 2], max_new_tokens=8, temperature=0.9,
                             rng_seed=s) for s in (3, 4)]
        assert _drain(streams) == refs
    finally:
        cb.stop()


def test_spec_decode_agreeing_draft_accepts_chunks(lstm_net):
    """A draft that clones the target accepts whole chunks: more than
    one token per verify step, fewer device rounds than tokens."""
    ref = _compiled_tokens(lstm_net, [1, 2, 3], 9)
    cb = ContinuousBatcher(lstm_net, n_slots=1, max_seq=16,
                           prompt_buckets=(8,),
                           draft_net=_draft_net(agrees_with=lstm_net),
                           spec_k=3)
    try:
        assert cb.generate([1, 2, 3], max_new_tokens=9) == ref
        spec = cb.stats()["speculative"]
        assert spec["accepted_per_step"] > 1.0
    finally:
        cb.stop()


def test_spec_decode_rejects_invalid_configs(lstm_net, transformer_net):
    with pytest.raises(ValueError):  # spec_k < 2
        ContinuousBatcher(lstm_net, n_slots=1, max_seq=16,
                          prompt_buckets=(8,), draft_net=_draft_net(),
                          spec_k=1, auto_start=False)
    with pytest.raises(ValueError):  # attention draft (needs rollback-
        ContinuousBatcher(lstm_net, n_slots=1, max_seq=16,  # free state)
                          prompt_buckets=(8,),
                          draft_net=transformer_net, spec_k=2,
                          auto_start=False)


def test_all_flags_combined_token_parity(lstm_net):
    """Paged pool + prefix cache + speculation at once — the full
    accelerator stack is still token-identical to the plain path."""
    ref = _compiled_tokens(lstm_net, [1, 2, 3], 8)
    cb = ContinuousBatcher(lstm_net, n_slots=2, max_seq=16,
                           prompt_buckets=(8,), page_size=4,
                           prefix_cache=True, draft_net=_draft_net(),
                           spec_k=3)
    try:
        assert cb.generate([1, 2, 3], max_new_tokens=8) == ref
        assert cb.generate([1, 2, 3], max_new_tokens=8) == ref  # hit
        st = cb.stats()
        assert st["prefix_cache"]["hits"] == 1
        assert st["kv_pages"]["live"] == 0
    finally:
        cb.stop()


# -- ISSUE 16: satellite guards ----------------------------------------------

def test_positional_bound_enforced_at_admission_config(transformer_net):
    """The silent positional-table overrun: a transformer's learned
    positional table has max_seq_len rows, and a decode table longer
    than it would gather out of bounds SILENTLY (clamped) — so the
    batcher refuses the geometry outright."""
    assert decode_mod.positional_bound(transformer_net.conf) == 32
    cb = ContinuousBatcher(transformer_net, n_slots=1, max_seq=32,
                           prompt_buckets=(8,), auto_start=False)  # ok
    cb.stop()
    with pytest.raises(ValueError):
        ContinuousBatcher(transformer_net, n_slots=1, max_seq=40,
                          prompt_buckets=(8,), auto_start=False)


def test_positional_bound_unbounded_for_recurrent(lstm_net):
    """One-hot recurrent stacks have no positional table — no bound."""
    assert decode_mod.positional_bound(lstm_net.conf) == 0
    cb = ContinuousBatcher(lstm_net, n_slots=1, max_seq=512,
                           prompt_buckets=(8,), auto_start=False)
    cb.stop()


def test_flags_off_compiles_only_the_pre_issue16_programs():
    """Flags off = two program kinds, the table's decode step and the
    one admission program ('decode', 'prefill-slot'; the B=1 'prefill'
    is the single-stream callers'), no paged/verify/logp/write-row
    programs anywhere near the cache."""
    net = MultiLayerNetwork(char_lstm(VOCAB, hidden=16, n_layers=2),
                            seed=0).init()
    cb = ContinuousBatcher(net, n_slots=2, max_seq=16,
                           prompt_buckets=(8,))
    try:
        assert len(cb.generate([1, 2], max_new_tokens=4)) == 4
        kinds = {r["entry"] for r in net.infer_cache.programs_summary()}
        assert kinds == {"decode", "prefill-slot"}
        st = cb.stats()
        assert "kv_pages" not in st
        assert "prefix_cache" not in st
        assert "speculative" not in st
    finally:
        cb.stop()


def test_warmup_generate_covers_every_flag_combination():
    """warmup_generate with the accelerator flags precompiles exactly
    what a flag-enabled batcher runs: zero fresh compiles during
    traffic, for paged + prefix + speculative at once."""
    net = MultiLayerNetwork(char_lstm(VOCAB, hidden=16, n_layers=2),
                            seed=0).init()
    draft = _draft_net()
    net.warmup_generate(slots=2, max_seq=16, prompt_buckets=(8,),
                        page_size=4, prefix_cache=True, draft_net=draft,
                        spec_k=3)
    before = (net.infer_cache.stats.misses
              + draft.infer_cache.stats.misses)
    cb = ContinuousBatcher(net, n_slots=2, max_seq=16,
                           prompt_buckets=(8,), page_size=4,
                           prefix_cache=True, draft_net=draft, spec_k=3)
    try:
        assert len(cb.generate([1, 2, 3], max_new_tokens=6)) == 6
        after = (net.infer_cache.stats.misses
                 + draft.infer_cache.stats.misses)
        assert after == before  # fresh_compiles == 0 under traffic
        assert cb.stats()["fresh_compiles"] == after
    finally:
        cb.stop()
