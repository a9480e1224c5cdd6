"""Compiled KV-cache decode + continuous batching (ISSUE 14).

The correctness anchor: the compiled decode path (one prefill program +
one decode-step program with donated state) must reproduce the eager
per-token loop EXACTLY — same f32 ops, same PRNG key splits, so the
greedy token trajectory is equal token-for-token on both charLSTM and
charTransformer, and temperature sampling follows the same key stream.
Around that anchor: the continuous batcher's slot table (admission into
freed slots, no barrier on the longest sequence), the /v1/generate
chunked stream, and the chaos contract — a mid-generation fault ends
ONE stream cleanly while its neighbours keep decoding.

Tier-1: CPU-only, tiny models."""

import contextlib
import json
import threading
import time
import urllib.error
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.models.zoo import char_lstm, char_transformer, mlp
from deeplearning4j_tpu.nn import decode as decode_mod
from deeplearning4j_tpu.nn.conf import LayerType
from deeplearning4j_tpu.nn.layers import get_layer
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork, network_output
from deeplearning4j_tpu.reliability import faults
from deeplearning4j_tpu.serving.batcher import (ContinuousBatcher,
                                                ServerOverloaded)
from deeplearning4j_tpu.utils import profiling

VOCAB = 13


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.reset()
    yield
    faults.reset()


@pytest.fixture(scope="module")
def lstm_net():
    return MultiLayerNetwork(char_lstm(VOCAB, hidden=16, n_layers=2),
                             seed=0).init()


@pytest.fixture(scope="module")
def transformer_net():
    return MultiLayerNetwork(
        char_transformer(VOCAB, d_model=16, n_blocks=2, n_heads=2,
                         max_seq_len=32), seed=0).init()


def _compiled_tokens(net, prompt, n_new, temperature=0.0, rng_seed=0,
                     max_seq=16, bucket=8):
    """Prompt -> n_new tokens through the compiled prefill + decode
    programs (the exact sequence ContinuousBatcher runs per slot)."""
    ic = net.infer_cache
    state = ic.init_decode_state(net.conf, 1, max_seq)
    pb = np.zeros((1, bucket), np.int32)
    pb[0, :len(prompt)] = prompt
    length = jnp.asarray([len(prompt)], jnp.int32)
    keys = jnp.asarray(np.asarray(jax.random.PRNGKey(rng_seed))[None])
    temps = jnp.full((1,), float(temperature), jnp.float32)
    tok, keys, state = ic.prefill(net.conf, net.params, state,
                                  jnp.asarray(pb), length, keys, temps)
    got = [int(tok[0])]
    pos = jnp.asarray([len(prompt)], jnp.int32)
    for _ in range(n_new - 1):
        tok, keys, state = ic.decode(net.conf, net.params, state, tok,
                                     pos, keys, temps)
        got.append(int(tok[0]))
        pos = pos + 1
    return got


def _eager_lstm_tokens(net, prompt, n_new, temperature=0.0, rng_seed=0):
    """The CharLSTM.sample() loop, verbatim: step the fused cell one
    one-hot char at a time, split the key before EVERY token."""
    confs = [net.conf.conf(i) for i in range(net.conf.n_layers)]
    stack = list(zip(confs, net.params))
    lstm = get_layer(LayerType.LSTM)
    out_conf, out_p = stack[-1]
    hs = [jnp.zeros((1, c.n_out), jnp.float32) for c, _ in stack[:-1]]
    cs = [jnp.zeros((1, c.n_out), jnp.float32) for c, _ in stack[:-1]]
    eye = np.eye(VOCAB, dtype=np.float32)
    key = jax.random.PRNGKey(rng_seed)

    def step(x, hs, cs):
        inp, h2, c2 = x, [], []
        for li, (c, p) in enumerate(stack[:-1]):
            inp, cc = lstm.step(p, c, inp, hs[li], cs[li])
            h2.append(inp)
            c2.append(cc)
        probs = get_layer(out_conf.layer_type).forward(out_p, out_conf, inp)
        return jnp.log(jnp.clip(probs, 1e-9, 1.0)), h2, c2

    logp = None
    for cid in prompt:
        logp, hs, cs = step(jnp.asarray(eye[cid][None]), hs, cs)
    toks = []
    for _ in range(n_new):
        key, sub = jax.random.split(key)
        if temperature <= 0:
            t = int(jnp.argmax(logp[0]))
        else:
            t = int(jax.random.categorical(sub, logp[0] / temperature))
        toks.append(t)
        logp, hs, cs = step(jnp.asarray(eye[t][None]), hs, cs)
    return toks


def _eager_transformer_tokens(net, prompt, n_new):
    """Greedy reference by full re-forward over the growing sequence —
    no cache at all, so agreement means the cached path IS the model."""
    seq, toks = list(prompt), []
    for _ in range(n_new):
        ids = jnp.asarray([seq], jnp.int32)
        probs = network_output(net.conf, net.params, ids)
        probs = probs.reshape(len(seq), VOCAB)
        toks.append(int(jnp.argmax(
            jnp.log(jnp.clip(probs[-1], 1e-9, 1.0)))))
        seq.append(toks[-1])
    return toks


# -- the correctness anchor: compiled == eager, f32 exact ---------------------

def test_greedy_parity_char_lstm(lstm_net):
    ref = _eager_lstm_tokens(lstm_net, [1, 2, 3], 8)
    got = _compiled_tokens(lstm_net, [1, 2, 3], 8)
    assert got == ref


def test_greedy_parity_char_transformer(transformer_net):
    ref = _eager_transformer_tokens(transformer_net, [1, 2, 3], 8)
    got = _compiled_tokens(transformer_net, [1, 2, 3], 8)
    assert got == ref


def test_temperature_trajectory_parity_char_lstm(lstm_net):
    """Sampling splits the same key stream on both paths, so even the
    stochastic trajectory is equal token-for-token."""
    ref = _eager_lstm_tokens(lstm_net, [2, 5], 10, temperature=0.7,
                             rng_seed=3)
    got = _compiled_tokens(lstm_net, [2, 5], 10, temperature=0.7,
                           rng_seed=3)
    assert got == ref


def test_charlstm_generate_matches_sample():
    """The model-level satellite: CharLSTM.generate() (compiled decode)
    equals CharLSTM.sample() (eager loop) for greedy AND temperature —
    both share `_encode` and the key-split discipline."""
    from deeplearning4j_tpu.models.char_lstm import CharLSTM

    text = "the quick brown fox jumps over the lazy dog " * 4
    m = CharLSTM(hidden=16, n_layers=1, seq_len=8, iterations=2).fit(text)
    assert (m.sample("the q", n=10, temperature=0.0)
            == m.generate("the q", n=10, temperature=0.0))
    assert (m.sample("dog", n=10, temperature=0.9, rng_seed=7)
            == m.generate("dog", n=10, temperature=0.9, rng_seed=7))


# -- decode state + cache mechanics -------------------------------------------

def test_check_generative_accepts_and_rejects():
    decode_mod.check_generative(char_lstm(8, hidden=4, n_layers=1))
    decode_mod.check_generative(
        char_transformer(8, d_model=8, n_blocks=1, n_heads=2,
                         max_seq_len=8))
    with pytest.raises(ValueError):
        decode_mod.check_generative(mlp(n_in=4, hidden=[4], n_out=2))


def test_init_state_shapes_and_embedding_bound(transformer_net):
    state = decode_mod.init_state(transformer_net.conf, 3, 16)
    k = state[1]["k"]  # layer 0 is the embedding
    assert k.shape == (3, 16, 16)
    with pytest.raises(ValueError):
        # max_seq beyond the learned positional table would index junk
        decode_mod.init_state(transformer_net.conf, 1, 64)


def test_decode_programs_compile_once_and_key_by_batch(lstm_net):
    ic = lstm_net.infer_cache
    before = ic.stats.misses
    _compiled_tokens(lstm_net, [1], 4)
    _compiled_tokens(lstm_net, [2], 4)  # same shapes: pure cache hits
    after_same = ic.stats.misses
    assert after_same - before <= 2  # decode + prefill at most once
    summary = ic.programs_summary()
    assert any(p["entry"] == "decode" for p in summary)
    assert any(p["entry"] == "prefill" for p in summary)


def test_decode_donation_matches_backend(lstm_net):
    """On CPU donation is a no-op (and the audit rule is gated the same
    way); off-CPU the decode/prefill records must donate arg 1."""
    from deeplearning4j_tpu.nd.platform import default_backend

    ic = lstm_net.infer_cache
    _compiled_tokens(lstm_net, [1], 2)
    recs = [r for r in ic.audit_records()
            if r["key"][0] in ("decode", "prefill")]
    assert recs
    want = (1,) if default_backend() != "cpu" else ()
    assert all(tuple(r["donate_argnums"]) == want for r in recs)


# -- continuous batcher -------------------------------------------------------

def test_batcher_generates_and_reports(lstm_net):
    cb = ContinuousBatcher(lstm_net, n_slots=2, max_seq=16,
                           prompt_buckets=(8,))
    try:
        ref = _compiled_tokens(lstm_net, [1, 2, 3], 6)
        got = cb.generate([1, 2, 3], max_new_tokens=6)
        assert got == ref
        s1 = cb.submit([1, 2], max_new_tokens=4)
        s2 = cb.submit([3, 4], max_new_tokens=4)
        assert len(list(s1.tokens(timeout=30.0))) == 4
        assert len(list(s2.tokens(timeout=30.0))) == 4
        assert s1.ttft_s is not None and s1.ttft_s >= 0.0
        st = cb.stats()
        assert st["streams"] == {"admitted": 3, "completed": 3,
                                 "failed": 0}
        assert st["tokens"] == 14
        assert st["slots"] == {"width": 2, "active": 0, "free": 2}
        h = st["ttft_hist_s"]
        assert sum(h["counts"]) + h["inf"] == h["count"] == 3
    finally:
        cb.stop()


def test_batcher_interleaves_admissions_without_barrier(lstm_net):
    """Continuous batching: a long stream keeps decoding while short
    ones are admitted into freed slots — more streams than slots
    complete even though the long one started first."""
    cb = ContinuousBatcher(lstm_net, n_slots=2, max_seq=32,
                           prompt_buckets=(8,))
    try:
        long = cb.submit([1], max_new_tokens=24)
        shorts = [cb.submit([2, 3], max_new_tokens=2) for _ in range(3)]
        for s in shorts:
            assert len(list(s.tokens(timeout=30.0))) == 2
        assert len(list(long.tokens(timeout=30.0))) == 24
        assert cb.stats()["streams"]["completed"] == 4
    finally:
        cb.stop()


def test_submit_validation_and_overload(lstm_net):
    cb = ContinuousBatcher(lstm_net, n_slots=1, max_seq=8,
                           prompt_buckets=(4,), max_pending=1,
                           auto_start=False)
    with pytest.raises(ValueError):
        cb.submit([], max_new_tokens=1)
    with pytest.raises(ValueError):
        cb.submit(list(range(8)), max_new_tokens=1)  # prompt fills cache
    cb.submit([1], max_new_tokens=2)
    with pytest.raises(ServerOverloaded):
        cb.submit([1], max_new_tokens=2)  # pending bound
    cb.stop()


def test_max_new_tokens_clamped_to_cache(lstm_net):
    cb = ContinuousBatcher(lstm_net, n_slots=1, max_seq=8,
                           prompt_buckets=(4,))
    try:
        toks = cb.generate([1, 2, 3], max_new_tokens=100)
        assert len(toks) == 8 - 3  # prompt + output fit max_seq exactly
    finally:
        cb.stop()


def test_sequential_mode_still_serves_everything(lstm_net):
    """continuous=False (the bench's barrier arm) trades throughput,
    not correctness: every queued stream still completes."""
    cb = ContinuousBatcher(lstm_net, n_slots=2, max_seq=16,
                           prompt_buckets=(8,), continuous=False)
    try:
        streams = [cb.submit([1, 2], max_new_tokens=3) for _ in range(5)]
        for s in streams:
            assert len(list(s.tokens(timeout=30.0))) == 3
        assert cb.stats()["streams"]["completed"] == 5
    finally:
        cb.stop()


# -- chaos: fault isolation per stream ----------------------------------------

def test_decode_fault_fails_one_stream_others_decode_on(lstm_net):
    """Arm decode.step for slot A's traversal mid-generation: A's
    stream ends with the injected error, B runs to completion — the
    fault never crosses the slot boundary."""
    # armed BEFORE the first submission: the very first decode-table
    # traversal is slot 0 — the slot stream `a` (submitted first) is
    # admitted into — so the doomed stream is deterministic
    faults.arm("decode.step", "raise", nth=1)
    cb = ContinuousBatcher(lstm_net, n_slots=2, max_seq=32,
                           prompt_buckets=(8,))
    try:
        a = cb.submit([1, 2], max_new_tokens=20)
        b = cb.submit([3, 4], max_new_tokens=20)
        b_toks = list(b.tokens(timeout=30.0))
        assert len(b_toks) == 20
        with pytest.raises(faults.FaultInjected):
            list(a.tokens(timeout=30.0))
        st = cb.stats()
        assert st["streams"]["failed"] == 1
        assert st["streams"]["completed"] == 1
        # the failed slot was released: a new stream admits and finishes
        faults.disarm()
        assert len(cb.generate([5], max_new_tokens=3)) == 3
    finally:
        cb.stop()


def test_admit_fault_fails_only_the_admitted_stream(lstm_net):
    faults.arm("generate.admit", "raise", nth=1)
    cb = ContinuousBatcher(lstm_net, n_slots=2, max_seq=16,
                           prompt_buckets=(8,))
    try:
        doomed = cb.submit([1], max_new_tokens=4)
        with pytest.raises(faults.FaultInjected):
            list(doomed.tokens(timeout=30.0))
        # the registry disarms after firing once: next stream is fine
        assert len(cb.generate([2], max_new_tokens=4)) == 4
        assert cb.stats()["streams"]["failed"] == 1
    finally:
        cb.stop()


# -- HTTP: /v1/generate chunked streaming -------------------------------------

def _post_generate(url, body, timeout=30):
    req = urllib.request.Request(
        url + "/v1/generate", data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return resp.status, [json.loads(line) for line in
                             resp.read().decode().strip().splitlines()]


def test_http_generate_streams_tokens(lstm_net):
    lstm_net.warmup_generate(slots=2, max_seq=16, prompt_buckets=(8,))
    server = lstm_net.serve(generate=True, gen_slots=2, gen_max_seq=16,
                            gen_prompt_buckets=(8,))
    try:
        code, lines = _post_generate(server.url,
                                     {"prompt": [1, 2, 3],
                                      "max_new_tokens": 5})
        assert code == 200
        toks = [ln["token"] for ln in lines if "token" in ln]
        assert toks == _compiled_tokens(lstm_net, [1, 2, 3], 5)
        assert lines[-1]["done"] is True
        assert lines[-1]["tokens"] == 5
        assert lines[-1]["ttft_ms"] >= 0.0
        # stats carry the generation block
        st = json.loads(_httpget(server.url + "/v1/stats"))
        assert st["generation"]["streams"]["completed"] == 1
    finally:
        server.stop()


def _httpget(url, timeout=30):
    with urllib.request.urlopen(url, timeout=timeout) as resp:
        return resp.read().decode()


def test_http_generate_error_envelope(lstm_net):
    server = lstm_net.serve(generate=True, gen_slots=1, gen_max_seq=8,
                            gen_prompt_buckets=(4,))
    try:
        # bad prompt: 400 before any stream starts
        with pytest.raises(urllib.error.HTTPError) as ei:
            _post_generate(server.url, {"prompt": "not a list"})
        assert ei.value.code == 400
        with pytest.raises(urllib.error.HTTPError) as ei:
            _post_generate(server.url, {"prompt": list(range(8))})
        assert ei.value.code == 400  # prompt fills the whole cache
    finally:
        server.stop()


def test_http_generate_404_without_generator(lstm_net):
    server = lstm_net.serve()  # generate not enabled
    try:
        with pytest.raises(urllib.error.HTTPError) as ei:
            _post_generate(server.url, {"prompt": [1]})
        assert ei.value.code == 404
    finally:
        server.stop()


def test_http_admit_fault_is_clean_5xx_other_stream_unharmed(lstm_net):
    """The ISSUE 14 chaos contract over HTTP: stream B is decoding, a
    fault fires on stream A's admission — A gets a clean 5xx, B streams
    every one of its tokens."""
    lstm_net.warmup_generate(slots=2, max_seq=32, prompt_buckets=(8,))
    server = lstm_net.serve(generate=True, gen_slots=2, gen_max_seq=32,
                            gen_prompt_buckets=(8,))
    try:
        results = {}

        def run_b():
            results["b"] = _post_generate(
                server.url, {"prompt": [3, 4], "max_new_tokens": 24})

        tb = threading.Thread(target=run_b)
        tb.start()
        # wait until B was ADMITTED (not merely queued) before arming,
        # so the fault can only hit A's admission
        deadline = time.monotonic() + 20.0
        while time.monotonic() < deadline:
            gen = json.loads(
                _httpget(server.url + "/v1/stats"))["generation"]
            if gen["streams"]["admitted"] >= 1:
                break
            time.sleep(0.005)
        faults.arm("generate.admit", "raise", nth=1)
        code_a = None
        try:
            _post_generate(server.url, {"prompt": [1], "max_new_tokens": 4})
        except urllib.error.HTTPError as e:
            code_a = e.code
        assert code_a == 500
        tb.join(timeout=30.0)
        code_b, lines_b = results["b"]
        assert code_b == 200
        assert sum(1 for ln in lines_b if "token" in ln) == 24
        assert lines_b[-1]["done"] is True
    finally:
        server.stop()


# -- ISSUE 16: paged KV cache -------------------------------------------------

def _drain(streams, timeout=60.0):
    return [list(s.tokens(timeout=timeout)) for s in streams]


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

def _draft_net(agrees_with=None):
    """A draft model: `agrees_with` clones the target (full acceptance)
    while None builds a smaller, differently-seeded one (frequent
    rejection — the adversarial case for the rollback math)."""
    if agrees_with is not None:
        return MultiLayerNetwork(agrees_with.conf, seed=0).init()
    return MultiLayerNetwork(char_lstm(VOCAB, hidden=8, n_layers=1),
                             seed=1).init()


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


@contextlib.contextmanager
def _watch_compiles():
    """What `benchmark/run.py::watch_compiles` counts: every backend
    compile and every fetch from JAX's persistent cache.  An eager
    `zeros` or `scatter` after `jax.clear_caches()` is one."""
    import jax.monitoring as mon

    seen = {"count": 0, "names": []}

    def on_duration(name, seconds, **_):
        if name in ("/jax/core/compile/backend_compile_duration",
                    "/jax/compilation_cache/cache_retrieval_time_sec"):
            seen["count"] += 1
            seen["names"].append(name)

    mon.register_event_duration_secs_listener(on_duration)
    try:
        yield seen
    finally:
        mon.unregister_event_duration_listener(on_duration)


@pytest.mark.parametrize("draft", [False, True])
@pytest.mark.parametrize("which", ["lstm", "transformer"])
def test_warm_admissions_compile_nothing_and_call_the_cache_once(which, draft):
    """After `jax.clear_caches()` and `warmup_generate`, a dense admission
    dispatches nothing outside its one compiled cache entry: no compile
    of any kind in the window, and one cache call a stream (streams of
    one token end at their admission, so no decode step runs)."""
    conf = (char_lstm(VOCAB, hidden=16, n_layers=2) if which == "lstm" else
            char_transformer(VOCAB, d_model=16, n_blocks=2, n_heads=2,
                             max_seq_len=32))
    net = MultiLayerNetwork(conf, seed=0).init()
    kw = {"draft_net": _draft_net(), "spec_k": 3} if draft else {}
    jax.clear_caches()
    net.warmup_generate(slots=3, max_seq=16, prompt_buckets=(4, 8), **kw)
    cb = ContinuousBatcher(net, n_slots=3, max_seq=16,
                           prompt_buckets=(4, 8), **kw).start()
    caches = [net.infer_cache] + ([kw["draft_net"].infer_cache] if draft
                                  else [])
    jax.random.PRNGKey(0)   # `submit` makes the stream's key, on the caller
    try:
        with _watch_compiles() as seen:
            misses = [ic.stats.misses for ic in caches]
            calls = [ic.stats.steps for ic in caches]
            streams = [cb.submit(p, max_new_tokens=1, temperature=t,
                                 rng_seed=i)
                       for i, (p, t) in enumerate(
                           [([1, 2, 3], 0.0), ([4, 5, 6, 7, 1], 0.7),
                            ([2], 0.0), ([1, 2, 3], 0.0), ([5, 5], 1.3)])]
            assert [len(t) for t in _drain(streams)] == [1] * 5
            cb.stop()
            assert seen["count"] == 0, seen["names"]
        assert [ic.stats.misses for ic in caches] == misses
        assert [ic.stats.steps - c for ic, c in zip(caches, calls)] \
            == [5] * len(caches)
    finally:
        cb.stop()


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
