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

Paged K/V, the prefix cache and speculative decoding are in
`test_generate_paged.py`, the one-program admission in
`test_generate_admission.py`.

Tier-1: CPU-only, tiny models."""

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
from deeplearning4j_tpu.nn.multilayer import network_output
from deeplearning4j_tpu.reliability import faults
from deeplearning4j_tpu.serving.batcher import (ContinuousBatcher,
                                                ServerOverloaded)
from generate_helpers import (VOCAB, _clean_faults, _compiled_tokens,   # noqa: F401
                              lstm_net, transformer_net)


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
    """Greedy reference by full eager re-forward over the sequence so far —
    no cache at all, so agreement means the cached path IS the model.  The
    ids are padded to one width (a causal model's outputs do not see what
    follows), so that the eager ops compile for one shape and not for
    `n_new`."""
    seq, toks = list(prompt), []
    width = len(prompt) + n_new
    for _ in range(n_new):
        ids = np.zeros((1, width), np.int32)
        ids[0, :len(seq)] = seq
        probs = network_output(net.conf, net.params, jnp.asarray(ids))
        probs = probs.reshape(width, VOCAB)
        toks.append(int(jnp.argmax(
            jnp.log(jnp.clip(probs[len(seq) - 1], 1e-9, 1.0)))))
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
