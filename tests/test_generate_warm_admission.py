"""ISSUE 26, once warm: after `jax.clear_caches()` and `warmup_generate` a
dense admission compiles nothing and calls its cache once.  Apart from
`test_generate_admission.py` because each case starts from cleared caches,
which costs the worker every program it had.

Tier-1: CPU-only, tiny models."""

import contextlib

import jax
import pytest

from deeplearning4j_tpu.models.zoo import char_lstm, char_transformer
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.serving.batcher import ContinuousBatcher
from generate_helpers import VOCAB, _clean_faults, _draft_net, _drain   # noqa: F401


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
