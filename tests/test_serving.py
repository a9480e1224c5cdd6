"""Micro-batching serving gateway (ISSUE 4 tentpole): multithreaded
bitwise correctness vs direct `net.output()`, flush policy (full bucket
vs deadline), bounded-queue backpressure, the HTTP endpoints, the
zero-fresh-compile warmed-server criterion, and a closed-loop load test
(slow)."""

import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from deeplearning4j_tpu.models.zoo import mlp
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.serving import MicroBatcher, ServerOverloaded

N_IN, N_OUT = 6, 3


def _net(seed=0):
    return MultiLayerNetwork(mlp(n_in=N_IN, hidden=[8], n_out=N_OUT,
                                 lr=0.05), seed=seed).init()


def _x(rows, seed):
    rng = np.random.RandomState(seed)
    return rng.randn(rows, N_IN).astype(np.float32)


def _http(url, body=None):
    req = urllib.request.Request(
        url, data=None if body is None else json.dumps(body).encode(),
        headers={"Content-Type": "application/json"},
        method="GET" if body is None else "POST")
    with urllib.request.urlopen(req, timeout=30) as r:
        return r.status, json.loads(r.read())


# -- acceptance criterion: interleaved concurrent ragged requests return
# bitwise the same outputs as direct net.output() per request ---------------

def test_gateway_bitwise_matches_direct_under_concurrency():
    net = _net()
    sizes = [1, 2, 3, 5, 7, 4, 1, 6]
    xs = [_x(r, seed=i) for i, r in enumerate(sizes)]
    # direct per-request reference, computed single-threaded up front
    direct = [np.asarray(net.output(x)) for x in xs]

    batcher = MicroBatcher(net, max_delay_ms=5.0, max_batch_rows=16)
    errors, lock = [], threading.Lock()

    def client(i):
        try:
            for _ in range(5):  # interleave repeatedly
                got = batcher.predict(xs[i], timeout=30.0)
                np.testing.assert_array_equal(direct[i], got)
        except BaseException as e:  # noqa: BLE001
            with lock:
                errors.append((i, e))

    threads = [threading.Thread(target=client, args=(i,))
               for i in range(len(sizes))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60.0)
        assert not t.is_alive(), "client thread hung"
    batcher.stop()
    assert not errors, errors
    st = batcher.stats()
    assert st["requests"] == 5 * len(sizes)
    assert st["rows"] == 5 * sum(sizes)


def test_full_bucket_flush_coalesces_before_deadline():
    net = _net()
    net.warmup([8])  # declares the row bucket the gateway targets
    # deadline far away: completion proves the full-bucket trigger fired
    batcher = MicroBatcher(net, max_delay_ms=5000.0)
    assert batcher._target_rows() == 8
    results = [None] * 8

    def client(i):
        results[i] = batcher.predict(_x(1, seed=i), timeout=30.0)

    t0 = time.monotonic()
    threads = [threading.Thread(target=client, args=(i,)) for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30.0)
        assert not t.is_alive()
    assert time.monotonic() - t0 < 60.0
    batcher.stop()
    assert all(r is not None and r.shape == (1, N_OUT) for r in results)
    hist = batcher.stats()["batch_rows_hist"]
    # the 8 single-row requests coalesced (an 8-row flush exists; exact
    # splits below 8 depend on thread arrival order)
    assert "8" in hist, hist


def test_deadline_flush_serves_partial_batch():
    net = _net()
    batcher = MicroBatcher(net, max_delay_ms=20.0, max_batch_rows=64)
    out = batcher.predict(_x(3, seed=1), timeout=30.0)  # alone: no co-riders
    batcher.stop()
    assert out.shape == (3, N_OUT)
    assert batcher.stats()["batch_rows_hist"] == {"3": 1}


def test_backpressure_fails_fast_beyond_max_pending():
    net = _net()
    # dispatcher NOT running: requests stay queued
    batcher = MicroBatcher(net, max_pending=2, auto_start=False,
                           max_delay_ms=1.0)
    done = []
    threads = [threading.Thread(
        target=lambda i=i: done.append(
            (i, batcher.predict(_x(1, seed=i), timeout=30.0))))
        for i in range(2)]
    for t in threads:
        t.start()
    deadline = time.time() + 5.0
    while batcher.queue_depth() < 2 and time.time() < deadline:
        time.sleep(0.01)
    assert batcher.queue_depth() == 2
    with pytest.raises(ServerOverloaded):
        batcher.predict(_x(1, seed=99))
    batcher.start()  # dispatcher drains the queue; blocked clients finish
    for t in threads:
        t.join(timeout=30.0)
        assert not t.is_alive()
    batcher.stop()
    assert len(done) == 2


def test_stop_drains_queued_requests():
    net = _net()
    batcher = MicroBatcher(net, max_delay_ms=5000.0, auto_start=False)
    got = []
    t = threading.Thread(
        target=lambda: got.append(batcher.predict(_x(2, seed=0),
                                                  timeout=30.0)))
    t.start()
    deadline = time.time() + 5.0
    while batcher.queue_depth() < 1 and time.time() < deadline:
        time.sleep(0.01)
    batcher.start()
    batcher.stop()  # drain-on-stop: the queued request is served, not lost
    t.join(timeout=30.0)
    assert not t.is_alive()
    assert got and got[0].shape == (2, N_OUT)


def test_sequence_model_requests_get_all_their_output_rows():
    """A sequence model answers T rows per input row ([B*T, V]): coalesced
    requests each get their own T-row runs back, not the first `rows` rows
    of the batch (what the gateway answered for every transformer until
    the first run on the chip compared it with `net.output`)."""
    from deeplearning4j_tpu.models.zoo import char_transformer

    seq, vocab = 8, 12
    net = MultiLayerNetwork(char_transformer(
        vocab, d_model=16, n_blocks=1, n_heads=2, max_seq_len=seq)).init()
    rng = np.random.RandomState(0)
    xs = [rng.randint(0, vocab, (rows, seq)).astype(np.float32)
          for rows in (1, 2, 1)]
    want = [np.asarray(net.output(x)) for x in xs]
    batcher = MicroBatcher(net, max_delay_ms=5000.0, auto_start=False)
    got = [None] * len(xs)
    threads = [threading.Thread(
        target=lambda i=i: got.__setitem__(
            i, batcher.predict(xs[i], timeout=60.0))) for i in range(len(xs))]
    for t in threads:
        t.start()
    deadline = time.time() + 10.0
    while batcher.queue_depth() < len(xs) and time.time() < deadline:
        time.sleep(0.01)
    batcher.start()
    batcher.stop()  # one coalesced flush of all four rows
    for t in threads:
        t.join(timeout=60.0)
    for x, g, w in zip(xs, got, want):
        assert g.shape == (x.shape[0] * seq, vocab)
        np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-6)


def test_dispatcher_error_delivered_to_caller():
    net = _net()
    batcher = MicroBatcher(net, max_delay_ms=5.0)
    with pytest.raises(Exception):
        # feature width mismatch: the device call fails, and the error
        # must surface at the caller instead of hanging it
        batcher.predict(np.zeros((2, N_IN + 1), np.float32), timeout=30.0)
    batcher.stop()


# -- HTTP server -------------------------------------------------------------

def test_model_server_predict_and_stats_endpoints():
    net = _net()
    net.warmup([8])
    server = net.serve(max_delay_ms=2.0)
    try:
        x = _x(3, seed=7)
        direct = np.asarray(net.output(x))
        code, body = _http(server.url + "/v1/predict",
                           {"features": x.tolist()})
        assert code == 200 and body["rows"] == 3
        np.testing.assert_array_equal(
            direct, np.asarray(body["output"], np.float32))

        # single unbatched example is promoted to a 1-row batch
        code, body = _http(server.url + "/v1/predict",
                           {"features": x[0].tolist()})
        assert code == 200 and body["rows"] == 1

        code, stats = _http(server.url + "/v1/stats")
        assert code == 200
        for key in ("queue_depth", "batch_rows_hist", "latency_ms",
                    "rows_per_sec", "fresh_compiles", "cache", "batching"):
            assert key in stats, key
        assert stats["requests"] >= 2
        assert "disk_hits" in stats["cache"]  # observable in one curl
        assert stats["latency_ms"]["p50"] <= stats["latency_ms"]["p99"]
    finally:
        server.stop()


def test_model_server_error_codes():
    net = _net()
    server = net.serve()
    try:
        for path, body in [("/v1/predict", {"wrong_key": []}),
                           ("/nope", None), ("/nope", {"features": []})]:
            with pytest.raises(urllib.error.HTTPError) as ei:
                _http(server.url + path, body)
            assert ei.value.code in (400, 404)
    finally:
        server.stop()


def test_server_overload_returns_503():
    net = _net()
    server = net.serve(max_pending=1, max_delay_ms=1.0)
    server.batcher.stop()  # wedge the gateway so the queue stays full
    try:
        def fill():
            try:  # never served: the gateway is wedged; times out quietly
                server.batcher.predict(_x(1, seed=0), timeout=5.0)
            except TimeoutError:
                pass

        filler = threading.Thread(target=fill)
        filler.start()
        deadline = time.time() + 5.0
        while server.batcher.queue_depth() < 1 and time.time() < deadline:
            time.sleep(0.01)
        with pytest.raises(urllib.error.HTTPError) as ei:
            _http(server.url + "/v1/predict",
                  {"features": _x(1, seed=1).tolist()})
        assert ei.value.code == 503
        filler.join(timeout=30.0)
    finally:
        server.stop()


# -- acceptance criterion: a server started against a warmed compile cache
# serves its first request with zero fresh compiles --------------------------

def test_warmed_server_first_request_zero_fresh_compiles(tmp_path):
    cache_dir = str(tmp_path / "compile-cache")
    conf = mlp(n_in=N_IN, hidden=[8], n_out=N_OUT, lr=0.05)

    warm = MultiLayerNetwork(conf, seed=0).init()
    warm.set_compile_cache(cache_dir)
    warm.warmup([4, 8])
    assert warm.infer_cache.stats.misses == 2  # the compiles we prepaid

    # a FRESH process-alike: new network, same conf, same cache dir
    net = MultiLayerNetwork(conf, seed=0).init()
    net.set_compile_cache(cache_dir)
    net.warmup([4, 8])  # disk restores, not compiles
    server = net.serve(max_delay_ms=2.0)
    try:
        code, body = _http(server.url + "/v1/predict",
                           {"features": _x(3, seed=3).tolist()})
        assert code == 200
        _, stats = _http(server.url + "/v1/stats")
        assert stats["fresh_compiles"] == 0, stats
        assert stats["cache"]["disk_hits"] == 2, stats
    finally:
        server.stop()


def test_serve_cli_parser_and_builder(tmp_path):
    from deeplearning4j_tpu.cli.driver import _build_server, build_parser
    from deeplearning4j_tpu.parallel import checkpoint

    net = _net()
    ckpt = str(tmp_path / "ckpt")
    checkpoint.save(ckpt, net.params, conf=net.conf)

    args = build_parser().parse_args(
        ["serve", "--model", ckpt, "--shapes", "8",
         "--max-delay-ms", "2.0", "--max-pending", "16"])
    assert args.fn.__name__ == "cmd_serve"
    srv_net, server, summary = _build_server(args)
    try:
        assert summary["url"] == server.url
        assert summary["warmed"] == [(8, N_IN)]
        assert summary["batching"] is True
        code, body = _http(server.url + "/v1/predict",
                           {"features": _x(2, seed=5).tolist()})
        assert code == 200 and body["rows"] == 2
        np.testing.assert_array_equal(
            np.asarray(srv_net.output(_x(2, seed=5))),
            np.asarray(body["output"], np.float32))
    finally:
        server.stop()


def test_serve_generate_warmed_zero_fresh_compiles(tmp_path):
    """ISSUE 14 satellite: `warmup --generate` prepays the decode +
    prefill compiles into the persistent store; a fresh-process `serve
    --generate` with the same gen_* flags starts from disk restores and
    streams its first generation with fresh_compiles == 0."""
    from deeplearning4j_tpu.cli.driver import _build_server, build_parser
    from deeplearning4j_tpu.models.zoo import char_lstm
    from deeplearning4j_tpu.parallel import checkpoint

    cache_dir = str(tmp_path / "compile-cache")
    conf = char_lstm(11, hidden=12, n_layers=1)
    warm = MultiLayerNetwork(conf, seed=0).init()
    ckpt = str(tmp_path / "ckpt")
    checkpoint.save(ckpt, warm.params, conf=warm.conf)
    warm.set_compile_cache(cache_dir)
    warm.warmup_generate(slots=2, max_seq=16, prompt_buckets=(8,))
    assert warm.infer_cache.stats.misses > 0  # the compiles we prepaid

    args = build_parser().parse_args(
        ["serve", "--model", ckpt, "--compile-cache", cache_dir,
         "--shapes", "", "--generate", "--gen-slots", "2",
         "--gen-max-seq", "16", "--gen-prompt-buckets", "8"])
    srv_net, server, summary = _build_server(args)
    try:
        assert summary["fresh_compiles"] == 0, summary
        assert summary["generation"]["prompt_buckets"] == [8], summary
        req = urllib.request.Request(
            server.url + "/v1/generate",
            data=json.dumps({"prompt": [1, 2], "max_new_tokens": 4}
                            ).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=30) as r:
            lines = [json.loads(ln) for ln in
                     r.read().decode().strip().splitlines()]
        assert sum(1 for ln in lines if "token" in ln) == 4
        _, stats = _http(server.url + "/v1/stats")
        assert stats["generation"]["fresh_compiles"] == 0, stats
        assert srv_net.infer_cache.stats.misses == 0
    finally:
        server.stop()


# -- closed-loop load (CI satellite: slow, mirrors bench_serve) --------------

@pytest.mark.slow
def test_closed_loop_load_batches_and_stays_bitwise():
    net = _net()
    net.warmup([32])
    batcher = MicroBatcher(net, max_delay_ms=3.0)
    xs = [_x(1 + i % 3, seed=i) for i in range(16)]
    direct = [np.asarray(net.output(x)) for x in xs]
    errors, lock = [], threading.Lock()

    def client(i):
        try:
            for _ in range(20):
                np.testing.assert_array_equal(
                    direct[i], batcher.predict(xs[i], timeout=60.0))
        except BaseException as e:  # noqa: BLE001
            with lock:
                errors.append(e)

    threads = [threading.Thread(target=client, args=(i,))
               for i in range(len(xs))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=300.0)
        assert not t.is_alive()
    batcher.stop()
    assert not errors, errors[:3]
    st = batcher.stats()
    # closed-loop concurrency actually coalesced: fewer device calls
    # than requests
    flushes = sum(st["batch_rows_hist"].values())
    assert flushes < st["requests"], st


# -- deadline-heap eviction order (ISSUE 19 satellite) ------------------------

def test_eviction_order_is_deadline_then_fifo():
    """The dispatcher's deadline heap pins eviction order to
    (deadline, t_enqueue): earliest deadline first, FIFO within a tie —
    independent of arrival order.  Driven directly against the enqueue
    plumbing so deadlines and enqueue times are exact, not wall-clock."""
    import heapq
    from collections import deque

    from deeplearning4j_tpu.serving.batcher import _Pending

    batcher = MicroBatcher(_net(), auto_start=False)
    evicted = []

    class _Recorder:
        def __init__(self, name):
            self.name = name

        def set(self):
            evicted.append(self.name)

    def enqueue(name, t_enqueue, deadline):
        req = _Pending(_x(1, seed=0))
        req.t_enqueue = t_enqueue
        req.deadline = deadline
        req.done = _Recorder(name)
        key = (req.x.shape[1:], str(req.x.dtype))
        with batcher._cv:
            batcher._queues.setdefault(key, deque()).append(req)
            batcher._seq += 1
            heapq.heappush(batcher._arrival_heap,
                           (req.t_enqueue, batcher._seq, key, req))
            heapq.heappush(batcher._deadline_heap,
                           (req.deadline, req.t_enqueue, batcher._seq,
                            key, req))
            batcher._pending += 1
            batcher._pending_by[req.priority] += 1
        return req

    # arrival order a, b, c, d — NOT the eviction order
    enqueue("a", t_enqueue=1.0, deadline=30.0)   # latest deadline
    enqueue("b", t_enqueue=2.0, deadline=10.0)   # deadline tie with c,
    enqueue("c", t_enqueue=3.0, deadline=10.0)   # broken by t_enqueue
    enqueue("d", t_enqueue=4.0, deadline=5.0)       # earliest deadline
    with batcher._cv:
        batcher._evict_expired_locked(now=20.0)  # a's deadline unexpired
    assert evicted == ["d", "b", "c"]
    assert batcher.queue_depth() == 1
    assert batcher.stats()["deadline_misses"] == 3
    # the survivor is still dispatchable: its heap entries are live
    with batcher._cv:
        assert batcher._earliest_deadline_locked() == 30.0
        assert batcher._oldest_key() is not None
    batcher.stop()
