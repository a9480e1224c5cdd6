import numpy as np

from benchmark import traffic


def take(gen, n):
    return [next(gen) for _ in range(n)]


def test_every_seed_gets_the_same_sizes_in_another_order():
    mix = traffic.load("decode-closed-8")
    a = take(traffic.requests(mix, 50257, 1), mix["cycle"])
    b = take(traffic.requests(mix, 50257, 2**31 + 7), mix["cycle"])
    for key in (lambda r: len(r["prompt"]), lambda r: r["max_new"]):
        assert sorted(map(key, a)) == sorted(map(key, b))
        assert list(map(key, a)) != list(map(key, b))
    assert min(len(r["prompt"]) for r in a) >= 32 and max(r["max_new"] for r in a) <= 256


def test_same_seed_same_traffic():
    mix = traffic.load("pretrain-2k", rehearse=True)
    x1, y1 = next(traffic.token_batches(mix, 512, 9))
    x2, y2 = next(traffic.token_batches(mix, 512, 9))
    assert np.array_equal(x1, x2) and np.array_equal(y1, y2)
    assert np.array_equal(x1[:, 1:].reshape(-1), y1.reshape(x1.shape)[:, :-1].reshape(-1))
    assert not np.array_equal(x1[0], x1[1])


def test_open_loop_gaps_have_the_stated_rate():
    mix = dict(traffic.load("decode-closed-8"),
               arrival={"loop": "open", "clients": 8, "rate_per_s": 4.0})
    gaps = [r["gap_s"] for r in take(traffic.requests(mix, 100, 3), mix["cycle"])]
    assert abs(np.mean(gaps) - 0.25) < 0.01
