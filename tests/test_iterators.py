"""Iterator-wrapper tests: ReconstructionDataSetIterator and
MovingWindowBaseDataSetIterator (VERDICT r3 missing #3)."""

import numpy as np
import pytest

from deeplearning4j_tpu.datasets.dataset import DataSet, labels_to_one_hot
from deeplearning4j_tpu.datasets.iterator import (
    ListDataSetIterator, MovingWindowBaseDataSetIterator,
    ReconstructionDataSetIterator, SamplingDataSetIterator,
    moving_window_dataset)


def _ds(n=12, d=16, classes=3, seed=0):
    rng = np.random.RandomState(seed)
    return DataSet(rng.rand(n, d).astype(np.float32),
                   labels_to_one_hot(rng.randint(0, classes, n), classes))


def _sequence_ds(n=5, t=3, v=4):
    """Example i holds the ids i*t .. i*t+t-1, and label row r starts at
    r*v: an example's label rows are recognisable from its features."""
    feats = np.arange(n * t).reshape(n, t)
    labels = np.arange(n * t * v).reshape(n * t, v)
    return DataSet(feats, labels, label_rows=t), feats, labels


def _assert_rows_follow_examples(ds, v=4):
    np.testing.assert_array_equal(ds.labels[:, 0], ds.features.reshape(-1) * v)


def test_batches_of_sequence_data_keep_their_label_rows():
    """The `text:` scheme carries T label rows per example ([B*T, V]):
    mini-batches must take the matching run of label rows, or `cli train
    --properties batch=N` trains a char model on the wrong targets."""
    ds, feats, labels = _sequence_ds()
    n, t = feats.shape
    batches = ds.batch_by(2)
    assert [b.features.shape[0] for b in batches] == [2, 2, 1]
    assert [b.labels.shape[0] for b in batches] == [2 * t, 2 * t, t]
    np.testing.assert_array_equal(
        np.concatenate([b.labels for b in batches]), labels)
    np.testing.assert_array_equal(batches[1].labels, labels[2 * t:4 * t])
    it = ListDataSetIterator(ds, 4)
    np.testing.assert_array_equal(next(it).labels, labels[:4 * t])
    # row-per-example data slices as before
    plain = DataSet(feats, labels[:n]).batch_by(2)
    np.testing.assert_array_equal(plain[2].labels, labels[4:5])
    with pytest.raises(ValueError, match="label rows"):
        DataSet(feats, labels[:n], label_rows=t)


def test_shuffled_sequence_data_keeps_its_label_rows():
    """Index arrays, not only slices: shuffle, split, sample, the sampling
    iterator and per-example iteration all take an example's own rows."""
    ds, feats, _ = _sequence_ds()
    shuffled = ds.shuffle(seed=7)
    assert not np.array_equal(shuffled.features, feats)
    train, test = ds.split_test_and_train(3, seed=7)
    sampled = SamplingDataSetIterator(ds, 4, total_batches=2).next()
    picked = [shuffled, train, test, ds.sample(8), sampled,
              shuffled.batch_by(2)[1], DataSet.merge([test, train]),
              ds.copy(), *ds]
    for d in picked:
        assert d.label_rows == ds.label_rows
        _assert_rows_follow_examples(d)
    assert (train.num_examples(), test.num_examples()) == (3, 2)


def test_text_scheme_records_label_rows(tmp_path):
    from deeplearning4j_tpu.cli.schemes import load_input

    path = tmp_path / "corpus.txt"
    path.write_text("abcdefgh" * 8)
    ds = load_input(f"text:{path}:4")
    assert ds.label_rows == 4
    shuffled = ds.shuffle(seed=1)
    # every window's targets are its own ids shifted by one
    x = shuffled.features.argmax(-1)
    y = shuffled.labels.argmax(-1).reshape(x.shape)
    np.testing.assert_array_equal(y[:, :-1], x[:, 1:])
    np.testing.assert_array_equal(y[:, -1], (x[:, -1] + 1) % 8)


def test_reconstruction_iterator_sets_labels_to_features():
    data = _ds()
    it = ReconstructionDataSetIterator(ListDataSetIterator(data, 5))
    batches = list(it)
    assert sum(b.num_examples() for b in batches) == 12
    for b in batches:
        np.testing.assert_array_equal(b.labels, b.features)
        assert b.labels is not b.features  # a copy, not an alias
    assert it.total_outcomes() == it.input_columns() == 16
    # reset replays identically
    it.reset()
    again = list(it)
    np.testing.assert_array_equal(again[0].features, batches[0].features)


def test_moving_window_tiles_and_rotations():
    # one 4x4 image with distinct quadrant values, 2x2 windows
    img = np.array([[1, 1, 2, 2],
                    [1, 1, 2, 2],
                    [3, 3, 4, 4],
                    [3, 3, 4, 4]], np.float32).reshape(1, 16)
    data = DataSet(img, labels_to_one_hot([1], 2))
    out = moving_window_dataset(data, 2, 2, rotate=False)
    # 4 tiles, each constant-valued (the MovingWindowMatrix.java docstring
    # example: 1 1 2 2 / 3 3 4 4 quadrants -> flattened windows)
    assert out.features.shape == (4, 4)
    tile_vals = sorted(set(out.features.ravel().tolist()))
    assert tile_vals == [1.0, 2.0, 3.0, 4.0]
    for row in out.features:
        assert len(set(row.tolist())) == 1
    # every window inherits the source label
    np.testing.assert_array_equal(out.labels,
                                  np.repeat(data.labels, 4, axis=0))

    # addRotate=true quadruples the windows (90/180/270 variants)
    rot = moving_window_dataset(data, 2, 2, rotate=True)
    assert rot.features.shape == (16, 4)


def test_moving_window_iterator_batches():
    rng = np.random.RandomState(1)
    data = DataSet(rng.rand(6, 36).astype(np.float32),
                   labels_to_one_hot(rng.randint(0, 2, 6), 2))
    it = MovingWindowBaseDataSetIterator(data, 3, 3, batch_size=8)
    total = it.total_examples()
    assert total == 6 * 4 * 4  # 4 tiles x 4 rotation variants per image
    served = sum(b.num_examples() for b in it)
    assert served == total
    assert it.input_columns() == 9


def test_moving_window_rejects_non_tiling_shapes():
    import pytest

    data = _ds(n=2, d=16)
    with pytest.raises(ValueError):
        moving_window_dataset(data, 3, 3)  # 4x4 doesn't tile into 3x3
    with pytest.raises(ValueError):
        moving_window_dataset(_ds(n=2, d=15), 3, 3)  # not square


# -- PrefetchIterator threading contract (serving gateway shares these
# idioms: bounded queue, timed waits + stop event, in-order error
# propagation, cross-thread shutdown) ------------------------------------

def _prefetch_items(n, rows=2):
    return [(np.full((rows, 3), i, np.float32),
             np.full((rows, 1), i, np.float32)) for i in range(n)]


def test_prefetch_concurrent_consumers_partition_the_stream():
    import threading

    from deeplearning4j_tpu.datasets.iterator import PrefetchIterator

    items = _prefetch_items(40)
    it = PrefetchIterator(items, buffer_batches=2, to_device=False)
    it.start()
    got, lock = [], threading.Lock()

    def consume():
        while True:
            try:
                feats, _ = it.pull()
            except StopIteration:
                return
            with lock:
                got.append(int(feats[0, 0]))

    threads = [threading.Thread(target=consume) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=10.0)
        assert not t.is_alive(), "consumer failed to terminate"
    # every batch delivered exactly once across all consumers
    assert sorted(got) == list(range(40))
    it.close()


def test_prefetch_cross_thread_close_unblocks_parked_consumer():
    import threading
    import time

    from deeplearning4j_tpu.datasets.iterator import PrefetchIterator

    stall = threading.Event()

    def slow_gen():
        yield (np.zeros((1, 2), np.float32), np.zeros((1, 1), np.float32))
        stall.wait(timeout=30.0)  # producer hangs: consumer must park

    it = PrefetchIterator(slow_gen(), to_device=False)
    served = []

    def consume():
        for feats, _ in it:
            served.append(feats)

    t = threading.Thread(target=consume)
    t.start()
    deadline = time.time() + 5.0
    while not served and time.time() < deadline:
        time.sleep(0.01)
    assert served, "first batch never arrived"
    # close from another thread, while the consumer is parked on get and
    # the producer is still wedged: the consumer must be released and
    # close() must not block on the wedged worker
    it.close(join_timeout=0.2)
    t.join(timeout=5.0)
    assert not t.is_alive(), "close() stranded a blocked consumer"
    stall.set()


def test_prefetch_worker_error_releases_all_consumers():
    import threading

    from deeplearning4j_tpu.datasets.iterator import PrefetchIterator

    def bad_gen():
        yield (np.zeros((1, 2), np.float32), np.zeros((1, 1), np.float32))
        raise RuntimeError("boom")

    it = PrefetchIterator(bad_gen(), to_device=False)
    it.start()
    outcomes, lock = [], threading.Lock()

    def consume():
        try:
            while True:
                it.pull()
        except RuntimeError as e:
            with lock:
                outcomes.append(("error", str(e)))
        except StopIteration:
            with lock:
                outcomes.append(("stop", None))

    threads = [threading.Thread(target=consume) for _ in range(3)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=10.0)
        assert not t.is_alive(), "worker error left a consumer blocked"
    # the error surfaces at exactly one consumer; the rest stop cleanly
    assert sorted(o[0] for o in outcomes) == ["error", "stop", "stop"]
    assert ("error", "boom") in outcomes
    it.close()


def test_prefetch_restarts_after_midstream_break():
    from deeplearning4j_tpu.datasets.iterator import PrefetchIterator

    data = _ds(n=12)
    it = PrefetchIterator(ListDataSetIterator(data, 4), to_device=False)
    first = next(iter(it))  # break mid-iteration (generator finalized)
    assert first.num_examples() == 4
    # a fresh iteration restarts from the top and serves everything
    assert sum(b.num_examples() for b in it) == 12
