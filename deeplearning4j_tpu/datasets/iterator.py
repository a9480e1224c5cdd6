"""DataSetIterator family.

Parity: reference `datasets/iterator/DataSetIterator.java:54` (batch(),
totalExamples(), inputColumns(), reset(), cursor) and the wrappers in
`datasets/iterator/` — `ListDataSetIterator`, `SamplingDataSetIterator`,
`MultipleEpochsIterator`, `ReconstructionDataSetIterator`,
`MovingWindowBaseDataSetIterator`, and the test-support
`TestDataSetIterator` (`datasets/test/TestDataSetIterator.java`).
"""

from __future__ import annotations

import queue
import threading
import time
from typing import Iterator, List, NamedTuple, Optional

import numpy as np

from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.reliability import faults
from deeplearning4j_tpu.utils.profiling import span


class DataSetIterator:
    """Abstract batch iterator over a dataset."""

    def __init__(self, batch_size: int, total_examples: int):
        self.batch_size = batch_size
        self._total = total_examples
        self.cursor = 0

    # contract ------------------------------------------------------------
    def total_examples(self) -> int:
        return self._total

    def batch(self) -> int:
        return self.batch_size

    def input_columns(self) -> int:
        raise NotImplementedError

    def total_outcomes(self) -> int:
        raise NotImplementedError

    def reset(self) -> None:
        self.cursor = 0

    def has_next(self) -> bool:
        return self.cursor < self._total

    def next(self, num: Optional[int] = None) -> DataSet:
        raise NotImplementedError

    # pythonic ------------------------------------------------------------
    def __iter__(self) -> Iterator[DataSet]:
        self.reset()
        while self.has_next():
            yield self.next()

    def __next__(self) -> DataSet:
        if not self.has_next():
            raise StopIteration
        return self.next()


class ListDataSetIterator(DataSetIterator):
    """Batches over an in-memory DataSet (ListDataSetIterator parity)."""

    def __init__(self, data: DataSet, batch_size: int = 10):
        super().__init__(batch_size, data.num_examples())
        self.data = data

    def input_columns(self) -> int:
        return self.data.num_inputs()

    def total_outcomes(self) -> int:
        return self.data.num_outcomes()

    def next(self, num: Optional[int] = None) -> DataSet:
        # `if num is None`, not `num or ...`: a falsy num=0 must mean an
        # empty batch, not silently substitute the full batch size
        n = self.batch_size if num is None else num
        out = self.data.get(slice(self.cursor, self.cursor + n))
        # advance by the rows actually served, so a ragged final slice
        # reports its true length (prefetch bucket selection and cursor
        # accounting key on real rows, not the requested batch size)
        self.cursor += out.num_examples()
        return out


class SamplingDataSetIterator(DataSetIterator):
    """Random-with-replacement sampling batches (SamplingDataSetIterator)."""

    def __init__(self, data: DataSet, batch_size: int, total_batches: int,
                 seed: int = 123):
        super().__init__(batch_size, total_batches * batch_size)
        self.data = data
        self._rng = np.random.RandomState(seed)

    def input_columns(self) -> int:
        return self.data.num_inputs()

    def total_outcomes(self) -> int:
        return self.data.num_outcomes()

    def next(self, num: Optional[int] = None) -> DataSet:
        n = self.batch_size if num is None else num
        idx = self._rng.choice(self.data.num_examples(), size=n)
        self.cursor += n
        return self.data.get(idx)


class MultipleEpochsIterator(DataSetIterator):
    """Replays an underlying iterator for N epochs (MultipleEpochsIterator)."""

    def __init__(self, epochs: int, base: DataSetIterator):
        super().__init__(base.batch_size, base.total_examples() * epochs)
        self.epochs = epochs
        self.base = base
        self._epoch = 0

    def input_columns(self) -> int:
        return self.base.input_columns()

    def total_outcomes(self) -> int:
        return self.base.total_outcomes()

    def reset(self) -> None:
        super().reset()
        self._epoch = 0
        self.base.reset()

    def has_next(self) -> bool:
        if self.base.has_next():
            return self._epoch < self.epochs
        return self._epoch + 1 < self.epochs

    def next(self, num: Optional[int] = None) -> DataSet:
        if not self.base.has_next():
            self.base.reset()
            self._epoch += 1
        out = self.base.next(num)
        self.cursor += out.num_examples()
        return out


class ReconstructionDataSetIterator(DataSetIterator):
    """Serves each batch with labels := features, turning any iterator into
    an autoencoder/RBM pretraining stream
    (`datasets/iterator/ReconstructionDataSetIterator.java:46-49`:
    `ret.setLabels(ret.getFeatureMatrix())`)."""

    def __init__(self, base: DataSetIterator):
        super().__init__(base.batch_size, base.total_examples())
        self.base = base

    def input_columns(self) -> int:
        return self.base.input_columns()

    def total_outcomes(self) -> int:
        # reconstruction target = the features themselves
        return self.base.input_columns()

    def reset(self) -> None:
        super().reset()
        self.base.reset()

    def has_next(self) -> bool:
        return self.base.has_next()

    def next(self, num: Optional[int] = None) -> DataSet:
        d = self.base.next(num)
        self.cursor = self.base.cursor
        return DataSet(d.features, np.array(d.features, copy=True))


def moving_window_dataset(data: DataSet, window_rows: int,
                          window_cols: int, rotate: bool = True) -> DataSet:
    """Tile every image into all non-overlapping window_rows x window_cols
    patches (plus, when square, their 90/180/270-degree rotations), each
    labeled with the source image's label.

    Capability parity with `util/MovingWindowMatrix.java` +
    `iterator/impl/MovingWindowDataSetFetcher.java` (window extraction +
    addRotate augmentation), redesigned for static shapes: the reference
    merges wr*wc-column windows with the H*W-column originals into one
    DataSet (ragged rows); here every row is a window of one homogeneous
    shape, which is what an XLA-compiled conv stack can consume."""
    n, d = data.features.shape
    side = int(round(d ** 0.5))
    if side * side != d:
        raise ValueError(f"features ({d} columns) are not square images")
    if side % window_rows or side % window_cols:
        raise ValueError(f"{side}x{side} images do not tile into "
                         f"{window_rows}x{window_cols} windows")
    imgs = data.features.reshape(n, side // window_rows, window_rows,
                                 side // window_cols, window_cols)
    # [n, tiles, wr, wc]
    tiles = imgs.transpose(0, 1, 3, 2, 4).reshape(
        n, -1, window_rows, window_cols)
    variants = [tiles]
    if rotate and window_rows == window_cols:
        for k in (1, 2, 3):
            variants.append(np.rot90(tiles, k=k, axes=(2, 3)))
    stacked = np.concatenate(variants, axis=1)          # [n, v*tiles, wr, wc]
    per_img = stacked.shape[1]
    feats = np.ascontiguousarray(stacked).reshape(
        n * per_img, window_rows * window_cols)
    labels = np.repeat(data.labels, per_img, axis=0)
    return DataSet(feats.astype(np.float32), labels)


class MovingWindowBaseDataSetIterator(ListDataSetIterator):
    """Batches over the moving-window augmentation of `data`
    (`datasets/iterator/MovingWindowBaseDataSetIterator.java` wiring a
    MovingWindowDataSetFetcher)."""

    def __init__(self, data: DataSet, window_rows: int, window_cols: int,
                 batch_size: int = 10, rotate: bool = True):
        super().__init__(
            moving_window_dataset(data, window_rows, window_cols, rotate),
            batch_size)


class TestDataSetIterator(DataSetIterator):
    """Wraps any iterator, recording what was served (test support parity)."""

    def __init__(self, base: DataSetIterator):
        super().__init__(base.batch_size, base.total_examples())
        self.base = base
        self.served: List[DataSet] = []

    def input_columns(self) -> int:
        return self.base.input_columns()

    def total_outcomes(self) -> int:
        return self.base.total_outcomes()

    def reset(self) -> None:
        super().reset()
        self.base.reset()

    def has_next(self) -> bool:
        return self.base.has_next()

    def next(self, num: Optional[int] = None) -> DataSet:
        d = self.base.next(num)
        self.served.append(d)
        self.cursor = self.base.cursor
        return d


class DeviceBatch(NamedTuple):
    """A (features, labels) pair already resident on (or in flight to)
    the device.  Quacks like a DataSet for every training/eval consumer
    (`MultiLayerNetwork._as_batches`, the bucketed eval loop) without
    `DataSet.__init__`'s `np.asarray`, which would drag the arrays back
    to the host."""

    features: object
    labels: object

    def num_examples(self) -> int:
        return int(self.features.shape[0])


class PrefetchIterator:
    """Async host→device input pipeline (ROADMAP: host-side prefetch).

    Wraps any iterable of batches — a `DataSetIterator`, a list of
    `DataSet`s, or a generator of (features, labels) pairs — and runs
    `jax.device_put` one or more batches AHEAD of the consumer on a
    background thread, so the compiled train step / bucketed eval loop
    never waits on host→device transfer (the input-feed stall Jouppi et
    al. single out as the top non-compute cost on TPU serving).

    Design:
      - bounded queue (`buffer_batches`) so prefetch never races more
        than a few batches of HBM ahead of the consumer;
      - the worker parks on a timed `put` and re-checks a stop event, so
        an early `break` / `close()` can never deadlock it against a
        full queue;
      - worker exceptions are caught, queued in order, and re-raised at
        the consumer's matching `next()` — batches already produced are
        still served first;
      - `close()` (also via context manager / generator finalization)
        shuts the worker down and joins it.

    Iterating again after exhaustion or `close()` restarts the pipeline
    (resetting the underlying iterator when it supports `reset()`).
    """

    _DONE = "done"
    _ERROR = "error"
    _ITEM = "item"

    def __init__(self, base, buffer_batches: Optional[int] = None,
                 device=None, to_device: bool = True):
        from deeplearning4j_tpu.optimize import tunables

        self.base = base
        # None -> the "data.prefetch_depth" tunable (registry default 2)
        if buffer_batches is None:
            buffer_batches = tunables.resolve("data.prefetch_depth")
        self.buffer_batches = max(1, int(buffer_batches))
        self.device = device
        self.to_device = to_device
        self._queue: Optional[queue.Queue] = None
        self._thread: Optional[threading.Thread] = None
        self._stop = threading.Event()
        self._lock = threading.Lock()

    # -- transfer ----------------------------------------------------------
    def _transfer(self, item):
        if not self.to_device:
            return item
        import jax

        put = (jax.device_put if self.device is None
               else lambda a: jax.device_put(a, self.device))
        if hasattr(item, "features") and hasattr(item, "labels"):
            return DeviceBatch(put(item.features), put(item.labels))
        if isinstance(item, tuple):
            return tuple(put(a) for a in item)
        return put(item)

    # -- worker ------------------------------------------------------------
    def _put(self, q: queue.Queue, stop: threading.Event, msg) -> bool:
        """Queue `msg`, parking in bounded slices so a stopped consumer
        releases the worker instead of deadlocking it against a full
        queue."""
        while not stop.is_set():
            try:
                q.put(msg, timeout=0.05)
                return True
            except queue.Full:
                continue
        return False

    def _worker(self, q: queue.Queue, stop: threading.Event) -> None:
        try:
            items = iter(self.base)
            while True:
                with span("prefetch.next"):     # the base iterator's time
                    try:
                        item = next(items)
                    except StopIteration:
                        break
                if stop.is_set():
                    return
                # armed faults simulate a worker crash mid-epoch; the
                # exception rides the ERROR message to exactly one consumer
                faults.fire("prefetch.worker")
                with span("prefetch.transfer"):
                    item = self._transfer(item)
                if not self._put(q, stop, (self._ITEM, item)):
                    return
            self._put(q, stop, (self._DONE, None))
        except BaseException as e:  # noqa: BLE001 — re-raised at next()
            self._put(q, stop, (self._ERROR, e))

    def _start_locked(self) -> None:
        self.close()  # tear down any previous run
        if hasattr(self.base, "reset"):
            self.base.reset()
        self._stop = threading.Event()
        self._queue = queue.Queue(maxsize=self.buffer_batches)
        self._thread = threading.Thread(
            target=self._worker, args=(self._queue, self._stop),
            name="dl4j-prefetch", daemon=True)
        self._thread.start()

    def start(self) -> None:
        """(Re)start the pipeline; `__iter__` / the first `pull()` call
        this automatically."""
        with self._lock:
            self._start_locked()

    # -- consumer ----------------------------------------------------------
    def pull(self):
        """Return the next prefetched batch; thread-safe.

        Any number of consumer threads may call this against one running
        pipeline — each batch is delivered to exactly one of them.  Raises
        StopIteration at end-of-stream (re-queuing the DONE marker so every
        concurrent consumer terminates) or when `close()` is called
        mid-iteration; a worker error is raised at exactly one consumer and
        stops the rest.  Consumers always park on a timed get and re-check
        the stop event, so a cross-thread `close()` can never strand a
        blocked consumer."""
        with self._lock:
            if self._queue is None:
                self._start_locked()
            q, stop = self._queue, self._stop
        # what the consumer waits for the worker: about nothing while the
        # queue holds a batch, the base iterator's time when it does not
        with span("prefetch.wait", depth=q.qsize()):
            while True:
                if stop.is_set():
                    raise StopIteration
                try:
                    kind, payload = q.get(timeout=0.05)
                    break
                except queue.Empty:
                    continue
        if kind == self._ITEM:
            return payload
        if kind == self._ERROR:
            stop.set()  # terminal: release the other consumers too
            raise payload
        # DONE: put it back so every other consumer also terminates
        # (worker has exited, so the freed slot can't be re-filled)
        try:
            q.put_nowait((self._DONE, None))
        except queue.Full:
            pass
        raise StopIteration

    def __iter__(self):
        self.start()
        try:
            while True:
                try:
                    yield self.pull()
                except StopIteration:
                    break
        finally:
            self.close()

    def reset(self) -> None:
        """DataSetIterator-style reset: stop the pipeline; the next
        iteration restarts it (and resets the wrapped iterator)."""
        self.close()

    def close(self, join_timeout: float = 5.0) -> None:
        """Stop the worker and join it (idempotent; safe mid-iteration,
        including from a thread other than the consumer's)."""
        self._stop.set()
        thread, self._thread = self._thread, None
        q, self._queue = self._queue, None
        if thread is not None:
            # drain so a worker parked on a full queue sees the stop flag
            deadline = time.monotonic() + join_timeout
            while thread.is_alive() and time.monotonic() < deadline:
                if q is not None:
                    try:
                        q.get_nowait()
                    except queue.Empty:
                        pass
                thread.join(timeout=0.05)
            # a worker wedged inside the wrapped iterable (e.g. a data
            # source blocked on I/O) is abandoned as a daemon rather than
            # blocking shutdown: stop is set, so it exits the moment its
            # blocking call returns

    def __enter__(self) -> "PrefetchIterator":
        return self

    def __exit__(self, *exc) -> None:
        self.close()
