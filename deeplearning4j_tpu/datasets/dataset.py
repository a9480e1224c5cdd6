"""DataSet — (features, labels) pair.

Parity: ND4J `org.nd4j.linalg.dataset.DataSet` as consumed throughout the
reference (65 imports): merge, normalization, binarization, shuffle,
`splitTestAndTrain`, batching, `numExamples`.  Host-side numpy (data prep
stays off-device; arrays move to TPU only inside jitted steps).
"""

from __future__ import annotations

from typing import Iterable, List, Sequence, Tuple

import numpy as np


class DataSet:
    """`label_rows` is how many label rows belong to one example: 1 for
    row-per-example data, T for sequence targets (the `text:` scheme's
    [B*T, V] next-token rows).  Every way of picking examples takes each
    example's own label rows with it."""

    def __init__(self, features, labels=None, label_rows: int = 1):
        self.features = np.asarray(features)
        self.labels = (np.asarray(labels) if labels is not None
                       else np.zeros((len(self.features), 0), np.float32))
        self.label_rows = int(label_rows)
        want = self.label_rows * self.features.shape[0]
        if self.label_rows != 1 and self.labels.shape[0] != want:
            raise ValueError(
                f"{self.features.shape[0]} examples of {self.label_rows} "
                f"label rows each need {want} label rows, got "
                f"{self.labels.shape[0]}")

    # -- basics ------------------------------------------------------------
    def num_examples(self) -> int:
        return int(self.features.shape[0])

    def num_inputs(self) -> int:
        return int(np.prod(self.features.shape[1:]))

    def num_outcomes(self) -> int:
        return int(self.labels.shape[-1]) if self.labels.ndim > 1 else 0

    def __len__(self) -> int:
        return self.num_examples()

    def __iter__(self):
        for i in range(self.num_examples()):
            yield self.get(slice(i, i + 1))

    def _like(self, features, labels) -> "DataSet":
        return DataSet(features, labels, self.label_rows)

    def get(self, idx) -> "DataSet":
        """The examples `idx` names (a slice or an index array) with
        their label rows."""
        if self.label_rows == 1:
            return self._like(self.features[idx], self.labels[idx])
        tail = self.labels.shape[1:]
        per_example = self.labels.reshape(
            self.num_examples(), self.label_rows, *tail)
        return self._like(self.features[idx],
                          per_example[idx].reshape(-1, *tail))

    def copy(self) -> "DataSet":
        return self._like(self.features.copy(), self.labels.copy())

    # -- transforms --------------------------------------------------------
    @staticmethod
    def merge(datasets: Sequence["DataSet"]) -> "DataSet":
        return datasets[0]._like(
            np.concatenate([d.features for d in datasets], axis=0),
            np.concatenate([d.labels for d in datasets], axis=0),
        )

    def shuffle(self, seed: int = 123) -> "DataSet":
        rng = np.random.RandomState(seed)
        return self.get(rng.permutation(self.num_examples()))

    def normalize_zero_mean_unit_variance(self) -> "DataSet":
        mean = self.features.mean(axis=0, keepdims=True)
        std = self.features.std(axis=0, keepdims=True) + 1e-6
        return self._like((self.features - mean) / std, self.labels)

    def scale_to_unit(self) -> "DataSet":
        mx = np.abs(self.features).max() or 1.0
        return self._like(self.features / mx, self.labels)

    def binarize(self, threshold: float = 0.0) -> "DataSet":
        return self._like((self.features > threshold).astype(np.float32),
                          self.labels)

    def split_test_and_train(self, n_train: int, seed: int = 123
                             ) -> Tuple["DataSet", "DataSet"]:
        shuffled = self.shuffle(seed)
        return shuffled.get(slice(0, n_train)), shuffled.get(slice(n_train, None))

    def batch_by(self, batch_size: int) -> List["DataSet"]:
        return [self.get(slice(i, i + batch_size))
                for i in range(0, self.num_examples(), batch_size)]

    def sample(self, n: int, seed: int = 123) -> "DataSet":
        rng = np.random.RandomState(seed)
        idx = rng.choice(self.num_examples(), size=n, replace=n > self.num_examples())
        return self.get(idx)


def labels_to_one_hot(labels: Iterable[int], n_classes: int) -> np.ndarray:
    labels = np.asarray(list(labels), np.int64)
    out = np.zeros((len(labels), n_classes), np.float32)
    out[np.arange(len(labels)), labels] = 1.0
    return out
