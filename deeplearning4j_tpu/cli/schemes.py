"""Input URI-scheme loaders.

Parity: reference `cli/api/schemes/` + `cli/files/FileScheme` — map an
`--input` string onto a DataSet. Supported:
  - builtin datasets: `mnist[:n]`, `iris[:n]`, `lfw[:n]`, `curves[:n]`
  - csv files: `csv:/path/to/file.csv[:label_col]` or a bare `*.csv` path
"""

from __future__ import annotations

import numpy as np

from typing import Optional

from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.datasets.fetchers import (
    Cifar10DataFetcher, CSVDataFetcher, CurvesDataFetcher, IrisDataFetcher,
    LFWDataFetcher, MnistDataFetcher)

_BUILTIN_DEFAULT_N = {"mnist": 10000, "iris": 150, "lfw": 1000,
                      "curves": 1000, "cifar10": 10000}


def load_input(uri: str, label_column: int = -1,
               num_examples: Optional[int] = None) -> DataSet:
    """Resolve an --input URI to a DataSet."""
    scheme, _, rest = uri.partition(":")
    scheme = scheme.lower()

    if scheme in _BUILTIN_DEFAULT_N:
        n = num_examples or (int(rest) if rest else _BUILTIN_DEFAULT_N[scheme])
        fetcher = {"mnist": MnistDataFetcher, "iris": IrisDataFetcher,
                   "lfw": LFWDataFetcher, "curves": CurvesDataFetcher,
                   "cifar10": Cifar10DataFetcher}[scheme]()
        return fetcher.fetch(n)

    if scheme == "csv" or uri.endswith(".csv"):
        if scheme == "csv":
            # split at the LAST colon, and only when the suffix is an
            # integer, so paths containing ':' (drive letters, timestamps)
            # survive
            path, _, col = rest.rpartition(":")
            if path and col.lstrip("-").isdigit():
                lc = int(col)
            else:
                path, lc = rest, label_column
        else:
            path, lc = uri, label_column
        data = CSVDataFetcher(path, label_column=lc).fetch(
            num_examples or int(1e9))
        return data

    if scheme == "text":
        # text:<path>[:seq_len] -> char-LM DataSet: features [B, T, V]
        # one-hot windows, labels [B*T, V] next-char targets (the shape
        # char_lstm's rnn_to_ff output stage consumes), T label rows to
        # an example; ds.vocab_size and ds.char_index carry the
        # vocabulary for --zoo auto-sizing
        path, _, slen = rest.rpartition(":")
        if path and slen.isdigit():
            seq_len = int(slen)
        else:
            path, seq_len = rest, 32
        with open(path, encoding="utf-8", errors="replace") as f:
            textdata = f.read()
        chars = sorted(set(textdata))
        idx = {c: i for i, c in enumerate(chars)}
        v = len(chars)
        ids = np.asarray([idx[c] for c in textdata], np.int32)
        n_win = (len(ids) - 1) // seq_len
        if num_examples:
            n_win = min(n_win, num_examples)
        if n_win < 1:
            raise ValueError(f"text input too short for seq_len={seq_len}")
        xs = ids[:n_win * seq_len].reshape(n_win, seq_len)
        ys = ids[1:n_win * seq_len + 1].reshape(n_win, seq_len)
        eye = np.eye(v, dtype=np.float32)
        ds = DataSet(eye[xs], eye[ys.reshape(-1)], label_rows=seq_len)
        ds.vocab_size = v
        ds.char_index = idx
        return ds

    raise ValueError(
        f"unrecognized --input '{uri}': expected mnist/iris/lfw/curves, "
        "csv:<path>[:label_col], text:<path>[:seq_len], or a .csv path")
