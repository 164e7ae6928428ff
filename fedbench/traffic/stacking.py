"""Client-stacking helpers shared by the dense generators (copies of the
program's ``data/partition.py:build_client_arrays`` and
``data/pipeline.py:split_client_holdout``, kept here so the benchmark's
traffic does not move when the program's data code does)."""
from __future__ import annotations

from typing import Sequence

import numpy as np


def build_client_arrays(x: np.ndarray, y: np.ndarray,
                        parts: Sequence[np.ndarray], rows: int):
    """Pack per-client rows into ``[N, rows, ...]`` stacks (rows past a
    client's count repeat its own rows; a client never holds more than
    ``rows``) and the ``[N]`` counts. The width is fixed by the traffic,
    not by the seed's partition, so every seed runs the same shapes."""
    n = len(parts)
    m = rows
    parts = [p[:m] for p in parts]
    xs = np.zeros((n, m) + x.shape[1:], dtype=x.dtype)
    ys = np.zeros((n, m) + y.shape[1:], dtype=y.dtype)
    counts = np.zeros((n,), dtype=np.int32)
    for i, p in enumerate(parts):
        counts[i] = len(p)
        if len(p):
            sel = np.tile(p, int(np.ceil(m / len(p))))[:m]
            xs[i], ys[i] = x[sel], y[sel]
    return xs, ys, counts


def split_holdout(xs, ys, counts, frac: float) -> dict:
    """Each client's tail ``frac`` of rows becomes its tester shard
    (tiled to the stack width), the rest its training shard."""
    n, m = xs.shape[0], xs.shape[1]
    n_test = np.maximum((counts * frac).astype(np.int32), 1)
    n_train = np.maximum(counts - n_test, 1)
    test_x, test_y = np.zeros_like(xs), np.zeros_like(ys)
    for i in range(n):
        seg_x = xs[i, int(n_train[i]):int(counts[i])]
        seg_y = ys[i, int(n_train[i]):int(counts[i])]
        reps = int(np.ceil(m / max(len(seg_x), 1)))
        test_x[i] = np.tile(seg_x, (reps,) + (1,) * (xs.ndim - 2))[:m]
        test_y[i] = np.tile(seg_y, (reps,) + (1,) * (ys.ndim - 2))[:m]
    return {"train_x": xs, "train_y": ys,
            "train_counts": n_train.astype(np.int32),
            "test_x": test_x, "test_y": test_y,
            "test_counts": n_test.astype(np.int32)}
