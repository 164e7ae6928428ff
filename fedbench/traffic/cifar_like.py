"""CIFAR-shaped class-conditional images split non-IID over the clients.

A copy of the program's ``data/synthetic.py:make_image_dataset`` (smooth
per-class prototypes, random shifts, Gaussian noise) and
``data/partition.py:paper_noniid_partition`` (each client holds a random
2-6 classes with a random share of each), as
``data/builders.py:make_federated_image_dataset`` assembles them.
"""
from __future__ import annotations

import numpy as np

from fedbench.traffic.stacking import build_client_arrays, split_holdout


def _smooth(x: np.ndarray, k: int) -> np.ndarray:
    for axis in (0, 1):
        acc = np.zeros_like(x)
        for d in range(-k, k + 1):
            acc += np.roll(x, d, axis=axis)
        x = acc / (2 * k + 1)
    return x


def images(num: int, *, size: int, channels: int, classes: int,
           noise: float, shift: int, smooth: int, seed: int):
    rng = np.random.default_rng(seed)
    protos = rng.normal(size=(classes, size, size, channels))
    protos = np.stack([_smooth(p, smooth) for p in protos])
    protos /= protos.std(axis=(1, 2, 3), keepdims=True) + 1e-8
    labels = rng.integers(0, classes, size=num)
    shifts = rng.integers(-shift, shift + 1, size=(num, 2))
    x = protos[labels]
    for i in range(num):
        x[i] = np.roll(x[i], tuple(shifts[i]), axis=(0, 1))
    x = x + rng.normal(scale=noise, size=x.shape)
    return x.astype(np.float32), labels.astype(np.int32)


def paper_partition(labels, num_users: int, min_classes: int,
                    max_classes: int, seed: int):
    rng = np.random.default_rng(seed)
    num_classes = int(labels.max()) + 1
    by_class = [np.flatnonzero(labels == c) for c in range(num_classes)]
    for idx in by_class:
        rng.shuffle(idx)
    cursors = np.zeros(num_classes, dtype=int)
    user_classes = [list(rng.choice(num_classes,
                                    size=rng.integers(min_classes,
                                                      max_classes + 1),
                                    replace=False))
                    for _ in range(num_users)]
    for c in range(num_classes):        # every class has a holder
        if not any(c in ucs for ucs in user_classes):
            user_classes[int(rng.integers(num_users))].append(c)
    parts = [[] for _ in range(num_users)]
    for c in range(num_classes):
        holders = [u for u in range(num_users) if c in user_classes[u]]
        if not holders:
            continue
        pool = by_class[c]
        share = len(pool) // len(holders)
        for u in holders:
            lo = cursors[c]
            take = min(max(int(share * rng.uniform(0.4, 1.0)), 1),
                       len(pool) - lo)
            parts[u].extend(pool[lo:lo + take])
            cursors[c] += take
    return [np.array(sorted(p), dtype=np.int64) for p in parts]


def make(params: dict, seed: int) -> dict:
    p = params
    n, g = p["num_samples"], p["global_test"]
    x, y = images(n + g, size=p["image_size"], channels=p["channels"],
                  classes=p["num_classes"], noise=p["noise"],
                  shift=p["shift"], smooth=p["smooth"], seed=seed)
    gx, gy, x, y = x[n:], y[n:], x[:n], y[:n]
    n_server = int(n * p["server_frac"])
    sx, sy, x, y = x[:n_server], y[:n_server], x[n_server:], y[n_server:]
    parts = paper_partition(y, p["num_users"], p["min_classes"],
                            p["max_classes"], seed + 1)
    xs, ys, counts = build_client_arrays(x, y, parts, p["stack_rows"])
    out = split_holdout(xs, ys, counts, p["holdout_frac"])
    out.update(global_x=gx, global_y=gy, server_x=sx, server_y=sy)
    return out
