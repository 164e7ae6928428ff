"""Data pipeline: partition properties, batch sampling, synthetic sets."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.data import (
    CIFAR_LIKE, MNIST_LIKE, ClientData, dirichlet_partition,
    make_federated_image_dataset, make_image_dataset, make_token_stream,
    paper_noniid_partition, sample_client_batches)
from repro.data.partition import build_client_arrays


@settings(max_examples=15, deadline=None)
@given(num_users=st.integers(2, 10), seed=st.integers(0, 1000))
def test_paper_partition_disjoint(num_users, seed):
    labels = np.random.default_rng(seed).integers(0, 10, size=600)
    parts = paper_noniid_partition(labels, num_users, seed=seed)
    seen = np.concatenate(parts) if parts else np.array([])
    assert len(seen) == len(set(seen.tolist()))          # disjoint
    assert all((p >= 0).all() and (p < 600).all() for p in parts)


def test_paper_partition_is_noniid():
    labels = np.random.default_rng(0).integers(0, 10, size=5000)
    parts = paper_noniid_partition(labels, 10, min_classes=2, max_classes=4,
                                   seed=0)
    for p in parts:
        classes = set(labels[p].tolist())
        assert 1 <= len(classes) <= 4                    # skewed classes


@settings(max_examples=10, deadline=None)
@given(alpha=st.sampled_from([0.1, 0.5, 5.0]), seed=st.integers(0, 100))
def test_dirichlet_partition_covers_everything(alpha, seed):
    labels = np.random.default_rng(seed).integers(0, 10, size=800)
    parts = dirichlet_partition(labels, 6, alpha=alpha, seed=seed)
    seen = sorted(np.concatenate(parts).tolist())
    assert seen == list(range(800))                      # exact cover


def test_build_client_arrays_counts():
    x = np.arange(40, dtype=np.float32).reshape(20, 2)
    y = np.arange(20, dtype=np.int32)
    parts = [np.array([0, 1, 2]), np.array([5]), np.arange(10, 18)]
    xs, ys, counts = build_client_arrays(x, y, parts)
    assert xs.shape[0] == 3 and xs.shape[1] == 8
    np.testing.assert_array_equal(counts, [3, 1, 8])
    np.testing.assert_array_equal(ys[1][:1], [5])


def test_sample_batches_respect_counts():
    xs = jnp.arange(3 * 10).reshape(3, 10, 1).astype(jnp.float32)
    ys = jnp.arange(3 * 10).reshape(3, 10)
    counts = jnp.array([2, 10, 5], jnp.int32)
    data = ClientData(xs, ys, counts)
    bx, by = sample_client_batches(jax.random.PRNGKey(0), data, steps=4,
                                   batch=16)
    assert bx.shape == (3, 4, 16, 1)
    # client 0 only ever sees its first 2 rows
    assert set(np.asarray(by[0]).ravel().tolist()) <= {0, 1}
    # client 2 only its first 5
    assert set(np.asarray(by[2]).ravel().tolist()) <= {20, 21, 22, 23, 24}


def test_synthetic_images_are_class_separable():
    """A nearest-prototype classifier must beat chance by a wide margin —
    otherwise the convergence experiments would be meaningless."""
    x, y = make_image_dataset(MNIST_LIKE, 600, seed=0)
    protos = np.stack([x[y == c].mean(0) for c in range(10)])
    dists = ((x[:, None] - protos[None]) ** 2).sum(axis=(2, 3, 4))
    acc = (dists.argmin(1) == y).mean()
    assert acc > 0.55, acc


def test_cifar_like_is_harder_than_mnist_like():
    accs = {}
    for name, spec in [("m", MNIST_LIKE), ("c", CIFAR_LIKE)]:
        x, y = make_image_dataset(spec, 600, seed=1)
        protos = np.stack([x[y == c].mean(0) for c in range(10)])
        dists = ((x[:, None] - protos[None]) ** 2).sum(axis=(2, 3, 4))
        accs[name] = (dists.argmin(1) == y).mean()
    assert accs["c"] < accs["m"]


def test_token_stream_bigram_structure():
    toks, topics = make_token_stream(97, 50, 64, num_topics=4, seed=0,
                                     noise=0.0)
    # noise-free stream follows next = prev * a + b (mod V) exactly
    assert toks.shape == (50, 64)
    diffs_consistent = 0
    for i in range(10):
        t = toks[i]
        # affine consistency: (t2 - t1*a) constant — check determinism by
        # regenerating
        toks2, _ = make_token_stream(97, 50, 64, num_topics=4, seed=0,
                                     noise=0.0)
        diffs_consistent += (toks2[i] == t).all()
    assert diffs_consistent == 10


def test_federated_dataset_shapes():
    data = make_federated_image_dataset(MNIST_LIKE, 6, num_samples=900,
                                        global_test=100, seed=0)
    assert data.train.num_clients == 6
    assert data.global_x.shape[0] == 100
    assert data.server_x.shape[0] == 90
    assert int(data.train.counts.min()) >= 1
    assert int(data.test.counts.min()) >= 1
