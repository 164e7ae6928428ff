"""The CNN's 2x2 max-pool (``repro.models.cnn._maxpool2``) against the
rank-6 reshape formulation it replaced, kept here as the oracle: the same
values and the same gradients through ``relu(x + b)``, alone, under a
client ``vmap`` and in the whole model; and no reshape into ``(2, C)``
minor dimensions in the lowered training step."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import cnn
from repro.models.common import softmax_cross_entropy


def reshape_pool(x):
    """The oracle: pad an odd H or W with -inf, reshape to
    [B, H/2, 2, W/2, 2, C] and take the max over the two window axes."""
    B, H, W, C = x.shape
    ph, pw = (-H) % 2, (-W) % 2
    if ph or pw:
        x = jnp.pad(x, ((0, 0), (0, ph), (0, pw), (0, 0)),
                    constant_values=-jnp.inf)
    H2, W2 = x.shape[1] // 2, x.shape[2] // 2
    return x.reshape(B, H2, 2, W2, 2, C).max(axis=(2, 4))


def oracle_forward(p, cfg, images):
    """``cnn_forward`` as it was with the reshape pool."""
    x = images
    for i in range(len(cfg.cnn_channels)):
        x = jax.lax.conv_general_dilated(
            x, p[f"conv{i}"]["w"], window_strides=(1, 1), padding="SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
        x = reshape_pool(jax.nn.relu(x + p[f"conv{i}"]["b"]))
    x = x.reshape(x.shape[0], -1)
    x = jax.nn.relu(x @ p["fc1"]["w"] + p["fc1"]["b"])
    return x @ p["fc2"]["w"] + p["fc2"]["b"]


def block_input(key, shape):
    """Pre-activations [..., H, W, C] with ties: every fourth 2x2 window
    is negative throughout, so ReLU turns it into four equal zeros."""
    x = jax.random.normal(key, shape)
    hh, ww = np.meshgrid(np.arange(shape[-3]) // 2,
                         np.arange(shape[-2]) // 2, indexing="ij")
    dead = jnp.asarray(((hh + ww) % 4 == 0)[:, :, None])
    return jnp.where(dead, -1.0 - jnp.abs(x), x)


@pytest.mark.parametrize("clients", [None, 3], ids=["unbatched", "vmap3"])
@pytest.mark.parametrize("C", [32, 64])
@pytest.mark.parametrize("H", [32, 7])
def test_pool_matches_reshape_pool_bitwise(H, C, clients):
    lead = () if clients is None else (clients,)
    kx, kb, kw = jax.random.split(jax.random.PRNGKey(H * 100 + C), 3)
    x = block_input(kx, lead + (4, H, H, C))
    b = 0.1 * jax.random.normal(kb, lead + (C,))
    H2 = (H + 1) // 2
    w = jax.random.normal(kw, lead + (4, H2, H2, C))

    def run(pool):
        def fwd(x, b):
            return pool(jax.nn.relu(x + b))

        def loss(x, b, w):
            return jnp.sum(fwd(x, b) * w)

        y, f = fwd, jax.value_and_grad(loss, argnums=(0, 1))
        if clients is not None:
            y, f = jax.vmap(y), jax.vmap(f)
        return jax.jit(y)(x, b), jax.jit(f)(x, b, w)

    (y, (v, (gx, gb))), (y0, (v0, (gx0, gb0))) = run(cnn._maxpool2), \
        run(reshape_pool)
    assert y.shape == lead + (4, H2, H2, C)
    for a, a0 in ((y, y0), (v, v0), (gx, gx0), (gb, gb0)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(a0))
    # ties at zero are there, and the gradient is not all zero
    assert float(jnp.sum(y == 0)) > 0
    assert float(jnp.sum(gx != 0)) > 0


# 24 -> 12 -> 6 -> 3: the flatten's reshapes end in (3, C), not (2, C)
TINY = get_config("fedtest-cnn").replace(cnn_channels=(8, 16, 16),
                                         cnn_hidden=32, image_size=24)


def client_step_hlo(cfg, clients=3, batch=4):
    """The lowered loss and gradient of ``cnn_forward`` vmapped over
    clients with their own weights, as ``LocalBackend.train`` runs it."""
    params = jax.vmap(lambda k: cnn.init_cnn(cfg, k))(
        jax.random.split(jax.random.PRNGKey(0), clients))
    imgs = jnp.zeros((clients, batch, cfg.image_size, cfg.image_size,
                      cfg.image_channels))
    labels = jnp.zeros((clients, batch), jnp.int32)

    def loss(p, x, y):
        return softmax_cross_entropy(cnn.cnn_forward(p, cfg, x), y).mean()

    step = jax.vmap(jax.value_and_grad(loss))
    return jax.jit(step).lower(params, imgs, labels).as_text()


def pool_reshapes(hlo: str, channels):
    """Result shapes of the reshapes whose two minor dimensions are
    ``(2, C)`` for a channel count C of the model."""
    out = []
    for m in re.finditer(r"stablehlo\.reshape .*-> tensor<([0-9x]+)x\w+>",
                         hlo):
        dims = tuple(int(d) for d in m.group(1).split("x"))
        if len(dims) >= 2 and dims[-2] == 2 and dims[-1] in channels:
            out.append(dims)
    return out


def test_no_reshape_into_pool_windows(monkeypatch):
    channels = set(TINY.cnn_channels)
    assert pool_reshapes(client_step_hlo(TINY), channels) == []
    # the guard sees the reshape pool where it is brought back
    monkeypatch.setattr(cnn, "_maxpool2", reshape_pool)
    assert pool_reshapes(client_step_hlo(TINY), channels)


@pytest.mark.parametrize("arch", ["fedtest-cnn", "fedtest-cnn-mnist"])
def test_whole_model_matches_reshape_pool(arch):
    """At the configuration's widths, 3 clients x 8 images: the loss and
    every leaf's gradient agree with the reshape-pool model."""
    cfg = get_config(arch)
    clients, batch = 3, 8
    kp, kb, kx, ky = jax.random.split(jax.random.PRNGKey(7), 4)
    params = jax.vmap(lambda k: cnn.init_cnn(cfg, k))(
        jax.random.split(kp, clients))
    # nonzero biases, so that bias, ReLU and pool all act
    for i, layer in enumerate(params.values()):
        layer["b"] = 0.05 * jax.random.normal(jax.random.fold_in(kb, i),
                                              layer["b"].shape)
    imgs = jax.random.normal(kx, (clients, batch, cfg.image_size,
                                  cfg.image_size, cfg.image_channels))
    labels = jax.random.randint(ky, (clients, batch), 0, cfg.num_classes)

    def run(forward):
        def loss(p, x, y):
            return softmax_cross_entropy(forward(p, cfg, x), y).mean()
        return jax.jit(jax.vmap(jax.value_and_grad(loss)))(
            params, imgs, labels)

    (v, g), (v0, g0) = run(cnn.cnn_forward), run(oracle_forward)
    np.testing.assert_allclose(np.asarray(v), np.asarray(v0), rtol=1e-6)
    leaves, leaves0 = (jax.tree_util.tree_leaves_with_path(t)
                       for t in (g, g0))
    for (path, a), (_, a0) in zip(leaves, leaves0):
        a, a0 = np.asarray(a), np.asarray(a0)
        scale = np.abs(a0).max()
        assert scale > 0, path
        np.testing.assert_allclose(a, a0, rtol=1e-6, atol=1e-6 * scale,
                                   err_msg=jax.tree_util.keystr(path))
