"""The paper CNN's channels-major forward against a plain NHWC reference.

``paper_models.apply`` computes each 3x3 SAME convolution as one contraction
over a parity-major (channels, pixels x samples) layout and pools by taking
maxima of halves. The reference here is the textbook form:
``lax.conv_general_dilated`` and ``lax.reduce_window`` on NHWC arrays, at
HIGHEST precision. Logits and the per-device gradients that the federated
round takes (``vmap(vmap(grad(loss_fn)))`` over teams and devices) must
agree on every leaf.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from repro.configs.paper_cnn import CONFIG as CNN
from repro.models import paper_models

M, N = 2, 2


def _reference_apply(params, x):
    h = x
    i = 0
    while f"conv{i}" in params:
        h = lax.conv_general_dilated(
            h, params[f"conv{i}"]["w"], (1, 1), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            precision=lax.Precision.HIGHEST)
        h = jax.nn.relu(h + params[f"conv{i}"]["b"])
        h = lax.reduce_window(h, -jnp.inf, lax.max, (1, 2, 2, 1),
                              (1, 2, 2, 1), "VALID")
        i += 1
    h = h.reshape(h.shape[0], -1)
    j = 0
    while f"dense{j}" in params:
        h = jnp.dot(h, params[f"dense{j}"]["w"],
                    precision=lax.Precision.HIGHEST) + params[f"dense{j}"]["b"]
        if f"dense{j + 1}" in params:
            h = jax.nn.relu(h)
        j += 1
    return h


def _reference_loss(params, batch):
    logp = jax.nn.log_softmax(_reference_apply(params, batch["x"]), axis=-1)
    return -jnp.take_along_axis(logp, batch["y"][:, None], axis=-1).mean()


def _device_params(seed):
    """(M, N) stacked CNN parameters, each device's own, biases non-zero."""
    keys = jax.random.split(jax.random.PRNGKey(seed), M * N)
    trees = [paper_models.init_params(k, CNN) for k in keys]
    stacked = jax.tree.map(lambda *ls: jnp.stack(ls).reshape(
        (M, N) + ls[0].shape), *trees)
    noise = jax.random.PRNGKey(seed + 1)
    return jax.tree.map(
        lambda a: a + 0.05 * jax.random.normal(noise, a.shape), stacked)


def _images(b, seed):
    return jax.random.normal(jax.random.PRNGKey(seed),
                             (M, N, b) + CNN.input_shape)


def _ties(params, b):
    """Sparse images and negative first-layer biases: most 2x2 pool windows
    of both layers hold only relu zeros, the rest mix zeros and values."""
    rng = np.random.default_rng(5)
    x = np.zeros((M, N, b) + CNN.input_shape, np.float32)
    hit = rng.random(x.shape) < 0.03
    x[hit] = rng.normal(size=int(hit.sum())) * 3.0
    params = jax.tree.map(lambda a: a, params)
    params["conv0"]["b"] = jnp.full_like(params["conv0"]["b"], -0.5)
    params["conv1"]["b"] = jnp.full_like(params["conv1"]["b"], -0.2)
    return params, jnp.asarray(x)


def _zero_tie_windows(params, x):
    """Number of first-layer pool windows whose four relu outputs are 0."""
    h = lax.conv_general_dilated(
        x, params["conv0"]["w"], (1, 1), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=lax.Precision.HIGHEST)
    h = jax.nn.relu(h + params["conv0"]["b"])
    b, hh, ww, c = h.shape
    win = h.reshape(b, hh // 2, 2, ww // 2, 2, c).max(axis=(2, 4))
    return int((win == 0).sum()), int((win > 0).sum())


@jax.jit
def _program(params, x, batch):
    logits = jax.vmap(jax.vmap(
        lambda p, v: paper_models.apply(p, CNN, v)))(params, x)
    grads = jax.vmap(jax.vmap(jax.grad(
        lambda p, bt: paper_models.loss_fn(p, CNN, bt))))(params, batch)
    return logits, grads


@jax.jit
def _reference(params, x, batch):
    return (jax.vmap(jax.vmap(_reference_apply))(params, x),
            jax.vmap(jax.vmap(jax.grad(_reference_loss)))(params, batch))


@pytest.mark.parametrize("case", ["b1", "b5", "b12", "b36", "relu_zero_ties"])
def test_cnn_matches_nhwc_reference(case):
    params = _device_params(3)
    if case == "relu_zero_ties":
        params, x = _ties(params, 12)
        zeros, positive = _zero_tie_windows(
            jax.tree.map(lambda a: a[0, 0], params), x[0, 0])
        assert zeros > 0 and positive > 0
    else:
        b = int(case[1:])
        x = _images(b, b)
    y = jnp.arange(x.shape[2], dtype=jnp.int32) % CNN.num_classes
    y = jnp.broadcast_to(y, x.shape[:3])
    batch = {"x": x, "y": y}

    with jax.default_matmul_precision("highest"):
        logits, grads = _program(params, x, batch)
    ref_logits, ref_grads = _reference(params, x, batch)

    scale = float(jnp.abs(ref_logits).max())
    np.testing.assert_allclose(logits, ref_logits, rtol=0, atol=2e-6 * scale)
    for (path, g), r in zip(jax.tree_util.tree_leaves_with_path(grads),
                            jax.tree.leaves(ref_grads)):
        assert g.shape == r.shape, path
        np.testing.assert_allclose(
            g, r, rtol=0, atol=2e-5 * float(jnp.abs(r).max()) + 1e-9,
            err_msg=jax.tree_util.keystr(path))


def test_cnn_rejects_input_the_pools_cannot_halve():
    params = jax.tree.map(lambda a: a[0, 0], _device_params(0))
    with pytest.raises(ValueError, match="max-pools"):
        paper_models.apply(params, CNN, jnp.zeros((2, 30, 30, 1)))
