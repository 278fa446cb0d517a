"""Inputs made on the device from ``--seed``, in one jitted call each:
the federations' data and the serve cell's request images. The same seed
gives the same arrays; the program receives them as its inputs."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

HI = lax.Precision.HIGHEST


def seed_key(seed: int):
    """A PRNG key from any non-negative seed, however many bits it has:
    31 bits at a time are folded into a fixed root key."""
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.PRNGKey(0)
    while True:
        key = jax.random.fold_in(key, seed & 0x7FFFFFFF)
        seed >>= 31
        if not seed:
            return key


def int_seed(key) -> int:
    """A 31-bit integer drawn from ``key``, for entries that take an int."""
    return int(jax.random.randint(key, (), 0, 2**31 - 1))


@functools.partial(jax.jit, static_argnums=(1,))
def class_templates(key, shape):
    """Ten MNIST-shaped class templates that share a low-rank base and
    differ by a scaled deviation, as ``repro.data.synthetic`` draws
    them: any two classes are easy to tell apart, all ten are not."""
    h, w, _ = shape
    rank, sep = 6, 0.35
    kb, kv, ku, kw = jax.random.split(key, 4)
    ub = jax.random.normal(kb, (h, rank))
    vb = jax.random.normal(kv, (rank, w))
    u = ub + sep * jax.random.normal(ku, (10, h, rank))
    v = vb + sep * jax.random.normal(kw, (10, rank, w))
    return jnp.tanh(jnp.einsum("chr,crw->chw", u, v, precision=HI)
                    / jnp.sqrt(rank))


def _images(key, labels, templates, noise):
    x = templates[labels] + noise * jax.random.normal(
        key, labels.shape + templates.shape[1:])
    return x[..., None]


@functools.partial(jax.jit, static_argnames=(
    "m", "n", "samples", "n_val", "shape", "noise"))
def label_skew_images(key, *, m, n, samples, n_val, shape, noise):
    """An (M, N) federation of MNIST-shaped images with two classes per
    device, half its samples each, in a per-device random order (the
    paper's label-skew partition). The first ``n_val`` samples of each
    device are its validation set. Returns ``(train, val)`` dicts of
    ``x`` (M, N, S, H, W, 1) f32 and ``y`` (M, N, S) i32."""
    kt, k1, k2, kp, kx = jax.random.split(key, 5)
    templates = class_templates(kt, shape)
    c1 = jax.random.randint(k1, (m, n), 0, 10)
    c2 = (c1 + jax.random.randint(k2, (m, n), 1, 10)) % 10
    pick = jnp.arange(samples) % 2 == 0
    y = jnp.where(pick, c1[..., None], c2[..., None])
    order = jnp.argsort(jax.random.uniform(kp, (m, n, samples)), axis=-1)
    y = jnp.take_along_axis(y, order, axis=-1).astype(jnp.int32)
    x = _images(kx, y, templates, noise)
    return ({"x": x[:, :, n_val:], "y": y[:, :, n_val:]},
            {"x": x[:, :, :n_val], "y": y[:, :, :n_val]})


@functools.partial(jax.jit, static_argnames=("count", "shape", "noise"))
def image_pool(key, *, count, shape, noise):
    """``count`` single images of random classes: the serve cell's
    request payloads."""
    kt, ky, kx = jax.random.split(key, 3)
    templates = class_templates(kt, shape)
    y = jax.random.randint(ky, (count,), 0, 10)
    return _images(kx, y, templates, noise)


@functools.partial(jax.jit, static_argnames=(
    "m", "n", "samples", "n_val", "dim", "shift"))
def virtual_tabular(key, *, m, n, samples, n_val, dim, shift):
    """The cohort-scale tabular federation of
    ``repro.data.synthetic.virtual_tabular``, drawn on the device: a
    shared linear labelling concept, team-shifted feature means, device
    jitter, and per-feature scales j^-0.6. Train and validation samples
    are drawn apart, so the full (M, N, S, dim) array never exists."""
    kw, kc, km, kv, kt, ks = jax.random.split(key, 6)
    w = jax.random.normal(kw, (dim, 10))
    c = jax.random.normal(kc, (10,))
    scale = jnp.arange(1, dim + 1, dtype=jnp.float32) ** -0.6
    mu = shift * jax.random.normal(km, (m, 1, 1, dim))
    v = mu + 0.1 * jax.random.normal(kv, (m, n, 1, dim))

    def part(k, s):
        x = v + jax.random.normal(k, (m, n, s, dim)) * scale
        y = jnp.argmax(jnp.einsum("mnsd,dc->mnsc", x, w, precision=HI) + c,
                       axis=-1).astype(jnp.int32)
        return {"x": x, "y": y}

    return part(kt, samples - n_val), part(ks, n_val)
