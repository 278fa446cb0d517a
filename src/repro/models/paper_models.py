"""The paper's own learning models: MCLR, 2-layer CNN, 2-hidden-layer DNN.

MCLR (multinomial logistic regression with l2) is the strongly-convex model
of Theorem 1 — its loss is (l2_reg)-strongly convex and smooth, so the
linear-rate validation tests run against it. The CNN/DNN cover Theorem 2's
smooth non-convex setting, matching §4 of the paper.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import PaperModelConfig


def init_params(key, cfg: PaperModelConfig, dtype=jnp.float32):
    if cfg.kind == "mclr":
        d = int(jnp.prod(jnp.array(cfg.input_shape)))
        return {"w": jnp.zeros((d, cfg.num_classes), dtype),
                "b": jnp.zeros((cfg.num_classes,), dtype)}
    if cfg.kind == "dnn":
        dims = [int(jnp.prod(jnp.array(cfg.input_shape)))] + \
            list(cfg.hidden) + [cfg.num_classes]
        ks = jax.random.split(key, len(dims) - 1)
        return {f"layer{i}": {
            "w": (jax.random.normal(ks[i], (dims[i], dims[i + 1])) *
                  jnp.sqrt(2.0 / dims[i])).astype(dtype),
            "b": jnp.zeros((dims[i + 1],), dtype)}
            for i in range(len(dims) - 1)}
    if cfg.kind == "cnn":
        h, w, c_in = cfg.input_shape
        chans = [c_in] + list(cfg.conv_channels)
        ks = jax.random.split(key, len(chans) + 1)
        p = {}
        for i in range(len(chans) - 1):
            fan_in = 9 * chans[i]
            p[f"conv{i}"] = {
                "w": (jax.random.normal(ks[i], (3, 3, chans[i], chans[i + 1]))
                      * jnp.sqrt(2.0 / fan_in)).astype(dtype),
                "b": jnp.zeros((chans[i + 1],), dtype)}
        # two 2x2 maxpools -> spatial /4
        flat = (h // 4) * (w // 4) * chans[-1]
        dims = [flat] + list(cfg.hidden) + [cfg.num_classes]
        for i in range(len(dims) - 1):
            p[f"dense{i}"] = {
                "w": (jax.random.normal(ks[len(chans) + i - 1],
                                        (dims[i], dims[i + 1])) *
                      jnp.sqrt(2.0 / dims[i])).astype(dtype),
                "b": jnp.zeros((dims[i + 1],), dtype)}
        return p
    raise ValueError(cfg.kind)


# The CNN runs channels-major: each activation is (channels, rows x
# columns x samples), so the lane (minor) axis is one merged spatial and
# sample axis of 784B, 196B or 49B elements at any per-model batch B. The
# NHWC im2col form it replaced left XLA nothing wide to put on the lanes
# under vmap(vmap(grad)) over (M, N) with a weight set per device: it laid
# the 36-image batch on the 128 lanes, so the compiled gradient's large
# arrays held 3.6x their bytes in lane padding, and the gradient was
# bandwidth-bound on padding. On a TPU v5e this layout takes the gradient
# of 8 x 32 devices x 36 images from 66.3 to 26.5 ms a step (PERF.md).
#
# The merged axis is kept parity-major, one level per max-pool still ahead.
# At depth d a pixel (2^d qh + rh, 2^d qw + rw) sits in chunk (rh0, rw0,
# rh1, rw1, ...) of the low bits, outermost first, at (qh, qw, sample)
# inside it. A 2x2 max-pool is then the max of the axis' two halves, twice,
# and leaves depth d - 1; a 3x3 tap moves whole chunks and slides inside
# them.


def _chunk(rh: int, rw: int, depth: int) -> int:
    """Index of the chunk of pixels whose low bits are (rh, rw)."""
    j = 0
    for i in range(depth):
        j = 4 * j + 2 * (rh >> i & 1) + (rw >> i & 1)
    return j


def _slide(a, k: int):
    """out[..., i] = a[..., i + k], zero where i + k falls outside."""
    if k == 0:
        return a
    n = a.shape[-1]
    a = jnp.pad(a, [(0, 0)] * (a.ndim - 1) + [(max(-k, 0), max(k, 0))])
    return a[..., max(k, 0):max(k, 0) + n]


def _parity_major(x, depth: int):
    """(B, H, W, C) -> (C, H W B) in the order above."""
    b, h, w, c = x.shape
    x = x.reshape((b, h >> depth) + (2,) * depth + (w >> depth,)
                  + (2,) * depth + (c,))
    bits = [ax for i in range(depth)
            for ax in (1 + depth - i, 2 + 2 * depth - i)]
    return x.transpose([3 + 2 * depth] + bits + [1, 2 + depth, 0]).reshape(
        c, h * w * b)


def _taps(a, depth: int, hq: int, wq: int, b: int):
    """(C, L) at ``depth`` -> (9 C, L): the 3x3 SAME neighbourhood of each
    pixel, taps in (dy, dx, c) order, zero outside the image."""
    n = 2 ** depth
    chunks = jnp.split(a, n * n, axis=-1)
    order = sorted(((rh, rw) for rh in range(n) for rw in range(n)),
                   key=lambda r: _chunk(*r, depth))
    qw = jnp.arange(hq * wq * b) // b % wq
    taps = []
    for dy in range(3):
        for dx in range(3):
            parts = []
            for rh, rw in order:
                ch, rh = divmod(rh + dy - 1, n)
                cw, rw = divmod(rw + dx - 1, n)
                part = _slide(chunks[_chunk(rh, rw, depth)],
                              (ch * wq + cw) * b)
                if cw:
                    part = jnp.where((qw + cw >= 0) & (qw + cw < wq), part, 0)
                parts.append(part)
            taps.append(jnp.concatenate(parts, axis=-1))
    return jnp.concatenate(taps, axis=0)


def _cnn_features(params, x):
    """Conv blocks of the CNN: x (B, H, W, C) -> (B, features) in (h, w, c)
    order. Each 3x3 SAME conv is one (taps x c_in) contraction. A pool's
    gradient splits between tied maxima (``reduce_window`` gives it to the
    first); after relu, ties are zeros, where relu's gradient is 0."""
    n_conv = sum(1 for k in params if k.startswith("conv"))
    b, hh, ww, _ = x.shape
    if hh % 2 ** n_conv or ww % 2 ** n_conv:
        raise ValueError(f"cnn input {hh}x{ww} is not divisible by "
                         f"2^{n_conv} for its {n_conv} max-pools")
    h = _parity_major(x, n_conv)
    for i in range(n_conv):
        depth = n_conv - i
        w = params[f"conv{i}"]["w"]                  # (3, 3, cin, cout)
        patches = _taps(h, depth, hh >> depth, ww >> depth, b)
        h = jnp.einsum("ko,kl->ol",
                       w.reshape(9 * w.shape[2], w.shape[3]), patches)
        h = jax.nn.relu(h + params[f"conv{i}"]["b"][:, None])
        h = jnp.maximum(*jnp.split(h, 2, axis=-1))  # rows
        h = jnp.maximum(*jnp.split(h, 2, axis=-1))  # columns
        hh, ww = hh // 2, ww // 2
    return h.reshape(-1, hh, ww, b).transpose(3, 1, 2, 0).reshape(b, -1)


def apply(params, cfg: PaperModelConfig, x):
    """x: (b, *input_shape) -> logits (b, num_classes)."""
    if cfg.kind == "mclr":
        xf = x.reshape(x.shape[0], -1)
        return xf @ params["w"] + params["b"]
    if cfg.kind == "dnn":
        h = x.reshape(x.shape[0], -1)
        n = len(params)
        for i in range(n):
            h = h @ params[f"layer{i}"]["w"] + params[f"layer{i}"]["b"]
            if i < n - 1:
                h = jax.nn.relu(h)
        return h
    if cfg.kind == "cnn":
        h = _cnn_features(params, x)
        j = 0
        while f"dense{j}" in params:
            h = h @ params[f"dense{j}"]["w"] + params[f"dense{j}"]["b"]
            if f"dense{j + 1}" in params:
                h = jax.nn.relu(h)
            j += 1
        return h
    raise ValueError(cfg.kind)


def loss_fn(params, cfg: PaperModelConfig, batch):
    """Mean CE (+ l2 for the strongly-convex MCLR)."""
    logits = apply(params, cfg, batch["x"])
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, batch["y"][:, None], axis=-1).mean()
    if cfg.l2_reg > 0.0:
        sq = sum(jnp.vdot(a, a) for a in jax.tree.leaves(params))
        nll = nll + 0.5 * cfg.l2_reg * sq
    return nll


def accuracy(params, cfg: PaperModelConfig, batch):
    logits = apply(params, cfg, batch["x"])
    return (jnp.argmax(logits, -1) == batch["y"]).mean()
