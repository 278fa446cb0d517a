"""Plain reference of PerMFL (Algorithm 1 of arXiv 2407.14251) at full
participation: straightforward jax.numpy over the (M teams, N devices)
stacks, independent of the program's round, kernels and engine.

One global round:
    w_i = x                                     (team models from global)
    K times:  theta_ij = w_i;  L prox-SGD steps
                  theta_ij -= alpha * (grad f_ij(theta_ij)
                                       + lam * (theta_ij - w_i))
              w_i = (1 - eta*lam - eta*gamma) w_i + eta*gamma x
                    + eta*lam * mean_j theta_ij
    x = (1 - beta*gamma) x + beta*gamma * mean_i w_i

``dtype`` is the precision of every parameter and input (bfloat16 for the
control); ``precision`` the matmul precision (HIGHEST for the reference).
``half_batch`` plants a fault: each device's gradient and loss use only
the first half of its samples."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def _bcast(tree, lead):
    return jax.tree.map(
        lambda a: jnp.broadcast_to(a.reshape((1,) * len(lead) + a.shape)
                                   if a.ndim == 0 else
                                   a[(None,) * len(lead)], lead + a.shape),
        tree)


def _expand(tree_m, n):
    """(M, ...) -> (M, N, ...) by repeating each team's leaf."""
    return jax.tree.map(
        lambda a: jnp.broadcast_to(a[:, None], (a.shape[0], n) + a.shape[1:]),
        tree_m)


def _mean(tree, axis):
    return jax.tree.map(lambda a: jnp.mean(a.astype(jnp.float32), axis=axis)
                        .astype(a.dtype), tree)


HPARAMS = ("alpha", "eta", "beta", "lam", "gamma")


def make_round(loss, hp: dict, *, k_team: int, l_local: int, m: int, n: int):
    """One PerMFL round on (x, data) for an M x N federation; returns
    (x, w, theta). ``loss(params, device_batch)`` is a device's loss;
    ``hp`` holds the float hyperparameters (traced or not)."""
    grad = jax.vmap(jax.vmap(jax.grad(loss)))
    alpha, lam, eta, beta, gamma = (hp[k] for k in
                                    ("alpha", "lam", "eta", "beta", "gamma"))

    def round_(x, data):
        def team_iter(_, carry):
            w, _ = carry
            anchor = _expand(w, n)

            def step(_, theta):
                g = grad(theta, data)
                return jax.tree.map(
                    lambda t, gg, a: (t - alpha * (gg + lam * (t - a)))
                    .astype(t.dtype), theta, g, anchor)

            theta = jax.lax.fori_loop(0, l_local, step, anchor)
            tbar = _mean(theta, 1)
            w = jax.tree.map(
                lambda wl, xl, tb: ((1 - eta * lam - eta * gamma) * wl
                                    + eta * gamma * xl[None]
                                    + eta * lam * tb).astype(wl.dtype),
                w, x, tbar)
            return w, theta

        w0 = _bcast(x, (m,))
        w, theta = jax.lax.fori_loop(0, k_team, team_iter,
                                     (w0, _bcast(x, (m, n))))
        wbar = _mean(w, 0)
        x = jax.tree.map(lambda xl, wb: ((1 - beta * gamma) * xl
                                         + beta * gamma * wb).astype(xl.dtype),
                         x, wbar)
        return x, w, theta

    return round_


def cast(tree, dtype):
    return jax.tree.map(
        lambda a: a.astype(dtype) if jnp.issubdtype(a.dtype, jnp.floating)
        else a, tree)


def half(data):
    """The fault of a batch half left out: keep each device's first half
    of its samples (axis 2), so means run over the rest."""
    return jax.tree.map(lambda a: a[:, :, : a.shape[2] // 2], data)


@functools.partial(jax.jit, static_argnames=(
    "loss", "k_team", "l_local", "m", "n", "rounds", "eval_every", "dtype",
    "half_batch"))
def run(params0, train, hp, *, loss, k_team, l_local, m, n, rounds,
        eval_every, dtype=jnp.float32, half_batch=False):
    """A whole experiment from ``params0``: returns (the mean device
    train loss after every ``eval_every`` rounds and after the last, the
    final (x, w, theta) in float32). ``hp`` maps HPARAMS to floats."""
    hp = {k: jnp.asarray(hp[k], dtype) for k in HPARAMS}
    x = cast(params0, dtype)
    tr = cast(train, dtype)
    fit = half(tr) if half_batch else tr
    round_ = make_round(loss, hp, k_team=k_team, l_local=l_local, m=m, n=n)
    eval_loss = jax.vmap(jax.vmap(loss))
    losses = []
    w = theta = None
    for r in range(1, rounds + 1):
        x, w, theta = round_(x, fit)
        if r % eval_every == 0 or r == rounds:
            losses.append(jnp.mean(eval_loss(theta, tr).astype(jnp.float32)))
    return jnp.stack(losses), cast((x, w, theta), jnp.float32)


@functools.partial(jax.jit, static_argnames=(
    "loss", "k_team", "l_local", "m", "c", "dtype", "half_batch"))
def cohort_round(x, data_c, hp, *, loss, k_team, l_local, m, c,
                 dtype=jnp.float32, half_batch=False):
    """One round at cohort width ``c`` on the gathered (M, c) data."""
    hp = {k: jnp.asarray(hp[k], dtype) for k in HPARAMS}
    round_ = make_round(loss, hp, k_team=k_team, l_local=l_local, m=m, n=c)
    data_c = cast(data_c, dtype)
    if half_batch:
        data_c = half(data_c)
    x, w, theta = round_(cast(x, dtype), data_c)
    return cast((x, w, theta), jnp.float32)
