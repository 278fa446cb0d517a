"""The numbers that decide ``correct``, each computed the same way for
the program's output and for a control, and held against its limit.

Training cells compare what a call returns at each of its eval points
and at its end:

* ``loss_gap``: the largest relative gap between the program's mean
  device train loss and the reference's, over the eval points;
* ``change_gap``: over the leaves of every tier (global x, team w,
  device theta), the largest gap between the norm of the program's
  change from the initial model and the reference's, over the larger of
  the reference's norm of that leaf and the median leaf's. A leaf whose
  reference change is under a thousandth of the median leaf's moves by
  round-off alone and is left out.

Norms, not differences: at the TPU's default matmul precision a CNN's
device models drift apart element by element from any other rounding of
the same arithmetic, while the size of each leaf's change does not.

The serve cell compares the served answers: ``logit_gap`` is the widest
gap by which the reference's logit of the class the server put first
lies below the reference's best logit."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def leaf_names(tree, prefix: str):
    return [prefix + "/" + "/".join(str(getattr(k, "key", k)) for k in path)
            for path, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]


@jax.jit
def _norms(tree, base):
    return [jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32)
                                        - b.astype(jnp.float32))))
            for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(base))]


def change_norms(x, w, theta, params0) -> dict:
    """{tier/leaf: |final - initial|} for x (unstacked), w (M, ...) and
    theta (M, N, ...) against the initial model ``params0``."""
    out = {}
    for name, tree in (("x", x), ("w", w), ("theta", theta)):
        lead = jax.tree.leaves(tree)[0].ndim - jax.tree.leaves(params0)[0].ndim
        base = jax.tree.map(
            lambda p, t: jnp.broadcast_to(p, t.shape), params0, tree) \
            if lead else params0
        vals = [float(v) for v in _norms(tree, base)]
        out.update(zip(leaf_names(tree, name), vals))
    return out


@jax.jit
def _stacked_norms(tree, base):
    return [jnp.sqrt(jnp.sum(jnp.square(
        a.astype(jnp.float32) - b.reshape(b.shape[:1] + (1,) * (a.ndim - b.ndim)
                                          + b.shape[1:]).astype(jnp.float32)),
        axis=tuple(range(1, a.ndim))))
        for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(base))]


def stacked_change_norms(x, w, theta, params0) -> dict:
    """``change_norms`` for S configurations at once: every tree has a
    leading (S,) axis; returns {tier/leaf: (S,) numpy array}."""
    out = {}
    for name, tree in (("x", x), ("w", w), ("theta", theta)):
        vals = [np.asarray(v) for v in _stacked_norms(tree, params0)]
        out.update(zip(leaf_names(tree, name), vals))
    return out


def loss_gap(prog, ref) -> float:
    prog, ref = np.asarray(prog, np.float64), np.asarray(ref, np.float64)
    if prog.shape != ref.shape:
        raise ValueError(f"eval points differ: {prog.shape} vs {ref.shape}")
    if not np.all(np.isfinite(prog)):
        return float("inf")
    return float(np.max(np.abs(prog - ref) / np.abs(ref)))


def leaf_gaps(prog: dict, ref: dict) -> dict:
    """{leaf: its gap} for every leaf ``change_gap`` keeps."""
    med = float(np.median(list(ref.values())))
    return {k: (abs(prog[k] - r) / max(r, med) if np.isfinite(prog[k])
                else float("inf"))
            for k, r in ref.items() if r >= 1e-3 * med}


def change_gap(prog: dict, ref: dict) -> float:
    """The worst leaf's gap."""
    return max(leaf_gaps(prog, ref).values())


def logit_gap(served, ref) -> float:
    """served, ref: (R, classes) logits of the same requests."""
    served, ref = np.asarray(served, np.float64), np.asarray(ref, np.float64)
    if not np.all(np.isfinite(served)):
        return float("inf")
    top = ref.max(axis=1)
    picked = ref[np.arange(len(ref)), served.argmax(axis=1)]
    return float(np.max(top - picked))


def verdict(numbers: dict, limits: dict):
    """(correct, {name: {"value", "limit"}}): every number at or under
    its limit; a missing or non-finite number fails."""
    checks, ok = {}, True
    for name, lim in limits.items():
        v = numbers.get(name)
        good = v is not None and np.isfinite(v) and v <= lim
        ok = ok and good
        checks[name] = {"value": v, "limit": lim}
    return ok, checks
