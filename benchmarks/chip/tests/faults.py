"""Faults planted in the program under test, one at a time, for the
tests that show ``correct`` comes out false when the timed path is
broken underneath the harness."""
import contextlib

import jax.numpy as jnp


def _halve(data):
    import jax
    return jax.tree.map(lambda a: a[:, :, : a.shape[2] // 2], data)


@contextlib.contextmanager
def planted(name):
    """``None``: nothing planted. ``"unchanged"``: each round returns its
    state unchanged. ``"half_batch"``: each round sees only the first
    half of every device's samples, its means taken over the rest.
    ``"answer"``: the server's first answer of every batch is altered
    where it is produced."""
    from repro.core.algorithm import PerMFL
    from repro.serve.personalized import PersonalizedServer
    patches = []
    if name == "unchanged":
        patches.append((PerMFL, "round",
                        lambda self, state, data, **kw: state))
    elif name == "half_batch":
        orig = PerMFL.round
        patches.append((PerMFL, "round", lambda self, state, data, **kw:
                        orig(self, state, _halve(data), **kw)))
    elif name == "answer":
        orig_serve = PersonalizedServer.serve

        def serve(self, teams, devices, xs):
            out = orig_serve(self, teams, devices, xs)
            return out.at[0].set(jnp.roll(out[0], 1))
        patches.append((PersonalizedServer, "serve", serve))
    elif name is not None:
        raise ValueError(name)
    saved = [(cls, attr, getattr(cls, attr)) for cls, attr, _ in patches]
    try:
        for cls, attr, fn in patches:
            setattr(cls, attr, fn)
        yield
    finally:
        for cls, attr, fn in saved:
            setattr(cls, attr, fn)
