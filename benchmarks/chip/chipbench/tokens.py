"""Token streams made on the device from ``--seed``: the LM federation's
data. Each team has a topic, and a topic is a Zipf bigram chain over the
vocabulary slice (as ``repro.data.tokens.zipf_bigram_stream`` draws it):
every token has four successors, taken with probabilities 0.5, 0.25,
0.15 and 0.1, and with probability 0.1 the chain restarts at a token
drawn from a Zipf(1.3) marginal. Successor tables differ by topic, so
devices of one team share their statistics and teams differ (the LM
analogue of label skew)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

SUCCESSOR_P = (0.5, 0.25, 0.15, 0.1)
RESTART_P = 0.1
ZIPF_A = 1.3


@functools.partial(jax.jit, static_argnames=(
    "m", "n", "seqs", "n_val", "seq_len", "vocab"))
def topic_streams(key, *, m, n, seqs, n_val, seq_len, vocab):
    """An (M, N) federation of ``seqs`` sequences of ``seq_len`` tokens a
    device, team i on topic i; the last ``n_val`` sequences of each
    device are its validation set. Returns ``(train, val)`` dicts of
    ``x`` (tokens) and ``y`` (the next tokens), (M, N, S, T) int32."""
    ks, k0, kr, kz, kc = jax.random.split(key, 5)
    succ = jax.random.randint(ks, (m, vocab, len(SUCCESSOR_P)), 0, vocab)
    shape = (m, n, seqs)
    zipf = -ZIPF_A * jnp.log(jnp.arange(1, vocab + 1, dtype=jnp.float32))
    steps = seq_len                   # tokens after the first
    restart = jax.random.bernoulli(kr, RESTART_P, (steps,) + shape)
    fresh = jax.random.categorical(kz, zipf, shape=(steps,) + shape)
    pick = jax.random.categorical(kc, jnp.log(jnp.asarray(SUCCESSOR_P)),
                                  shape=(steps,) + shape)
    team = jnp.arange(m)[:, None, None]

    def step(tok, draw):
        r, f, c = draw
        nxt = jnp.where(r, f, succ[team, tok, c])
        return nxt, nxt

    first = jax.random.randint(k0, shape, 0, vocab)
    _, rest = jax.lax.scan(step, first, (restart, fresh, pick))
    toks = jnp.concatenate([first[None], rest], 0)          # (T+1, M, N, S)
    toks = jnp.moveaxis(toks, 0, -1).astype(jnp.int32)
    data = {"x": toks[..., :-1], "y": toks[..., 1:]}
    return (jax.tree.map(lambda a: a[:, :, :seqs - n_val], data),
            jax.tree.map(lambda a: a[:, :, seqs - n_val:], data))
