"""Driver of the serving cells: ``PersonalizedServer.serve`` in front of
an int8 ``ModelStore`` of every device's personalized model, fed by an
open-loop generator.

Arrivals follow a Poisson schedule fixed from the seed before the
window opens. Each tick takes the requests that are due (up to the
largest batch), pads them to the smallest of the cell's batch sizes that
holds them (every size compiled in set-up), and serves them. A request
is timed from when it was due until its batch's answers are ready, so a
stall counts against every request queued behind it. The batcher is the
harness's stand-in for a server-side scheduler, which the program lacks.

The store is exported with ``ModelStore.from_state`` from a PerMFL state
drawn from the seed, one team at a time (a whole population of f32
device models would not fit beside its export). Device models are
``w_t + scale * noise`` with noise keyed by (team, device), so the
reference can draw any one of them again on its own."""
from __future__ import annotations

import functools
import gc
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from chipbench import compare, fl, traffic
from chipbench.data import image_pool, seed_key
from chipbench.harness import Run


def _scale(leaf):
    return jnp.maximum(jnp.std(leaf), 0.01)


def _jitter(key, tree, scale_of, factor):
    """``tree`` + factor x (the scale of the matching leaf of
    ``scale_of``) x unit noise, one key per leaf."""
    leaves, tdef = jax.tree.flatten(tree)
    keys = jax.random.split(key, len(leaves))
    return tdef.unflatten([
        a + factor * _scale(b) * jax.random.normal(k, a.shape)
        for a, b, k in zip(leaves, jax.tree.leaves(scale_of), keys)])


@functools.partial(jax.jit, static_argnames=("m", "team_scale"))
def _teams(key, x, *, m, team_scale):
    return jax.vmap(lambda t: _jitter(jax.random.fold_in(key, t), x, x,
                                      team_scale))(jnp.arange(m))


def device_model(key, x, w_t, t, d, scale):
    """Device (t, d)'s model: its team's plus noise keyed by (t, d)."""
    k = jax.random.fold_in(jax.random.fold_in(key, t), d)
    return _jitter(k, w_t, x, scale)


@functools.partial(jax.jit, static_argnames=("n", "scale"))
def _team_devices(key, x, w_t, t, *, n, scale):
    return jax.vmap(lambda d: device_model(key, x, w_t, t, d, scale))(
        jnp.arange(n))


def tiers(ctx):
    """(x, w, device key): the seeded global and team tiers, and the key
    every device model is drawn from. Seed-dependent values are arguments
    of the jitted functions, never constants, so every seed shares their
    compiled programs."""
    p, cfg = ctx.params, ctx.cell.config
    k_x, k_w, k_d = jax.random.split(jax.random.fold_in(
        seed_key(ctx.seed), 1), 3)
    x = fl.init_fn(ctx.cell.reference, cfg)(k_x)
    w = _teams(k_w, x, m=p["m"], team_scale=p["team_scale"])
    return x, w, k_d


def build_server(ctx, x, w, k_d):
    """Export the int8 store team by team and wrap it in the server."""
    from repro.core import PerMFL
    from repro.core.permfl import PerMFLHParams, PerMFLState
    from repro.models import paper_models as PM
    from repro.scenarios.spec import fns_for
    from repro.serve import ModelStore, PersonalizedServer

    p = ctx.params
    pcfg = fl.program_config(ctx.cell.config)
    algo = PerMFL(fns_for(pcfg)[0], PerMFLHParams())
    payloads = []
    for t in range(p["m"]):
        w_t = jax.tree.map(lambda a: a[t], w)
        theta = jax.tree.map(lambda a: a[None], _team_devices(
            k_d, x, w_t, t, n=p["n"], scale=p["device_scale"]))
        st = PerMFLState(x=x, w=jax.tree.map(lambda a: a[None], w_t),
                         theta=theta, round=jnp.int32(0))
        payloads.append(ModelStore.from_state(
            algo, st, m=1, n=p["n"], encoding=p["encoding"]).device_payload)
        del st, theta
    payload = jax.tree.map(lambda *a: jnp.concatenate(a, 0), *payloads)
    del payloads
    store = ModelStore(x, w, payload, encoding=p["encoding"], m=p["m"],
                       n=p["n"])
    server = PersonalizedServer(
        store, lambda prm, xx: PM.apply(prm, pcfg, xx[None])[0])
    return server


def schedule(ctx, seconds, rate=None, salt=1):
    """Due times, tags and image indices of the window's requests (of
    another stream of the seed for another ``salt``)."""
    p = ctx.params
    due = traffic.poisson_schedule(rate or p["rate"], seconds,
                                   traffic.rng_for(ctx.seed, salt))
    teams, devices = traffic.zipf_tags(
        p["m"], p["n"], len(due), traffic.rng_for(ctx.seed, salt + 1),
        alpha=p["zipf_alpha"], unknown_frac=p["unknown_frac"])
    images = traffic.rng_for(ctx.seed, salt + 2).integers(0, p["pool"],
                                                         len(due))
    return due, teams, devices, images


def warm(ctx, server, pool, sizes):
    """Compile every batch size, then serve a short burst of the cell's
    own traffic (half a second of it, from a stream of the seed no
    window uses) so that nothing the window does runs for the first
    time inside it."""
    for b in sizes:
        z = np.zeros((b,), np.int32)
        jax.block_until_ready(server.serve(z, z, pool[:b]))
    due, teams, devices, images = schedule(ctx, 0.5, salt=5)
    serve_window(server, pool, due, teams, devices, images, sizes,
                 ctx.annotate)
    server.reset_tier_counts()


def serve_window(server, pool, due, teams, devices, images, sizes,
                 annotate):
    """Serve every request of the schedule; returns (sent, done, window
    seconds, [(first, last, answers)] per batch, the five slowest serve
    calls as (seconds, batch size, first request)). Times are seconds
    from the schedule's start."""
    n, cap = len(due), max(sizes)
    sent, done = np.zeros(n), np.zeros(n)
    calls = []                            # (seconds, batch, first request)
    batches = []
    i = 0
    start = time.perf_counter()
    while i < n:
        now = time.perf_counter() - start
        if due[i] > now:
            with annotate("wait"):
                wait = due[i] - now
                if wait > 2e-4:
                    time.sleep(wait - 1e-4)
            continue
        j = min(int(np.searchsorted(due, now, side="right")), i + cap)
        with annotate("batch"):
            b = traffic.pad_size(j - i, sizes)
            pad = np.full(b - (j - i), i)
            rows = np.concatenate([np.arange(i, j), pad])
            ts, ds, xs = teams[rows], devices[rows], pool[images[rows]]
        sent[i:j] = time.perf_counter() - start
        with annotate("serve"):
            out = server.serve(ts, ds, xs)
            out.block_until_ready()
        done[i:j] = time.perf_counter() - start
        calls.append((done[i] - sent[i], b, i))
        batches.append((i, j, out))
        i = j
    return (sent, done, time.perf_counter() - start, batches,
            sorted(calls, reverse=True)[:5])


def served_rows(batches, rows):
    """The server's answers for request indices ``rows`` (sorted)."""
    out, k = [], 0
    for i, j, ans in batches:
        want = [r for r in rows[k:] if r < j]
        if want:
            host = np.asarray(ans)
            out.extend(host[r - i] for r in want)
            k += len(want)
    return np.stack(out)


def reference_logits(ctx, x, w, k_d, teams, devices, xs, *,
                     quantize=None, block=64):
    """Logits of the requests under the plain reference, each under the
    model its tags resolve to: its device's, else its team's, else the
    global one. ``quantize`` (a residual -> residual map) stands in for a
    lower-precision store of the device tier, for the control."""
    p, ref = ctx.params, ctx.cell.reference
    hi = lax.Precision.HIGHEST

    @jax.jit
    def logits(x, w, k_d, ts, ds, xb):
        ok_t = (ts >= 0) & (ts < p["m"])
        ok_d = ok_t & (ds >= 0) & (ds < p["n"])
        tc = jnp.clip(ts, 0, p["m"] - 1)
        dc = jnp.clip(ds, 0, p["n"] - 1)

        def one(t, d, okt, okd, xi):
            w_t = jax.tree.map(lambda a: a[t], w)
            dev = device_model(k_d, x, w_t, t, d, p["device_scale"])
            if quantize is not None:
                dev = jax.tree.map(lambda a, b: b + quantize(a - b), dev, w_t)
            prm = jax.tree.map(lambda g, tm, dv: jnp.where(
                okd, dv, jnp.where(okt, tm, g)), x, w_t, dev)
            return ref.apply(prm, xi[None], hi)[0]

        return jax.vmap(one)(tc, dc, ok_t, ok_d, xb)

    out = []
    for lo in range(0, len(teams), block):
        sl = slice(lo, lo + block)
        out.append(np.asarray(logits(x, w, k_d, jnp.asarray(teams[sl]),
                                     jnp.asarray(devices[sl]),
                                     jnp.asarray(xs[sl]))))
    return np.concatenate(out)


def setup(ctx):
    """The seeded tiers, the server over their int8 store with every
    batch size compiled, and the pool of request images (on the host)."""
    p = ctx.params
    shape = tuple(ctx.cell.config["input_shape"])
    x, w, k_d = tiers(ctx)
    ctx.mark("tiers drawn")
    server = build_server(ctx, x, w, k_d)
    ctx.mark("int8 store exported")
    pool = np.asarray(image_pool(jax.random.fold_in(seed_key(ctx.seed), 2),
                                 count=p["pool"], shape=shape,
                                 noise=p["noise"]))
    warm(ctx, server, pool, tuple(p["batch_sizes"]))
    ctx.mark("batch sizes warmed")
    return x, w, k_d, server, pool


def run(ctx) -> Run:
    p = ctx.params
    sizes = tuple(p["batch_sizes"])
    x, w, k_d, server, pool = setup(ctx)
    due, teams, devices, images = schedule(ctx, ctx.seconds)
    # what set-up left alive moves out of the collector's generations, as
    # a long-lived server's start-up state would; the collector stays on
    gc.collect()
    gc.freeze()
    with ctx.window():
        setup_s = ctx.since_start()
        sent, done, window_s, batches, slowest = serve_window(
            server, pool, due, teams, devices, images, sizes, ctx.annotate)
    memory = ctx.memory_peak()
    ctx.mark("window closed; slowest serve calls (ms, batch, first "
             "request): " + ", ".join("(%.3f, %d, %d)" % (1e3 * t, b, i)
                                      for t, b, i in slowest))
    rows = np.sort(traffic.rng_for(ctx.seed, 4).choice(
        len(due), size=min(p["sample"], len(due)), replace=False))
    served = served_rows(batches, rows)
    n_batches = len(batches)
    del batches, server
    ref = reference_logits(ctx, x, w, k_d, teams[rows], devices[rows],
                           pool[images[rows]])
    ctx.mark("reference done")
    lat = traffic.latency_ms(due, done)
    late = traffic.lateness_ms(due, sent)
    from chipbench.stats import percentile
    n = len(due)
    return Run(setup_s=setup_s, window_s=window_s, attempted=n, failed=0,
               end_to_end={"serve_rps": n / window_s,
                           "serve_p50_ms": percentile(lat, 50)},
               stats={"requests": n, "batches": n_batches,
                      "p99_ms": percentile(lat, 99),
                      "late_p99_ms": percentile(late, 99)},
               numbers={"logit_gap": compare.logit_gap(served, ref)},
               memory_peak_bytes=memory)
