"""Batched personalized inference over (team, device)-tagged requests.

The serving half of the store (DESIGN.md §12): a
:class:`PersonalizedServer` wraps a :class:`repro.serve.store.ModelStore`
and a single-example forward function, and answers request batches where
every row carries its own ``(team, device)`` tag. One jitted program
does the whole step — tier-fallback gather of each request's personal
params (the store's in-graph decode) followed by one vmapped forward —
so a 64-request batch over 64 *different* personalized models costs one
XLA dispatch, not 64.

Two paths answer the same question two ways and must agree — bit-for-bit
under the exact encodings, to float tolerance under lossy ``int8``,
whose multiply-add decode is sensitive to XLA fusion boundaries
(tests/test_serve_store.py): :meth:`PersonalizedServer.serve` gathers
and delta-decodes every request row in-graph, while
:meth:`PersonalizedServer.serve_cached` first collapses the batch to
its unique principals, pulls each one's decoded params through the
store's host-side LRU (hot devices skip decode entirely), and stacks.
Replay traffic whose popularity is Zipf-skewed — i.e. real traffic —
mostly hits the cache; :func:`replay_traffic` generates exactly that
workload and measures p50/p95/p99 latency and queries/sec, which is
what `benchmarks/bench_serving.py` publishes to ``BENCH_serving.json``.

Serving telemetry (`repro.obs`) rides both paths: the jitted serve step
also emits per-batch tier-resolution counts (how many requests landed on
their personal model vs fell back to team / global — computed in-graph
from the same masks as the gather, so XLA shares the work), accumulated
on ``PersonalizedServer.tier_counts``; replay publishes those counts,
the LRU hit rate, raw per-batch latencies, and a gather-vs-forward stage
split into a :class:`repro.obs.metrics.MetricsRegistry` when one is
passed.
"""
from __future__ import annotations

import time
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs.spans import install_gc_spans, span
from repro.serve.store import ModelStore

__all__ = ["PersonalizedServer", "replay_traffic", "zipf_requests"]


class PersonalizedServer:
    """Batched tier-resolved inference in front of a :class:`ModelStore`.

    ``apply_fn(params, x) -> logits`` is the *single-example* forward for
    one model; the server vmaps it over the batch axis shared by the
    gathered params and the inputs, and jits the combined
    gather-then-forward step once per input shape.
    """

    def __init__(self, store: ModelStore, apply_fn: Callable[[Any, Any], Any]):
        """Wrap ``store`` and a single-example ``apply_fn``."""
        install_gc_spans()
        self.store = store
        self.apply_fn = apply_fn

        # the tier counts are extra outputs of the same jitted step —
        # they reuse the gather's validity masks, so telemetry costs a
        # couple of reductions, not a second pass over the tags
        def step(st, t, d, xs):
            with jax.named_scope("serve.gather"):
                params = st.gather(t, d)
            with jax.named_scope("serve.forward"):
                out = jax.vmap(apply_fn)(params, xs)
            with jax.named_scope("serve.tiers"):
                return out, st.resolve_tiers(t, d)

        self._step = jax.jit(step)
        self._fwd = jax.jit(lambda params, xs: jax.vmap(apply_fn)(params, xs))
        self.tier_counts = {"device": 0, "team": 0, "global": 0}

    def reset_tier_counts(self) -> None:
        """Zero the accumulated tier-resolution counts (call after
        warm-up so timed traffic reports clean telemetry)."""
        self.tier_counts = {"device": 0, "team": 0, "global": 0}

    def serve(self, teams, devices, xs):
        """Answer a request batch fully in-graph.

        teams/devices: ``(B,)`` int tags (out-of-range falls down the
        tier ladder — device → team → global); xs: ``(B, ...)`` inputs.
        Returns ``(B, ...)`` outputs, row ``i`` computed under request
        ``i``'s resolved personal params. Tier-resolution counts for the
        batch accumulate onto :attr:`tier_counts`.
        """
        with span("serve.put"):
            teams = jnp.asarray(teams, jnp.int32)
            devices = jnp.asarray(devices, jnp.int32)
            xs = jax.device_put(xs)
        with span("serve.dispatch"):
            out, tiers = self._step(self.store, teams, devices, xs)
        with span("serve.tiers"):      # the read-back waits for the step
            for k, v in tiers.items():
                self.tier_counts[k] += int(v)
        return out

    def serve_cached(self, teams, devices, xs):
        """Answer a request batch through the store's LRU hot path.

        Collapses the batch to its unique ``(team, device)`` principals,
        fetches each one's decoded params via
        :meth:`ModelStore.params_for` (LRU-cached on the host), stacks
        the unique models, and runs the same vmapped forward. Output
        matches :meth:`serve` bit-for-bit under the exact encodings
        (``"delta"``/``"raw"`` decode in integer arithmetic, immune to
        fusion) and to float tolerance under ``"int8"``; it wins when
        traffic is skewed enough that the unique count is far below the
        batch size.
        """
        t = np.asarray(teams, np.int64)
        d = np.asarray(devices, np.int64)
        # same ladder as ModelStore.resolve_tiers, host-side (the batch
        # never goes through the jitted step on this path)
        ok_t = (t >= 0) & (t < self.store.m)
        ok_d = ok_t & (d >= 0) & (d < self.store.n)
        self.tier_counts["device"] += int(ok_d.sum())
        self.tier_counts["team"] += int((ok_t & ~ok_d).sum())
        self.tier_counts["global"] += int((~ok_t).sum())
        pairs, inverse = np.unique(np.stack([t, d], axis=1), axis=0,
                                   return_inverse=True)
        per_uniq = [self.store.params_for(int(a), int(b)) for a, b in pairs]
        uniq_params = jax.tree.map(lambda *ls: jnp.stack(ls), *per_uniq)
        params = jax.tree.map(lambda l: l[jnp.asarray(inverse)], uniq_params)
        return self._fwd(params, xs)


def zipf_requests(m: int, n: int, count: int, *, alpha: float = 1.2,
                  unknown_frac: float = 0.0, seed: int = 0):
    """Zipf-skewed request tags over an ``m x n`` device population.

    Device popularity rank is drawn from a Zipf(``alpha``) law and
    mapped onto the population through a fixed random permutation (so
    the hot set is scattered across teams, not clustered in team 0). A
    ``unknown_frac`` share of requests is tagged with an out-of-range
    device (and half of those with an out-of-range team) to exercise the
    fallback ladder the way stale production IDs would. Returns
    ``(teams, devices)`` int64 arrays of length ``count``.
    """
    rng = np.random.default_rng(seed)
    population = m * n
    ranks = (rng.zipf(alpha, size=count) - 1) % population
    flat = rng.permutation(population)[ranks]
    teams, devices = flat // n, flat % n
    if unknown_frac > 0.0:
        bad = rng.random(count) < unknown_frac
        devices = np.where(bad, n + 1, devices)
        teams = np.where(bad & (rng.random(count) < 0.5), m + 1, teams)
    return teams.astype(np.int64), devices.astype(np.int64)


def replay_traffic(server: PersonalizedServer, inputs, *, requests: int = 512,
                   batch: int = 64, alpha: float = 1.2,
                   unknown_frac: float = 0.0, seed: int = 0,
                   cached: bool = False, metrics: Optional[Any] = None,
                   ) -> dict:
    """Replay Zipf-popularity traffic and measure serving latency.

    Draws ``requests`` tags via :func:`zipf_requests`, pairs each with a
    row sampled from ``inputs`` (a ``(P, ...)`` pool), and serves them
    in fixed ``batch``-size steps through :meth:`PersonalizedServer.serve`
    (or :meth:`~PersonalizedServer.serve_cached` when ``cached``). The
    first batch is replayed once untimed to absorb compilation, then the
    server's tier counters (and the store's LRU counters) are reset so
    the report covers exactly the timed traffic; each timed batch is
    ``block_until_ready``-synced. A second pass times the gather-decode
    and forward stages separately (same batches, each stage jitted and
    warmed on its own) so the latency split is visible.

    Returns a dict with ``qps``, ``p50_ms``/``p95_ms``/``p99_ms``,
    ``mean_ms``, the raw per-batch latencies (``lat_ms``, timing order),
    ``tier_counts`` (summing to ``requests``), the stage split
    (``stage_gather_ms``/``stage_forward_ms`` means), ``cache_hit_rate``
    on cached runs, the workload knobs, and the store's encoded
    device-tier size. When ``metrics`` (a
    :class:`repro.obs.metrics.MetricsRegistry`) is given, the same
    telemetry is published as counters/gauges/histograms.
    """
    store = server.store
    requests = max(batch, (requests // batch) * batch)
    n_batches = requests // batch
    teams, devices = zipf_requests(store.m, store.n, requests, alpha=alpha,
                                   unknown_frac=unknown_frac, seed=seed)
    rng = np.random.default_rng(seed + 1)
    pool = np.asarray(inputs)
    xs = jnp.asarray(pool[rng.integers(0, pool.shape[0], size=requests)])
    step = server.serve_cached if cached else server.serve

    with span("replay", requests=requests, batches=n_batches,
              cached=bool(cached)):
        jax.block_until_ready(
            step(teams[:batch], devices[:batch], xs[:batch]))
        # warm-up served the first batch once outside the timed loop —
        # drop its tier/LRU contributions so the counters below cover
        # exactly the `requests` timed requests
        server.reset_tier_counts()
        store.reset_cache_stats()
        lat = []
        t_all = time.perf_counter()
        for lo in range(0, requests, batch):
            hi = lo + batch
            with span("replay_batch", lo=lo):
                t0 = time.perf_counter()
                jax.block_until_ready(
                    step(teams[lo:hi], devices[lo:hi], xs[lo:hi]))
                lat.append(time.perf_counter() - t0)
        total = time.perf_counter() - t_all

    lat_ms = np.asarray(lat) * 1e3
    lat_sorted = np.sort(lat_ms)

    def pct(p):
        return float(lat_sorted[min(len(lat_sorted) - 1,
                                    int(np.ceil(p / 100 * len(lat_sorted)))
                                    - 1)])

    # stage split: gather-decode vs forward, timed separately over the
    # same batches (each stage warmed on its own so neither pays the
    # other's compile)
    with span("replay_stages", batches=n_batches):
        gather_fn = jax.jit(lambda st, t, d: st.gather(t, d))
        p0 = jax.block_until_ready(
            gather_fn(store, teams[:batch], devices[:batch]))
        jax.block_until_ready(server._fwd(p0, xs[:batch]))
        g_ms, f_ms = [], []
        for lo in range(0, requests, batch):
            hi = lo + batch
            t0 = time.perf_counter()
            params = jax.block_until_ready(
                gather_fn(store, teams[lo:hi], devices[lo:hi]))
            t1 = time.perf_counter()
            jax.block_until_ready(server._fwd(params, xs[lo:hi]))
            t2 = time.perf_counter()
            g_ms.append((t1 - t0) * 1e3)
            f_ms.append((t2 - t1) * 1e3)

    stats = {
        "requests": requests, "batch": batch, "alpha": alpha,
        "unknown_frac": unknown_frac, "cached": bool(cached),
        "encoding": store.encoding, "m": store.m, "n": store.n,
        "device_tier_bytes": store.device_tier_nbytes(),
        "qps": float(requests / total),
        "p50_ms": pct(50), "p95_ms": pct(95), "p99_ms": pct(99),
        "mean_ms": float(lat_ms.mean()),
        "lat_ms": [float(v) for v in lat_ms],
        "tier_counts": dict(server.tier_counts),
        "stage_gather_ms": float(np.mean(g_ms)),
        "stage_forward_ms": float(np.mean(f_ms)),
    }
    if cached:
        stats["cache_hit_rate"] = store.cache_stats()["hit_rate"]

    if metrics is not None:
        metrics.counter("serving.requests").inc(requests)
        for tier, cnt in stats["tier_counts"].items():
            metrics.counter(f"serving.tier.{tier}").inc(cnt)
        h = metrics.histogram("serving.replay.latency_ms")
        for v in lat_ms:
            h.observe(float(v))
        hg = metrics.histogram("serving.stage.gather_ms")
        hf = metrics.histogram("serving.stage.forward_ms")
        for g, f in zip(g_ms, f_ms):
            hg.observe(g)
            hf.observe(f)
        if cached:
            cs = store.cache_stats()
            metrics.counter("serving.lru.hits").inc(cs["hits"])
            metrics.counter("serving.lru.misses").inc(cs["misses"])
            metrics.gauge("serving.cache_hit_rate").set(cs["hit_rate"])
    return stats
