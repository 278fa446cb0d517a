"""Personalized serving subsystem (DESIGN.md §12): serving identity for
every algorithm family, tier fallback, encodings, persistence, replay."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import paper_models
from repro.scenarios import SCENARIOS, build_scenario, run_scenario
from repro.serve.personalized import (PersonalizedServer, replay_traffic,
                                      zipf_requests)
from repro.serve.store import ModelStore

ALGOS = ("permfl", "fedavg", "perfedavg", "pfedme", "ditto", "hsgd",
         "l2gd")


@functools.lru_cache(maxsize=None)
def _trained(algo: str):
    s = SCENARIOS[f"table1/mnist/mclr/{algo}"].scaled(
        m_teams=2, n_devices=3, samples_per_device=16, rounds=1)
    res = run_scenario(s, seed=0)
    b = build_scenario(s, seed=0)
    xv = np.asarray(b.val["x"], np.float32)
    pool = jnp.asarray(xv.reshape((-1,) + xv.shape[3:]))
    apply1 = lambda p, x: paper_models.apply(p, b.config, x[None])[0]
    return b, res.state, apply1, pool


def _all_pairs(m, n):
    return (np.repeat(np.arange(m), n), np.tile(np.arange(n), m))


# ---------------------------------------------------------------------------
# serving identity: store-served == direct evaluation, per family
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("algo", ALGOS)
def test_served_predictions_bit_identical_per_family(algo):
    b, state, apply1, pool = _trained(algo)
    store = ModelStore.from_state(b.algo, state, m=b.m, n=b.n)
    server = PersonalizedServer(store, apply1)
    ts, ds = _all_pairs(b.m, b.n)
    xs = pool[: b.m * b.n]
    served = server.serve(ts, ds, xs)
    # reference: the device's trained params straight out of the state,
    # through the same vmapped forward program
    direct = jax.tree.map(
        lambda *ls: jnp.stack(ls),
        *[b.algo.serving_params(state, int(t), int(d))
          for t, d in zip(ts, ds)])
    ref = server._fwd(direct, xs)
    np.testing.assert_array_equal(np.asarray(served), np.asarray(ref))
    assert bool(jnp.isfinite(served).all())


@pytest.mark.parametrize("algo", ("permfl", "ditto"))
def test_single_model_forward_agrees(algo):
    # same logits as a plain single-model apply per device (batch-of-one
    # forwards): the batched tier-resolved path adds nothing numerically
    b, state, apply1, pool = _trained(algo)
    store = ModelStore.from_state(b.algo, state, m=b.m, n=b.n)
    server = PersonalizedServer(store, apply1)
    ts, ds = _all_pairs(b.m, b.n)
    xs = pool[: b.m * b.n]
    served = np.asarray(server.serve(ts, ds, xs))
    for i, (t, d) in enumerate(zip(ts, ds)):
        p = b.algo.serving_params(state, int(t), int(d))
        one = paper_models.apply(p, b.config, xs[i][None])[0]
        np.testing.assert_allclose(served[i], np.asarray(one),
                                   rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# tier fallback
# ---------------------------------------------------------------------------

def _fwd_one(server, params, x):
    """One principal's params through the server's own jitted vmapped
    forward: the reference the fallback must match bit for bit (an eager
    ``apply`` fuses differently and differs in the last ulp)."""
    return server._fwd(jax.tree.map(lambda l: l[None], params), x)


@pytest.mark.parametrize("encoding", ("delta", "int8", "raw"))
def test_unknown_device_falls_back_to_team(encoding):
    b, state, apply1, pool = _trained("permfl")
    store = ModelStore.from_state(b.algo, state, m=b.m, n=b.n,
                                  encoding=encoding)
    server = PersonalizedServer(store, apply1)
    x = pool[:1]
    for t in range(b.m):
        for bad_d in (-1, b.n, b.n + 7):
            out = server.serve(np.array([t]), np.array([bad_d]), x)
            ref = _fwd_one(server, b.algo.serving_params(state, t), x)
            np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


@pytest.mark.parametrize("encoding", ("delta", "int8"))
def test_unknown_team_falls_back_to_global(encoding):
    b, state, apply1, pool = _trained("permfl")
    store = ModelStore.from_state(b.algo, state, m=b.m, n=b.n,
                                  encoding=encoding)
    server = PersonalizedServer(store, apply1)
    x = pool[:1]
    ref = _fwd_one(server, b.algo.serving_params(state), x)
    for bad_t in (-3, b.m, b.m + 9):
        for d in (0, b.n + 1):
            out = server.serve(np.array([bad_t]), np.array([d]), x)
            np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_params_for_walks_the_same_ladder():
    b, state, _, _ = _trained("permfl")
    store = ModelStore.from_state(b.algo, state, m=b.m, n=b.n)
    g = store.params_for()
    team0 = store.params_for(0)
    dev01 = store.params_for(0, 1)
    for got, want in ((g, b.algo.serving_params(state)),
                      (team0, b.algo.serving_params(state, 0)),
                      (dev01, b.algo.serving_params(state, 0, 1)),
                      (store.params_for(0, b.n + 1),
                       b.algo.serving_params(state, 0)),
                      (store.params_for(b.m + 1, 0),
                       b.algo.serving_params(state))):
        for a, c in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(c))


def test_params_for_lru_caches_and_evicts():
    b, state, _, _ = _trained("permfl")
    store = ModelStore.from_state(b.algo, state, m=b.m, n=b.n,
                                  cache_size=2)
    p = store.params_for(0, 0)
    assert store.params_for(0, 0) is p          # hit: same object
    store.params_for(0, 1)
    store.params_for(0, 2)                      # evicts (0, 0)
    assert store.params_for(0, 0) is not p
    assert len(store._cache) == 2


# ---------------------------------------------------------------------------
# encodings and the cached serve path
# ---------------------------------------------------------------------------

def test_cached_path_bit_identical_for_exact_encodings():
    b, state, apply1, pool = _trained("pfedme")
    ts, ds = _all_pairs(b.m, b.n)
    ts, ds = np.concatenate([ts, ts]), np.concatenate([ds, ds])
    xs = pool[: len(ts)]
    for encoding in ("delta", "raw"):
        store = ModelStore.from_state(b.algo, state, m=b.m, n=b.n,
                                      encoding=encoding)
        server = PersonalizedServer(store, apply1)
        np.testing.assert_array_equal(
            np.asarray(server.serve(ts, ds, xs)),
            np.asarray(server.serve_cached(ts, ds, xs)))


def test_int8_encoding_bounded_error_and_smaller():
    b, state, apply1, pool = _trained("permfl")
    exact = ModelStore.from_state(b.algo, state, m=b.m, n=b.n)
    lossy = ModelStore.from_state(b.algo, state, m=b.m, n=b.n,
                                  encoding="int8")
    assert lossy.device_tier_nbytes() < exact.device_tier_nbytes() / 3
    ts, ds = _all_pairs(b.m, b.n)
    pe = exact.gather(jnp.asarray(ts), jnp.asarray(ds))
    pl = lossy.gather(jnp.asarray(ts), jnp.asarray(ds))
    for e, l, t in zip(jax.tree.leaves(pe), jax.tree.leaves(pl),
                       jax.tree.leaves(exact.team_params)):
        # int8 residual quantization: error per element bounded by the
        # per-128-lane scale = max|residual| / 127
        resid = np.abs(np.asarray(e) - np.asarray(t)[ts])
        bound = resid.reshape(len(ts), -1).max(axis=1) / 127 + 1e-7
        err = np.abs(np.asarray(e) - np.asarray(l)).reshape(len(ts), -1)
        assert (err.max(axis=1) <= bound).all()


def test_unknown_encoding_rejected():
    b, state, _, _ = _trained("permfl")
    with pytest.raises(ValueError, match="encoding"):
        ModelStore.from_state(b.algo, state, m=b.m, n=b.n,
                              encoding="float8")


# ---------------------------------------------------------------------------
# persistence
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("encoding", ("delta", "int8"))
def test_save_load_roundtrip_serves_identically(tmp_path, encoding):
    b, state, apply1, pool = _trained("l2gd")
    store = ModelStore.from_state(b.algo, state, m=b.m, n=b.n,
                                  encoding=encoding)
    path = str(tmp_path / "store.zip")
    store.save(path)
    loaded = ModelStore.load(path)
    assert (loaded.encoding, loaded.m, loaded.n) == (encoding, b.m, b.n)
    ts, ds = _all_pairs(b.m, b.n)
    xs = pool[: len(ts)]
    np.testing.assert_array_equal(
        np.asarray(PersonalizedServer(store, apply1).serve(ts, ds, xs)),
        np.asarray(PersonalizedServer(loaded, apply1).serve(ts, ds, xs)))


def test_load_rejects_non_store_checkpoint(tmp_path):
    from repro.train.checkpoint import save_checkpoint

    path = str(tmp_path / "not_store.zip")
    save_checkpoint(path, {"w": jnp.zeros(2)}, metadata={"step": 1})
    with pytest.raises(ValueError, match="ModelStore"):
        ModelStore.load(path)


# ---------------------------------------------------------------------------
# traffic replay
# ---------------------------------------------------------------------------

def test_zipf_requests_skewed_and_fallback_tagged():
    teams, devices = zipf_requests(4, 10, 2000, alpha=1.3,
                                   unknown_frac=0.2, seed=3)
    known = (teams < 4) & (devices < 10)
    assert 0.05 < 1 - known.mean() < 0.4
    assert (teams[known] >= 0).all() and (devices[known] >= 0).all()
    # popularity is skewed: the most popular principal dominates a
    # uniform draw's expected share several-fold
    flat = teams[known] * 10 + devices[known]
    top_share = np.bincount(flat).max() / len(flat)
    assert top_share > 3.0 / 40


def test_replay_traffic_stats_shape():
    b, state, apply1, pool = _trained("permfl")
    store = ModelStore.from_state(b.algo, state, m=b.m, n=b.n)
    server = PersonalizedServer(store, apply1)
    stats = replay_traffic(server, np.asarray(pool), requests=64,
                           batch=16, unknown_frac=0.1, seed=1)
    assert stats["requests"] == 64 and stats["batch"] == 16
    assert stats["qps"] > 0
    assert stats["p50_ms"] <= stats["p95_ms"] <= stats["p99_ms"]
    assert stats["device_tier_bytes"] == store.device_tier_nbytes()


# ---------------------------------------------------------------------------
# serving telemetry (tier counts, LRU stats, metrics)
# ---------------------------------------------------------------------------

def test_tier_counts_sum_to_request_count_and_match_tags():
    b, state, apply1, pool = _trained("permfl")
    store = ModelStore.from_state(b.algo, state, m=b.m, n=b.n)
    server = PersonalizedServer(store, apply1)
    # hand-built batch: 2 personal, 1 unknown device, 1 unknown team
    ts = np.array([0, 1, 0, b.m + 3])
    ds = np.array([0, 2, b.n + 5, 0])
    server.serve(ts, ds, pool[:4])
    assert server.tier_counts == {"device": 2, "team": 1, "global": 1}
    # the cached path counts the same ladder host-side
    server.reset_tier_counts()
    server.serve_cached(ts, ds, pool[:4])
    assert server.tier_counts == {"device": 2, "team": 1, "global": 1}
    assert sum(server.tier_counts.values()) == len(ts)


@pytest.mark.parametrize("cached", (False, True))
def test_replay_tier_counts_sum_to_requests(cached):
    b, state, apply1, pool = _trained("permfl")
    store = ModelStore.from_state(b.algo, state, m=b.m, n=b.n)
    server = PersonalizedServer(store, apply1)
    stats = replay_traffic(server, np.asarray(pool), requests=64,
                           batch=16, unknown_frac=0.2, seed=1,
                           cached=cached)
    tiers = stats["tier_counts"]
    assert set(tiers) == {"device", "team", "global"}
    # the warm-up batch's contribution was reset: counts cover exactly
    # the timed requests
    assert sum(tiers.values()) == stats["requests"] == 64
    assert tiers["team"] + tiers["global"] > 0  # unknown_frac fired


def test_replay_reports_live_lru_hit_rate():
    b, state, apply1, pool = _trained("permfl")
    store = ModelStore.from_state(b.algo, state, m=b.m, n=b.n)
    server = PersonalizedServer(store, apply1)
    stats = replay_traffic(server, np.asarray(pool), requests=64,
                           batch=16, seed=1, cached=True)
    # warm-up populated the hot principals and the counters were reset,
    # so the timed traffic's hit rate is the steady-state one
    assert 0.0 < stats["cache_hit_rate"] <= 1.0
    cs = store.cache_stats()
    assert cs["hits"] + cs["misses"] > 0
    assert cs["hit_rate"] == stats["cache_hit_rate"]


def test_store_cache_stats_count_and_reset():
    b, state, apply1, pool = _trained("permfl")
    store = ModelStore.from_state(b.algo, state, m=b.m, n=b.n)
    store.params_for(0, 0)
    store.params_for(0, 0)
    store.params_for(1, 1)
    assert store.cache_stats()["hits"] == 1
    assert store.cache_stats()["misses"] == 2
    assert store.cache_stats()["hit_rate"] == pytest.approx(1 / 3)
    store.reset_cache_stats()
    cs = store.cache_stats()
    assert cs["hits"] == 0 and cs["misses"] == 0 and cs["hit_rate"] == 0.0
    # cached entries survive the counter reset
    store.params_for(0, 0)
    assert store.cache_stats()["hits"] == 1


def test_replay_publishes_metrics_and_raw_latencies():
    from repro.obs.metrics import MetricsRegistry

    b, state, apply1, pool = _trained("permfl")
    store = ModelStore.from_state(b.algo, state, m=b.m, n=b.n)
    server = PersonalizedServer(store, apply1)
    metrics = MetricsRegistry()
    stats = replay_traffic(server, np.asarray(pool), requests=64,
                           batch=16, unknown_frac=0.1, seed=1,
                           cached=True, metrics=metrics)
    assert len(stats["lat_ms"]) == 64 // 16
    assert stats["stage_gather_ms"] > 0 and stats["stage_forward_ms"] > 0
    snap = {(e["metric"], e["type"]): e for e in metrics.snapshot()}
    assert snap[("serving.requests", "counter")]["value"] == 64
    tier_total = sum(
        snap[(f"serving.tier.{t}", "counter")]["value"]
        for t in ("device", "team", "global"))
    assert tier_total == 64
    lat = snap[("serving.replay.latency_ms", "histogram")]
    assert lat["count"] == 64 // 16
    assert ("serving.cache_hit_rate", "gauge") in snap


# ---------------------------------------------------------------------------
# zipf_requests workload properties
# ---------------------------------------------------------------------------

def test_zipf_requests_deterministic_under_fixed_seed():
    a = zipf_requests(4, 10, 500, alpha=1.3, unknown_frac=0.2, seed=7)
    b = zipf_requests(4, 10, 500, alpha=1.3, unknown_frac=0.2, seed=7)
    np.testing.assert_array_equal(a[0], b[0])
    np.testing.assert_array_equal(a[1], b[1])
    c = zipf_requests(4, 10, 500, alpha=1.3, unknown_frac=0.2, seed=8)
    assert not (np.array_equal(a[0], c[0]) and np.array_equal(a[1], c[1]))


def test_zipf_requests_unknown_split_device_vs_team():
    m, n, count = 4, 10, 4000
    teams, devices = zipf_requests(m, n, count, alpha=1.3,
                                   unknown_frac=0.3, seed=5)
    bad_dev = devices >= n
    bad_team = teams >= m
    # every unknown-team row is also unknown-device (team badness is a
    # coin flip *within* the bad-device rows), and the split is roughly
    # half/half of a ~unknown_frac share
    assert (bad_team <= bad_dev).all()
    assert 0.2 < bad_dev.mean() < 0.4
    assert 0.3 < bad_team.sum() / bad_dev.sum() < 0.7
    # out-of-range tags are exactly the sentinel values
    assert set(np.unique(devices[bad_dev])) == {n + 1}
    assert set(np.unique(teams[bad_team])) == {m + 1}


def test_zipf_requests_permutation_scatters_hot_set_across_teams():
    m, n = 8, 8
    teams, devices = zipf_requests(m, n, 20000, alpha=1.5, seed=11)
    flat = teams * n + devices
    top8 = np.argsort(np.bincount(flat, minlength=m * n))[-8:]
    # without the permutation the 8 hottest principals would be ranks
    # 0..7 = all of team 0; with it they spread over several teams
    assert len(set(int(p) // n for p in top8)) >= 3
