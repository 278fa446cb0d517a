"""run_sweep (one vmapped program) vs looped run_experiment: identical
trajectories, final states, participation, and byte ledgers — for PerMFL
with and without comm and for a baseline — plus grid semantics (non-
uniform grids, seeds, per-seed inits, chunking, sharding, validation)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.comm import CommConfig
from repro.core import PerMFL, baselines as B
from repro.core.permfl import PerMFLHParams
from repro.launch.mesh import make_host_mesh
from repro.sharding.specs import sweep_pspecs
from repro.train.engine import run_experiment
from repro.train.sweep import FLSweepResult, grid_product, run_sweep

M, N, D = 3, 4, 5


def quad_loss(params, batch):
    return 0.5 * jnp.sum((params - batch["c"]) ** 2)


def neg_loss(params, batch):
    return -quad_loss(params, batch)


@pytest.fixture(scope="module")
def quad_data():
    rng = np.random.default_rng(0)
    return {"c": jnp.asarray(rng.normal(size=(M, N, D)).astype(np.float32))}


HP = PerMFLHParams(alpha=0.05, eta=0.04, beta=0.3, lam=0.8, gamma=2.0,
                   k_team=3, l_local=4)

# non-uniform on purpose: different keys set per config
GRID = [dict(lam=0.3), dict(lam=0.9, beta=0.5), dict(gamma=1.0)]


def assert_results_match(sweep_res, looped_res):
    for f in ("pm_acc", "tm_acc", "gm_acc", "train_loss"):
        np.testing.assert_allclose(getattr(sweep_res, f),
                                   getattr(looped_res, f), atol=1e-5)
    assert sweep_res.participation == looped_res.participation
    for a, b in zip(jax.tree.leaves(sweep_res.state),
                    jax.tree.leaves(looped_res.state)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


def test_sweep_matches_looped_permfl(quad_data):
    sw = run_sweep(PerMFL(quad_loss, HP), GRID, (0,), jnp.zeros(D),
                   quad_data, quad_data, metric_fn=neg_loss, rounds=5,
                   m=M, n=N)
    assert len(sw) == 3 and sw.dispatches == 1
    for i, g in enumerate(GRID):
        ref = run_experiment(
            PerMFL(quad_loss, dataclasses.replace(HP, **g)), jnp.zeros(D),
            quad_data, quad_data, metric_fn=neg_loss, rounds=5, m=M, n=N)
        assert_results_match(sw[i], ref)
        for k, v in g.items():
            assert sw.configs[i][k] == v
    # the stacked state keeps the (S,) axis
    assert jax.tree.leaves(sw.state_stacked)[0].shape[0] == 3


def test_sweep_matches_looped_permfl_comm_and_participation(quad_data):
    cfg = CommConfig("topk", k_frac=0.4)
    sw = run_sweep(PerMFL(quad_loss, HP, comm=cfg), GRID, (0, 7),
                   jnp.zeros(D), quad_data, quad_data, metric_fn=neg_loss,
                   rounds=4, m=M, n=N, team_frac=0.5)
    assert len(sw) == 6        # grid-major: (g0,s0), (g0,s7), (g1,s0), ...
    i = 0
    for g in GRID:
        for seed in (0, 7):
            ref = run_experiment(
                PerMFL(quad_loss, dataclasses.replace(HP, **g), comm=cfg),
                jnp.zeros(D), quad_data, quad_data, metric_fn=neg_loss,
                rounds=4, m=M, n=N, team_frac=0.5, seed=seed)
            assert sw.configs[i]["seed"] == seed
            assert_results_match(sw[i], ref)
            assert sw[i].comm.total_bytes() == ref.comm.total_bytes()
            assert len(sw[i].comm.rounds) == 4
            np.testing.assert_allclose(
                np.asarray(sw[i].state.comm.ef_team),
                np.asarray(ref.state.comm.ef_team), atol=1e-6)
            i += 1


def test_sweep_matches_looped_baseline(quad_data):
    grid = [dict(lr=0.05), dict(lr=0.1, lam=0.2)]
    algo = B.Ditto(quad_loss, lr=0.05, lam=0.5, local_steps=3)
    sw = run_sweep(algo, grid, (0,), jnp.zeros(D), quad_data, quad_data,
                   metric_fn=neg_loss, rounds=4, m=M, n=N)
    for i, g in enumerate(grid):
        ref = run_experiment(dataclasses.replace(algo, **g), jnp.zeros(D),
                             quad_data, quad_data, metric_fn=neg_loss,
                             rounds=4, m=M, n=N)
        np.testing.assert_allclose(sw[i].pm_acc, ref.pm_acc, atol=1e-5)
        np.testing.assert_allclose(sw[i].gm_acc, ref.gm_acc, atol=1e-5)


def test_sweep_per_seed_init_fn(quad_data):
    """params0 as seed->params callable: each seed trains from its own
    init, matching looped run_experiment with the same params."""
    init_fn = lambda seed: jnp.full((D,), 0.1 * seed, jnp.float32)
    sw = run_sweep(PerMFL(quad_loss, HP), [{}], (0, 2), init_fn, quad_data,
                   quad_data, metric_fn=neg_loss, rounds=3, m=M, n=N)
    for i, seed in enumerate((0, 2)):
        ref = run_experiment(PerMFL(quad_loss, HP), init_fn(seed),
                             quad_data, quad_data, metric_fn=neg_loss,
                             rounds=3, m=M, n=N, seed=seed)
        assert_results_match(sw[i], ref)
    # different inits must actually produce different trajectories
    assert sw[0].pm_acc != sw[1].pm_acc


def test_sweep_eval_every_chunking_and_remainder(quad_data):
    sw = run_sweep(PerMFL(quad_loss, HP), [dict(lam=0.4)], (0,),
                   jnp.zeros(D), quad_data, quad_data, metric_fn=neg_loss,
                   rounds=7, m=M, n=N, eval_every=3)
    assert sw.dispatches == 2      # 2 full chunks + remainder chunk
    assert len(sw[0].pm_acc) == 3  # evals after rounds 3, 6, 7
    assert len(sw[0].participation) == 7
    ref = run_experiment(PerMFL(quad_loss,
                                dataclasses.replace(HP, lam=0.4)),
                         jnp.zeros(D), quad_data, quad_data,
                         metric_fn=neg_loss, rounds=7, m=M, n=N,
                         eval_every=3)
    assert_results_match(sw[0], ref)


def test_sweep_grid_dict_is_product(quad_data):
    sw = run_sweep(PerMFL(quad_loss, HP),
                   {"lam": [0.3, 0.9], "beta": [0.5]}, (0,), jnp.zeros(D),
                   quad_data, quad_data, metric_fn=neg_loss, rounds=2,
                   m=M, n=N)
    assert [c["lam"] for c in sw.configs] == [0.3, 0.9]
    assert all(c["beta"] == 0.5 for c in sw.configs)


def test_grid_product():
    g = grid_product(a=[1, 2], b=[3])
    assert g == [{"a": 1, "b": 3}, {"a": 2, "b": 3}]


def test_sweep_rejects_unknown_hparam(quad_data):
    with pytest.raises(ValueError, match="k_team"):
        run_sweep(PerMFL(quad_loss, HP), [dict(k_team=2)], (0,),
                  jnp.zeros(D), quad_data, quad_data, metric_fn=neg_loss,
                  rounds=2, m=M, n=N)


def test_sweep_rejects_mask_blind_participation(quad_data):
    with pytest.raises(ValueError, match="participation"):
        run_sweep(B.FedAvg(quad_loss, lr=0.1, local_steps=2),
                  [dict(lr=0.2)], (0,), jnp.zeros(D), quad_data, quad_data,
                  metric_fn=neg_loss, rounds=2, m=M, n=N, team_frac=0.5)


def test_sweep_rejects_empty(quad_data):
    with pytest.raises(ValueError, match="empty grid"):
        run_sweep(PerMFL(quad_loss, HP), [], (0,), jnp.zeros(D), quad_data,
                  quad_data, metric_fn=neg_loss, rounds=2, m=M, n=N)
    with pytest.raises(ValueError, match="empty seeds"):
        run_sweep(PerMFL(quad_loss, HP), [{}], (), jnp.zeros(D), quad_data,
                  quad_data, metric_fn=neg_loss, rounds=2, m=M, n=N)


def test_sweep_on_sweep_mesh_matches_unsharded(quad_data):
    """mesh= places the (S,) config axis on the mesh's sweep axis; on the
    CPU host mesh (1 device) this must be a pure no-op numerically."""
    mesh = make_host_mesh(n_sweep=1)
    assert mesh.axis_names == ("sweep", "data", "model")
    plain = run_sweep(PerMFL(quad_loss, HP), GRID, (0,), jnp.zeros(D),
                      quad_data, quad_data, metric_fn=neg_loss, rounds=3,
                      m=M, n=N)
    sharded = run_sweep(PerMFL(quad_loss, HP), GRID, (0,), jnp.zeros(D),
                        quad_data, quad_data, metric_fn=neg_loss, rounds=3,
                        m=M, n=N, mesh=mesh)
    for a, b in zip(plain, sharded):
        assert_results_match(b, a)


def test_sweep_mesh_rejects_wide_data_axis(quad_data):
    """Configs split over the sweep axis only: a mesh that would shard
    each config's (M, N) state, or split the configs unevenly, is
    refused rather than silently replicated."""
    from jax.sharding import AbstractMesh

    mesh = AbstractMesh((1, 2, 1), ("sweep", "data", "model"))
    with pytest.raises(ValueError, match="size 1"):
        run_sweep(PerMFL(quad_loss, HP), GRID, (0,), jnp.zeros(D),
                  quad_data, quad_data, metric_fn=neg_loss, rounds=1,
                  m=M, n=N, mesh=mesh)
    uneven = AbstractMesh((2, 1, 1), ("sweep", "data", "model"))
    with pytest.raises(ValueError, match="split evenly"):
        run_sweep(PerMFL(quad_loss, HP), GRID[:1], (0,), jnp.zeros(D),
                  quad_data, quad_data, metric_fn=neg_loss, rounds=1,
                  m=M, n=N, mesh=uneven)


def test_sweep_pspecs_axis_mapping():
    """(S, M, N, ...) -> (sweep, data, model); (S, M, ...) -> (sweep,
    data); (S, ...) -> (sweep,) on the leading axis only."""
    from jax.sharding import PartitionSpec as P
    tree = {
        "theta": jnp.zeros((8, M, N, D)),
        "w": jnp.zeros((8, M, D)),
        "x": jnp.zeros((8, D)),
        "round": jnp.zeros((8,), jnp.int32),
    }
    specs = sweep_pspecs(tree, m=M, n=N)
    assert specs["theta"] == P("sweep", "data", "model", None)
    assert specs["w"] == P("sweep", "data", None)
    assert specs["x"] == P("sweep", None)
    assert specs["round"] == P("sweep")


def test_flsweepresult_accessors(quad_data):
    sw = run_sweep(PerMFL(quad_loss, HP), GRID, (0,), jnp.zeros(D),
                   quad_data, quad_data, metric_fn=neg_loss, rounds=2,
                   m=M, n=N)
    assert isinstance(sw, FLSweepResult)
    assert len(sw.best("pm")) == len(sw.final("gm")) == len(GRID)
    assert [r.pm_acc[-1] for r in sw] == sw.final("pm")
