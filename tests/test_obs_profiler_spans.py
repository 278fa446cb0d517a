"""The program's spans on the profiler's clock, and its phases named in
the compiled programs (repro.obs.spans, DESIGN.md §13): every span is a
``repro.<name>`` host event of a ``jax.profiler`` trace, with or without
an active `SpanLog`; the collector's passes are spans; the serve step,
the eval program and the cohort round carry their ``jax.named_scope``
names in their op metadata."""
import gc
import glob

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.core import PerMFL
from repro.core.permfl import PerMFLHParams
from repro.obs.spans import SpanLog, install_gc_spans, span
from repro.serve import ModelStore, PersonalizedServer
from repro.train import engine

M, N, D = 2, 3, 5


def quad_loss(params, batch):
    return 0.5 * jnp.sum((params - batch["c"]) ** 2)


def neg_loss(params, batch):
    return -quad_loss(params, batch)


HP = PerMFLHParams(alpha=0.05, eta=0.04, beta=0.3, lam=0.8, gamma=2.0,
                   k_team=2, l_local=2)


def _host_events(trace_dir):
    """Names of every host event in the trace written under
    ``trace_dir``."""
    (path,) = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    pd = ProfileData.from_file(path)
    return [e.name for p in pd.planes if not p.name.startswith("/device")
            for line in p.lines for e in line.events]


def test_span_is_a_profiler_event_with_and_without_a_log(tmp_path):
    log = SpanLog()
    # once a test in this process has installed the collector's spans, a
    # pass inside the active log would add a gc.* span of its own
    collecting = gc.isenabled()
    gc.disable()
    try:
        with jax.profiler.trace(str(tmp_path)):
            with span("orphan", attr=1) as sp:
                sp.set(late=2)
            with log.activate():
                with span("logged", attr=3):
                    with span("inner"):
                        pass
    finally:
        if collecting:
            gc.enable()
    names = _host_events(tmp_path)
    for name in ("repro.orphan", "repro.logged", "repro.inner"):
        assert names.count(name) == 1, name
    # attributes stay in the log, out of the annotation's name
    assert not any(n.startswith("repro.") and "attr" in n for n in names)
    assert [s.name for s in log.spans] == ["logged", "inner"]
    assert log.spans[0].attrs == {"attr": 3}


def test_span_closes_its_annotation_on_error(tmp_path):
    with jax.profiler.trace(str(tmp_path)):
        with pytest.raises(KeyError):
            with span("fails"):
                raise KeyError("x")
        with span("after"):
            pass
    names = _host_events(tmp_path)
    assert "repro.fails" in names and "repro.after" in names


def test_gc_passes_are_spans_and_the_hook_installs_once(tmp_path):
    install_gc_spans()
    install_gc_spans()
    hooks = [cb for cb in gc.callbacks
             if getattr(cb, "__module__", "") == "repro.obs.spans"]
    assert len(hooks) == 1
    log = SpanLog()
    with jax.profiler.trace(str(tmp_path)):
        with log.activate():
            gc.collect()
    assert "repro.gc.gen2" in _host_events(tmp_path)
    assert "gc.gen2" in [s.name for s in log.spans]


@pytest.fixture(scope="module")
def quad_data():
    rng = np.random.default_rng(0)
    return {"c": jnp.asarray(rng.normal(size=(M, N, D)).astype(np.float32))}


def test_serve_step_names_gather_forward_and_tiers():
    algo = PerMFL(quad_loss, HP)
    state = algo.init_state(jnp.arange(D, dtype=jnp.float32), M, N)
    store = ModelStore.from_state(algo, state, m=M, n=N, encoding="int8")
    server = PersonalizedServer(store, lambda p, x: p * x)
    t = jnp.zeros((4,), jnp.int32)
    xs = jnp.ones((4, D))
    text = server._step.lower(store, t, t, xs).as_text(debug_info=True)
    for scope in ("serve.gather", "serve.forward", "serve.tiers"):
        assert scope in text, scope


def test_eval_program_names_its_eval(quad_data):
    algo = PerMFL(quad_loss, HP)
    skel, hleaves = engine.hparam_skeleton(algo)
    state = algo.init_state(jnp.zeros(D), M, N)
    prog = engine._eval_program(skel, neg_loss)
    text = prog.lower(hleaves, state, quad_data, quad_data).as_text(
        debug_info=True)
    assert "engine.eval" in text


def test_cohort_round_names_store_eval_and_permfl_phases(quad_data):
    algo = PerMFL(quad_loss, HP)
    skel, hleaves = engine.hparam_skeleton(algo)
    state = algo.init_state(jnp.zeros(D), M, N)
    prog = engine._scan_program(skel, neg_loss, M, N, 1.0, 1.0,
                                cohort=2)
    text = prog.lower(hleaves, state, jax.random.PRNGKey(0), quad_data,
                      quad_data, length=2, n_steps=1).as_text(
                          debug_info=True)
    for scope in ("store.gather", "store.scatter", "engine.eval",
                  "permfl.grad", "permfl.prox", "permfl.team",
                  "permfl.global"):
        assert scope in text, scope


def test_engine_spans_name_every_dispatch(quad_data):
    """No dispatch is labelled a compile: the first call's and a warm
    call's spans are the same."""
    algo = PerMFL(quad_loss, HP)
    kw = dict(metric_fn=neg_loss, rounds=4, m=M, n=N, eval_every=3)
    names = []
    for _ in range(2):
        log = SpanLog()
        with log.activate():
            engine.run_experiment(algo, jnp.zeros(D), quad_data, quad_data,
                                  **kw)
        names.append([s.name for s in log.spans
                      if not s.name.startswith("gc.")])
    assert names[0] == names[1] == ["build", "dispatch", "eval",
                                    "dispatch", "eval"]
