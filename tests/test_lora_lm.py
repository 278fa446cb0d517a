"""The federated LoRA path for zoo architectures (DeepSeek-V2-Lite's
reduced variant on the CPU): the held-experts layer, the adapters over a
frozen base, the round's frozen-base argument, and a scenario of the
"zoo" kind end to end."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.deepseek_v2_lite import REDUCED
from repro.core import PerMFL
from repro.core.permfl import PerMFLHParams, init_state, permfl_round
from repro.models import lora_lm, moe

M, N, S, T = 2, 2, 2, 8


def _cfg(held=None, **moe_kw):
    return dataclasses.replace(REDUCED, moe=dataclasses.replace(
        REDUCED.moe, held=held, **moe_kw))


def _tokens(key, shape, cfg=REDUCED):
    tok = jax.random.randint(key, shape[:-1] + (shape[-1] + 1,), 0,
                             cfg.vocab_size)
    return {"x": tok[..., :-1], "y": tok[..., 1:]}


def _random_adapters(key, cfg, lead=()):
    """Adapters with B drawn too, so that they change the model."""
    ad = lora_lm.init_adapters(key, cfg)
    leaves, tree = jax.tree.flatten(ad)
    keys = jax.random.split(jax.random.fold_in(key, 1), len(leaves))
    leaves = [0.1 * jax.random.normal(k, lead + a.shape)
              for k, a in zip(keys, leaves)]
    return jax.tree.unflatten(tree, leaves)


@pytest.mark.parametrize("shares", [2, 4])
def test_expert_shares_add_up_to_the_uncut_layer(shares):
    """Summed over every chip's share, with the shared experts counted
    once, the held-experts layer gives the whole layer's output."""
    e = REDUCED.moe.num_experts
    x = jax.random.normal(jax.random.PRNGKey(0), (24, REDUCED.d_model))
    full = moe.held_init(jax.random.PRNGKey(1), _cfg(held=(0, e)))
    whole, _ = moe.held_apply(full, _cfg(held=(0, e)), x)
    shared = jax.jit(lambda p, v: moe.layers.frozen_swiglu(p, v))(
        full["shared"], x)
    per = e // shares
    total = -(shares - 1) * shared
    pairs = 0
    for c in range(shares):
        cfg = _cfg(held=(c * per, per))
        part = dict(full, experts=jax.tree.map(
            lambda a: a[c * per:(c + 1) * per], full["experts"]))
        y, counters = moe.held_apply(part, cfg, x)
        total = total + y
        pairs += int(counters["held_pairs"])
        assert int(counters["dropped_pairs"]) == 0
    assert pairs == x.shape[0] * REDUCED.moe.top_k
    np.testing.assert_allclose(total, whole, rtol=1e-5, atol=1e-5)


def test_no_pair_dropped_when_every_token_picks_the_held_experts():
    """A router skewed so that every token's whole top-k lies on this
    chip: all T * k pairs are computed, one expert taking every token."""
    cfg = _cfg(held=(0, 4))
    k = cfg.moe.top_k
    p = moe.held_init(jax.random.PRNGKey(2), cfg)
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(3), (32, cfg.d_model)))
    bias = jnp.zeros((cfg.d_model, cfg.moe.num_experts)).at[:, :k].set(
        jnp.asarray([3.0, 2.0, 1.0])[:k])
    p["router"] = p["router"] * 1e-3 + bias
    y, counters = moe.held_apply(p, cfg, x)
    assert int(counters["held_pairs"]) == 32 * k
    assert int(counters["dropped_pairs"]) == 0
    assert int(counters["max_per_expert"]) == 32
    # the same pairs, one expert at a time
    probs = jax.nn.softmax(x @ p["router"], -1)
    ref = moe.layers.frozen_swiglu(p["shared"], x)
    for e in range(k):
        ex = jax.tree.map(lambda a: a[e], p["experts"])
        ref = ref + probs[:, e:e + 1] * moe.layers.frozen_swiglu(ex, x)
    np.testing.assert_allclose(y, ref, rtol=1e-4, atol=1e-4)


def test_fresh_adapters_leave_the_base_unchanged():
    """B = 0: the LoRA model's logits are the base's (to the rounding
    of two differently fused programs)."""
    base = lora_lm.init_base(jax.random.PRNGKey(4), REDUCED, jnp.float32)
    ad = lora_lm.init_adapters(jax.random.PRNGKey(5), REDUCED)
    ad = jax.tree.map(lambda a: jnp.broadcast_to(a, (3,) + a.shape), ad)
    tok = _tokens(jax.random.PRNGKey(6), (3, S, T))["x"]
    logits = jax.jit(lambda a: lora_lm.logits(base, a, REDUCED, tok))
    with_ad, alone = logits(ad), logits(None)
    np.testing.assert_allclose(with_ad, alone, rtol=1e-5, atol=1e-5)
    moved = logits(_random_adapters(jax.random.PRNGKey(7), REDUCED, (3,)))
    assert not np.allclose(moved, alone)


def test_gradient_of_the_summed_losses_is_each_devices_own():
    """The round over a frozen base takes the gradient of the summed
    (M, N) losses: each device's slice of it is the gradient of that
    device's loss alone, and the round moves the adapters."""
    base = lora_lm.init_base(jax.random.PRNGKey(8), REDUCED, jnp.float32)
    ad = _random_adapters(jax.random.PRNGKey(9), REDUCED, (M, N))
    data = _tokens(jax.random.PRNGKey(10), (M, N, S, T))
    loss, _ = lora_lm.fns(REDUCED)
    grad = jax.jit(jax.grad(lambda a, d: jnp.sum(loss(a, d, base))))
    with jax.default_matmul_precision("highest"):
        stacked = grad(ad, data)
        for i in range(M):
            for j in range(N):
                one = jax.tree.map(lambda a: a[i:i + 1, j:j + 1], (ad, data))
                alone = grad(*one)
                for a, b in zip(jax.tree.leaves(stacked),
                                jax.tree.leaves(alone)):
                    np.testing.assert_allclose(a[i, j], b[0, 0], rtol=1e-4,
                                               atol=1e-7)
    hp = PerMFLHParams(alpha=0.05, k_team=1, l_local=2)
    ad0 = jax.tree.map(lambda a: a[0, 0], ad)
    out = permfl_round(init_state(ad0, M, N), data, hp, loss, m_teams=M,
                       n_devices=N, base=base)
    moved = jax.tree.map(lambda a, b: float(jnp.abs(a - b).max()),
                         out.theta, init_state(ad0, M, N).theta)
    assert min(jax.tree.leaves(moved)) > 0


def test_a_base_selects_the_stacked_convention():
    """A loss written over the (M, N) stack, given an empty base, runs
    the same round as that loss per device with no base."""
    def per_device(p, b):
        return jnp.mean((b["x"] @ p["w"] - b["y"]) ** 2)

    def stacked(theta, data, base):
        assert base == {}
        return jax.vmap(jax.vmap(per_device))(theta, data)

    keys = jax.random.split(jax.random.PRNGKey(12), 3)
    data = {"x": jax.random.normal(keys[0], (M, N, 6, 5)),
            "y": jax.random.normal(keys[1], (M, N, 6))}
    state = init_state({"w": jax.random.normal(keys[2], (5,))}, M, N)
    hp = PerMFLHParams(alpha=0.05, k_team=2, l_local=3)
    want = permfl_round(state, data, hp, per_device, m_teams=M, n_devices=N)
    got = permfl_round(state, data, hp, stacked, m_teams=M, n_devices=N,
                       base={})
    for a, b in zip(jax.tree.leaves((want.x, want.w, want.theta)),
                    jax.tree.leaves((got.x, got.w, got.theta))):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


def test_frozen_base_is_an_argument_of_the_compiled_experiment():
    """Another base runs the same compiled program (no recompile) and
    gives another result: the base is an input, not a constant."""
    from repro.train import engine
    loss, metric = lora_lm.fns(REDUCED)
    algo = PerMFL(loss, PerMFLHParams(k_team=1, l_local=2))
    ad0 = lora_lm.init_adapters(jax.random.PRNGKey(11), REDUCED)
    tr = _tokens(jax.random.PRNGKey(12), (M, N, S, T))
    va = _tokens(jax.random.PRNGKey(13), (M, N, 1, T))
    losses = []
    for seed in (14, 15):
        base = lora_lm.init_base(jax.random.PRNGKey(seed), REDUCED,
                                 jnp.float32)
        before = engine._scan_program.cache_info().misses
        res = engine.run_experiment(algo, ad0, tr, va, metric_fn=metric,
                                    rounds=1, m=M, n=N, base=base)
        losses.append(res.train_loss[-1])
    assert engine._scan_program.cache_info().misses == before
    assert losses[0] != losses[1] and np.all(np.isfinite(losses))


def test_zoo_scenario_runs_through_the_scenarios():
    from repro.scenarios import run_scenario
    from repro.scenarios.spec import DataSpec, FLScenario, ModelSpec
    s = FLScenario(
        name="lora/tokens/deepseek-v2-lite-reduced",
        data=DataSpec(dataset="tokens", partitioner="topics", m_teams=M,
                      n_devices=N, samples_per_device=3, seq_len=T,
                      vocab_size=REDUCED.vocab_size),
        model=ModelSpec(kind="zoo", arch="deepseek-v2-lite", reduced=True),
        rounds=2)
    assert FLScenario.from_dict(s.to_dict()) == s
    res = run_scenario(s, eval_every=1)
    assert len(res.train_loss) == 2 and np.all(np.isfinite(res.train_loss))
    assert 0.0 <= res.pm_acc[-1] <= 1.0


def test_legacy_specs_serialize_without_the_zoo_fields():
    from repro.scenarios.spec import DataSpec, ModelSpec, _set_fields
    assert "arch" not in _set_fields(ModelSpec(kind="cnn"))
    assert "seq_len" not in _set_fields(DataSpec())
    with pytest.raises(ValueError):
        ModelSpec(kind="zoo", arch="deepseek-v2-lite").config(DataSpec())


def test_rows_past_the_groups_never_reach_a_token(monkeypatch):
    """The TPU's grouped matmul leaves rows past the last group
    unwritten, in its product and in its input gradient. With those rows
    poisoned (NaN) here, the layer's output and gradients stay finite
    and equal to the clean ones."""
    ragged_dot = jax.lax.ragged_dot

    def poison(y, sizes):
        past = jnp.arange(y.shape[0]) >= jnp.sum(sizes)
        return jnp.where(past[:, None], jnp.nan, y).astype(y.dtype)

    @jax.custom_vjp
    def poisoned_dot(x, rhs, sizes):
        return poison(ragged_dot(x, rhs, sizes,
                                 preferred_element_type=jnp.float32), sizes)

    def fwd(x, rhs, sizes):
        return poisoned_dot(x, rhs, sizes), (x, rhs, sizes)

    def bwd(res, ct):
        x, rhs, sizes = res
        dx = ragged_dot(ct.astype(rhs.dtype), jnp.swapaxes(rhs, 1, 2), sizes,
                        preferred_element_type=jnp.float32)
        return poison(dx, sizes).astype(x.dtype), None, None

    poisoned_dot.defvjp(fwd, bwd)

    def poisoned(lhs, rhs, group_sizes, **kw):
        return poisoned_dot(lhs, rhs, group_sizes)

    cfg = _cfg(held=(0, 4))
    p = moe.held_init(jax.random.PRNGKey(16), cfg)
    x = jax.random.normal(jax.random.PRNGKey(17), (20, cfg.d_model))

    def total(v):
        y, _ = moe.held_apply(p, cfg, v)
        return jnp.sum(jnp.sin(y))

    clean = jax.value_and_grad(total)(x)
    monkeypatch.setattr(jax.lax, "ragged_dot", poisoned)
    dirty = jax.value_and_grad(total)(x)
    for a, b in zip(clean, dirty):
        assert np.all(np.isfinite(b))
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


def _in_mode(monkeypatch, mode, fn, *args):
    """``fn(*args)`` jitted afresh under ``REPRO_KERNEL_MODE=mode``."""
    monkeypatch.setenv("REPRO_KERNEL_MODE", mode)
    out = jax.jit(lambda *a: fn(*a))(*args)
    return [np.asarray(a, np.float32) for a in jax.tree.leaves(out)]


@pytest.mark.parametrize("dtype,layer_tol,grad_tol",
                         [(jnp.float32, 1e-5, 1e-5),
                          (jnp.bfloat16, 1e-2, 1e-1)])
def test_fused_latent_attention_matches_the_plain_softmax(
        monkeypatch, dtype, layer_tol, grad_tol):
    """At T=256, with a base held in ``dtype``: the MLA layer's output
    and the adapters' gradient through the whole LoRA model agree
    between the fused kernel (interpret mode) and the plain softmax
    (xla), each leaf within its tolerance times its largest entry. In
    bfloat16 the kernel's backward pass rounds dO and dS to bfloat16
    before its products, as a TPU's default precision does for the
    plain softmax's, where XLA's CPU backend multiplies in float32: the
    gradients then part by some percent (at most 7.4% measured)."""
    t = 256
    p = lora_lm.mla.mla_init(jax.random.PRNGKey(20), REDUCED, dtype)
    ad = jax.tree.map(lambda a: a[:, 0], _random_adapters(
        jax.random.PRNGKey(21), REDUCED, (2,))["dense"])
    x = jax.random.normal(jax.random.PRNGKey(22), (2, t, REDUCED.d_model))

    def layer(a, v):
        return lora_lm.mla.mla_apply(p, REDUCED, v, seq_len=t, adapters=a,
                                     scale=REDUCED.lora.scale)

    base = lora_lm.init_base(jax.random.PRNGKey(23), REDUCED, dtype)
    ads = _random_adapters(jax.random.PRNGKey(24), REDUCED, (1, 2))
    data = _tokens(jax.random.PRNGKey(25), (1, 2, 1, t))
    loss, _ = lora_lm.fns(REDUCED)

    def grad(a):
        return jax.grad(lambda b: jnp.sum(loss(b, data, base)))(a)

    for fn, args, tol in ((layer, (ad, x), layer_tol),
                          (grad, (ads,), grad_tol)):
        got = _in_mode(monkeypatch, "interpret", fn, *args)
        want = _in_mode(monkeypatch, "xla", fn, *args)
        for g, w in zip(got, want):
            np.testing.assert_allclose(g, w, atol=tol * np.abs(w).max(),
                                       rtol=0)
