"""Per-kernel correctness: Pallas body (interpret=True on CPU) vs the
pure-jnp oracle in ref.py, swept over shapes and dtypes."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

os.environ.setdefault("FORCE_PALLAS_INTERPRET", "0")  # per-test control


def _interp(monkeypatch):
    monkeypatch.setenv("FORCE_PALLAS_INTERPRET", "1")


# ---------------------------------------------------------------------------
# prox_update — fused PerMFL device step (eq. 4)
# ---------------------------------------------------------------------------

PROX_SHAPES = [(128,), (1024,), (257,), (8, 128), (3, 5, 64), (4096,)]


@pytest.mark.parametrize("shape", PROX_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("momentum,wd", [(0.0, 0.0), (0.9, 0.0), (0.9, 0.01)])
def test_prox_sgd_matches_ref(monkeypatch, shape, dtype, momentum, wd):
    _interp(monkeypatch)
    from repro.kernels.prox_update.ops import prox_sgd
    from repro.kernels.prox_update.ref import prox_sgd_ref

    key = jax.random.PRNGKey(hash((shape, str(dtype))) % 2**31)
    ks = jax.random.split(key, 4)
    theta = jax.random.normal(ks[0], shape).astype(dtype)
    grad = jax.random.normal(ks[1], shape).astype(dtype)
    anchor = jax.random.normal(ks[2], shape).astype(dtype)
    mom = jax.random.normal(ks[3], shape).astype(jnp.float32)

    t_k, m_k = prox_sgd(theta, grad, anchor, mom, alpha=0.05, lam=0.7,
                        momentum=momentum, weight_decay=wd)
    t_r, m_r = prox_sgd_ref(theta, grad, anchor, mom_buf=mom, alpha=0.05,
                            lam=0.7, momentum=momentum, weight_decay=wd)
    tol = 1e-6 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(t_k, np.float32),
                               np.asarray(t_r, np.float32), atol=tol, rtol=tol)
    np.testing.assert_allclose(np.asarray(m_k), np.asarray(m_r),
                               atol=tol, rtol=tol)


def test_prox_sgd_formula(monkeypatch):
    """theta' = theta - alpha*g - alpha*lam*(theta - w), momentum=0."""
    _interp(monkeypatch)
    from repro.kernels.prox_update.ops import prox_sgd

    k = jax.random.PRNGKey(0)
    theta, grad, anchor = (jax.random.normal(kk, (513,))
                           for kk in jax.random.split(k, 3))
    alpha, lam = 0.03, 1.5
    t_new, _ = prox_sgd(theta, grad, anchor, alpha=alpha, lam=lam)
    expect = theta - alpha * grad - alpha * lam * (theta - anchor)
    np.testing.assert_allclose(np.asarray(t_new), np.asarray(expect),
                               atol=1e-6)


def test_prox_sgd_tree_pytree(monkeypatch):
    _interp(monkeypatch)
    from repro.kernels.prox_update.ops import prox_sgd_tree

    k = jax.random.PRNGKey(1)
    mk = lambda kk: {"a": jax.random.normal(kk, (65, 3)),
                     "b": [jax.random.normal(kk, (7,))]}
    theta, grad, anchor = mk(k), mk(jax.random.split(k)[0]), mk(k)
    t_new, m_new = prox_sgd_tree(theta, grad, anchor, alpha=0.1, lam=0.5)
    assert jax.tree.structure(t_new) == jax.tree.structure(theta)
    assert jax.tree.structure(m_new) == jax.tree.structure(theta)
    for leaf in jax.tree.leaves(t_new):
        assert np.isfinite(np.asarray(leaf)).all()


# ---------------------------------------------------------------------------
# quantize — fused int8 stochastic quantize/pack (comm uplink)
# ---------------------------------------------------------------------------

QUANT_SHAPES = [(128,), (1024,), (257,), (8, 128), (3, 5, 64), (4096,)]


@pytest.mark.parametrize("shape", QUANT_SHAPES)
def test_quantize_int8_pallas_matches_ref(monkeypatch, shape):
    _interp(monkeypatch)
    from repro.kernels.quantize.ops import quantize_int8
    from repro.kernels.quantize.ref import quantize_int8_ref

    key = jax.random.PRNGKey(hash(shape) % 2**31)
    v = jax.random.normal(key, shape) * 2.5
    noise = jax.random.uniform(jax.random.fold_in(key, 1), shape)
    q_k, s_k, dq_k = quantize_int8(v, noise)
    q_r, s_r, dq_r = quantize_int8_ref(v.reshape(-1), noise.reshape(-1))
    # same explicit noise -> bit-identical across backends
    np.testing.assert_array_equal(np.asarray(q_k).reshape(-1),
                                  np.asarray(q_r))
    np.testing.assert_array_equal(np.asarray(s_k), np.asarray(s_r))
    np.testing.assert_array_equal(np.asarray(dq_k).reshape(-1),
                                  np.asarray(dq_r))


def test_quantize_int8_error_bound():
    """Stochastic rounding error < 1 step = rowmax/127, per element."""
    from repro.kernels.quantize.ref import quantize_int8_ref

    key = jax.random.PRNGKey(11)
    v = jax.random.normal(key, (5000,)) * 4.0
    noise = jax.random.uniform(jax.random.fold_in(key, 1), (5000,))
    _, scales, dq = quantize_int8_ref(v, noise)
    step = np.repeat(np.asarray(scales), 128)[:5000]
    assert (np.abs(np.asarray(dq) - np.asarray(v)) <= step + 1e-7).all()


def test_quantize_int8_unbiased():
    """E[dq] = v over the rounding noise."""
    from repro.kernels.quantize.ref import quantize_int8_ref

    v = jax.random.normal(jax.random.PRNGKey(12), (256,))
    keys = jax.random.split(jax.random.PRNGKey(13), 500)
    dqs = jax.vmap(lambda k: quantize_int8_ref(
        v, jax.random.uniform(k, (256,)))[2])(keys)
    err = np.abs(np.asarray(dqs.mean(0)) - np.asarray(v)).max()
    # step ~ 3/127 ~ 0.024; 500 draws shrink the mean error well below it
    assert err < 5e-3, err


def test_quantize_int8_roundtrip_pack_unpack():
    from repro.kernels.quantize.ref import (dequantize_int8_ref,
                                            quantize_int8_ref)

    v = jax.random.normal(jax.random.PRNGKey(14), (777,))
    noise = jax.random.uniform(jax.random.PRNGKey(15), (777,))
    q, s, dq = quantize_int8_ref(v, noise)
    assert q.dtype == jnp.int8 and s.shape == (-(-777 // 128),)
    np.testing.assert_array_equal(np.asarray(dequantize_int8_ref(q, s)),
                                  np.asarray(dq))


# ---------------------------------------------------------------------------
# flash_attention — causal / sliding-window GQA
# ---------------------------------------------------------------------------

def _naive_attention(q, k, v, causal=True, window=0, q_offset=None):
    """Dense O(s^2) oracle for the oracle (independent of ref.py blocking)."""
    b, sq, hq, d = q.shape
    skv, hkv = k.shape[1], k.shape[2]
    rep = hq // hkv
    k = np.repeat(np.asarray(k, np.float64), rep, axis=2)
    v = np.repeat(np.asarray(v, np.float64), rep, axis=2)
    q = np.asarray(q, np.float64) * d ** -0.5
    if q_offset is None:
        q_offset = skv - sq
    s = np.einsum("bqhd,bkhd->bhqk", q, k)
    q_pos = np.arange(sq) + q_offset
    kv_pos = np.arange(skv)
    mask = np.ones((sq, skv), bool)
    if causal:
        mask &= kv_pos[None, :] <= q_pos[:, None]
    if window > 0:
        mask &= kv_pos[None, :] > q_pos[:, None] - window
    s = np.where(mask[None, None], s, -1e30)
    p = np.exp(s - s.max(-1, keepdims=True))
    p = np.where(mask[None, None], p, 0.0)
    p /= np.maximum(p.sum(-1, keepdims=True), 1e-30)
    out = np.einsum("bhqk,bkhd->bqhd", p, v)
    return out.astype(np.float32)


ATTN_CASES = [
    # (b, sq, skv, hq, hkv, d, causal, window)
    (1, 128, 128, 4, 4, 64, True, 0),
    (2, 128, 128, 4, 1, 64, True, 0),       # GQA
    (1, 256, 256, 2, 2, 64, True, 64),      # sliding window
    (1, 64, 64, 4, 2, 32, False, 0),        # non-causal (encoder)
    (2, 1, 96, 4, 2, 64, True, 0),          # decode: 1 query vs cache
]


@pytest.mark.parametrize("case", ATTN_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_attention_ref_matches_naive(case, dtype):
    b, sq, skv, hq, hkv, d, causal, window = case
    from repro.kernels.flash_attention.ref import attention_ref

    key = jax.random.PRNGKey(3)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (b, sq, hq, d)).astype(dtype)
    k = jax.random.normal(kk, (b, skv, hkv, d)).astype(dtype)
    v = jax.random.normal(kv, (b, skv, hkv, d)).astype(dtype)
    out = attention_ref(q, k, v, causal=causal, window=window, kv_chunk=32)
    want = _naive_attention(q.astype(jnp.float32), k.astype(jnp.float32),
                            v.astype(jnp.float32), causal, window)
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(out, np.float32), want,
                               atol=tol, rtol=tol)


@pytest.mark.parametrize("case", ATTN_CASES[:3])
def test_attention_pallas_matches_ref(monkeypatch, case):
    _interp(monkeypatch)
    b, sq, skv, hq, hkv, d, causal, window = case
    from repro.kernels.flash_attention.ops import attention
    from repro.kernels.flash_attention.ref import attention_ref

    key = jax.random.PRNGKey(4)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (b, sq, hq, d))
    k = jax.random.normal(kk, (b, skv, hkv, d))
    v = jax.random.normal(kv, (b, skv, hkv, d))
    out = attention(q, k, v, causal=causal, window=window,
                    block_q=64, block_kv=64)
    want = attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_attention_decode_offset():
    """q_offset places the single query at the end of the cache."""
    from repro.kernels.flash_attention.ref import attention_ref

    key = jax.random.PRNGKey(5)
    kq, kk, kv = jax.random.split(key, 3)
    s = 48
    q_full = jax.random.normal(kq, (1, s, 2, 32))
    k_full = jax.random.normal(kk, (1, s, 2, 32))
    v_full = jax.random.normal(kv, (1, s, 2, 32))
    full = attention_ref(q_full, k_full, v_full, causal=True)
    one = attention_ref(q_full[:, -1:], k_full, v_full, causal=True,
                        q_offset=s - 1)
    np.testing.assert_allclose(np.asarray(one[:, 0]), np.asarray(full[:, -1]),
                               atol=1e-5, rtol=1e-5)


# ---------------------------------------------------------------------------
# causal_attention — training attention with its backward pass
# ---------------------------------------------------------------------------

# latent attention's softmax scale: (nope + rope)^-0.5 m^2, YaRN factor 40
MLA_SCALE = 192 ** -0.5 * (0.1 * 0.707 * np.log(40.0) + 1.0) ** 2

CAUSAL_CASES = [
    # (b, h, t, dqk, dv, dtype, scale)
    (2, 3, 256, 192, 128, jnp.float32, MLA_SCALE),    # MLA's widths
    (3, 2, 256, 192, 128, jnp.bfloat16, MLA_SCALE),   # as the layer feeds it
    (2, 4, 256, 128, 128, jnp.float32, 128 ** -0.5),  # dqk == dv
    (2, 2, 200, 192, 128, jnp.float32, MLA_SCALE),    # padded to the blocks
    (1, 2, 768, 192, 128, jnp.bfloat16, MLA_SCALE),   # three blocks a row
]


def _causal_inputs(key, b, h, t, dqk, dv, dtype):
    kq, kk, kv, kc = jax.random.split(key, 4)
    q = jax.random.normal(kq, (b, h, t, dqk)).astype(dtype)
    k = jax.random.normal(kk, (b, h, t, dqk)).astype(dtype)
    v = jax.random.normal(kv, (b, h, t, dv)).astype(dtype)
    return q, k, v, jax.random.normal(kc, (b, h, t, dv))


def _causal_value_and_grads(q, k, v, ct, scale, mode):
    from repro.kernels.flash_attention.ops import causal_attention

    def loss(q_, k_, v_):
        o = causal_attention(q_, k_, v_, scale=scale, mode=mode)
        return jnp.sum(o * ct), o

    (_, o), grads = jax.jit(jax.value_and_grad(
        loss, argnums=(0, 1, 2), has_aux=True))(q, k, v)
    return [np.asarray(a, np.float32) for a in (o, *grads)]


def test_causal_attention_ref_matches_naive():
    """The plain softmax reference against a float64 one."""
    from repro.kernels.flash_attention.ref import causal_attention_ref
    q, k, v, _ = _causal_inputs(jax.random.PRNGKey(6), 2, 2, 64, 24, 16,
                                jnp.float32)
    with jax.default_matmul_precision("highest"):
        out = causal_attention_ref(q, k, v, scale=MLA_SCALE)
    s = np.einsum("bhqd,bhkd->bhqk", np.asarray(q, np.float64),
                  np.asarray(k, np.float64)) * MLA_SCALE
    s = np.where(np.tril(np.ones((64, 64), bool)), s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    want = np.einsum("bhqk,bhkd->bhqd", p / p.sum(-1, keepdims=True),
                     np.asarray(v, np.float64))
    np.testing.assert_allclose(np.asarray(out), want, atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("case", CAUSAL_CASES)
def test_causal_attention_kernel_matches_ref(case):
    """The splash kernels (interpret mode) against the plain softmax,
    in value and in the gradients of q, k and v. f32 inputs: within
    1e-5 of the largest entry. bf16 inputs: against the softmax of the
    same bf16 values in f32, no further off than the reference itself
    computed in bf16, to within a factor of two."""
    b, h, t, dqk, dv, dtype, scale = case
    q, k, v, ct = _causal_inputs(jax.random.PRNGKey(7), b, h, t, dqk, dv,
                                 dtype)
    got = _causal_value_and_grads(q, k, v, ct, scale, "interpret")
    wide = [a.astype(jnp.float32) for a in (q, k, v)]
    with jax.default_matmul_precision("highest"):
        want = _causal_value_and_grads(*wide, ct, scale, "xla")
    if dtype == jnp.float32:
        for g, w in zip(got, want):
            np.testing.assert_allclose(g, w, atol=1e-5 * np.abs(w).max(),
                                       rtol=0)
        return
    plain = _causal_value_and_grads(q, k, v, ct, scale, "xla")
    for g, p, w in zip(got, plain, want):
        assert (np.abs(g - w).max()
                <= 2 * np.abs(p - w).max() + 1e-6 * np.abs(w).max())


def test_causal_attention_xla_mode_is_the_reference():
    """``mode="xla"`` runs the plain softmax itself, gradients and all."""
    from repro.kernels.flash_attention.ref import causal_attention_ref
    q, k, v, ct = _causal_inputs(jax.random.PRNGKey(8), 2, 2, 64, 24, 16,
                                 jnp.bfloat16)
    got = _causal_value_and_grads(q, k, v, ct, 0.3, "xla")

    def loss(q_, k_, v_):
        o = causal_attention_ref(q_, k_, v_, scale=0.3)
        return jnp.sum(o * ct), o

    (_, o), grads = jax.jit(jax.value_and_grad(
        loss, argnums=(0, 1, 2), has_aux=True))(q, k, v)
    for g, w in zip(got, (o, *grads)):
        np.testing.assert_array_equal(g, np.asarray(w, np.float32))


# ---------------------------------------------------------------------------
# rwkv6_scan — WKV recurrence with data-dependent decay
# ---------------------------------------------------------------------------

def _naive_wkv6(r, k, v, w, u, state=None):
    b, t, h, n = r.shape
    r, k, v, w = (np.asarray(x, np.float64) for x in (r, k, v, w))
    u = np.asarray(u, np.float64)
    S = np.zeros((b, h, n, n)) if state is None else np.asarray(state, np.float64)
    out = np.zeros((b, t, h, n))
    for bi in range(b):
        for hi in range(h):
            Sl = S[bi, hi].copy()
            for ti in range(t):
                kv = np.outer(k[bi, ti, hi], v[bi, ti, hi])
                out[bi, ti, hi] = r[bi, ti, hi] @ (Sl + u[hi][:, None] * kv)
                Sl = w[bi, ti, hi][:, None] * Sl + kv
            S[bi, hi] = Sl
    return out.astype(np.float32), S.astype(np.float32)


@pytest.mark.parametrize("b,t,h,n", [(1, 16, 1, 8), (2, 33, 2, 16),
                                     (1, 130, 1, 8)])
def test_wkv6_ref_matches_naive(b, t, h, n):
    from repro.kernels.rwkv6_scan.ref import wkv6_ref

    key = jax.random.PRNGKey(6)
    ks = jax.random.split(key, 5)
    r = jax.random.normal(ks[0], (b, t, h, n)) * 0.3
    k = jax.random.normal(ks[1], (b, t, h, n)) * 0.3
    v = jax.random.normal(ks[2], (b, t, h, n)) * 0.3
    w = jax.nn.sigmoid(jax.random.normal(ks[3], (b, t, h, n)))  # decay in (0,1)
    u = jax.random.normal(ks[4], (h, n)) * 0.1
    out, S = wkv6_ref(r, k, v, w, u)
    want_o, want_S = _naive_wkv6(r, k, v, w, u)
    np.testing.assert_allclose(np.asarray(out), want_o, atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(S), want_S, atol=1e-4, rtol=1e-4)


def test_wkv6_pallas_matches_ref(monkeypatch):
    _interp(monkeypatch)
    from repro.kernels.rwkv6_scan.ops import wkv
    from repro.kernels.rwkv6_scan.ref import wkv6_ref

    key = jax.random.PRNGKey(7)
    ks = jax.random.split(key, 5)
    b, t, h, n = 1, 64, 2, 16
    r = jax.random.normal(ks[0], (b, t, h, n)) * 0.3
    k = jax.random.normal(ks[1], (b, t, h, n)) * 0.3
    v = jax.random.normal(ks[2], (b, t, h, n)) * 0.3
    w = jax.nn.sigmoid(jax.random.normal(ks[3], (b, t, h, n)))
    u = jax.random.normal(ks[4], (h, n)) * 0.1
    out_k, S_k = wkv(r, k, v, w, u, chunk=16)
    out_r, S_r = wkv6_ref(r, k, v, w, u)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(S_k), np.asarray(S_r),
                               atol=1e-4, rtol=1e-4)


def test_wkv6_state_carry():
    """Splitting a sequence in two and carrying state == one long scan."""
    from repro.kernels.rwkv6_scan.ref import wkv6_ref

    key = jax.random.PRNGKey(8)
    ks = jax.random.split(key, 5)
    b, t, h, n = 1, 40, 1, 8
    r = jax.random.normal(ks[0], (b, t, h, n)) * 0.3
    k = jax.random.normal(ks[1], (b, t, h, n)) * 0.3
    v = jax.random.normal(ks[2], (b, t, h, n)) * 0.3
    w = jax.nn.sigmoid(jax.random.normal(ks[3], (b, t, h, n)))
    u = jax.random.normal(ks[4], (h, n)) * 0.1
    full, S_full = wkv6_ref(r, k, v, w, u)
    h1, S1 = wkv6_ref(r[:, :17], k[:, :17], v[:, :17], w[:, :17], u)
    h2, S2 = wkv6_ref(r[:, 17:], k[:, 17:], v[:, 17:], w[:, 17:], u, state=S1)
    np.testing.assert_allclose(np.asarray(h2), np.asarray(full[:, 17:]),
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(S2), np.asarray(S_full),
                               atol=1e-4, rtol=1e-4)


# ---------------------------------------------------------------------------
# moe_router — fused top-k gating
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("t,e,k", [(64, 8, 2), (128, 64, 6), (37, 16, 4)])
def test_route_topk_properties(t, e, k):
    from repro.kernels.moe_router.ops import route_topk

    logits = jax.random.normal(jax.random.PRNGKey(9), (t, e))
    gates, idx, aux = route_topk(logits, top_k=k)
    assert gates.shape == (t, k) and idx.shape == (t, k)
    g = np.asarray(gates)
    np.testing.assert_allclose(g.sum(-1), 1.0, atol=1e-5)  # renormalized
    assert (g >= 0).all()
    i = np.asarray(idx)
    assert ((i >= 0) & (i < e)).all()
    # top-k indices must be the true argmax set
    want = np.argsort(-np.asarray(logits), axis=-1)[:, :k]
    assert (np.sort(i, -1) == np.sort(want, -1)).all()


def test_route_topk_pallas_matches_ref(monkeypatch):
    _interp(monkeypatch)
    from repro.kernels.moe_router.ops import route_topk

    logits = jax.random.normal(jax.random.PRNGKey(10), (64, 16))
    g_k, i_k, _ = route_topk(logits, top_k=4)
    monkeypatch.setenv("FORCE_PALLAS_INTERPRET", "0")
    g_r, i_r, _ = route_topk(logits, top_k=4)
    # compare as (index -> gate) maps (order of equal gates may differ)
    gk = np.zeros((64, 16)); gr = np.zeros((64, 16))
    np.put_along_axis(gk, np.asarray(i_k), np.asarray(g_k), -1)
    np.put_along_axis(gr, np.asarray(i_r), np.asarray(g_r), -1)
    np.testing.assert_allclose(gk, gr, atol=1e-5)


def test_load_balance_loss_uniform_is_one():
    """Perfectly uniform routing gives aux loss ~= 1 (E * sum f*p)."""
    from repro.kernels.moe_router.ref import load_balance_loss, route_ref

    t, e = 512, 8
    logits = jnp.zeros((t, e))
    _, _, _, aux = route_ref(logits, top_k=2)
    lb = load_balance_loss(aux, e)
    np.testing.assert_allclose(float(lb), 1.0, rtol=0.05)


def test_load_balance_loss_skewed_is_large():
    """All tokens to one expert -> loss ~ E (worst case)."""
    from repro.kernels.moe_router.ref import load_balance_loss, route_ref

    t, e = 256, 8
    logits = jnp.zeros((t, e)).at[:, 0].set(20.0)
    _, _, _, aux = route_ref(logits, top_k=1)
    lb = load_balance_loss(aux, e)
    assert float(lb) > 4.0
