"""The program's DeepSeek-V2-Lite share against the plain reference
(``configs/deepseek-v2-lite.py``) at a small size on the CPU, with
seeded random weights and adapters whose B is drawn too: latent
attention with YaRN rope, and the whole LoRA model's logits."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

import chipbench_path  # noqa: F401
import tiny_cells

HI = lax.Precision.HIGHEST


@pytest.fixture(scope="module")
def parts():
    from chipbench import harness
    name = "deepseek-v2-lite.lora"
    cell = harness.find_cell(name, tiny_cells.TINY[name])
    harness.program_path()
    driver = harness.load_module(
        harness.BENCH_DIR / "drivers" / "lora_experiment.py", "lora_drv")
    cfg = {**cell.config, **cell.params["config"]}
    return cell.reference, cfg, driver.program_config(cfg)


def _drawn(key, tree, scale=0.05):
    leaves, treedef = jax.tree.flatten(tree)
    keys = jax.random.split(key, len(leaves))
    return jax.tree.unflatten(treedef, [
        scale * jax.random.normal(k, a.shape, jnp.float32)
        for k, a in zip(keys, leaves)])


def test_yarn_at_published_widths(parts):
    """Frequencies and softmax scale of the published configuration."""
    from repro.models import mla
    ref, _, _ = parts
    from chipbench import harness
    cfg = harness.find_cell("deepseek-v2-lite.lora").config
    pcfg = harness.load_module(
        harness.BENCH_DIR / "drivers" / "lora_experiment.py",
        "lora_drv").program_config(cfg)
    np.testing.assert_allclose(
        mla.yarn_inv_freq(64, 10_000.0, pcfg.yarn), ref.inv_freq(cfg),
        rtol=1e-6)
    m = 0.1 * 0.707 * np.log(40) + 1
    assert mla.softmax_scale(pcfg) == pytest.approx(192 ** -0.5 * m * m)


@pytest.mark.parametrize("seq_len", [16, 64])
def test_latent_attention_matches_reference(parts, seq_len):
    """MLA with YaRN rope, LoRA on every projection, against the
    reference's explicit softmax (rope pairs kept interleaved there, de-
    interleaved in the program): within and past the original context."""
    from repro.models import mla
    ref, cfg, pcfg = parts
    base, ad = ref.init(jax.random.PRNGKey(0), cfg, jnp.float32)
    p = jax.tree.map(lambda a: a[0], base["dense"]["mla"])
    ad = _drawn(jax.random.PRNGKey(1), jax.tree.map(lambda a: a[0],
                                                    ad["dense"]))
    x = jax.random.normal(jax.random.PRNGKey(2), (2, seq_len,
                                                  cfg["hidden_size"]))
    scale = cfg["lora"]["alpha"] / cfg["lora"]["rank"]
    with jax.default_matmul_precision("highest"):
        got = jax.jit(lambda p_, a_, x_: mla.mla_apply(
            p_, pcfg, x_.reshape(1, -1, x_.shape[-1]), seq_len=seq_len,
            adapters=jax.tree.map(lambda a: a[None], a_), scale=scale))(
                p, ad, x)
    want = jax.vmap(lambda s: ref.attention(p, ad, s, cfg, HI))(x)
    np.testing.assert_allclose(got.reshape(want.shape), want, rtol=2e-4,
                               atol=2e-4)


def test_lora_model_logits_match_reference(parts):
    """Every layer (dense, then held-experts MoE), LoRA on, each device
    its own adapters: the program's logits against the reference's."""
    from repro.models import lora_lm
    ref, cfg, pcfg = parts
    base, ad = ref.init(jax.random.PRNGKey(3), cfg, jnp.float32)
    ads = [_drawn(jax.random.PRNGKey(4 + i), ad) for i in range(2)]
    stacked = jax.tree.map(lambda *a: jnp.stack(a), *ads)
    tok = jax.random.randint(jax.random.PRNGKey(6), (2, 2, 16), 0,
                             cfg["vocab_size"])
    with jax.default_matmul_precision("highest"):
        got = jax.jit(lambda b, a, t: lora_lm.logits(b, a, pcfg, t))(
            base, stacked, tok)
    want = jnp.stack([jax.jit(lambda b, a, t: ref.apply(b, a, t, cfg, HI))(
        base, ads[i], tok[i]) for i in range(2)])
    np.testing.assert_allclose(got, want, rtol=5e-4, atol=5e-4)
