"""The main path's Pallas kernels compile for a TPU v5e.

Nothing here runs on a chip: the TPU compiler installed with JAX compiles
for a described ``v5e:2x2`` topology, and refuses what the chip would
refuse (unsupported Mosaic ops, casts, VMEM overruns). Interpret-mode
tests cannot see those failures. Each test asserts that the compiled
program holds the Pallas kernel (``tpu_custom_call``), so a kernel that
silently routed to its XLA reference would fail here too.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and pytest-xdist workers all import
this file.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.paper_cnn import CONFIG as CNN
from repro.kernels.compress import compress as C
from repro.kernels.interface import KernelType
from repro.kernels.prox_update.ops import prox_sgd_tree
from repro.kernels.prox_update.prox_update import prox_sgd_flat
from repro.kernels.quantize.quantize import quantize_int8_flat
from repro.models import paper_models


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        # the compiler otherwise writes its logs under the temp dir
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — any failure means no TPU compiler
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a described-topology executable cannot be read back from the
        # persistent cache without a chip, so keep these compiles out of it
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            compilation_cache.reset_cache()


def _cnn_shapes():
    return jax.eval_shape(
        functools.partial(paper_models.init_params, cfg=CNN),
        jax.random.PRNGKey(0))


def _largest_cnn_leaf() -> int:
    return max(leaf.size for leaf in jax.tree.leaves(_cnn_shapes()))


def _compile(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


def _f32(sharding, *shape):
    return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)


@pytest.mark.parametrize("size", (7850, 65536, 262144))
def test_prox_sgd_flat_compiles(one_chip, size):
    x = _f32(one_chip, size)
    s = _f32(one_chip)
    _compile(lambda t, g, a, m, al, la: prox_sgd_flat(
        t, g, a, m, alpha=al, lam=la), x, x, x, x, s, s)


def test_prox_kernel_keeps_its_name(one_chip):
    """The chip benchmark finds the prox kernel's device time by the
    custom call's name, ``%prox_sgd_flat`` (prox_roofline.train)."""
    x = _f32(one_chip, 7850)
    s = _f32(one_chip)
    text = jax.jit(lambda t, g, a, m, al, la: prox_sgd_flat(
        t, g, a, m, alpha=al, lam=la)).lower(
            x, x, x, x, s, s).compile().as_text()
    assert any(line.lstrip().startswith("%prox_sgd_flat")
               and "custom-call(" in line for line in text.splitlines())


def test_quantize_int8_flat_compiles(one_chip):
    x = _f32(one_chip, _largest_cnn_leaf())
    _compile(quantize_int8_flat, x, x)


# (kernel, number of (p,) array operands, number of scalar operands)
_COMPRESS = {
    "topk_select_flat": (
        lambda p: functools.partial(C.topk_select_flat, k=p // 10), 1, 1),
    "ef_topk_select_flat": (
        lambda p: functools.partial(C.ef_topk_select_flat, k=p // 10), 2, 1),
    "randk_select_flat": (
        lambda p: functools.partial(C.randk_select_flat, k=p // 10,
                                    scale=10.0), 2, 1),
    "ef_randk_select_flat": (
        lambda p: functools.partial(C.ef_randk_select_flat, k=p // 10), 3, 1),
    "ef_quantize_int8_flat": (lambda p: C.ef_quantize_int8_flat, 3, 0),
    "sign_compress_flat": (lambda p: C.sign_compress_flat, 1, 1),
    "ef_sign_compress_flat": (lambda p: C.ef_sign_compress_flat, 2, 1),
}


@pytest.mark.parametrize("where", ("cnn_leaf", "max_elems"))
@pytest.mark.parametrize("name", sorted(_COMPRESS))
def test_compress_kernel_compiles(one_chip, name, where):
    p = _largest_cnn_leaf() if where == "cnn_leaf" else C.PALLAS_MAX_ELEMS
    make, n_arrays, n_scalars = _COMPRESS[name]
    args = ([_f32(one_chip, p)] * n_arrays + [_f32(one_chip)] * n_scalars)
    _compile(make(p), *args)


def test_prox_sgd_tree_vmapped_over_teams_and_devices_compiles(one_chip):
    """The PerMFL device step as the round runs it: every CNN leaf,
    vmapped over (M, N) = (4, 8) senders, alpha/lam traced."""
    stacked = jax.tree.map(lambda l: _f32(one_chip, 4, 8, *l.shape),
                           _cnn_shapes())
    s = _f32(one_chip)

    def step(t, g, a, m, alpha, lam):
        return prox_sgd_tree(t, g, a, m, alpha=alpha, lam=lam,
                             mode=KernelType.PALLAS)

    in_axes = (0, 0, 0, 0, None, None)
    _compile(jax.vmap(jax.vmap(step, in_axes), in_axes),
             stacked, stacked, stacked, stacked, s, s)
