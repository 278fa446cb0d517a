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
import math
import re

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


# An array in the compiled program: dtype, dims, minor-to-major order, and
# the first tile of its layout, e.g. f32[8,32,144,7056]{3,2,1,0:T(8,128)}.
_ARRAY = re.compile(r"\b([a-z]+\d*)\[([\d,]*)\]\{([\d,]*)(?::T\(([\d,]+)\))?")
# ops whose result is a view of, or holds, buffers counted elsewhere
_NO_BUFFER = {"parameter", "get-tuple-element", "tuple", "bitcast", "while",
              "conditional", "call", "constant", "copy-start", "copy-done"}


def _tiled_over_real(hlo: str, min_bytes: int) -> tuple[float, int]:
    """Tiled bytes over real bytes of the arrays that the compiled program
    writes (not those inside fusions), summed over arrays whose tiled size
    is ``min_bytes`` or more: the tile pads each layout's minor dimensions,
    8 x 128 for the usual T(8,128). Returns (ratio, arrays counted)."""
    fused = set(re.findall(r"calls=(%[\w.\-]+)", hlo))
    real = tiled = count = 0
    comp = None
    for line in hlo.splitlines():
        head = re.match(r"(?:ENTRY\s+)?(%[\w.\-]+)\s", line)
        if head and line.rstrip().endswith("{"):
            comp = head.group(1)
            continue
        inst = re.match(r"\s*(?:ROOT\s+)?%[\w.\-]+\s*=\s*(.*?)\s([\w\-]+)\(",
                        line)
        if comp in fused or not inst or inst.group(2) in _NO_BUFFER:
            continue
        for dtype, dims, order, tile in _ARRAY.findall(inst.group(1)):
            dims = [int(d) for d in dims.split(",") if d]
            padded = list(dims)
            minor_first = [int(i) for i in order.split(",") if i]
            tile = [int(t) for t in tile.split(",") if t][::-1]
            for axis, t in zip(minor_first, tile):
                padded[axis] = -(-padded[axis] // t) * t
            size = max(int(re.sub(r"\D", "", dtype) or 8) // 8, 1)
            if size * math.prod(padded) >= min_bytes:
                real += size * math.prod(dims)
                tiled += size * math.prod(padded)
                count += 1
    return tiled / max(real, 1), count


def _stacked_cnn(sharding, *lead):
    return jax.tree.map(lambda l: _f32(sharding, *lead, *l.shape),
                        _cnn_shapes())


def _train_grad(sharding):
    """The PerMFL round's model gradient at paper-cnn.train's shapes: 8
    teams x 32 devices x 36 images, each device its own weights."""
    x = _f32(sharding, 8, 32, 36, *CNN.input_shape)
    y = jax.ShapeDtypeStruct((8, 32, 36), jnp.int32, sharding=sharding)
    grad = jax.vmap(jax.vmap(jax.grad(
        lambda p, x, y: paper_models.loss_fn(p, CNN, {"x": x, "y": y}))))
    return grad, (_stacked_cnn(sharding, 8, 32), x, y)


def _serve_forward(sharding):
    """The serve step's forward: 256 requests, each one image under its
    own model."""
    x = _f32(sharding, 256, *CNN.input_shape)
    fwd = jax.vmap(lambda p, v: paper_models.apply(p, CNN, v[None])[0])
    return fwd, (_stacked_cnn(sharding, 256), x)


@pytest.mark.parametrize("shape, min_bytes, limit", [
    ("train", 50_000_000, 1.5),
    ("serve", 1_000_000, 2.0),
])
def test_cnn_activations_are_lane_dense(one_chip, shape, min_bytes, limit):
    """The CNN's large activations do not carry small dimensions (the
    per-device batch, channels, patch taps) on the 128 lanes: the NHWC
    im2col form it replaced read 3.58 (train) and 6.32 (serve) here."""
    fn, args = {"train": _train_grad, "serve": _serve_forward}[shape](
        one_chip)
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    ratio, count = _tiled_over_real(hlo, min_bytes)
    assert count > 0
    assert ratio < limit, f"tiled / real bytes {ratio:.2f} over {count} arrays"


def _bench_module(name: str):
    """A module of the chip benchmark (``benchmarks/chip``), whose cells
    hold the programs' sizes."""
    import importlib.util
    import pathlib
    import sys
    bench = pathlib.Path(__file__).resolve().parents[1] / "benchmarks" / "chip"
    if str(bench) not in sys.path:
        sys.path.insert(0, str(bench))
    path = bench / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "tpu_compile_" + name.replace("/", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# temp bytes of the scanned programs compiled for v5e before the frozen
# base joined the round: the paper models pass no base and must compile
# to the same programs
PAPER_TEMP_BYTES = {"paper-cnn.train": 3_877_892_608,
                    "paper-mclr.cohort": 4_916_909_568}


@pytest.mark.parametrize("cell", sorted(PAPER_TEMP_BYTES))
def test_paper_cell_programs_keep_their_temp_bytes(one_chip, cell,
                                                    monkeypatch):
    monkeypatch.setenv("REPRO_KERNEL_MODE", "pallas")
    check = _bench_module("compile_check")
    from chipbench import harness
    harness.program_path()
    prog, args, kw = check.experiment_program(harness.find_cell(cell),
                                              one_chip)
    mem = prog.lower(*args, **kw).compile().memory_analysis()
    assert mem.temp_size_in_bytes == PAPER_TEMP_BYTES[cell]


def test_lora_cell_round_compiles_and_fits_the_chip(one_chip, monkeypatch):
    """deepseek-v2-lite.lora's scanned call at the published widths: one
    round of K=2 x L=2 steps over 16 devices' 32,768 tokens a step and
    the eval, each layer recomputed in the backward pass. It fits a
    v5e's 16 GB, runs the held experts as grouped matmuls and the
    latent attention as the fused causal kernel with its backward
    pass, all under the ``mla`` scope."""
    monkeypatch.setenv("REPRO_KERNEL_MODE", "pallas")
    _bench_module("compile_check")
    from chipbench import harness
    harness.program_path()
    cell = harness.find_cell("deepseek-v2-lite.lora")
    driver = _bench_module("drivers/lora_experiment")
    prog, args, kw = driver.described_call(cell, one_chip)
    compiled = prog.lower(*args, **kw).compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert total < 16e9, f"{total / 1e9:.2f} GB"
    text = compiled.as_text()
    assert "ragged-dot" in text and "tpu_custom_call" in text
    # the attention is the fused kernel, forward and backward, and no
    # (heads, T, T) score matrix is left in the program
    scopes = {}
    for kernel, op_name in re.findall(
            r"%(splash_mha_[a-z_]+?)(?:\.\d+)? = .*?op_name=\"([^\"]*)\"",
            text, re.S):
        scopes.setdefault(kernel, set()).add(op_name)
    # the forward without residuals, then the block's recomputation
    # with them, and the backward's dq and dk/dv kernels
    assert set(scopes) == {"splash_mha_fwd_no_residuals",
                           "splash_mha_fwd_residuals",
                           "splash_mha_dq_no_residuals",
                           "splash_mha_dkv_no_residuals"}, sorted(scopes)
    # each carries the scope `mla_device_ms_per_step.lora` sums
    for kernel, names in scopes.items():
        assert all("/mla/" in n for n in names), (kernel, names)
    assert not re.search(r"f32\[[0-9,]*1024,1024\]", text)
    # 9,650,629,632 bytes compiled with the fused attention (9,044,443,136
    # with the chunked softmax, whose chunks kept fewer sequences' q, k
    # and v alive at once)
    assert mem.temp_size_in_bytes < 9.7e9, mem.temp_size_in_bytes
