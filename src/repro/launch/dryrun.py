import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x input-shape) on the
production meshes, print memory/cost analysis, extract roofline terms.

MUST be run as its own process (the device-count flag is locked at first
jax init):

    PYTHONPATH=src python -m repro.launch.dryrun --arch phi3-mini-3.8b \
        --shape train_4k --mesh pod
    PYTHONPATH=src python -m repro.launch.dryrun --all --out results/

The train step lowered here is the PerMFL *device step* (eq. 4 prox-SGD
with momentum toward the team anchor) — the paper's technique as the
first-class training unit (DESIGN.md §2); --plain lowers vanilla SGD
instead (the paper's implicit ERM baseline). Decode shapes lower
``serve_step``: ONE token against a seq_len cache.
"""

import argparse
import dataclasses
import json
import sys
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import INPUT_SHAPES, ARCH_IDS, get_config
from repro.configs.base import active_param_count, param_count
from repro.launch.mesh import (batch_axes, make_production_mesh,
                               mesh_batch_size)
from repro.models import model as model_lib
from repro.roofline import analyze, model_flops_decode, model_flops_train
from repro.sharding.specs import (batch_pspecs, cache_pspecs, param_pspecs,
                                  to_named)

SWA_WINDOW = 8192           # sliding window used for dense long_500k
ACT_DTYPE = jnp.bfloat16


def resolve_config(arch: str, shape_name: str):
    """Arch config adjusted per input shape policy (DESIGN.md §5).

    Returns (cfg, skip_reason | None)."""
    cfg = get_config(arch)
    shape = INPUT_SHAPES[shape_name]
    if shape_name == "long_500k":
        if not cfg.supports_long_decode():
            return cfg, ("enc-dec decoder context is 448 by construction; "
                         "524k decode contradicts the architecture")
        needs_swa = any(k == "attn" for k in cfg.layer_kinds()) and \
            cfg.family not in ("hybrid",)
        if needs_swa:
            cfg = cfg.replace(sliding_window=SWA_WINDOW)
    if shape.kind == "decode" and cfg.is_encoder_decoder and \
            shape_name == "long_500k":
        return cfg, "skip"
    return cfg, None


def cache_len_for(cfg, shape) -> int:
    if cfg.sliding_window > 0:
        # steady-state ring-buffer window (the live KV state under SWA)
        return min(shape.seq_len, cfg.sliding_window)
    return shape.seq_len


def build_step_and_args(cfg, shape, mesh, *, plain=False):
    """Returns (fn, arg_specs, in_shardings, out_shardings)."""
    baxes = batch_axes(mesh)
    baxes_spec = baxes if len(baxes) > 1 else baxes[0]
    mesh_b = mesh_batch_size(mesh)
    p_specs = model_lib.param_specs(cfg, dtype=ACT_DTYPE)
    p_shard = to_named(param_pspecs(p_specs), mesh, p_specs)

    if shape.kind == "train":
        from repro.kernels.prox_update import prox_sgd_tree

        def step(theta, w, mom, batch):
            def loss(params):
                return model_lib.loss_fn(params, cfg, batch, remat=True)
            lv, grads = jax.value_and_grad(loss)(theta)
            if plain:
                theta2 = jax.tree.map(lambda t, g: t - 0.01 * g, theta, grads)
                return theta2, mom, {"loss": lv}
            theta2, mom2 = prox_sgd_tree(theta, grads, w, mom,
                                         alpha=0.01, lam=0.5, momentum=0.9)
            return theta2, mom2, {"loss": lv}

        batch = model_lib.input_specs(cfg, batch=shape.global_batch,
                                      seq_len=shape.seq_len, kind="train",
                                      act_dtype=ACT_DTYPE)
        mom_specs = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32), p_specs)
        b_shard = to_named(batch_pspecs(batch, batch_axes=baxes_spec), mesh,
                           batch)
        mom_shard = to_named(param_pspecs(mom_specs), mesh, mom_specs)
        args = (p_specs, p_specs, mom_specs, batch)
        in_sh = (p_shard, p_shard, mom_shard, b_shard)
        out_sh = (p_shard, mom_shard, None)
        return step, args, in_sh, out_sh

    if shape.kind == "prefill":
        def step(params, batch, cache):
            return model_lib.prefill(params, cfg, batch, cache,
                                     last_only=True)

        batch = model_lib.input_specs(cfg, batch=shape.global_batch,
                                      seq_len=shape.seq_len, kind="prefill",
                                      act_dtype=ACT_DTYPE)
        cache = model_lib.cache_specs(cfg, shape.global_batch,
                                      shape.seq_len, dtype=ACT_DTYPE)
        b_shard = to_named(batch_pspecs(batch, batch_axes=baxes_spec), mesh,
                           batch)
        c_shard = to_named(cache_pspecs(cache, batch_axes=baxes_spec,
                                        mesh_batch=mesh_b), mesh, cache)
        args = (p_specs, batch, cache)
        in_sh = (p_shard, b_shard, c_shard)
        out_sh = (None, c_shard)
        return step, args, in_sh, out_sh

    # decode
    max_len = cache_len_for(cfg, shape)
    # Decode sharding (beyond-paper, §Perf hillclimb 2): FSDP would
    # all-gather every weight once PER TOKEN (one decode step has no
    # sequence dim to amortize it) — rwkv6-7b decode_32k was
    # collective-bound purely on those gathers. Serving uses pure TP
    # (params sharded over `model` only, never gathered) WHEN the TP shard
    # fits comfortably in HBM; very large models (dbrx 16.5 GB/dev,
    # jamba 50 GB/dev at TP-16) keep FSDP — replicating their banks over
    # `data` cannot fit a 16 GB v5e. REPRO_DECODE_FSDP=1 forces the
    # FSDP baseline everywhere (§Perf).
    # ... and only for batch-dense decode: at global_batch=1 (long_500k)
    # the per-token weight read amortizes over nothing, so keeping weights
    # FSDP-sharded (each device streams 1/16 of them + ICI) beats local
    # full-TP-shard reads (measured 0.1-0.7x regressions otherwise).
    tp_param_bytes = 2 * param_count(cfg) / mesh.shape["model"]
    if os.environ.get("REPRO_DECODE_FSDP") != "1" and \
            tp_param_bytes < 4e9 and shape.global_batch >= 16:
        p_shard = to_named(param_pspecs(p_specs, fsdp=False), mesh, p_specs)

    def step(params, cache, batch, pos):
        return model_lib.decode_step(params, cfg, cache, batch, pos)

    batch = model_lib.input_specs(cfg, batch=shape.global_batch,
                                  seq_len=shape.seq_len, kind="decode",
                                  act_dtype=ACT_DTYPE)
    cache = model_lib.cache_specs(cfg, shape.global_batch, max_len,
                                  dtype=ACT_DTYPE)
    pos_spec = jax.ShapeDtypeStruct((), jnp.int32)
    b_shard = to_named(batch_pspecs(batch, batch_axes=baxes_spec), mesh,
                           batch)
    c_shard = to_named(cache_pspecs(cache, batch_axes=baxes_spec,
                                    mesh_batch=mesh_b), mesh, cache)
    args = (p_specs, cache, batch, pos_spec)
    in_sh = (p_shard, c_shard, b_shard, NamedSharding(mesh, P()))
    out_sh = (None, c_shard)
    return step, args, in_sh, out_sh


def run_one(arch: str, shape_name: str, *, multi_pod: bool,
            plain: bool = False, verbose: bool = True):
    shape = INPUT_SHAPES[shape_name]
    cfg, skip = resolve_config(arch, shape_name)
    mesh_name = "multipod_2x16x16" if multi_pod else "pod_16x16"
    record = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name,
        "params": param_count(get_config(arch)),
        "active_params": active_param_count(get_config(arch)),
    }
    if skip:
        record["status"] = "skipped"
        record["reason"] = skip
        return record

    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.devices.size
    t0 = time.time()
    step, args, in_sh, out_sh = build_step_and_args(cfg, shape, mesh,
                                                    plain=plain)
    with jax.set_mesh(mesh):
        jitted = jax.jit(step, in_shardings=in_sh, out_shardings=out_sh)
        lowered = jitted.lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode"
                                   else 1)
    if shape.kind == "train":
        mflops = model_flops_train(cfg, tokens)
    elif shape.kind == "prefill":
        mflops = model_flops_decode(cfg, tokens)  # forward-only
    else:
        mflops = model_flops_decode(cfg, tokens)
    hlo_text = compiled.as_text()
    roof = analyze(compiled, chips=chips, model_flops=mflops,
                   hlo_text=hlo_text)

    record.update({
        "status": "ok",
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "chips": chips,
        "hlo_flops": roof.flops,
        "hbm_bytes": roof.hbm_bytes,
        "collective_bytes": roof.collective_bytes,
        "collectives": roof.collectives,
        "compute_s": roof.compute_s,
        "memory_s": roof.memory_s,
        "collective_s": roof.collective_s,
        "dominant": roof.dominant,
        "model_flops": mflops,
        "useful_ratio": roof.useful_ratio,
        "bytes_per_device": {
            "argument": getattr(mem, "argument_size_in_bytes", None),
            "output": getattr(mem, "output_size_in_bytes", None),
            "temp": getattr(mem, "temp_size_in_bytes", None),
            "peak": (getattr(mem, "argument_size_in_bytes", 0) or 0)
            + (getattr(mem, "temp_size_in_bytes", 0) or 0),
        },
    })
    if verbose:
        print(f"== {arch} x {shape_name} x {mesh_name} ==")
        print(f"  lower {t_lower:.1f}s compile {t_compile:.1f}s")
        print(f"  memory_analysis: {mem}")
        print(f"  roofline: {roof.summary()}")
        print(f"  collectives: { {k: f'{v/1e9:.3f}GB' for k, v in roof.collectives.items()} }")
    return record


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(INPUT_SHAPES))
    ap.add_argument("--mesh", default="pod", choices=["pod", "multipod"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--plain", action="store_true",
                    help="vanilla SGD step instead of PerMFL device step")
    ap.add_argument("--out", default=None, help="JSON output path")
    args = ap.parse_args(argv)

    records = []
    if args.all:
        combos = [(a, s, m) for a in ARCH_IDS for s in INPUT_SHAPES
                  for m in ("pod", "multipod")]
    else:
        combos = [(args.arch, args.shape, args.mesh)]
    for arch, shape, meshname in combos:
        try:
            rec = run_one(arch, shape, multi_pod=(meshname == "multipod"),
                          plain=args.plain)
        except Exception as e:  # a failure here is a bug in the system
            traceback.print_exc()
            rec = {"arch": arch, "shape": shape, "mesh": meshname,
                   "status": "FAILED", "error": repr(e)}
        records.append(rec)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(records, f, indent=1)
    bad = [r for r in records if r["status"] == "FAILED"]
    print(f"\n{len(records) - len(bad)}/{len(records)} combos OK")
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
