"""Compile each cell's main program for a described TPU v5e, without a
chip, at the timed sizes, and print what ``memory_analysis()`` says it
needs. Run here before spending chip time:

    JAX_PLATFORMS=cpu REPRO_KERNEL_MODE=pallas \\
        python3 benchmarks/chip/compile_check.py paper-cnn.train ...

A program that does not fit the chip's memory is refused by the
compiler, as it would be on the chip. Prints one JSON line per cell."""
import json
import os
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))


def shapes(tree, sharding):
    import jax
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def federation_shapes(cell, key):
    import jax

    from chipbench import fl
    return jax.eval_shape(lambda k: fl.federation(k, cell.params), key)


def experiment_program(cell, dev):
    """(jitted program, its argument shapes, static kwargs)."""
    import jax
    import jax.numpy as jnp

    from chipbench import fl
    from repro.core import PerMFL
    from repro.core.permfl import PerMFLHParams
    from repro.kernels.interface import dispatch_key
    from repro.scenarios.spec import fns_for
    from repro.train.engine import _scan_program, hparam_skeleton
    p, cfg = cell.params, cell.config
    key = jax.random.PRNGKey(0)
    loss_fn, metric_fn = fns_for(fl.program_config(cfg))
    algo = PerMFL(loss_fn, PerMFLHParams(**p["hp"]))
    skel, hleaves = hparam_skeleton(algo)
    params0 = jax.eval_shape(fl.init_fn(cell.reference, cfg), key)
    train, val = federation_shapes(cell, key)
    static = dict(length=p["eval_every"],
                  n_steps=p["rounds"] // p["eval_every"])
    prog = _scan_program(skel, metric_fn, p["m"], p["n"], 1.0, 1.0,
                         None, None, dispatch_key(), p.get("cohort"))
    state = jax.eval_shape(lambda q: algo.init_state(q, p["m"], p["n"]),
                           params0)
    hl = {k: jax.ShapeDtypeStruct((), jnp.float32) for k in hleaves}
    args = (shapes(hl, dev), shapes(state, dev),
            shapes(key, dev), shapes(train, dev), shapes(val, dev))
    return prog, args, dict(static, sleaves=None)


def serve_program(cell, dev):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from chipbench import fl
    from repro.models import paper_models as PM
    from repro.serve import ModelStore, PersonalizedServer
    p, cfg = cell.params, cell.config
    pcfg = fl.program_config(cfg)
    x = jax.eval_shape(fl.init_fn(cell.reference, cfg), jax.random.PRNGKey(0))

    def payload(a):
        lp = -(-int(np.prod(a.shape)) // 128) * 128
        return {"q": jax.ShapeDtypeStruct((p["m"], p["n"], lp), jnp.int8),
                "scales": jax.ShapeDtypeStruct((p["m"], p["n"], lp // 128),
                                               jnp.float32)}
    team = jax.tree.map(lambda a: jax.ShapeDtypeStruct((p["m"],) + a.shape,
                                                       a.dtype), x)
    store = ModelStore(shapes(x, dev), shapes(team, dev),
                       shapes(jax.tree.map(payload, x), dev),
                       encoding=p["encoding"], m=p["m"], n=p["n"])
    server = PersonalizedServer(
        store, lambda prm, xx: PM.apply(prm, pcfg, xx[None])[0])
    b = max(p["batch_sizes"])
    tag = jax.ShapeDtypeStruct((b,), jnp.int32, sharding=dev)
    xs = jax.ShapeDtypeStruct((b,) + tuple(cfg["input_shape"]), jnp.float32,
                              sharding=dev)
    return server._step, (store, tag, tag, xs), {}


def main(argv=None) -> int:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from chipbench import harness
    harness.program_path()
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    dev = SingleDeviceSharding(topo.devices[0])
    for name in argv if argv is not None else sys.argv[1:]:
        cell = harness.find_cell(name)
        line = {"workload": name}
        try:
            if cell.params["driver"] == "serve":
                prog, args, kw = serve_program(cell, dev)
            else:
                prog, args, kw = experiment_program(cell, dev)
            compiled = prog.lower(*args, **kw).compile()
            m = compiled.memory_analysis()
            line.update(
                arguments=m.argument_size_in_bytes,
                outputs=m.output_size_in_bytes, temp=m.temp_size_in_bytes,
                alias=m.alias_size_in_bytes,
                total=(m.argument_size_in_bytes + m.output_size_in_bytes
                       + m.temp_size_in_bytes - m.alias_size_in_bytes),
                pallas="tpu_custom_call" in compiled.as_text())
        except Exception as e:  # noqa: BLE001 — report the refusal
            line["refused"] = str(e).splitlines()[0][:300]
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
