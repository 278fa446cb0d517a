"""Driver of the LoRA fine-tuning cells: PerMFL over per-device LoRA
adapters on a frozen base (``repro.models.lora_lm``), through
``repro.train.engine.run_experiment(scan=True, base=...)``, called back
to back. Each call is one whole experiment of the cell's rounds from the
initial adapters, on token streams, base and adapters already on the
device, and ends with its state ready.

After the window it reads, against the plain reference
(``configs/<config>.py``, one device at a time at HIGHEST precision):

* ``change_gap``: each adapter leaf's change in x, w and theta
  (``chipbench.compare``) against the reference round's;
* ``logit_gap``: on ``sample_positions`` positions of the validation
  sequence of one sampled device per team, the program's logits under
  its trained personal adapters against the reference's logits under
  the reference's: the largest distance, over the spread of the
  reference's logits at that position (their norm about their mean);
* ``dropped_pairs``: the held-experts layer's own count of pairs routed
  to a held expert and left uncomputed, over every MoE layer of one
  step's tokens, read by a forward pass after the window: bookkeeping,
  0 by construction (the layer has no capacity).

The workload's ``limits`` say which of these decide ``correct``; the
others are reported beside them.
"""
from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from chipbench import compare, fl, permfl_ref, tokens, window
from chipbench.data import int_seed, seed_key
from chipbench.harness import Run


def program_config(cfg: dict):
    """The configuration file as the program's ``ModelConfig``."""
    from repro.configs.base import (LoRAConfig, MLAConfig, ModelConfig,
                                    MoEConfig, YarnConfig)
    if cfg["norm_topk_prob"] or cfg["routed_scaling_factor"] != 1:
        raise ValueError("the held-experts layer gates with the softmax "
                         "scores as they are (norm_topk_prob false, "
                         "routed_scaling_factor 1)")
    rs = cfg["rope_scaling"]
    return ModelConfig(
        name=cfg["name"], family="moe",
        num_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        rope_theta=float(cfg["rope_theta"]), norm_eps=cfg["rms_norm_eps"],
        moe=MoEConfig(
            num_experts=cfg["router_experts"],
            num_shared_experts=cfg["n_shared_experts"],
            top_k=cfg["num_experts_per_tok"],
            expert_d_ff=cfg["moe_intermediate_size"], router_aux_weight=0.0,
            held=(cfg["first_held_expert"], cfg["n_routed_experts"])),
        mla=MLAConfig(kv_lora_rank=cfg["kv_lora_rank"],
                      qk_nope_head_dim=cfg["qk_nope_head_dim"],
                      qk_rope_head_dim=cfg["qk_rope_head_dim"],
                      v_head_dim=cfg["v_head_dim"]),
        yarn=YarnConfig(
            factor=float(rs["factor"]),
            original_max_position_embeddings=rs[
                "original_max_position_embeddings"],
            mscale=rs["mscale"], mscale_all_dim=rs["mscale_all_dim"],
            beta_fast=float(rs["beta_fast"]),
            beta_slow=float(rs["beta_slow"])),
        first_k_dense_replace=cfg["first_k_dense_replace"],
        lora=LoRAConfig(rank=cfg["lora"]["rank"],
                        alpha=float(cfg["lora"]["alpha"]),
                        targets=tuple(cfg["lora"]["targets"])))


@functools.lru_cache(maxsize=4)
def _program(pcfg, hp_items):
    """(loss, metric, algorithm): one per configuration, so every seed's
    set-up in a process reuses the compiled call."""
    from repro.core import PerMFL
    from repro.core.permfl import PerMFLHParams
    from repro.models import lora_lm
    loss, metric = lora_lm.fns(pcfg)
    return loss, metric, PerMFL(loss, PerMFLHParams(**dict(hp_items)))


def config(ctx) -> dict:
    """The cell's configuration, with the workload's ``config`` entries
    over it (the CPU tests shrink the widths this way)."""
    return {**ctx.cell.config, **ctx.params.get("config", {})}


def federation(key, p: dict, cfg: dict):
    return tokens.topic_streams(
        key, m=p["m"], n=p["n"], seqs=p["samples"], n_val=p["n_val"],
        seq_len=p["seq_len"], vocab=cfg["vocab_size"])


def setup(ctx):
    """Data, base and adapters, and the call the window repeats."""
    from repro.train.engine import run_experiment   # the program first:
    from repro.models import lora_lm  # noqa: F401  # fails where absent
    p, cfg = ctx.params, config(ctx)
    pcfg = program_config(cfg)
    k_data, k_init, k_run = jax.random.split(seed_key(ctx.seed), 3)
    train, val = federation(k_data, p, cfg)
    base, adapters0 = jax.jit(lambda k: ctx.cell.reference.init(k, cfg))(
        k_init)
    jax.block_until_ready((train, val, base, adapters0))
    ctx.mark("data and weights made")
    _, metric, algo = _program(pcfg, tuple(sorted(p["hp"].items())))
    engine_seed = int_seed(k_run)

    def call():
        return run_experiment(
            algo, adapters0, train, val, metric_fn=metric,
            rounds=p["rounds"], m=p["m"], n=p["n"],
            eval_every=p["eval_every"], seed=engine_seed, scan=True,
            base=base)

    return train, val, base, adapters0, pcfg, call


def sampled(ctx, p):
    """(one device per team, positions of its validation sequence)."""
    rng = np.random.default_rng([ctx.seed, 15])
    devs = rng.integers(0, p["n"], size=p["m"])
    pos = np.sort(rng.choice(p["seq_len"], size=p["sample_positions"],
                             replace=False))
    return devs, pos


@functools.partial(jax.jit, static_argnames=("pcfg",))
def _program_logits(base, ad, tk, pos, *, pcfg):
    """Logits (M, P, V) of each team's sampled device under its adapters
    ``ad`` (M, ...) on its sequence ``tk`` (M, 1, T), at positions
    ``pos``."""
    from repro.models import lora_lm
    return lora_lm.logits(base, ad, pcfg, tk)[:, 0, pos]


@functools.partial(jax.jit, static_argnames=("pcfg",))
def _route_counters(base, theta, train, *, pcfg):
    from repro.models import lora_lm
    return lora_lm.route_counters(base, theta, pcfg, train)


def program_outputs(ctx, res, adapters0, base, train, val, pcfg) -> dict:
    """What the comparison reads of a call's result."""
    st = res.state
    p = ctx.params
    devs, pos = sampled(ctx, p)
    teams = np.arange(p["m"])
    mine = jax.tree.map(lambda a: a[teams, devs], st.theta)
    z = _program_logits(base, mine, val["x"][teams, devs][:, :1], pos,
                        pcfg=pcfg)
    counters = _route_counters(base, st.theta, train, pcfg=pcfg)
    return {"norms": compare.change_norms(st.x, st.w, st.theta, adapters0),
            "logits": np.asarray(z),
            "counters": {k: np.asarray(v).tolist()
                         for k, v in counters.items()}}


@functools.partial(jax.jit, static_argnames=(
    "ref", "cfg_json", "k_team", "l_local", "m", "n", "rounds", "dtype",
    "half_batch"))
def _reference_round(adapters0, train, base, hp, *, ref, cfg_json, k_team,
                     l_local, m, n, rounds, dtype, half_batch):
    cfg = json.loads(cfg_json)
    loss = ref.device_loss(base, cfg, lax.Precision.HIGHEST, dtype)
    hp = {k: jnp.asarray(v, dtype) for k, v in hp.items()}
    round_ = permfl_ref.make_round(loss, hp, k_team=k_team, l_local=l_local,
                                   m=m, n=n)
    x = permfl_ref.cast(adapters0, dtype)
    fit = permfl_ref.half(train) if half_batch else train
    for _ in range(rounds):
        x, w, theta = round_(x, fit)
    return permfl_ref.cast((x, w, theta), jnp.float32)


@functools.partial(jax.jit, static_argnames=("ref", "cfg_json", "dtype"))
def _reference_logits(base, ad, tk, pos, *, ref, cfg_json, dtype):
    """The reference's ``_program_logits``, one device at a time."""
    cfg = json.loads(cfg_json)
    return lax.map(lambda at: ref.apply(base, at[0], at[1], cfg,
                                        lax.Precision.HIGHEST, dtype)[0, pos],
                   (ad, tk))


def reference(ctx, base, adapters0, train, val, *, dtype=jnp.float32,
              half_batch=False):
    """The plain reference of the same experiment: (norms, logits at the
    sampled positions under its trained personal adapters)."""
    p, cfg, ref = ctx.params, config(ctx), ctx.cell.reference
    cfg_json = json.dumps(cfg, sort_keys=True)
    x, w, theta = _reference_round(
        adapters0, train, base, fl.hparams(p), ref=ref, cfg_json=cfg_json,
        k_team=p["hp"]["k_team"], l_local=p["hp"]["l_local"], m=p["m"],
        n=p["n"], rounds=p["rounds"], dtype=dtype, half_batch=half_batch)
    norms = compare.change_norms(x, w, theta, adapters0)
    devs, pos = sampled(ctx, p)
    teams = np.arange(p["m"])
    ad = jax.tree.map(lambda a: a[teams, devs], theta)
    z = _reference_logits(base, ad, val["x"][teams, devs][:, :1], pos,
                          ref=ref, cfg_json=cfg_json, dtype=dtype)
    return norms, np.asarray(z, np.float64)


def logit_gap(prog, ref) -> float:
    """Largest distance between the program's and the reference's logits
    at a position, over the norm of the reference's about their mean."""
    prog = np.asarray(prog, np.float64).reshape(-1, ref.shape[-1])
    ref = ref.reshape(-1, ref.shape[-1])
    if not np.all(np.isfinite(prog)):
        return float("inf")
    spread = np.linalg.norm(ref - ref.mean(-1, keepdims=True), axis=-1)
    return float(np.max(np.linalg.norm(prog - ref, axis=-1) / spread))


def numbers(prog: dict, ref) -> dict:
    norms, z = ref
    out = {"change_gap": compare.change_gap(prog["norms"], norms),
           "logit_gap": logit_gap(prog["logits"], z)}
    if "counters" in prog:
        out["dropped_pairs"] = int(np.sum(prog["counters"]["dropped_pairs"]))
    return out


def run(ctx) -> Run:
    p = ctx.params
    train, val, base, adapters0, pcfg, call = setup(ctx)
    res, calls, setup_s, window_s = window.timed_calls(
        ctx, call, lambda r: r.state)
    memory = ctx.memory_peak()
    ctx.mark("window closed")
    prog = program_outputs(ctx, res, adapters0, base, train, val, pcfg)
    del res
    ref = reference(ctx, base, adapters0, train, val)
    ctx.mark("reference done")
    samples = fl.samples_per_call(p) * calls
    steps = calls * p["rounds"] * p["hp"]["k_team"] * p["hp"]["l_local"]
    return Run(setup_s=setup_s, window_s=window_s, attempted=calls,
               failed=0,
               end_to_end={p["rate_metric"]: samples / window_s},
               stats={"calls": calls, "samples": samples, "steps": steps,
                      "tokens_per_step": (p["m"] * p["n"]
                                          * (p["samples"] - p["n_val"])
                                          * p["seq_len"]),
                      "moe_counters": prog["counters"]},
               numbers=numbers(prog, ref), memory_peak_bytes=memory)


def readings(ctx, controls: bool) -> dict:
    """For ``calibrate_cell.py``: the program's numbers for ``ctx.seed``
    (set-up and one call) and, with ``controls``, the control's (the
    reference in bfloat16: adapters, prox state and activations) and the
    half-batch fault's, each put in the program's place."""
    train, val, base, adapters0, pcfg, call = setup(ctx)
    res = call()
    jax.block_until_ready(res.state)
    prog = program_outputs(ctx, res, adapters0, base, train, val, pcfg)
    del res
    ref = reference(ctx, base, adapters0, train, val)
    line = {"program": numbers(prog, ref),
            "moe_counters": prog["counters"]}
    if controls:
        for name, kw in (("control", {"dtype": jnp.bfloat16}),
                         ("half_batch", {"half_batch": True})):
            norms, z = reference(ctx, base, adapters0, train, val, **kw)
            line[name] = numbers({"norms": norms, "logits": z}, ref)
    return line


def described_call(cell, sharding):
    """(the scanned call's jitted program, its argument shapes, static
    keywords) at the cell's size on ``sharding`` (a described device):
    for compiling without a chip."""
    from repro.kernels.interface import dispatch_key
    from repro.train.engine import _scan_program, hparam_skeleton
    p, cfg = cell.params, {**cell.config, **cell.params.get("config", {})}
    _, metric, algo = _program(program_config(cfg),
                               tuple(sorted(p["hp"].items())))
    skel, hleaves = hparam_skeleton(algo)
    key = jax.random.PRNGKey(0)
    base, ad0 = jax.eval_shape(lambda k: cell.reference.init(k, cfg), key)
    train, val = jax.eval_shape(lambda k: federation(k, p, cfg), key)
    state = jax.eval_shape(lambda a: algo.init_state(a, p["m"], p["n"]),
                           ad0)

    def on(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=sharding), tree)

    hl = {k: jax.ShapeDtypeStruct((), jnp.float32) for k in hleaves}
    prog = _scan_program(skel, metric, p["m"], p["n"], 1.0, 1.0, None, None,
                         dispatch_key(), None)
    return prog, (on(hl), on(state), on(key), on(train), on(val)), dict(
        sleaves=None, base=on(base), length=p["eval_every"],
        n_steps=p["rounds"] // p["eval_every"])
