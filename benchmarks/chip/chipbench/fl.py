"""Set-up shared by the federated cells: the model configuration as the
program takes it, the federation's data and initial weights made on the
device from the seed, and the reference's loss."""
from __future__ import annotations

import functools

import jax
from jax import lax

from chipbench import data as D

DATA = {"label_skew_images": D.label_skew_images,
        "virtual_tabular": D.virtual_tabular}


def program_config(cfg: dict):
    """The configuration file as the program's ``PaperModelConfig``."""
    from repro.configs.base import PaperModelConfig
    return PaperModelConfig(
        name=cfg["name"], kind=cfg["kind"],
        input_shape=tuple(cfg["input_shape"]),
        num_classes=cfg["num_classes"], hidden=tuple(cfg["hidden"]),
        conv_channels=tuple(cfg["conv_channels"]), l2_reg=cfg["l2_reg"],
        convex=cfg["convex"])


def federation(key, p: dict):
    """(train, val) of the cell's federation, from ``p["data"]``."""
    spec = dict(p["data"])
    gen = DATA[spec.pop("kind")]
    if "shape" in spec:
        spec["shape"] = tuple(spec["shape"])
    return gen(key, m=p["m"], n=p["n"], samples=p["samples"],
               n_val=p["n_val"], **spec)


def init_fn(ref, cfg: dict):
    """The reference's initializer as one jitted call of the key."""
    return jax.jit(lambda k: ref.init(k, cfg))


def ref_loss(ref, cfg: dict):
    """The reference's per-device loss at HIGHEST matmul precision."""
    return functools.partial(ref.loss, cfg=cfg,
                             precision=lax.Precision.HIGHEST)


def hparams(p: dict) -> dict:
    return {k: float(p["hp"][k]) for k in ("alpha", "eta", "beta", "lam",
                                           "gamma")}


def samples_per_call(p: dict, configs: int = 1) -> int:
    """Device-sample gradient evaluations in one call: rounds x devices
    that take part x K x L x train samples per device x configurations."""
    devices = p["m"] * (p.get("cohort") or p["n"])
    return (p["rounds"] * devices * p["hp"]["k_team"] * p["hp"]["l_local"]
            * (p["samples"] - p["n_val"]) * configs)
