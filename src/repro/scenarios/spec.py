"""The declarative `FLScenario` spec: data x topology x model x algorithm
x participation x comm as one frozen, serializable value.

Every experiment in the repo is a *scenario* — the paper's claims are all
scenario claims (PerMFL wins under known team structures, label-skew
dissemination, partial participation, constrained uplinks), and every
future workload is added as a new spec, not a new benchmark script. A
scenario is four nested frozen dataclasses:

    FLScenario
      ├── DataSpec   dataset + partitioner + (M, N) topology + team
      │              formation strategy + heterogeneity knobs
      ├── ModelSpec  which paper model (mclr | cnn | dnn), or a zoo
      │              architecture fine-tuned as LoRA over a frozen base
      └── AlgoSpec   algorithm name + hyperparameter overrides
      plus rounds, team/device participation fractions, an optional
      CommConfig, the data seed, and presentation metadata (family,
      paper reference numbers, notes).

Being frozen and built from hashable fields, a scenario is usable as a
cache key end-to-end: `spec_hash()` digests the physical fields (name
and presentation metadata excluded), and `repro.scenarios.runner` keys
its build cache on it so repeated runs of one scenario share loss/metric
closures — which is exactly what lets the engine's compiled-program
cache (`train.engine`, DESIGN.md §5/§7) hit across calls.

`to_dict()` / `from_dict()` round-trip through plain JSON-able dicts, so
specs can be dumped, diffed, and checked into experiment configs.
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
from dataclasses import dataclass, field
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.comm import CommConfig
from repro.configs.base import ModelConfig, PaperModelConfig
from repro.system import SystemSpec, get_profile
from repro.core import PerMFL
from repro.core import baselines as B
from repro.core.permfl import PerMFLHParams
from repro.data.federated import (FederatedData, partition_dirichlet,
                                  partition_label_skew,
                                  partition_quantity_skew, partition_tabular,
                                  stack_virtual)
from repro.data.synthetic import (feature_shift_tabular, make_dataset,
                                  synthetic_tabular, virtual_tabular)
from repro.data.tokens import federated_lm_data
from repro.models import lora_lm
from repro.models import paper_models as PM

__all__ = ["ALGO_METRICS", "AlgoSpec", "DataSpec", "FLScenario",
           "ModelSpec", "PAPER_HP", "fns_for", "init_base", "init_model",
           "to_jax"]

# paper §4.1.4 hyperparameters — the PerMFL defaults every scenario
# starts from (AlgoSpec overrides replace individual fields)
PAPER_HP = PerMFLHParams(alpha=0.01, eta=0.03, beta=0.6, lam=0.5,
                         gamma=1.5, k_team=5, l_local=10)

# metrics each algorithm reports (keys of FLAlgorithm.eval): the Table-1
# columns — personalized/team/global for PerMFL, GM-only for the purely
# global baselines, PM+GM for the personalized ones
ALGO_METRICS = {
    "permfl": ("pm", "tm", "gm"),
    "fedavg": ("gm",),
    "perfedavg": ("pm", "gm"),
    "pfedme": ("pm", "gm"),
    "ditto": ("pm", "gm"),
    "hsgd": ("gm",),
    "l2gd": ("pm", "gm"),
}

_TABULAR_DATASETS = ("synthetic", "featshift", "virtual")
_PARTITIONERS = ("label_skew", "dirichlet", "quantity", "tabular", "topics")
# DataSpec / ModelSpec fields serialized only when set, so specs that
# predate them keep their dicts and spec_hash
_OPTIONAL = {"seq_len": 0, "vocab_size": 0, "arch": "", "reduced": False}


# ---------------------------------------------------------------------------
# helpers shared with the benchmarks (historically benchmarks/fl_common.py)
# ---------------------------------------------------------------------------

def fns_for(cfg):
    """(loss_fn, metric_fn) closures over one paper model config; for a
    zoo architecture (a ``ModelConfig``) the federated LoRA pair of
    ``models.lora_lm``, which takes every device and the frozen base."""
    if isinstance(cfg, ModelConfig):
        return lora_lm.fns(cfg)
    loss = lambda p, b: PM.loss_fn(p, cfg, b)
    met = lambda p, b: PM.accuracy(p, cfg, b)
    return loss, met


def init_model(cfg, seed: int = 0):
    """Model parameters for `cfg` from PRNG seed `seed`: for a zoo
    architecture, one device's LoRA adapters (B zero)."""
    if isinstance(cfg, ModelConfig):
        return lora_lm.init_adapters(jax.random.PRNGKey(seed), cfg)
    return PM.init_params(jax.random.PRNGKey(seed), cfg)


def init_base(cfg, seed: int = 0):
    """The frozen base a zoo architecture's adapters train over (random
    weights from ``seed`` stand in for a pretrained model); None for a
    paper model, which trains whole."""
    if isinstance(cfg, ModelConfig):
        return lora_lm.init_base(jax.random.PRNGKey(seed), cfg)
    return None


def to_jax(fd: FederatedData):
    """FederatedData -> (train, val) dicts of stacked jnp arrays."""
    tr = {"x": jnp.asarray(fd.train_x), "y": jnp.asarray(fd.train_y)}
    va = {"x": jnp.asarray(fd.val_x), "y": jnp.asarray(fd.val_y)}
    return tr, va


# ---------------------------------------------------------------------------
# DataSpec
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class DataSpec:
    """What the federation holds: dataset, partitioner, and topology.

    dataset: "mnist" | "fmnist" | "emnist10" (image sets), "synthetic"
        (the paper's §D.2.6 tabular set), "featshift" (covariate-shift
        tabular — shared concept, team-shifted features), "virtual"
        (the cohort-scale featshift variant: fully vectorized
        construction, viable at 10^4-10^6 devices per team), or
        "tokens" (next-token streams, team i on topic i: Zipf bigram
        chains, ``data.tokens.federated_lm_data``; x holds the tokens,
        y the next tokens).
    partitioner: "label_skew" (paper §4.1.4), "dirichlet" (Dir(alpha)
        class mixes), "quantity" (power-law effective sizes),
        "tabular" (per-device tabular stacking; implied by the tabular
        datasets), or "topics" (per-team topics; goes with "tokens").
    m_teams / n_devices: the (M, N) topology.
    samples_per_device: S — stacked sample slots per device.
    classes_per_device: label-skew classes per device.
    strategy: team-formation label pools ("random" | "worst" | "average").
    alpha: Dirichlet concentration (partitioner="dirichlet").
    min_frac: minimum unique-sample fraction (partitioner="quantity").
    shift: team feature-shift magnitude (dataset="featshift").
    n_per_class: image-dataset pool size per class; 0 = auto
        (40 * n_devices, the benchmarks' historical sizing).
    seq_len / vocab_size: "tokens" only — tokens a sequence (each
        device holds ``samples_per_device`` sequences, 3:1 train/val)
        and the vocabulary the streams draw from.
    """
    dataset: str = "mnist"
    partitioner: str = "label_skew"
    m_teams: int = 4
    n_devices: int = 10
    samples_per_device: int = 48
    classes_per_device: int = 2
    strategy: str = "random"
    alpha: float = 0.5
    min_frac: float = 0.25
    shift: float = 2.0
    n_per_class: int = 0
    seq_len: int = 0
    vocab_size: int = 0

    def __post_init__(self):
        if self.partitioner not in _PARTITIONERS:
            raise ValueError(f"unknown partitioner {self.partitioner!r}; "
                             f"expected one of {_PARTITIONERS}")
        if (self.dataset in _TABULAR_DATASETS) != \
                (self.partitioner == "tabular"):
            raise ValueError(
                f"partitioner 'tabular' and the tabular datasets "
                f"{_TABULAR_DATASETS} go together; got dataset="
                f"{self.dataset!r} with partitioner={self.partitioner!r}")
        if (self.dataset == "tokens") != (self.partitioner == "topics"):
            raise ValueError("dataset 'tokens' and partitioner 'topics' go "
                             f"together; got {self.dataset!r} with "
                             f"{self.partitioner!r}")
        if self.dataset == "tokens" and min(self.seq_len,
                                            self.vocab_size) < 1:
            raise ValueError("dataset 'tokens' needs seq_len and "
                             "vocab_size")

    def build(self, seed: int) -> FederatedData:
        """Materialize the stacked FederatedData for PRNG seed `seed`
        (deterministic: same spec + seed -> identical arrays)."""
        rng = np.random.default_rng(seed)
        m, n, spd = self.m_teams, self.n_devices, self.samples_per_device
        if self.dataset == "tokens":
            lm = federated_lm_data(rng, self.vocab_size, m_teams=m,
                                   n_devices=n, seq_len=self.seq_len,
                                   seqs_per_device=spd)
            return stack_virtual(lm["tokens"], lm["targets"],
                                 samples_per_device=spd)
        if self.dataset == "synthetic":
            devs = synthetic_tabular(rng, m * n, min_samples=spd,
                                     max_samples=spd * 8)
            return partition_tabular(devs, m_teams=m, n_devices=n,
                                     samples_per_device=spd)
        if self.dataset == "featshift":
            devs = feature_shift_tabular(rng, m, n, shift=self.shift,
                                         samples_per_device=spd)
            return partition_tabular(devs, m_teams=m, n_devices=n,
                                     samples_per_device=spd)
        if self.dataset == "virtual":
            x, y = virtual_tabular(rng, m, n, shift=self.shift,
                                   samples_per_device=spd)
            return stack_virtual(x, y, samples_per_device=spd)
        x, y = make_dataset(self.dataset, rng,
                            n_per_class=self.n_per_class or 40 * n)
        if self.partitioner == "label_skew":
            return partition_label_skew(
                rng, x, y, m_teams=m, n_devices=n,
                classes_per_device=self.classes_per_device,
                samples_per_device=spd, strategy=self.strategy)
        if self.partitioner == "dirichlet":
            return partition_dirichlet(
                rng, x, y, m_teams=m, n_devices=n, alpha=self.alpha,
                samples_per_device=spd, strategy=self.strategy)
        assert self.partitioner == "quantity", self.partitioner
        return partition_quantity_skew(
            rng, x, y, m_teams=m, n_devices=n, samples_per_device=spd,
            min_frac=self.min_frac)


# ---------------------------------------------------------------------------
# ModelSpec
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class ModelSpec:
    """Which model trains on the scenario: a paper model, "mclr"
    (strongly convex) | "cnn" | "dnn" (non-convex), whose concrete
    PaperModelConfig is resolved against the DataSpec (input shape
    follows the dataset); or "zoo": the architecture ``arch`` (one of
    ``configs.base.ZOO_IDS``; ``reduced`` takes its small CPU variant)
    fine-tuned as per-device LoRA adapters over a frozen base on the
    "tokens" dataset (``models.lora_lm``)."""
    kind: str = "mclr"
    arch: str = ""
    reduced: bool = False

    def config(self, data: DataSpec):
        """Resolve to the concrete config for `data`'s shapes."""
        from repro.configs.paper_cnn import CONFIG as CNN
        from repro.configs.paper_dnn import CONFIG as DNN
        from repro.configs.paper_mclr import CONFIG as MCLR

        if self.kind == "zoo":
            from repro.configs.base import (ZOO_IDS, get_config,
                                            get_reduced_config)
            if self.arch not in ZOO_IDS:
                raise ValueError(f"unknown zoo architecture {self.arch!r}; "
                                 f"expected one of {ZOO_IDS}")
            cfg = (get_reduced_config if self.reduced else get_config)(
                self.arch)
            if data.dataset != "tokens" or data.vocab_size > cfg.vocab_size:
                raise ValueError(
                    f"{self.arch} needs the 'tokens' dataset over at most "
                    f"its {cfg.vocab_size} ids, got {data.dataset!r} over "
                    f"{data.vocab_size}")
            return cfg
        tabular = data.dataset in _TABULAR_DATASETS
        if self.kind == "mclr":
            return dataclasses.replace(MCLR, input_shape=(60,)) if tabular \
                else MCLR
        if self.kind == "dnn":
            return DNN
        if self.kind == "cnn":
            if tabular:
                raise ValueError("cnn needs image data, got "
                                 f"{data.dataset!r}")
            return CNN
        raise ValueError(f"unknown model kind {self.kind!r}")


# ---------------------------------------------------------------------------
# AlgoSpec
# ---------------------------------------------------------------------------

# paper-default constructor arguments per algorithm (Table-1 settings);
# AlgoSpec.overrides replaces individual entries
_ALGO_DEFAULTS = {
    "permfl": dict(alpha=0.01, eta=0.03, beta=0.6, lam=0.5, gamma=1.5,
                   k_team=5, l_local=10, momentum=0.0, weight_decay=0.0),
    "fedavg": dict(lr=0.03, local_steps=50),
    "perfedavg": dict(lr=0.03, inner_lr=0.03, local_steps=20),
    "pfedme": dict(lr=1.0, inner_lr=0.03, lam=15.0, inner_steps=10,
                   local_rounds=5),
    "ditto": dict(lr=0.03, lam=0.5, local_steps=20),
    "hsgd": dict(lr=0.03, k_team=5, l_local=10),
    "l2gd": dict(lr=0.03, lam_c=0.5, lam_g=0.5, k_team=5, l_local=10),
}


@dataclass(frozen=True)
class AlgoSpec:
    """Algorithm name + hyperparameter overrides on the paper defaults.

    overrides: sorted tuple of (field, value) pairs replacing entries of
    the algorithm's paper-default constructor arguments (PerMFLHParams
    fields for "permfl", constructor kwargs for the baselines) — a tuple
    so the spec stays hashable and JSON-round-trippable.
    """
    name: str = "permfl"
    overrides: Tuple[Tuple[str, Any], ...] = ()

    def __post_init__(self):
        if self.name not in _ALGO_DEFAULTS:
            raise ValueError(f"unknown algorithm {self.name!r}; expected "
                             f"one of {sorted(_ALGO_DEFAULTS)}")
        unknown = set(dict(self.overrides)) - set(_ALGO_DEFAULTS[self.name])
        if unknown:
            raise ValueError(
                f"unknown {self.name} override(s) {sorted(unknown)}; "
                f"valid: {sorted(_ALGO_DEFAULTS[self.name])}")
        # normalize: sorted, tuple-of-tuples (from_dict hands us lists)
        object.__setattr__(self, "overrides", tuple(
            sorted((str(k), v) for k, v in self.overrides)))

    def resolved(self) -> dict:
        """Paper defaults with this spec's overrides applied."""
        kw = dict(_ALGO_DEFAULTS[self.name])
        kw.update(dict(self.overrides))
        return kw

    def hparams(self) -> PerMFLHParams:
        """The resolved PerMFLHParams ("permfl" only)."""
        if self.name != "permfl":
            raise ValueError(f"{self.name} has no PerMFLHParams")
        return PerMFLHParams(**self.resolved())

    def build(self, loss_fn: Callable,
              comm: Optional[CommConfig] = None):
        """Construct the frozen FLAlgorithm instance for the engine."""
        kw = self.resolved()
        if self.name == "permfl":
            return PerMFL(loss_fn, PerMFLHParams(**kw), comm=comm)
        if comm is not None:
            raise ValueError(f"comm compression is a PerMFL feature; "
                             f"{self.name} does not route tiered uplinks")
        cls = {"fedavg": B.FedAvg, "perfedavg": B.PerFedAvg,
               "pfedme": B.PFedMe, "ditto": B.Ditto, "hsgd": B.HSGD,
               "l2gd": B.L2GD}[self.name]
        return cls(loss_fn, **kw)

    @property
    def metrics(self) -> tuple:
        """Eval metrics this algorithm reports (Table-1 columns)."""
        return ALGO_METRICS[self.name]


def _set_fields(spec) -> dict:
    """``asdict`` without the optional fields left at their defaults."""
    return {k: v for k, v in dataclasses.asdict(spec).items()
            if k not in _OPTIONAL or v != _OPTIONAL[k]}


# ---------------------------------------------------------------------------
# FLScenario
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class FLScenario:
    """One named, reproducible experiment: the unit the registry stores,
    `run_scenario` / `sweep_scenario` execute, and the build cache keys.

    data / model / algo: the nested physical specs.
    rounds: default global-round budget (overridable at run time).
    team_frac / device_frac: participation fractions (paper §3.1 modes).
    comm: optional CommConfig — compressed uplinks + byte accounting.
    system: optional SystemSpec — wall-clock simulation on a named
        device/link profile (`repro.system`); results gain a Timeline +
        sim_seconds, and a deadline_s drops stragglers from the masks.
        Serialized only when set, so legacy specs hash unchanged.
    cohort_size: optional per-team cohort width C — the engine samples C
        of the N devices each round and materializes only the (M, C)
        slab (the virtualized cohort engine, DESIGN.md §11). None keeps
        the full-population stacked path bit-identical to before.
        Serialized only when set, so legacy specs hash unchanged.
    data_seed: PRNG seed the federated partition is built from (model
        init / participation seeds are run-time arguments, so one data
        universe serves multi-seed sweeps — the paper's table protocol).
    family / paper_ref / notes: presentation metadata — excluded from
        `spec_hash()` and from the build cache key. paper_ref holds
        (metric, paper accuracy %) pairs for cells quoted in the paper.
    """
    name: str
    data: DataSpec = field(default_factory=DataSpec)
    model: ModelSpec = field(default_factory=ModelSpec)
    algo: AlgoSpec = field(default_factory=AlgoSpec)
    rounds: int = 10
    team_frac: float = 1.0
    device_frac: float = 1.0
    comm: Optional[CommConfig] = None
    system: Optional[SystemSpec] = None
    cohort_size: Optional[int] = None
    data_seed: int = 0
    family: str = ""
    paper_ref: Tuple[Tuple[str, float], ...] = ()
    notes: str = ""

    def __post_init__(self):
        object.__setattr__(self, "paper_ref", tuple(
            (str(k), float(v)) for k, v in self.paper_ref))
        if self.cohort_size is not None and not (
                1 <= self.cohort_size <= self.data.n_devices):
            raise ValueError(
                f"cohort_size must be in [1, n_devices="
                f"{self.data.n_devices}], got {self.cohort_size}")

    # -- identity ----------------------------------------------------------

    def canonical(self) -> "FLScenario":
        """The physics only: presentation metadata stripped (including
        the system profile's label — two identically-parameterized
        profiles are one world). Two registry entries with equal
        canonical() forms share builds and compiled programs."""
        system = (dataclasses.replace(self.system, name="")
                  if self.system is not None else None)
        return dataclasses.replace(self, name="", family="", paper_ref=(),
                                   notes="", system=system)

    def spec_hash(self) -> str:
        """Stable 16-hex digest of the canonical spec — the key the
        runner's build cache (and through it the engine's compiled-
        program cache) is organized around."""
        blob = json.dumps(self.canonical().to_dict(), sort_keys=True)
        return hashlib.sha256(blob.encode()).hexdigest()[:16]

    # -- (de)serialization -------------------------------------------------

    def to_dict(self) -> dict:
        """Plain JSON-able dict; `from_dict` inverts it exactly. The
        ``system`` and ``cohort_size`` keys appear only when set, so
        pre-existing specs (and their spec_hash) are byte-stable."""
        d = {
            "name": self.name,
            "data": _set_fields(self.data),
            "model": _set_fields(self.model),
            "algo": {"name": self.algo.name,
                     "overrides": [[k, v] for k, v in self.algo.overrides]},
            "rounds": self.rounds,
            "team_frac": self.team_frac,
            "device_frac": self.device_frac,
            "comm": dataclasses.asdict(self.comm) if self.comm else None,
            "data_seed": self.data_seed,
            "family": self.family,
            "paper_ref": [[k, v] for k, v in self.paper_ref],
            "notes": self.notes,
        }
        if self.system is not None:
            d["system"] = self.system.to_dict()
        if self.cohort_size is not None:
            d["cohort_size"] = self.cohort_size
        return d

    @classmethod
    def from_dict(cls, d: dict) -> "FLScenario":
        """Rebuild a spec from `to_dict()` output (or hand-written JSON);
        `from_dict(to_dict(s)) == s` for every registered scenario."""
        return cls(
            name=d["name"],
            data=DataSpec(**d["data"]),
            model=ModelSpec(**d["model"]),
            algo=AlgoSpec(d["algo"]["name"],
                          tuple(tuple(p) for p in d["algo"]["overrides"])),
            rounds=d["rounds"],
            team_frac=d["team_frac"],
            device_frac=d["device_frac"],
            comm=CommConfig(**d["comm"]) if d.get("comm") else None,
            system=(SystemSpec.from_dict(d["system"])
                    if d.get("system") else None),
            cohort_size=d.get("cohort_size"),
            data_seed=d["data_seed"],
            family=d.get("family", ""),
            paper_ref=tuple(tuple(p) for p in d.get("paper_ref", ())),
            notes=d.get("notes", ""),
        )

    # -- derivation --------------------------------------------------------

    def scaled(self, *, m_teams: Optional[int] = None,
               n_devices: Optional[int] = None,
               samples_per_device: Optional[int] = None,
               rounds: Optional[int] = None,
               cohort_size: Optional[int] = None,
               algo_overrides: Optional[dict] = None) -> "FLScenario":
        """A derived scenario at a different scale (the benchmarks' quick
        mode shrinks CNN cells this way). Unset arguments keep the
        spec's values; `algo_overrides` merge over `algo.overrides`. An
        inherited or given cohort_size is clamped to the (possibly
        shrunk) population so `--smoke` derivations stay valid."""
        data = dataclasses.replace(
            self.data,
            m_teams=m_teams if m_teams is not None else self.data.m_teams,
            n_devices=(n_devices if n_devices is not None
                       else self.data.n_devices),
            samples_per_device=(samples_per_device
                                if samples_per_device is not None
                                else self.data.samples_per_device))
        algo = self.algo
        if algo_overrides:
            merged = dict(algo.overrides)
            merged.update(algo_overrides)
            algo = AlgoSpec(algo.name, tuple(merged.items()))
        cohort = cohort_size if cohort_size is not None else self.cohort_size
        if cohort is not None:
            cohort = min(int(cohort), data.n_devices)
        return dataclasses.replace(
            self, data=data, algo=algo, cohort_size=cohort,
            rounds=rounds if rounds is not None else self.rounds)

    def with_system(self, profile) -> "FLScenario":
        """This scenario on a wall-clock system model: `profile` is a
        SystemSpec, a named profile ("wan-cellular", ...), a spec dict,
        or None to detach."""
        return dataclasses.replace(
            self, system=None if profile is None else get_profile(profile))

    # -- materialization ---------------------------------------------------

    def model_config(self):
        """The resolved model config for this scenario's data: a
        PaperModelConfig, or a zoo architecture's ModelConfig."""
        return self.model.config(self.data)

    def build(self, seed: int = 0):
        """Materialize (FederatedData, FLAlgorithm, params0, metric_fn)
        for model-init seed `seed` (data comes from `data_seed`).

        Thin uncached wrapper around `runner.build_scenario` — prefer
        that entry point inside loops; it shares data, closures, and
        thereby compiled programs across calls.
        """
        from repro.scenarios.runner import build_scenario
        b = build_scenario(self, seed)
        return b.fd, b.algo, b.params0, b.metric_fn
