"""Unified kernel dispatch: one ``KernelType`` enum for every Pallas op.

Every kernel package under ``repro.kernels`` ships a Pallas TPU kernel
and a pure-jnp XLA reference that stays the ground truth (DESIGN.md §10).
This module is the single place that decides which one runs — the
``KernelType`` enum-dispatch pattern from ddrous/mamba-jax's
``kernels/interface.py`` (SNIPPETS.md 1-2), grown an interpret mode so CI
can execute the actual Pallas kernel bodies on CPU:

  * ``PALLAS``    — compiled ``pl.pallas_call`` (needs a TPU backend)
  * ``XLA``       — the jnp reference implementation (``ref.py``)
  * ``INTERPRET`` — ``pl.pallas_call(..., interpret=True)``: the Pallas
                    body on any backend, bit-comparable to ``XLA``

Resolution precedence for :func:`kernel_mode`:

  1. an explicit ``mode=`` argument (string or ``KernelType``)
  2. ``REPRO_KERNEL_MODE`` = ``pallas`` | ``xla`` | ``interpret``
  3. the legacy ``FORCE_PALLAS_INTERPRET=1`` switch (-> ``INTERPRET``)
  4. backend default: ``PALLAS`` on TPU, ``XLA`` elsewhere

The resolved mode is an env lookup, so it is read at *trace* time; any
compiled program that bakes a kernel choice in must carry the mode on
its cache key — :func:`dispatch_key` is that key (the engine's compiled
program caches and ``permfl_round``'s jit include it, exactly like
``TraceConfig`` rides the probe path's keys). It also folds in
:func:`compress_fused` (``REPRO_COMPRESS_FUSED=0`` falls back to the
legacy unfused compressor ops — kept for the fused-vs-unfused engine
benchmark and as an escape hatch).
"""
from __future__ import annotations

import os
from enum import Enum

import jax

__all__ = ["KernelType", "KERNEL_MODES", "kernel_mode", "compress_fused",
           "dispatch_key", "on_tpu"]


class KernelType(Enum):
    """Which implementation of a kernel runs (see module docstring)."""
    PALLAS = "pallas"
    XLA = "xla"
    INTERPRET = "interpret"


# the REPRO_KERNEL_MODE spellings, mamba-jax's KernelTypeMapping pattern
KERNEL_MODES = {t.value: t for t in KernelType}


def on_tpu() -> bool:
    """True when the default JAX backend is a TPU. A backend that fails
    to start raises here rather than passing for a CPU."""
    return jax.default_backend() == "tpu"


def _parse(spelling: str, source: str) -> KernelType:
    try:
        return KERNEL_MODES[spelling.strip().lower()]
    except KeyError:
        raise ValueError(
            f"unknown kernel mode {spelling!r} (from {source}); expected "
            f"one of {sorted(KERNEL_MODES)}") from None


def kernel_mode(mode=None) -> KernelType:
    """Resolve the kernel dispatch mode (precedence in module docstring).

    ``mode`` may be a ``KernelType``, one of its string spellings, or
    None (read the environment / backend default).
    """
    if mode is not None:
        if isinstance(mode, KernelType):
            return mode
        return _parse(str(mode), "mode argument")
    env = os.environ.get("REPRO_KERNEL_MODE")
    if env:
        return _parse(env, "REPRO_KERNEL_MODE")
    if os.environ.get("FORCE_PALLAS_INTERPRET") == "1":
        return KernelType.INTERPRET
    return KernelType.PALLAS if on_tpu() else KernelType.XLA


def compress_fused() -> bool:
    """False when ``REPRO_COMPRESS_FUSED=0`` asks for the legacy unfused
    compressor ops (the fused `repro.kernels.compress` stack is the
    default); `benchmarks/bench_engine.py` measures the difference."""
    return os.environ.get("REPRO_COMPRESS_FUSED", "1") != "0"


def dispatch_key(mode=None) -> tuple:
    """Hashable (KernelType, fused?) pair capturing every env knob that
    changes a traced program's kernel choices. Compiled-program caches
    (engine/sweep programs, ``permfl_round``'s jit) take it as a static
    argument so flipping ``REPRO_KERNEL_MODE`` / ``REPRO_COMPRESS_FUSED``
    between runs re-traces instead of reusing a stale kernel choice."""
    return (kernel_mode(mode), compress_fused())
