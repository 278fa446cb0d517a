"""Public attention ops: dispatch Pallas-on-TPU / interpret / jnp-ref.

Model code calls :func:`attention` (forward only) or
:func:`causal_attention` (training, with its backward pass); the
backend is chosen by the unified
:func:`repro.kernels.interface.kernel_mode`:
  * TPU backend        -> compiled Pallas kernel
  * elsewhere          -> the blocked pure-jnp reference (same math), which
                          is what CPU smoke tests and the 512-host-device
                          dry-run compile.
  * ``REPRO_KERNEL_MODE=interpret`` (or the legacy
    ``FORCE_PALLAS_INTERPRET=1``) runs the Pallas kernel body in interpret
    mode instead (used by kernel correctness tests).
"""
from __future__ import annotations

from repro.kernels.flash_attention.causal import causal_flash_attention
from repro.kernels.flash_attention.flash_attention import flash_attention
from repro.kernels.flash_attention.ref import (attention_ref,
                                               causal_attention_ref)
from repro.kernels.interface import KernelType, kernel_mode


def attention(q, k, v, *, causal=True, window=0, q_offset=None,
              block_q=512, block_kv=512, mode=None):
    """Multi-head (optionally causal/windowed) attention over
    (B, S, H, D) tensors, GQA-aware.

    Routes through ``kernel_mode(mode)``: ``xla`` runs the blocked jnp
    reference, otherwise the flash-attention Pallas kernel (interpret
    unless on TPU).
    """
    kt = kernel_mode(mode)
    if kt is KernelType.XLA:
        return attention_ref(q, k, v, causal=causal, window=window,
                             q_offset=q_offset)
    return flash_attention(q, k, v, causal=causal, window=window,
                           q_offset=q_offset, block_q=block_q,
                           block_kv=block_kv,
                           interpret=kt is not KernelType.PALLAS)


def causal_attention(q, k, v, *, scale: float, mode=None):
    """Causal attention with its backward pass: q, k (B, H, T, dqk), v
    (B, H, T, dv) -> (B, H, T, dv) float32, softmax scale explicit.

    Routes through ``kernel_mode(mode)``: ``xla`` runs the plain softmax
    reference, otherwise the fused splash kernels (interpret unless on
    TPU).
    """
    kt = kernel_mode(mode)
    if kt is KernelType.XLA:
        return causal_attention_ref(q, k, v, scale=scale)
    return causal_flash_attention(q, k, v, scale=scale,
                                  interpret=kt is not KernelType.PALLAS)
