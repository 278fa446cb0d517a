from repro.kernels.flash_attention.ops import attention, causal_attention
from repro.kernels.flash_attention.ref import (attention_ref,
                                               causal_attention_ref)
from repro.kernels.flash_attention.flash_attention import flash_attention
from repro.kernels.flash_attention.causal import causal_flash_attention

__all__ = ["attention", "attention_ref", "flash_attention",
           "causal_attention", "causal_attention_ref",
           "causal_flash_attention"]
