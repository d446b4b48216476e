from .attention import cached_attention, update_kv_cache
from .norms import layer_norm, rms_norm
from .rotary import apply_rope, rope_cos_sin
from .sampling import (
    RECENT_WINDOW,
    SamplingParams,
    apply_repetition_penalty,
    make_recent_buffer,
    push_recent,
    sample_probs,
    sample_token,
    sample_tokens,
)

__all__ = [
    "cached_attention",
    "update_kv_cache",
    "layer_norm",
    "rms_norm",
    "apply_rope",
    "rope_cos_sin",
    "RECENT_WINDOW",
    "SamplingParams",
    "apply_repetition_penalty",
    "make_recent_buffer",
    "push_recent",
    "sample_probs",
    "sample_token",
    "sample_tokens",
]


def quant_kernel_report() -> dict:
    """Where this process's quantized matmul sites ran, as traced so far:
    per kernel, whether it is interpreted, how many sites took the Pallas
    path, and each site ``MxKxN`` -> "pallas tn=..,tk=.." or "xla"."""
    from . import int8_kernel, nf4_kernel

    return {
        name: {
            "interpret": mod._INTERPRET,
            "launches": mod._launches,
            "sites": {"x".join(map(str, mkn)): where
                      for mkn, where in sorted(mod._sites.items())},
        }
        for name, mod in (("int8", int8_kernel), ("nf4", nf4_kernel))
    }
