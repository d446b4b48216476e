"""Cache-aware multi-head attention (MHA/GQA/MQA) with static shapes.

TPU-first counterpart of the reference's manual sdpa + legacy-tuple KV concat
(``petals/llama/block.py:123-141``): instead of concatenating growing
per-session tuples, keys/values live in a preallocated fixed-size cache and new
tokens are written with ``dynamic_update_slice`` — shapes never change, so the
prefill and decode step functions each compile exactly once.

Softmax accumulates in float32 (matches reference ``block.py:138``: fp32
softmax), outputs return to the activation dtype (bfloat16 on TPU).

Implementation is pure XLA by DECISION, not omission: a hand-written Pallas
flash kernel (223 lines, VMEM-streamed KV) lived here through round 1 and
lost to XLA's fused attention at EVERY shape class tried under the honest
hard-sync methodology — e.g. 3.5 ms/step (XLA) vs 6.7 ms/step (kernel) at
S=8192 decode on a 0.5B model, v5e — because the kernel's unfused
custom-call boundary cost more than its streaming saved. It was deleted in
round 2 (see docs/PERFORMANCE.md "Flash kernel post-mortem"; history:
``git log -- **/flash_attention.py``). Revisit only with a measured win on
real hardware.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def update_kv_cache(
    k_cache: jnp.ndarray,
    v_cache: jnp.ndarray,
    k_new: jnp.ndarray,
    v_new: jnp.ndarray,
    cache_len: jnp.ndarray,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Write T new tokens at positions [cache_len, cache_len+T).

    k_cache/v_cache: [B, S, Hkv, Dh]; k_new/v_new: [B, T, Hkv, Dh];
    cache_len: scalar int32.

    CONTRACT: cache_len + T <= S. Under jit, ``dynamic_update_slice`` CLAMPS an
    out-of-range start index instead of raising, which would silently overwrite
    the newest cache rows. Callers must enforce max-length admission control
    BEFORE dispatching the step — the runtime does this at session level
    (`runtime.kv_cache`), mirroring the reference's ``inference_max_length``
    guard (``petals/server/block_functions.py:193-197``).
    """
    start = (0, cache_len.astype(jnp.int32), 0, 0)
    k_cache = jax.lax.dynamic_update_slice(k_cache, k_new.astype(k_cache.dtype), start)
    v_cache = jax.lax.dynamic_update_slice(v_cache, v_new.astype(v_cache.dtype), start)
    return k_cache, v_cache


def cached_attention(
    q: jnp.ndarray,
    k_cache: jnp.ndarray,
    v_cache: jnp.ndarray,
    cache_len: jnp.ndarray,
    *,
    sliding_window=None,
    scale: float = 0.0,
    logit_softcap: float = 0.0,
) -> jnp.ndarray:
    """Causal attention of T query tokens over a cache holding cache_len+T keys.

    q: [B, T, H, Dh] — query i has absolute position cache_len + i.
    k_cache/v_cache: [B, S, Hkv, Dh] with the new keys already written.
    Returns [B, T, H, Dh].

    sliding_window may be a static int OR a traced int32 scalar (the
    per-layer "window" leaf of alternating local/global models riding a
    layer scan); a value <= 0 disables the window, so one compiled body
    serves both layer kinds. scale overrides the head_dim ** -0.5 score
    scale (gemma2 query_pre_attn_scalar); logit_softcap > 0 applies
    cap * tanh(s / cap) to scores before masking (gemma2).

    Right-padded prefill is safe: a real query at position i only attends to
    keys j <= cache_len + i, all of which are real tokens; padded queries
    produce garbage rows that the caller discards.
    """
    b, t, h, dh = q.shape
    s = k_cache.shape[1]
    hkv = k_cache.shape[2]
    groups = h // hkv
    # Keep cache operands in their storage dtype (bf16 on TPU) — converting the
    # whole [B,S,Hkv,Dh] cache to fp32 would double HBM traffic per decode
    # step. fp32 accumulation comes from preferred_element_type instead.
    q = q * (scale if scale else dh ** -0.5)

    # [B, T, Hkv, G, Dh] x [B, S, Hkv, Dh] -> [B, Hkv, G, T, S]
    qg = q.reshape(b, t, hkv, groups, dh)
    scores = jnp.einsum(
        "bthgd,bshd->bhgts", qg, k_cache, preferred_element_type=jnp.float32
    )
    if logit_softcap:
        scores = logit_softcap * jnp.tanh(scores / logit_softcap)

    q_pos = cache_len + jnp.arange(t, dtype=jnp.int32)  # [T]
    k_pos = jnp.arange(s, dtype=jnp.int32)  # [S]
    allowed = k_pos[None, :] <= q_pos[:, None]  # causal
    if sliding_window is not None:
        w = jnp.asarray(sliding_window, jnp.int32)
        allowed &= (k_pos[None, :] > (q_pos[:, None] - w)) | (w <= 0)
    scores = jnp.where(allowed[None, None, None, :, :], scores, NEG_INF)

    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum(
        "bhgts,bshd->bthgd",
        probs.astype(v_cache.dtype),
        v_cache,
        preferred_element_type=jnp.float32,
    )
    return out.reshape(b, t, h, dh).astype(q.dtype)
