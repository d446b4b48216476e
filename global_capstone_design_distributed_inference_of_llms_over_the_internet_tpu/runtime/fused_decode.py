"""Fused multi-step greedy decode: the single-chip serving hot path.

The TPU-idiomatic analogue of the reference's CUDA-graph decode
(``petals/llama/cuda_graphs.py``): N decode steps run as ONE compiled XLA
program (``lax.scan`` over steps), so steady state pays zero per-step host
round trips.

Two structural choices, slope-timed on a v5e in round 4 (gpt2-124M b8 and
a 1.1B llama; that rig is gone and no benchmark cell runs this engine, so
the numbers below date from then):

  * **Caches as loop CARRY with per-layer in-place updates**, not as the
    layer scan's xs/ys. The xs/ys structure rewrites every layer's whole
    cache each step (5.6 ms/step at gpt2 b8 S=1024); carrying the stack
    and dynamic-indexing one layer at a time measured 3.7 ms — 1.5x. (An
    L-times-unrolled body over separate per-layer buffers measured another
    ~1.6x at long caches, but its giant HLO takes far longer to compile;
    the scan body is traced once and compiles in seconds.)
  * **Head fused with argmax, transposed.** The tied/untied head matmul is
    emitted as ``[V, B]`` (weights-stationary orientation) and consumed
    directly by the argmax, in the weight dtype with an fp32 upcast for the
    reduction — measured ~1.5x over the fp32-matmul + row-major argmax
    pair at gpt2's vocab.

Donation stays ungated here (cf. utils.platform.engine_donation): both
fused engines are single-controller programs — the oracle caller
owns every dispatch, so the CPU async-dispatch/free race the threaded
serving engines gate against has no second thread to race.

`make_fused_decode` is the greedy throughput engine (``--mode oracle``'s
fast path); `make_fused_sample_decode` folds the FULL reference sampler into
the scan for batch-1 sampled generation, bit-identical to the per-token
oracle loop. Distributed serving still samples per step on the final hop
(the sampler needs the request's live metadata there).
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp

from ..models.config import ModelConfig, refuse_single_pass
from ..models.transformer import _norm, embed_tokens, lm_head, stack_forward
from ..ops.sampling import RECENT_WINDOW, push_recent, sample_token

Params = Dict[str, Any]


def _decode_step(cfg: ModelConfig, params: Params, tok: jnp.ndarray,
                 kc: jnp.ndarray, vc: jnp.ndarray, cl: jnp.ndarray):
    """ONE decode step shared by the greedy and sampled fused engines:
    embed (+ learned positions), cache-carrying stack_forward (T == 1 fast
    path). tok: [B] int32 -> (h [B, T=1, D], kc, vc)."""
    batch = tok.shape[0]
    pos = cl + jnp.zeros((batch, 1), jnp.int32)
    # The SHARED embed (models.transformer.embed_tokens): a hand-rolled
    # wte gather here once dropped gemma's sqrt(hidden) embed scale.
    x = embed_tokens(cfg, params["embed"], tok[:, None], pos)
    return stack_forward(cfg, params["layers"], x, pos, kc, vc, cl)


def make_fused_decode(cfg: ModelConfig, max_steps: int, batch: int,
                      exact_head: bool = False):
    """Build a jitted fused decode program with a DYNAMIC step count.

    Returns ``fn(params, tok, kc, vc, start, n) -> (toks, kc, vc)``:
    ``tok``: [B] int32 last sampled token; ``kc``/``vc``: stacked caches
    [L, B, S, Hkv, Dh] (donated); ``start``: scalar int32 cache length;
    ``n``: scalar int32 number of steps (<= max_steps, traced — one compile
    serves every step count, which is what makes slope timing affordable).
    ``toks``: [max_steps, B]; rows >= n are zero.

    ``exact_head=True`` runs the head matmul in fp32 like ``lm_head`` does —
    bit-matching the per-token sampler's greedy argmax on reduced-precision
    checkpoints (near-tied logits can otherwise flip under the bf16 one-pass
    head). The oracle baseline uses it; the default is the fast weight-dtype
    head (the measured ~1.5x).
    """
    refuse_single_pass(cfg, "the fused decode engine")
    L = cfg.num_layers

    def head_argmax(params, h):
        # h: [B, D] -> greedy token [B] via the transposed head matmul.
        if cfg.tie_word_embeddings:
            w = params["embed"]["wte"]                    # [V, D]
        else:
            w = params["lm_head"]["w"].T                  # [V, D] (folded)
        dt = jnp.float32 if exact_head else w.dtype
        logits_t = w.astype(dt) @ h.T.astype(dt)          # [V, B]
        return jnp.argmax(logits_t.astype(jnp.float32), axis=0).astype(
            jnp.int32)

    @partial(jax.jit, donate_argnums=(2, 3))
    def fn(params, tok, kc, vc, start, n):
        # The layer scan carries the stacked caches and updates each layer's
        # rows in place via dynamic indexing (measured 1.5x over the
        # stacked-xs/ys structure, whose ys outputs rewrite every cache row
        # every step; the layer body is traced ONCE, keeping the HLO small —
        # an L-times-unrolled body was another ~1.6x at long caches but
        # produced compile jobs that wedged the shared compiler service).
        toks0 = jnp.zeros((max_steps, batch), jnp.int32)

        def body(i, carry):
            tok, kc, vc, cl, toks = carry
            h, kc, vc = _decode_step(cfg, params, tok, kc, vc, cl)
            h = _norm(cfg, params["final_norm"], h)[:, 0]
            tok = head_argmax(params, h)
            toks = jax.lax.dynamic_update_index_in_dim(toks, tok, i, 0)
            return (tok, kc, vc, cl + 1, toks)

        tok, kc, vc, _, toks = jax.lax.fori_loop(
            0, n, body, (tok, kc, vc, start, toks0))
        return toks, kc, vc

    return fn


def make_fused_sample_decode(cfg: ModelConfig, max_steps: int):
    """Fused multi-step SAMPLED decode (batch 1): the full reference sampler
    — count-scaled repetition penalty over the recent-50 window, triple-
    repeat guard, temperature, top-k, top-p (ops.sampling) — folded into the
    step scan, with the window carried as a ring buffer.

    The per-step key is ``PRNGKey(seed0 + i)`` (PRNGKey is traceable), the
    EXACT schedule of the per-token oracle loop (main.run_oracle /
    tests' oracle_generate) — so output is bit-identical to per-token
    sampled decoding while running as ONE compiled program.

    Returns ``fn(params, tok, kc, vc, start, n, seed0, recent, nvalid,
    temperature, top_p, top_k, repetition_penalty) ->
    (toks, kc, vc, recent, nvalid)`` — recent/nvalid thread across chunked
    calls so stop-condition checks between chunks don't reset the window.
    """
    refuse_single_pass(cfg, "the fused decode engine")

    @partial(jax.jit, donate_argnums=(2, 3))
    def fn(params, tok, kc, vc, start, n, seed0, recent, nvalid,
           temperature, top_p, top_k, repetition_penalty):
        toks0 = jnp.zeros((max_steps,), jnp.int32)

        def body(i, carry):
            tok, kc, vc, cl, recent, nvalid, toks = carry
            h, kc, vc = _decode_step(cfg, params, tok[None], kc, vc, cl)
            logits = lm_head(cfg, params, h)[0, 0]  # applies final_norm
            tok = sample_token(
                jax.random.PRNGKey(seed0 + i), logits, recent, nvalid,
                temperature, top_p, top_k, repetition_penalty)
            recent, nvalid = push_recent(recent, nvalid, tok)
            toks = jax.lax.dynamic_update_index_in_dim(toks, tok, i, 0)
            return (tok, kc, vc, cl + 1, recent, nvalid, toks)

        tok, kc, vc, _, recent, nvalid, toks = jax.lax.fori_loop(
            0, n, body, (tok, kc, vc, start, recent, nvalid, toks0))
        return toks, kc, vc, recent, nvalid

    return fn
