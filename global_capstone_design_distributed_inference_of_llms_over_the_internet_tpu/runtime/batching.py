"""Continuous batching: many concurrent sessions, ONE decode step.

The reference serves each session's decode step as its own forward
(``src/rpc_handler.py:149-325`` — one request, one compute); N concurrent
clients cost N sequential forwards per token. On TPU the idiomatic fix is
STATIC-SHAPE slot batching (the shape-stable cousin of vLLM-style
continuous batching): the server owns one slot-major KV cache
``[L, S, max_len, Hkv, Dh]`` (a looped stack's: ``loop_steps * L`` deep
for L layers of weights, rows of its own for every pass: `_run_passes`),
every live session occupies a slot, and one jitted step advances EVERY
active slot at once — per-slot cache lengths, an active mask for empty
slots. The cache stacks are the step's loop carry:
each layer scatters its T new rows a slot into them at the slots' own
lengths (`_append_rows`; an inactive slot rewrites the rows it holds) and
attends over its ``[S, max_len, Hkv, Dh]`` rows read straight out of the
stack, so a step moves the new rows and nothing else of the cache.
Compute scales with the slot count S (the server's intended concurrency),
not with how many requests happen to arrive, and the step is one compiled
program replayed forever. Where the backend would not keep ``Dh`` minor in
such an array, a row is held FOLDED (its heads side by side in one minor
dim, ``[L, S, max_len, W]``: `kv_fold_width`); every program tells by the
stack's rank, and nothing outside the programs reads a row.

Sessions join at prefill (slot allocated, prompt written into the slot's
rows), decode via `decode_batch` (whatever subset of sessions has a token
ready — inactive slots are masked), and leave via `end_session` (slot
recycled). Token parity with the per-session oracle is asserted in
tests/test_batching.py.

Scope: the batched path covers plain greedy/sampled decode AND speculative
verification — a draft step is rows of [last_accepted, d_1..d_K], i.e. a
multi-token batched forward plus per-row accept/reject, so spec sessions
coalesce the same way plain ones do (rounds are keyed by step width T; all
requests in a round share one compiled step). Beam reorder and training
still ride the per-session StageExecutor — servers route those requests to
it unchanged. Replay is accepted (prefill + multi-token KV rebuild rounds)
so a replacement batched peer can adopt a failed-over burst session.

BURST DECODE (the continuous-batching serving core, ROADMAP Open item 1):
a FULL-SPAN batched engine can additionally run N decode ticks in ONE
jitted dispatch — ``lax.scan`` over ticks, each tick embedding the carry
token, running the layer scan, sampling ON DEVICE with the session-local
seed schedule ``PRNGKey(step_seed + i)`` (bit-identical to the sequential
``_sample_rows`` path), and maintaining per-slot alive masks so eos /
repeat / budget stops truncate mid-scan without a host round trip. The
host pays one dispatch per N tokens instead of one per token.

THE RIDER LANE (a looped stack's engine only: `BatchedStageExecutor
.rider_rows`): there a prefill program streams the weights `loop_steps`
times, as long as a whole tick, and every session that is decoding pays for
it in whichever gap it falls into. So each burst tick also carries
`RIDER_ROWS` prompt rows of the ONE request that joins during the burst,
flat beside the slots' rows through every matmul, and samples that
request's first token; a prefill that finds another session in a slot
joins the next burst round as its rider (`BatchingStageAdapter._rides`). A
lane with no rider does the same work and writes nothing: every round costs
the same.
"""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import threading
import time
from functools import partial
from typing import Any, Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..models.config import ModelConfig, refuse_single_pass
from ..utils.platform import engine_donation
from ..models.partition import StageSpec
from ..models.transformer import (
    _dot,
    _dot_t,
    _plain,
    _mlp,
    _norm,
    close_pass,
    embed_tokens,
    exit_state,
    make_rope,
    qkv_proj,
)
from ..ops.slot_attention import (
    engaged as kernel_engaged,
    read_plan,
    slot_attention,
)
from ..ops.norms import layer_norm, rms_norm
from ..ops.rotary import apply_rope
from ..parallel.ring_attention import NEG_INF
from ..telemetry import catalog as _tm
from ..telemetry import events as _ev
from ..telemetry.profiling import get_profiler as _get_profiler
from .errors import register as _catalog
from .kv_cache import round_to_bucket

Params = Dict[str, Any]


def _host_ids(x, reads) -> np.ndarray:
    """A request's token ids on the host, flat. Off a wire frame they are
    there already (`net._stage_input` keeps an integer tensor as decoded);
    a caller that hands a device array pays a read per request, under the
    round's lock, and ``reads`` (the round's count of transfers down) says
    so."""
    if isinstance(x, jax.Array):
        reads.inc()
    return np.asarray(x).reshape(-1)


def _burst_entry(rq, reads) -> dict:
    """A StageRequest's burst spec in the engine's stateless per-burst form
    (everything the wire ships every step, so failover needs no server-side
    sampler state — the module-docstring contract)."""
    sp = rq.sampling
    return {
        "token": int(_host_ids(rq.hidden, reads)[0]),
        "seed": int(rq.step_seed),
        "budget": int(rq.burst_budget),
        "eos": rq.eos_token_id,
        "generated": rq.generated_tokens,
        "temperature": sp.temperature,
        "top_p": sp.top_p,
        "top_k": sp.top_k,
        "repetition_penalty": sp.repetition_penalty,
    }



def _gc_counts() -> List[int]:
    """Collections the garbage collector has run, by generation."""
    return [g["collections"] for g in gc.get_stats()]


def _rider_entry(rq, reads) -> dict:
    """A prefill StageRequest as the engine's rider (`decode_burst`): the
    prompt and what `executor._sample_rows` samples its first token with."""
    sp = rq.sampling
    return {
        "session_id": rq.session_id,
        "ids": _host_ids(rq.hidden, reads),
        "seed": int(rq.step_seed),
        "generated": rq.generated_tokens,
        "temperature": sp.temperature,
        "top_p": sp.top_p,
        "top_k": sp.top_k,
        "repetition_penalty": sp.repetition_penalty,
    }


PREFILL_BUCKETS = (8, 16, 32, 64, 128, 256, 512, 1024)

# Prompt rows a burst tick carries for the request that joins during the
# burst (the rider lane of an engine whose prefill program would cost a
# tick: `BatchedStageExecutor.rider_rows`). 16 ticks of 16 rows hold the
# 256-row prefill bucket; with the slots' 8 rows the tick's matmuls stay
# far under the rows at which a v5e stops being bound by the weights' read.
RIDER_ROWS = 16

# How long a round's leader holds the round open for a session that the
# last round of that key answered and that has not asked again yet, as a
# share of that round's wall time R (`BatchingStageAdapter._close_round`;
# never under `window_s`). Waiting w costs each joined session w; a session
# that misses its round waits a whole R for the next. Set on the chip
# (PERF.md section 6, PR 41): the way back takes 4-25 ms whatever the round
# (reply, wire, the client's turnaround, the next request, this lock). 8
# sessions of a 215 or 665 ms round were all in under R / 8; 16 sessions of
# a 92 ms round come back over 5-25 ms, so that R / 8 = 11.5 ms closed 57%
# of the rounds with somebody missing (two rounds a token again) where
# R / 4 = 23 ms closed none, and R / 3 read as R / 4.
REJOIN_SHARE = 1.0 / 4

# A round whose wall time is over this many times that of the last round of
# its key is a STALL: counted, and its parts recorded
# (`BatchingStageAdapter._stalled`). Against the engine's own last
# measurement, as `REJOIN_SHARE`: no configuration is named. A round ten
# times its predecessor shows about once in ten runs of a closed loop
# (PERF.md section 6, PR 41); the rounds of one cell differ by under 1.1 x.
STALL_FACTOR = 4.0
# The parts of a burst round the phase profiler brackets
# (`BatchedStageExecutor.burst_parts`); a stall's rest is ``other``.
# ``queued``: enqueue returned -> a prompt's programs ahead of the burst
# finished; ``device`` is from there to the results.
STALL_PARTS = ("build", "dispatch", "queued", "device", "readback")

# The client's repeat-stop heuristic (runtime.client.REPEAT_STOP), mirrored
# on device so a burst truncates exactly where the sequential host loop
# would have stopped. Keep the two in lockstep.
BURST_REPEAT_STOP = 5

# What a burst round sends across the host-device boundary, and how it is
# packed: its per-slot values go up as ONE int32 array ``[rows, S]``, a row
# a name below and then the `RECENT_WINDOW` columns of the recent-token
# window, and ONE float32 array ``[3, S]`` (`_burst_prep`); a rider lane's
# request as ONE int32 vector, these scalars, then its recent window and its
# prompt ids, its three float knobs riding as their bits (`_rider_args`);
# the results the host reads come back as ONE int32 vector
# (`_burst_collect`). The burst program slices them apart as its first
# operations and packs the results as its last (`_build_burst`): every
# array of its own is an allocation, a linearisation and a transfer with
# the chip idle, 0.3-0.5 ms each (PERF.md section 6, PR 49).
BURST_INTS = ("tok", "lengths", "alive", "seeds", "nvalid", "run", "left",
              "eos", "top_k")
BURST_FLOATS = ("temp", "top_p", "rp")
RIDER_INTS = ("len", "slot", "seed", "nvalid", "top_k")
# What a burst program of a family whose expert layers hold a share of the
# routed experts sums on the device and returns after its lengths, by
# series: a tick's rows' assignments over ALL experts, those that fell on a
# held expert, the held experts at least one row chose, the held experts
# there were (a layer a tick); `_burst_collect` unpacks them by these names.
MOE_COUNTERS = ("server_moe_assignments_total",
                "server_moe_assignments_held_total",
                "server_moe_experts_hit_total",
                "server_moe_expert_slots_total")


def holds_expert_share(cfg) -> bool:
    """Whether the span has expert layers that hold a share of the routed
    experts: their burst program carries `MOE_COUNTERS`. Asked of the
    expert layer's own keys, not of the attention's."""
    return (cfg.moe_intermediate_size > 0
            and cfg.num_layers > cfg.first_k_dense)


def _rider_fields(window: int):
    """The rider's vector by part, as slices: its `RIDER_INTS`, its recent
    window, the bits of its `BURST_FLOATS`, its prompt ids (the rest)."""
    a = len(RIDER_INTS)
    b = a + window
    c = b + len(BURST_FLOATS)
    return slice(a), slice(a, b), slice(b, c), slice(c, None)


@_catalog
class SlotFull(RuntimeError):
    """No free slot (admission control — the caller queues or fails over)."""


@_catalog
class WindowGone(ValueError):
    """A rewind across a window edge of a family whose older rows are
    summaries: the exact rows are gone (the client's journal replay
    rebuilds the slot through prefill)."""


# -- the decoder layer of the four engine programs --------------------------

def _qscale(cfg) -> float:
    """Attention score scale (gemma2 query_pre_attn_scalar override)."""
    return cfg.query_scale or cfg.head_dim ** -0.5


def _layer_mask(lp, mask, q_pos, k_pos):
    """Intersect the body's mask with this layer's window (the traced
    "window" leaf of alternating local/global models — gemma2): <= 0
    means global. q_pos/k_pos are broadcastable position grids matching
    the mask's trailing dims. The int32 cast is load-bearing: a dtype
    sweep over the layer tree (checkpoint conversion at bf16) would
    otherwise compute the window boundary in bfloat16 and mis-mask keys
    past position ~256."""
    w = lp.get("window")
    if w is None:
        return mask
    w = jnp.asarray(w, jnp.int32)
    return mask & ((k_pos > q_pos - w) | (w <= 0))


def _softcap_and_mask(cfg, scores, allowed):
    """Softcap scores (gemma2 attn_logit_softcapping, pre-mask) then mask."""
    if cfg.attn_softcap:
        scores = cfg.attn_softcap * jnp.tanh(scores / cfg.attn_softcap)
    return jnp.where(allowed, scores, NEG_INF)


def _normed(cfg, p, h):
    """`_norm` of the residual stream as the layer's matmuls take it: where
    the family carries the stream in float32 (``cfg.fp32_residual``), in
    the type of the weights again (the norm's own scale is never
    quantised)."""
    a = _norm(cfg, p, h)
    return a.astype(p["w"].dtype) if cfg.fp32_residual else a


def _residual(cfg, lp, h, attn_out):
    """Residual + MLP with optional sandwich norms (gemma2 post_norms:
    ln3 after attention, ln4 after the MLP, before each residual add): the
    ``mlp`` section of every engine program on the device trace. ``(h,
    extra)``: ``extra`` is what the layer adds to its scan's state beside
    the cache rows, nothing but for an expert layer that holds a share."""
    with jax.named_scope("mlp"):
        if cfg.post_norms:
            attn_out = _norm(cfg, lp["ln3"], attn_out)
        h = h + attn_out
        if "router_bias" in lp["mlp"]:
            # one chip's share of a sigmoid-routed expert layer: what the
            # layer scan stacks beside the cache rows is which rows chose
            # which held expert (the burst program's counters)
            from ..models.moe import held_moe_mlp

            mlp_out, assigned = held_moe_mlp(
                cfg, lp["mlp"], _normed(cfg, lp["ln2"], h))
            return h + mlp_out, (assigned,)
        mlp_out = _mlp(cfg, lp["mlp"], _normed(cfg, lp["ln2"], h), None)
        if cfg.post_norms:
            mlp_out = _norm(cfg, lp["ln4"], mlp_out)
        return h + mlp_out, ()


def _visible(cfg, q_pos, k_pos):
    """Keys a query may see in a cache: everything up to and including its
    own position (causal within a block of new rows too), inside the
    family's window ``(q_pos - window, q_pos]`` where it has one."""
    allowed = k_pos <= q_pos
    if cfg.sliding_window:
        allowed &= k_pos > q_pos - cfg.sliding_window
    return allowed


def _masked_scores(cfg, lp, qg, keys, grid):
    """Float32 scores ``[B, Hkv, G, T, S]`` of the grouped, scaled queries
    ``qg`` (``[B, T, Hkv, G, Dh]``) against ``keys`` (``[B, S, Hkv, Dh]``),
    softcapped, with ``NEG_INF`` wherever ``grid`` = ``(mask, q_pos,
    k_pos)`` and the layer's window forbid."""
    mask, q_pos, k_pos = grid
    scores = jnp.einsum(
        "bthgd,bshd->bhgts", qg, keys.astype(qg.dtype),
        preferred_element_type=jnp.float32)          # [B, Hkv, G, T, S]
    m = _layer_mask(lp, mask, q_pos, k_pos)
    m = m[:, None, None] if m.ndim == 3 else m[None, None, None]
    return _softcap_and_mask(cfg, scores, m)


def _attend(cfg, lp, q, keys, values, grid):
    """Attention of ``q`` (``[B, T, H, Dh]``, rotated) over ``keys`` and
    ``values`` (``[B, S, Hkv, Dh]``) under ``grid`` = ``(mask, q_pos,
    k_pos)``: ``[B, T, H * Dh]``, before the output projection. Over a
    `_CacheLayer` (of ``grid`` only ``q_pos`` then: the mask and ``k_pos``
    are each block's own): `_attend_cached`."""
    if isinstance(keys, _CacheLayer):
        return _attend_cached(cfg, lp, q, keys, values, grid[1])
    if isinstance(keys, _WindowedRead):
        return _attend_windowed(cfg, lp, q, keys, values, grid[1])
    b, t = q.shape[:2]
    groups = cfg.num_heads // cfg.num_kv_heads
    qg = q.reshape(b, t, cfg.num_kv_heads, groups, cfg.head_dim)
    scores = _masked_scores(cfg, lp, qg * _qscale(cfg), keys, grid)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgts,bshd->bthgd", probs.astype(values.dtype),
                     values.astype(q.dtype))
    return out.reshape(b, t, -1)


# Rows of a cache layer that a decode program reads in one piece
# (`_attend_cached`): the served ``max_len``s are multiples of it. Chosen on
# the v5e among 64, 128 and 256 (PERF.md section 6, PR 35).
ATTN_BLOCK = 128


def attn_block(max_len: int) -> int:
    """The block of a ``max_len``-row slot: the largest divisor of
    ``max_len`` that is at most `ATTN_BLOCK` (`ATTN_BLOCK` itself for every
    multiple of it). A slot shorter than `ATTN_BLOCK`, or of a length with
    no divisor above a quarter of it (a prime, say), is ONE block: the full
    read, and not a thousand blocks of a row."""
    return next((b for b in range(min(ATTN_BLOCK, max_len), ATTN_BLOCK // 4,
                                  -1) if max_len % b == 0), max_len)


def attn_blocks(lengths, active, t, max_len, xp=np, per_slot=False,
                block: int = 0):
    """How many blocks of `attn_block` rows (of ``block`` rows, for a read
    with a block of its own: `latent_block`) a step of ``t`` new rows a slot
    reads of every cache layer: up to the last new row of the LONGEST
    ACTIVE slot (along the last axis of ``lengths`` / ``active``), and none
    where no slot is active. An inactive slot's rows are never needed: its
    output is discarded. ``per_slot``: a slot at a time, each up to its OWN
    last new row and an inactive one 0, for a program that reads each slot
    by itself (`cache_read`: the kernel). The ONE statement of the bound:
    the decode programs call it on traced values (``xp=jnp``) and the host,
    for ``server_attn_rows_read_total``, on the lengths a step began
    with."""
    block = block or attn_block(max_len)
    need = xp.where(active, lengths + t, 0)
    if not per_slot:
        need = xp.max(need, axis=-1)
    return xp.minimum(-(-need // block), max_len // block)


def cache_read(cfg, layers, folded: bool, t: int = 1,
               rider: bool = False, max_len: int = 0) -> str:
    """The form in which a decode program of ``t`` new rows a slot reads a
    cache layer (`_attend_cached`, `_attend_windowed`), by what it is handed
    alone: ``folded`` stacks (`kv_fold_width`), the configuration and the
    layer tree ``layers``, a ``rider`` group beside the slots' rows.
    ``"kernel"`` (`ops.slot_attention`, each slot up to its own last block)
    where the kernel takes the input, else the ``"loop"`` over the blocks up
    to the longest active slot (`_block_stats`). The kernel has one query
    row a slot and no mask but a row limit, so a step of several rows,
    softcapped scores or a masked window of any kind are the loop's (a
    folded stack beside a rider lane too: no engine holds one); rows that
    stay ``[Hkv, Dh]`` are the kernel's where it is the chip's and a row
    fills its lanes (``Dh`` whole tiles of 128: `kernel_engaged`). The
    programs, the counter of the rows they read (`_count_attn_rows`) and
    the ``kv_layout`` event all ask here. A latent family under a learned
    selection (``cfg.kv_lora_rank``) scores a slot's index keys and attends
    to the top ``index_topk`` positions' latent rows (`_attend_latent`), in
    one of two forms. ``"select"``, the definition: ``jax.lax.top_k``, then
    a gather of those rows. ``"kernel"``, where the kernel is the chip's
    and a slot of ``max_len`` rows holds at most `LATENT_DENSE` times
    ``index_topk``: every row of a slot's own blocks, streamed, under the
    selection as a mask (a gather moves ``index_topk`` rows at a tenth of
    the rate a slot's contiguous blocks arrive at)."""
    if cfg.kv_lora_rank:
        dense = (t == 1 and kernel_engaged()
                 and max_len <= LATENT_DENSE * cfg.index_topk
                 and (latent_block(max_len) % 128 == 0
                      or jax.default_backend() != "tpu"))
        return "kernel" if dense else "select"
    plain = not (cfg.attn_softcap or cfg.sliding_window
                 or "window" in layers)
    if folded:
        return "kernel" if t == 1 and plain and not rider else "loop"
    if t == 1 and plain and cfg.head_dim % 128 == 0 and kernel_engaged():
        return "kernel"
    return "loop"


def kv_fold_width(layout, hkv: int, dh: int) -> Optional[int]:
    """Lanes of a FOLDED cache row, or None where a row stays ``[Hkv, Dh]``.

    ``layout`` is the backend's answer to how it holds a ``[.., max_len,
    Hkv, Dh]`` array (`BatchedStageExecutor._new_stacks` asks). Where it
    keeps ``Dh`` minor (every backend with one layout: the CPU; the TPU
    for a ``head_dim`` that fills its lanes) nothing is folded and every
    program is the one it always was. Where it does not (the v5e holds
    ``[48, 8, 1024, 25, 64]`` with ``max_len`` minor, to pad nothing),
    every decode program computes in ANOTHER layout than the stacks rest
    in: it re-lays both whole stacks on its way in and out, and reads them
    padded to full tiles in between (2.56 x at 25 x 64). No layout can be
    stated at a program's edge to prevent it: a serialized executable,
    i.e. one from the compile cache, has lost it (jax 0.9.0; PERF.md
    section 6, PR 45). So the SHAPE is changed: a row's heads side by side
    in ONE minor dim, padded with zeros to a whole number of the layout's
    own lane tile (``Hkv * Dh`` = 1600 -> 1664 = 13 x 128), which the
    device does keep minor (asked of the v5e: 1600 itself it would not)."""
    order = layout.major_to_minor
    if order[-1] == len(order) - 1 or not layout.tiling:
        return None
    lanes = layout.tiling[0][-1]
    return -(-hkv * dh // lanes) * lanes


def layout_text(layout) -> str:
    """A device layout as XLA spells it in a program's text and a trace's
    operation names: dimensions minor to major, then the tiles
    (``{3,2,1,0:T(8,128)(2,1)}``)."""
    order = ",".join(str(d) for d in reversed(layout.major_to_minor))
    tiles = "".join("(" + ",".join(str(n) for n in t) + ")"
                    for t in layout.tiling or ())
    return "{" + order + (":T" + tiles if tiles else "") + "}"


def _fold(stack, rows):
    """``rows`` (``[.., Hkv, Dh]``) as ``stack`` holds a row: as they are
    for a ``[L, S, max_len, Hkv, Dh]`` stack; for a folded one (``[L, S,
    max_len, W]``: `kv_fold_width`) the heads side by side, zeros up to
    ``W``."""
    if stack.ndim == 5:
        return rows
    flat = rows.reshape(*rows.shape[:-2], -1)
    return jnp.pad(flat, [(0, 0)] * (flat.ndim - 1)
                   + [(0, stack.shape[-1] - flat.shape[-1])])


def _unfold(stack, cfg, rows):
    """`_fold`'s inverse on rows read back out of ``stack``: ``[.., Hkv,
    Dh]``."""
    if stack.ndim == 5:
        return rows
    hkv, dh = cfg.num_kv_heads, cfg.head_dim
    return rows[..., :hkv * dh].reshape(*rows.shape[:-1], hkv, dh)


def _origin(stack, *lead):
    """Start indices into ``stack``: ``lead``, then 0 along every other
    dim (a row's dims: two, or one where rows are folded)."""
    return lead + (0,) * (stack.ndim - len(lead))


@dataclasses.dataclass(frozen=True)
class _CacheLayer:
    """Layer ``at`` of a carried ``[L, S, max_len, Hkv, Dh]`` cache stack
    (folded: ``[L, S, max_len, W]``), of which only the first ``blocks``
    (traced) blocks of `attn_block` rows are to be read, straight out of
    the stack: ONE count for every slot or, for the kernel, the slots' own
    as its `read_plan` (a vector)."""
    stack: Any
    at: Any
    blocks: Any


def _block_stats(cfg, lp, qg, keys, values, q_pos, allowed_of):
    """The softmax of the grouped, scaled queries ``qg`` (``[S, T, Hkv, G,
    Dh]``; against FOLDED stacks ``[S, T, 1, H, W]``) over the first
    ``keys.blocks`` blocks of `attn_block` rows of the cache layers ``keys``
    and ``values``, as its running statistics, undivided: ``(m, l, acc)``,
    the maximum score ``[S, Hkv, G, T]``, the denominator and the weighted
    sum of the values (``[.., Dh]``), float32. ``allowed_of(at)``: which of
    the rows ``at`` (``[1, 1, n]``) each query may see (``[S, T, n]``); the
    layer's own window (by ``q_pos``) and the softcap are `_masked_scores`'.
    A loop with a traced trip count, one block-sized operand of each stack
    a trip (~4 us a block a layer on the v5e: PERF.md section 6, PR 35):
    no trip with no active slot, none over a stack without rows. The ONE
    place a block of a stack is sliced for a read that is not the kernel's."""
    b, t, hkv, groups, dh = qg.shape
    stat = (b, hkv, groups, t)
    nothing = (jnp.full(stat, NEG_INF, jnp.float32),
               jnp.zeros(stat, jnp.float32),
               jnp.zeros(stat + (dh,), jnp.float32))
    if not keys.stack.shape[2]:
        return nothing
    rows = attn_block(keys.stack.shape[2])
    folded = keys.stack.ndim == 4

    def read(layer, j):
        """Block ``j`` of every slot: ``[S, rows, Hkv, Dh]`` (folded: ``[S,
        rows, 1, W]``)."""
        got = jax.lax.dynamic_slice(
            layer.stack, _origin(layer.stack, layer.at, 0, j * rows),
            (1, layer.stack.shape[1], rows) + layer.stack.shape[3:])[0]
        return got[:, :, None] if folded else got

    def block(j, carry):
        m, l, acc = carry
        k_rows = read(keys, j)
        at = (j * rows + jnp.arange(rows, dtype=jnp.int32))[None, None, :]
        sc = _masked_scores(cfg, lp, qg, k_rows, (allowed_of(at), q_pos, at))
        m2 = jnp.maximum(m, sc.max(-1))
        corr = jnp.exp(m - m2)
        w = jnp.exp(sc - m2[..., None])
        acc = acc * corr[..., None] + jnp.einsum(
            "bhgts,bshd->bhgtd", w.astype(values.stack.dtype),
            read(values, j).astype(qg.dtype),
            preferred_element_type=jnp.float32)
        return m2, l * corr + w.sum(-1), acc

    return jax.lax.fori_loop(0, keys.blocks, block, nothing)


def _attend_cached(cfg, lp, q, keys, values, q_pos):
    """`_attend` of ``q`` (``[S, T, H, Dh]``, at ``q_pos`` ``[S, T, 1]``)
    over the cache layers ``keys`` and ``values`` (`_CacheLayer`), reading
    only their first ``keys.blocks`` blocks of `attn_block` rows. A row
    past a query's position has probability exactly 0 in `_attend`, so
    leaving the blocks past the longest active slot unread changes no term
    of any sum. The count is a value of the program: `_block_stats`' loop,
    divided here.

    FOLDED stacks (``[L, S, max_len, W]``: `kv_fold_width`): every query
    head against ONE key "head" as wide as a folded row. A head's query
    sits in the lanes of its own KV head and is zero in all others
    (block-diagonal: the zeros add nothing to a score), and of the ``W``
    lanes its weighted sum comes back in, its KV head's are kept. The MXU
    multiplies ``Hkv`` times the zeros it needs not (31 GFLOP a gpt2-xl
    tick, 0.2 ms of its peak) and the stack is read ONCE, dense, in the
    layout it rests in (PERF.md section 6, PR 45).

    Stacks whose ``blocks`` are a `read_plan` (`cache_read`'s
    ``"kernel"``), folded or not: `ops.slot_attention`, a block as ONE MXU
    operand and the online softmax in ONE kernel that reads each slot's own
    blocks and keeps its running sum in VMEM (PERF.md section 6, PRs 52
    and 53)."""
    b, t = q.shape[:2]
    hkv, dh = cfg.num_kv_heads, cfg.head_dim
    groups = cfg.num_heads // hkv
    folded = keys.stack.ndim == 4
    if keys.blocks.ndim:
        return slot_attention(
            q[:, 0] * _qscale(cfg), keys.stack, values.stack, keys.at,
            keys.blocks, rows=attn_block(keys.stack.shape[2]),
            hkv=hkv)[:, None]
    out_dtype = jnp.promote_types(values.stack.dtype, q.dtype)  # `_attend`'s
    if folded:
        mine = (jnp.arange(cfg.num_heads)[:, None] // groups
                == jnp.arange(hkv)[None, :])                    # [H, Hkv]
        qg = _fold(keys.stack, q[:, :, :, None]
                   * mine[:, :, None].astype(q.dtype))[:, :, None]
    else:
        qg = q.reshape(b, t, hkv, groups, dh)
    _, l, acc = _block_stats(
        cfg, lp, qg * _qscale(cfg), keys, values, q_pos,
        lambda k_pos: _visible(cfg, q_pos, k_pos))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    if folded:
        out = jnp.einsum("bhtkd,hk->bthd",
                         _unfold(keys.stack, cfg, out[:, 0]),
                         mine.astype(out.dtype))
        return out.reshape(b, t, -1).astype(out_dtype)
    return out.transpose(0, 3, 1, 2, 4).reshape(b, t, -1).astype(out_dtype)


# -- a family whose older rows are summaries (``cfg.eva_window``) -----------

class _WindowedStacks(NamedTuple):
    """The K (or V) state of a family whose older rows are summaries, where
    every other family has ONE stack: ``exact`` ``[L, S, W, Hkv, Dh]``, the
    rows of each slot's CURRENT window (position p at row ``p % W``), and
    ``sums`` ``[L, S, R, Hkv, Dh]``, one summary row per chunk of its
    earlier windows (chunk c at row c). A pytree: the programs carry,
    donate and return it where they carry a stack."""
    exact: Any
    sums: Any


def windowed_rows(cfg, max_len: int) -> Tuple[int, int]:
    """Rows a slot of ``max_len`` positions holds a layer: ``(W, R)``, the
    exact rows of one window (the whole slot where it is shorter) and one
    summary row per chunk of every window but the slot's last, which no
    later window can ask for (16384 positions at 2048 / 16: 2048 and
    896)."""
    w = cfg.eva_window
    return (min(w, max_len),
            max(-(-max_len // w) - 1, 0) * (w // cfg.eva_chunk))


def windowed_blocks(cfg, lengths, active, rows: Tuple[int, int], xp=np,
                    per_slot=False):
    """`attn_blocks` for the two stacks of a windowed family, for a step of
    ONE new row a slot: ``(exact, sums)`` block counts. A slot at length p
    reads rows ``0 .. p % W`` of its window stack and the first ``(W / C)
    * (p // W)`` rows of its summary stack; every slot's read is bounded
    by the largest such count over the ACTIVE slots, whole blocks of
    `attn_block` rows of each stack (``rows``: the two stacks' rows a
    slot, `windowed_rows`), or (``per_slot``, as `attn_blocks`') each
    slot's by its own and an inactive slot's by 0. Called on traced values
    by the decode programs and on the host for the counters."""
    rows_e, rows_s = rows

    def blocks(need, rows):
        need = xp.where(active, need, 0)
        if not per_slot:
            need = xp.max(need, axis=-1)
        if not rows:
            return xp.zeros_like(need)
        block = attn_block(rows)
        return xp.minimum(-(-need // block), rows // block)

    return (blocks(lengths % cfg.eva_window + 1, rows_e),
            blocks(_summaries_visible(cfg, lengths), rows_s))


def _summaries_visible(cfg, q_pos):
    """How many rows of its slot's summary stack the query at ``q_pos``
    may see: those of every chunk of EARLIER windows, none of its own."""
    return q_pos // cfg.eva_window * (cfg.eva_window // cfg.eva_chunk)


def _pool_chunks(cfg, lp, k, v):
    """The summaries of whole chunks: ``k``, ``v`` ``[.., C, H, Dh]``
    (rotated keys) -> ``[.., H, Dh]`` each. ``k~ = sum_i softmax_i(s k_i .
    mu) k_i``, ``v~ = sum_i softmax_i(s k_i . phi) v_i`` over the chunk's C
    positions, ``s = Dh ** -0.5``, per head, in float32. The ONE place the
    ``kv_summarise`` scope is opened."""
    with jax.named_scope("kv_summarise"):
        k32, v32 = k.astype(jnp.float32), v.astype(jnp.float32)
        s = cfg.head_dim ** -0.5

        def weights(vec):
            return jax.nn.softmax(
                s * jnp.einsum("...chd,hd->...ch", k32,
                               vec.astype(jnp.float32)), axis=-2)[..., None]

        return ((weights(lp["attn"]["mu"]) * k32).sum(-3).astype(k.dtype),
                (weights(lp["attn"]["phi"]) * v32).sum(-3).astype(v.dtype))


@dataclasses.dataclass(frozen=True)
class _WindowedRead:
    """What a windowed family's queries attend over in one layer: ``own``,
    the rows of their own window (a `_CacheLayer` of the window stack for a
    decode step; a prefill's fresh rows ``[1, T, Hkv, Dh]``, all of ONE
    window and starting at its first position), and ``sums``, summary
    rows: a `_CacheLayer` of the summary stack, or one slot's rows ``[1,
    R, Hkv, Dh]``."""
    own: Any
    sums: Any


# Query rows a windowed prefill scores at once: a 2048-row window against
# its 2048 + 896 keys would be 0.77 GB of float32 scores a layer at 32
# heads, and as much again for their exponentials, beside 12.7 GB resident.
PREFILL_QUERY_ROWS = 512


def _attend_windowed(cfg, lp, q, keys, values, q_pos):
    """`_attend` for a family whose older rows are summaries: ONE softmax,
    per query, over the exact keys of its own window up to itself and the
    summaries of every chunk of earlier windows (``c // (W / C) < p //
    W``); the same weights over the values and their summaries.

    A decode step (``own`` a `_CacheLayer`, one query row a slot): two
    bounded reads, one of each stack: `_block_stats`' loop over the stack's
    blocks up to the longest active slot's (at most ``W / 128`` and ``R /
    128`` trips, 16 and 7 at 2048 / 896 rows, whatever the slot's length
    in positions) or, where the counts are `read_plan`s (`cache_read`'s
    ``"kernel"``), `ops.slot_attention` over each slot's own blocks; each
    returning its softmax's running statistics (max, denominator, weighted
    sum; float32) and not a result; the two are merged as the blocks of an
    online softmax are, which IS the one softmax over both. The window
    stack always holds a visible row (the query's own), so a summary read
    in which a slot sees nothing (its first window) is weighted
    exp(-1e30 - max) = 0.

    A prefill (``own`` the fresh rows): queries in blocks of
    `PREFILL_QUERY_ROWS`, each one `_attend` over the window's fresh keys
    (causal) beside the slot's summary rows (those of earlier windows):
    no mask is larger than ``[512, W + R]`` whatever the prompt."""
    w_rows = cfg.eva_window
    b, t = q.shape[:2]
    hkv, dh = cfg.num_kv_heads, cfg.head_dim
    groups = cfg.num_heads // hkv
    if not isinstance(keys.own, _CacheLayer):
        n_sum = keys.sums.shape[1]
        k_cat = jnp.concatenate([keys.own, keys.sums], axis=1)
        v_cat = jnp.concatenate([values.own, values.sums], axis=1)
        cols = jnp.arange(t + n_sum, dtype=jnp.int32)[None, :]
        seen = _summaries_visible(cfg, q_pos[0, 0, 0])  # ONE window's rows
        qb = next(n for n in range(min(t, PREFILL_QUERY_ROWS), 0, -1)
                  if t % n == 0)

        def block(i):
            rows = (i * qb + jnp.arange(qb, dtype=jnp.int32))[:, None]
            mask = jnp.where(cols < t, cols <= rows, cols - t < seen)
            return _attend(cfg, lp,
                           jax.lax.dynamic_slice_in_dim(q, i * qb, qb, 1),
                           k_cat, v_cat, (mask, rows, cols))

        out = jax.lax.map(block, jnp.arange(t // qb, dtype=jnp.int32))
        return out.transpose(1, 0, 2, 3).reshape(b, t, -1)

    qg = q.reshape(b, t, hkv, groups, dh) * _qscale(cfg)

    def stats(k_layer, v_layer, allowed_of):
        """Softmax statistics over the first ``k_layer.blocks`` blocks."""
        rows_all = k_layer.stack.shape[2]
        if k_layer.blocks.ndim and rows_all:  # a `read_plan`, the limits in it
            stat = (b, hkv, groups, t)
            m, l, acc = slot_attention(
                qg[:, 0].reshape(b, -1, dh), k_layer.stack, v_layer.stack,
                k_layer.at, k_layer.blocks, rows=attn_block(rows_all),
                hkv=hkv, stats=True)
            return (m.reshape(stat), l.reshape(stat),
                    acc.reshape(stat + (dh,)))
        return _block_stats(cfg, lp, qg, k_layer, v_layer, q_pos, allowed_of)

    m1, l1, a1 = stats(keys.own, values.own,
                       lambda at: at <= q_pos % w_rows)
    m2, l2, a2 = stats(keys.sums, values.sums,
                       lambda at: at < _summaries_visible(cfg, q_pos))
    m = jnp.maximum(m1, m2)
    c1, c2 = jnp.exp(m1 - m), jnp.exp(m2 - m)
    out = ((a1 * c1[..., None] + a2 * c2[..., None])
           / jnp.maximum(l1 * c1 + l2 * c2, 1e-30)[..., None])
    out_dtype = jnp.promote_types(values.own.stack.dtype, q.dtype)
    return out.transpose(0, 3, 1, 2, 4).reshape(b, t, -1).astype(out_dtype)


def _append_windowed(cfg, lp, at, k, v, k_all, v_all, lengths, active,
                     qpos, blocks):
    """A decode step's cache policy for a windowed family (ONE new row a
    slot): write the row at ``p % W`` of the window stacks; where it closes
    a chunk (``p % C == C - 1``), pool the chunk's C exact rows, the new
    one among them, into its summary row ``p // C`` (a chunk of the slot's
    last window has no row and is dropped: nothing can ask for it); hand
    attention both stacks to read by blocks. Every tick pools (S x C rows a
    layer: 1 MB against the GBs a tick streams) and only the closing
    slots' rows are kept, so that no conditional holds a stack."""
    w_rows, c_rows = cfg.eva_window, cfg.eva_chunk
    slots = k.shape[0]
    with jax.named_scope("kv_update"):
        row = lengths % w_rows
        new = [_append_rows(stack.exact, at, rows.astype(stack.exact.dtype),
                            row, active)
               for stack, rows in ((k_all, k), (v_all, v))]
        if k_all.sums.shape[2]:
            first = jnp.clip(row // c_rows * c_rows, 0,
                             k_all.exact.shape[2] - c_rows)
            pick = jnp.stack(jnp.broadcast_arrays(
                at, jnp.arange(slots, dtype=jnp.int32)[:, None],
                first[:, None] + jnp.arange(c_rows, dtype=jnp.int32)),
                axis=-1)
            chunk = [jax.lax.gather(
                stack, pick,
                jax.lax.GatherDimensionNumbers(
                    offset_dims=(2, 3), collapsed_slice_dims=(0, 1, 2),
                    start_index_map=(0, 1, 2)),
                slice_sizes=(1, 1, 1) + stack.shape[3:],
                mode="promise_in_bounds") for stack in new]
            pooled = _pool_chunks(cfg, lp, *chunk)       # [S, Hkv, Dh] each
            closes = (active & (lengths % c_rows == c_rows - 1)
                      & (lengths // c_rows < k_all.sums.shape[2]))
            sums = [_write_rows(stack.sums, at, rows,
                                jnp.arange(slots, dtype=jnp.int32),
                                lengths // c_rows, closes)
                    for stack, rows in zip((k_all, v_all), pooled)]
        else:
            sums = [k_all.sums, v_all.sums]
        state = tuple(_WindowedStacks(e, s) for e, s in zip(new, sums))
    reads = [_WindowedRead(_CacheLayer(st.exact, at, blocks[0]),
                           _CacheLayer(st.sums, at, blocks[1]))
             for st in state]
    return reads[0], reads[1], (None, qpos, None), state


# -- a family whose rows are latent, read through a learned selection -------
# (``cfg.kv_lora_rank``: MLA under an indexer's top ``index_topk``)

# Rows of a slot's index keys a decode tick scores in one piece, and keys a
# prefill chunk scores and attends over in one piece (`_attend_latent`).
INDEX_BLOCK = 2048
# Prompt rows a latent family's prefill program takes at once
# (`BatchedStageExecutor._prefill_chunks`): each chunk attends over the
# slot's rows so far, so a prompt of any length runs through ONE program
# shape and the buckets of its tail.
LATENT_CHUNK = 1024
# Rows of a slot's latent layer the kernel copies in one piece (the decode
# read of `cache_read`'s ``"kernel"``: 1.3 MB of 640-lane rows on the v5e,
# where 1024 rows a copy read a layer 12% faster than 512 and twice 256).
LATENT_BLOCK = 1024
# The longest slot, in multiples of ``index_topk``, whose latent rows a
# decode tick streams WHOLE under the selection as a mask rather than
# gather the selected ones (`cache_read`). Measured on the v5e, a layer of
# eight slots at ``index_topk`` 2048 (PERF.md section 6, PR 56): with EVERY
# slot full the kernel takes 0.25 ms where top_k + gather take 0.36 at 8 x
# (16384 rows) and ties them at 16 x (0.49 against 0.47); any slot shorter
# than full and it is ahead (a mix half full at 16 x: 0.29 against 0.47).
LATENT_DENSE = 16


def latent_block(max_len: int) -> int:
    """The kernel's block of a ``max_len``-row slot's latent rows: the
    largest divisor of ``max_len`` that is at most `LATENT_BLOCK`."""
    return next((b for b in range(min(LATENT_BLOCK, max_len), 0, -1)
                 if max_len % b == 0), 1)


def index_block(max_len: int) -> int:
    """The block of a ``max_len``-row slot's index keys and latent rows:
    `INDEX_BLOCK`, or the slot where it is shorter."""
    return min(INDEX_BLOCK, max_len)


def index_blocks(lengths, active, max_len, xp=np):
    """How many blocks of `index_block` rows of its index keys a decode
    tick scores a slot: up to the new row of the LONGEST ACTIVE slot (along
    the last axis), none where no slot is active. As `attn_blocks`: traced
    in the programs, on the host for ``server_index_rows_scored_total``."""
    block = index_block(max_len)
    need = xp.max(xp.where(active, lengths + 1, 0), axis=-1)
    return xp.minimum(-(-need // block), -(-max_len // block))


def _latent_proj(cfg, p, a, rope):
    """A latent family's attention projections of the normed stream ``a``
    (``[B, T, D]``): ``(q, row, key)``. ``q``: the queries, a dict of
    ``nope`` / ``rope`` (``[B, T, H, .]``, the rope part rotated) and the
    indexer's ``iq`` (``[B, T, Hi, Di]``, its first ``qk_rope_head_dim``
    rotated) and per-head weights ``iw`` (``[B, T, Hi]`` float32, scaled by
    ``Hi ** -0.5 * Di ** -0.5``). ``row`` ``[B, T, 1, kv_lora_rank +
    qk_rope_head_dim]``: the position's latent cache row, the normed
    compressed K/V beside the ONE rotated key every head shares. ``key``
    ``[B, T, 1, Di]``: its index key (LayerNorm, rotated likewise). A layer
    with no indexer (``cfg.index_topk`` 0: a sliding one, `_attend_window`)
    has neither ``iq`` / ``iw`` nor a key (None). ``cfg.lora_rescale``: both
    normed bottlenecks times ``(hidden_size / rank) ** 0.5``."""
    kl, r = cfg.kv_lora_rank, cfg.qk_rope_head_dim

    def rotated(x, n):
        """RoPE on the first ``n`` dims of ``[B, T, heads, .]``."""
        return jnp.concatenate(
            [apply_rope(x[..., :n], *rope), x[..., n:]], axis=-1)

    def rescale(x, rank):
        return x * (cfg.hidden_size / rank) ** 0.5 if cfg.lora_rescale else x

    c_q = rescale(rms_norm(_dot(a, p["wqa"]), p["q_norm"]["w"],
                           cfg.norm_eps), cfg.q_lora_rank)
    q = _dot_t(c_q, p["wqb_t"])                              # [B, T, H, Dh]
    kv = _dot_t(a, p["wkva_t"])
    c_kv = rescale(rms_norm(kv[..., :kl], p["kv_norm"]["w"], cfg.norm_eps),
                   kl)
    k_r = apply_rope(kv[..., None, kl:], *rope)               # [B, T, 1, r]
    index = {}
    key = None
    if cfg.index_topk:      # a sliding layer has no indexer, and no key
        index["iq"] = rotated(_dot_t(c_q, p["wiq_t"]), r)
        key = rotated(layer_norm(_dot(a, p["wik"]), p["ik_norm"]["w"],
                                 p["ik_norm"]["b"], 1e-6)[:, :, None], r)
        index["iw"] = (
            jnp.dot(a.astype(jnp.float32), p["wiw"].astype(jnp.float32))
            * (cfg.index_n_heads ** -0.5 * cfg.index_head_dim ** -0.5))
    nope = cfg.qk_nope_head_dim
    return ({"nope": q[..., :nope], "rope": apply_rope(q[..., nope:], *rope),
             **index},
            jnp.concatenate([c_kv[:, :, None], k_r], axis=-1), key)


def _index_scores(q, keys):
    """The indexer's score of every key for every query, float32: ``I(t, s)
    = sum_h w_h(t) ReLU(q_h(t) . k(s))``. ``q``: `_latent_proj`'s (``iq``
    ``[.., T, Hi, Di]``, ``iw`` ``[.., T, Hi]``); ``keys`` ``[.., n, Di]``
    -> ``[.., T, n]``. The ONE place the ``indexer`` scope is opened."""
    with jax.named_scope("indexer"):
        sc = jnp.einsum("...thd,...kd->...thk", q["iq"],
                        keys.astype(q["iq"].dtype),
                        preferred_element_type=jnp.float32)
        return (jax.nn.relu(sc) * q["iw"][..., None]).sum(-2)


def _scores_by_block(q, read, n_blocks, m: int):
    """`_index_scores` of the queries ``q`` (``[B, T, ..]``) against the
    first ``n_blocks`` (traced) blocks of `index_block` index keys of an
    ``m``-row slot, ``read(start)`` giving a block's keys ``[B, blk, Di]``:
    ``[B, T, m]`` float32, `NEG_INF` past the blocks read. A last block
    that would pass the slot's end starts early and scores rows again."""
    blk = index_block(m)

    def one(j, acc):
        start = jnp.minimum(j * blk, m - blk)
        return jax.lax.dynamic_update_slice(
            acc, _index_scores(q, read(start)), (0, 0, start))

    return jax.lax.fori_loop(0, n_blocks, one, jnp.full(
        q["iw"].shape[:2] + (m,), NEG_INF, jnp.float32))


def select_topk(scores, k: int):
    """Which ``k`` entries of every row of ``scores`` (``[.., n]`` float32)
    are its largest, as a mask: EXACTLY what ``jax.lax.top_k`` picks, ties
    to the lower position, with no sort of a ``[T, n]`` array. The k-th
    largest value is found by bisection on the scores' integer image (a
    float's bits, made monotone, as an unsigned number: 32 compares and
    counts whatever ``n``); entries above it are in, and of those equal to
    it the first ``k - (number above)`` by position. ``k >= n``: every
    entry. The prefill's form of the selection: a chunk's ``[T, n]`` scores
    need a MASK over the blocks it attends by, and a sort of them is what
    this avoids; a decode tick needs the ``k`` INDICES of one row a slot
    for its gather, which a mask would have to be compacted into (a sort
    or a scatter of ``n`` entries again), so it takes ``jax.lax.top_k``
    itself (`_attend_latent`)."""
    n = scores.shape[-1]
    if k >= n:
        return jnp.ones(scores.shape, bool)
    bits = jax.lax.bitcast_convert_type(scores, jnp.int32)
    img = jnp.where(bits >= 0, bits, bits ^ jnp.int32(0x7FFFFFFF))
    img = jax.lax.bitcast_convert_type(img, jnp.uint32) ^ jnp.uint32(1 << 31)

    def bit(i, kth):
        cand = kth | (jnp.uint32(1) << (jnp.uint32(31) - i.astype(jnp.uint32)))
        enough = (img >= cand[..., None]).sum(-1) >= k
        return jnp.where(enough, cand, kth)

    kth = jax.lax.fori_loop(0, 32, bit,
                            jnp.zeros(scores.shape[:-1], jnp.uint32))
    above = img > kth[..., None]
    tie = img == kth[..., None]
    rank = jnp.cumsum(tie.astype(jnp.int32), axis=-1) - 1
    return above | (tie & (rank < (k - above.sum(-1))[..., None]))


def _absorbed(q, w_k):
    """A decode step's queries against a latent row's compressed part,
    ``q_nope W_kvb,k^T``: ``[S, H, kv_lora_rank]`` (``w_k`` ``[H,
    qk_nope_head_dim, kv_lora_rank]``: the heads' key ROWS of ``wkvb_t``)."""
    dt = q["nope"].dtype
    return jnp.einsum("shn,hnl->shl", q["nope"][:, 0], w_k.astype(dt))


def _attend_latent(cfg, lp, q, rows, keys, q_pos):
    """Attention of a latent family under its learned selection: ``[B, T,
    H * v_head_dim]``, before the output projection. Every query scores
    the index keys of the positions up to its own (`_index_scores`),
    attends to the ``index_topk`` best (all, while it has no more) and
    reads only those positions' latent rows. ONE function of the rows,
    in the two forms the programs need:

    A decode step (``rows`` / ``keys`` `_CacheLayer`s of the two stacks,
    one query row a slot): scores by blocks of `index_block` index keys up
    to the longest active slot's length (``keys.blocks``, traced),
    ``jax.lax.top_k``, a gather of the selected rows, and the ABSORBED
    products: ``q_nope W_kvb,k^T`` against the row's compressed part and
    ``q_rope`` against its rotated key, the weighted sum of compressed
    parts through ``W_kvb,v`` after it. No key or value of a head is ever
    made for a cached position. Where ``rows.blocks`` is a `read_plan`
    (`cache_read`'s ``"kernel"``) the same sum is taken over every row of
    a slot's own blocks with the unselected ones at probability exactly 0:
    `ops.slot_attention` streams the blocks as they rest and finds the
    selection, ``jax.lax.top_k``'s entry for entry, as a mask.

    A prefill chunk (``rows`` ``[1, M, .]`` / ``keys`` ``[1, M, Di]``: the
    slot's layer with the chunk's own rows written, ``q_pos`` ``[T, 1]``):
    the EXPANDED form. Scores of all T queries against the blocks of index
    keys up to the chunk's end, the selection as a mask (`select_topk`;
    skipped while no query of the chunk is past row ``index_topk``), then
    an online softmax over the same blocks, each block's rows expanded
    through ``W_kvb`` to the heads' keys and values once for all T
    queries."""
    if not cfg.index_topk:          # a sliding layer: its window, no indexer
        return _attend_window(cfg, lp, q, rows, q_pos)
    kl, nope, vd = cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.v_head_dim
    heads, topk = cfg.num_heads, cfg.index_topk
    # ``[H, nope + vd, kl]`` as it rests: a head's key rows, then its value
    # rows, the compressed axis LAST (a slice of rows, not of lanes)
    wkvb = _plain(lp["attn"]["wkvb_t"])
    scale = cfg.head_dim ** -0.5
    if isinstance(rows, _CacheLayer):
        _, slots, m = rows.stack.shape[:3]
        at = jnp.arange(m, dtype=jnp.int32)[None, :]
        p = q_pos[:, 0]                                        # [S, 1]
        scores = jnp.where(at <= p, _scores_by_block(
            q, lambda start: jax.lax.dynamic_slice(
                keys.stack, (keys.at, 0, start, 0),
                (1, slots, index_block(m), keys.stack.shape[3]))[0],
            keys.blocks, m)[:, 0], NEG_INF)
        dt = q["nope"].dtype
        if rows.blocks.ndim:
            # a `read_plan` (`cache_read`'s ``"kernel"``): a latent row as
            # it rests is the folded row of ONE KV head, its keys the row,
            # its values the row's compressed part. Each slot's own blocks,
            # streamed, the selection a mask made in the kernel: no sort,
            # no index, no gathered copy
            mine = jnp.concatenate([_absorbed(q, wkvb[:, :nope]),
                                    q["rope"][:, 0]], -1) * scale
            mine = jnp.pad(mine, ((0, 0), (0, 0), (
                0, rows.stack.shape[3] - mine.shape[-1]))).astype(dt)
            with jax.named_scope("latent_read"):
                o_lat = slot_attention(
                    mine, rows.stack, None, rows.at, rows.blocks,
                    rows=latent_block(m), hkv=1,
                    select=(scores, min(topk, m)))[..., :kl].astype(dt)
            out = jnp.einsum("shl,hvl->shv", o_lat,
                             wkvb[:, nope:].astype(dt))
            return out.reshape(slots, 1, -1)
        with jax.named_scope("topk_select"):
            # INDICES, for the gather below: ``jax.lax.top_k``'s pick is
            # the definition of the selection (ties to the lower position)
            # and `select_topk`, the prefill's mask, and the kernel's are
            # held to it
            _, sel = jax.lax.top_k(scores, min(topk, m))       # [S, k]
        with jax.named_scope("latent_read"):
            pick = jnp.stack(jnp.broadcast_arrays(
                rows.at, jnp.arange(slots, dtype=jnp.int32)[:, None], sel),
                axis=-1)
            got = jax.lax.gather(
                rows.stack, pick,
                jax.lax.GatherDimensionNumbers(
                    offset_dims=(2,), collapsed_slice_dims=(0, 1, 2),
                    start_index_map=(0, 1, 2)),
                slice_sizes=(1, 1, 1, rows.stack.shape[3]),
                mode="promise_in_bounds")                      # [S, k, .]
        c_kv = got[..., :kl].astype(dt)
        k_r = got[..., kl:kl + q["rope"].shape[-1]].astype(dt)
        q_abs = _absorbed(q, wkvb[:, :nope])
        sc = (jnp.einsum("shl,skl->shk", q_abs, c_kv,
                         preferred_element_type=jnp.float32)
              + jnp.einsum("shr,skr->shk", q["rope"][:, 0], k_r,
                           preferred_element_type=jnp.float32)) * scale
        probs = jax.nn.softmax(
            jnp.where((sel <= p)[:, None, :], sc, NEG_INF), axis=-1)
        o_lat = jnp.einsum("shk,skl->shl", probs.astype(dt), c_kv)
        out = jnp.einsum("shl,hvl->shv", o_lat, wkvb[:, nope:].astype(dt))
        return out.reshape(slots, 1, -1)

    t, m = q["nope"].shape[1], rows.shape[1]
    blk = index_block(m)
    last = q_pos.max()
    n_blocks = last // blk + 1

    def start_of(j):
        return jnp.minimum(j * blk, m - blk)

    at = jnp.arange(m, dtype=jnp.int32)[None, :]
    causal = at <= q_pos                                       # [T, M]
    scores = jnp.where(causal, _scores_by_block(
        q, lambda start: jax.lax.dynamic_slice_in_dim(keys, start, blk, 1),
        n_blocks, m)[0], NEG_INF)
    with jax.named_scope("topk_select"):
        chosen = (causal if topk >= m else jax.lax.cond(
            last >= topk, lambda: causal & select_topk(scores, topk),
            lambda: causal))
    dt = q["nope"].dtype
    q_n, q_r = q["nope"][0], q["rope"][0]                      # [T, H, .]

    def block(j, carry):
        mx, l, acc = carry
        with jax.named_scope("latent_read"):
            got = jax.lax.dynamic_slice_in_dim(
                rows[0], start_of(j), blk, 0).astype(dt)       # [blk, .]
        kv = jnp.einsum("kl,hel->khe", got[:, :kl], wkvb.astype(dt))
        sc = (jnp.einsum("thn,khn->htk", q_n, kv[..., :nope],
                         preferred_element_type=jnp.float32)
              + jnp.einsum("thr,kr->htk", q_r,
                           got[:, kl:kl + q_r.shape[-1]],
                           preferred_element_type=jnp.float32)) * scale
        # a last block that starts early (``m`` no multiple of the block)
        # holds rows an earlier block has counted
        ok = (jax.lax.dynamic_slice_in_dim(chosen, start_of(j), blk, 1)
              & (start_of(j) + jnp.arange(blk) >= j * blk)[None, :])[None]
        sc = jnp.where(ok, sc, NEG_INF)
        m2 = jnp.maximum(mx, sc.max(-1))
        corr = jnp.exp(mx - m2)
        w = jnp.where(ok, jnp.exp(sc - m2[..., None]), 0.0)
        acc = acc * corr[..., None] + jnp.einsum(
            "htk,khv->htv", w.astype(dt), kv[..., nope:],
            preferred_element_type=jnp.float32)
        return m2, l * corr + w.sum(-1), acc

    stat = (heads, t)
    _, l, acc = jax.lax.fori_loop(0, n_blocks, block, (
        jnp.full(stat, NEG_INF, jnp.float32), jnp.zeros(stat, jnp.float32),
        jnp.zeros(stat + (vd,), jnp.float32)))
    out = acc / jnp.maximum(l, 1e-30)[..., None]               # [H, T, v]
    return out.transpose(1, 0, 2).reshape(1, t, -1).astype(dt)


# -- latent layers of two kinds alternating in one stack --------------------
# (``cfg.layer_types``: full layers under the selection, sliding layers of
# another latent geometry over a window)

class _LatentStacks(NamedTuple):
    """The latent rows of a family whose layers are of two kinds, where a
    family of one kind has ONE stack: ``rows`` ``[full layers, S, max_len,
    .]``, a row a position of every FULL layer, and ``ring`` ``[sliding
    layers, S, R, .]`` (`ring_rows`), position p of a SLIDING layer at row
    ``p % R``. Each layer indexes the stack of its kind by its index among
    that kind. A pytree: the programs carry, donate and return it where
    they carry a stack (the index keys, of the full layers only, are the
    other stack they carry)."""
    rows: Any
    ring: Any


def _full_rows(k_all):
    """The full layers' stack of latent rows."""
    return k_all.rows if isinstance(k_all, _LatentStacks) else k_all


def ring_rows(window: int, max_len: int) -> int:
    """Rows of a sliding layer's ring for a window of ``window`` positions:
    the next multiple of `ATTN_BLOCK` above it (513 -> 640), so that a
    rewind of up to ``R - window + 1`` positions finds every row its window
    needs still unwritten (`BatchedStageExecutor.rewind`); never more than
    the slot."""
    return min((window // ATTN_BLOCK + 1) * ATTN_BLOCK, max_len)


@dataclasses.dataclass(frozen=True)
class _RingChunk:
    """What a prefill chunk's queries attend over in a sliding layer:
    ``before`` ``[1, R, .]``, the slot's ring as the chunks before it left
    it (position p at row ``p % R``), and ``fresh`` ``[1, T, .]``, the
    chunk's own rows, the first at position ``start``."""
    before: Any
    fresh: Any
    start: Any


def _attend_window(cfg, lp, q, rows, q_pos):
    """Attention of a SLIDING latent layer (``cfg``: `ModelConfig
    .sliding_kind`): ``[B, T, H * v_head_dim]``, before the gate and the
    output projection. The query at t attends to every position s with ``t
    - cfg.sliding_window < s <= t``; no indexer, no selection.

    A decode step (``rows`` a `_CacheLayer` of the ring stack, the step's
    own row written; one query row a slot): the slot's whole ring, read
    ONCE as it rests under the ``window_read`` scope, in the ABSORBED form
    (`_absorbed`): row j holds position ``t - (t - j) % R``, visible while
    that is inside the window and not before the slot's first.

    A prefill chunk (``rows`` a `_RingChunk`, ``q_pos`` ``[T, 1]``): the
    EXPANDED form over the ring as it was before the chunk beside the
    chunk's own rows, each key at its position, in blocks of
    `PREFILL_QUERY_ROWS` queries."""
    kl, nope = cfg.kv_lora_rank, cfg.qk_nope_head_dim
    r, w = cfg.qk_rope_head_dim, cfg.sliding_window
    wkvb = _plain(lp["attn"]["wkvb_t"])
    scale = cfg.head_dim ** -0.5
    dt = q["nope"].dtype
    if isinstance(rows, _CacheLayer):
        ring = rows.stack.shape[2]
        with jax.named_scope("window_read"):
            got = jax.lax.dynamic_index_in_dim(
                rows.stack, rows.at, 0, keepdims=False).astype(dt)
        # a row as it rests: compressed part, rotated key, the lanes' pad
        mine = jnp.concatenate([_absorbed(q, wkvb[:, :nope]),
                                q["rope"][:, 0]], -1) * scale
        mine = jnp.pad(mine, ((0, 0), (0, 0),
                              (0, got.shape[-1] - mine.shape[-1]))).astype(dt)
        sc = jnp.einsum("shw,skw->shk", mine, got,
                        preferred_element_type=jnp.float32)
        p = q_pos[:, 0]                                        # [S, 1]
        back = (p - jnp.arange(ring, dtype=jnp.int32)[None, :]) % ring
        seen = (back < w) & (back <= p)
        probs = jax.nn.softmax(jnp.where(seen[:, None, :], sc, NEG_INF), -1)
        o_lat = jnp.einsum("shk,skw->shw", probs.astype(dt), got)[..., :kl]
        out = jnp.einsum("shl,hvl->shv", o_lat, wkvb[:, nope:].astype(dt))
        return out.reshape(out.shape[0], 1, -1)

    t, ring = q["nope"].shape[1], rows.before.shape[1]
    at = jnp.arange(ring, dtype=jnp.int32)
    last = rows.start - 1           # the newest position the ring holds
    # a ring row's position: the newest that rests there; negative where
    # the slot has not reached it
    k_pos = jnp.concatenate([
        last - (last - at) % ring,
        rows.start + jnp.arange(t, dtype=jnp.int32)])          # [R + T]
    with jax.named_scope("window_read"):
        got = jnp.concatenate([rows.before[0], rows.fresh[0]]).astype(dt)
    kv = jnp.einsum("kl,hel->khe", got[:, :kl], wkvb.astype(dt))
    k_r = got[:, kl:kl + r]
    qb = next(n for n in range(min(t, PREFILL_QUERY_ROWS), 0, -1)
              if t % n == 0)

    def block(i):
        take = lambda x: jax.lax.dynamic_slice_in_dim(x, i * qb, qb, 0)
        q_n, q_r, pos = take(q["nope"][0]), take(q["rope"][0]), take(q_pos)
        sc = (jnp.einsum("thn,khn->htk", q_n, kv[..., :nope],
                         preferred_element_type=jnp.float32)
              + jnp.einsum("thr,kr->htk", q_r, k_r,
                           preferred_element_type=jnp.float32)) * scale
        seen = ((k_pos[None, :] >= 0) & (k_pos[None, :] <= pos)
                & (k_pos[None, :] > pos - w))                  # [qb, R + T]
        probs = jax.nn.softmax(jnp.where(seen[None], sc, NEG_INF), -1)
        return jnp.einsum("htk,khv->thv", probs.astype(dt), kv[..., nope:])

    out = jax.lax.map(block, jnp.arange(t // qb, dtype=jnp.int32))
    return out.reshape(1, t, -1)


def _write_ring(ring_l, fresh, start, t_real):
    """A slot's ring layer ``ring_l`` (``[1, R, .]``) after a prefill chunk
    of ``t_real`` real rows ``fresh`` (``[1, T, .]``, padded past them), the
    first at position ``start``: each real row at ``position % R``, of a
    chunk longer than the ring the newest R (a row past ``t_real`` would
    overwrite one a later query still needs, and is dropped)."""
    t, ring = fresh.shape[1], ring_l.shape[1]
    i = jnp.arange(t, dtype=jnp.int32)
    keep = (i < t_real) & (i >= t_real - ring)
    at = jnp.where(keep, (start + i) % ring, ring + i)[:, None]
    return jax.lax.scatter(
        ring_l[0], at, fresh[0].astype(ring_l.dtype),
        jax.lax.ScatterDimensionNumbers(
            update_window_dims=(1,), inserted_window_dims=(0,),
            scatter_dims_to_operand_dims=(0,)),
        mode="drop", unique_indices=True)[None]


def _decoder_layer(cfg, lp, h, rope, cache_policy):
    """One decoder layer of every engine program: ``(h, state)``.

    ``cache_policy(k, v)`` is all a program supplies. Given the layer's
    fresh keys and values (``[B, T, Hkv, Dh]``, rotated) it returns
    ``(keys, values, (mask, q_pos, k_pos), state)``: what to attend over
    (``[B, S, Hkv, Dh]``), the allowed grid (``[T, S]`` for one sequence or
    per row ``[B, T, S]``) with the position grids `_layer_mask` windows it
    by, and what the layer scan carries or stacks. Cache writes are the
    policy's, under its own ``kv_update`` scope.

    Rows of TWO shapes in one layer (a burst tick that carries a joining
    request's prompt rows beside the slots' single rows: `_decode_span`):
    ``h`` is then flat, ``[1, rows, D]``, so that every matmul of the layer
    reads its weight ONCE for all of them, and the policy returns
    ``keys``, ``values`` and the grid as TUPLES, one entry a group, each
    mask ``[B_g, T_g, S_g]``: group g attends as ``B_g`` sequences of
    ``T_g`` rows, the groups side by side along the flat row axis."""
    from ..models.quant import dequant_tree

    lp = dequant_tree(lp, keep_experts=cfg.is_moe)
    with jax.named_scope("attention"):
        a = _normed(cfg, lp["ln1"], h)
        if cfg.kv_lora_rank:
            # the policy is handed the position's TWO rows, latent row and
            # index key, where every other family's gets k and v
            q, k, v = _latent_proj(cfg, lp["attn"], a, rope)
        else:
            q, k, v = qkv_proj(cfg, lp["attn"], a)      # [B, T, H/Hkv, Dh]
            if rope is not None:
                q = apply_rope(q, *rope)
                k = apply_rope(k, *rope)
    keys, values, grid, state = cache_policy(k, v)
    with jax.named_scope("attention"):
        if cfg.kv_lora_rank:
            out = _attend_latent(cfg, lp, q, keys, values, grid[1])
        elif isinstance(keys, tuple):
            outs, row = [], 0
            for k_g, v_g, grid_g in zip(keys, values, grid):
                b_g, t_g = grid_g[1].shape[:2]
                q_g = q[0, row:row + b_g * t_g].reshape(
                    b_g, t_g, *q.shape[2:])
                outs.append(_attend(cfg, lp, q_g, k_g, v_g, grid_g)
                            .reshape(1, b_g * t_g, -1))
                row += b_g * t_g
            out = jnp.concatenate(outs, axis=1)
        else:
            out = _attend(cfg, lp, q, keys, values, grid)
        if "wgate" in lp["attn"]:
            # headwise: head h's output times ONE number of the normed
            # stream's. The ONE place the ``attn_gate`` scope is opened.
            with jax.named_scope("attn_gate"):
                gate = jax.nn.sigmoid(_dot(a, lp["attn"]["wgate"]))
                out = (out.reshape(*gate.shape, -1)
                       * gate[..., None].astype(out.dtype)).reshape(out.shape)
        out = _dot(out, lp["attn"]["wo"])
        if "bo" in lp["attn"]:
            out = out + lp["attn"]["bo"]
    h, extra = _residual(cfg, lp, h, out)
    return h, (*state, *extra)


def _split_stacks(layers):
    """``(xs, held)`` of a stacked layer tree. ``held`` maps the key path of
    every dense int8 weight stack (a 3-D `QuantizedTensor`, ``[L, K, N]``)
    to the leaf: those stay WHOLE, and a layer scan's body reads them
    through `_layer_at`. ``xs`` is the tree without them, for ``lax.scan``
    to slice: norms, biases, ``window``, every unquantised leaf, MoE expert
    stacks (4-D), NF4 leaves. As scan ``xs`` an int8 stack reaches the
    body as a ``dynamic-slice``, and the Pallas call (a custom call, which
    needs its operand in a buffer of its own) makes XLA write that slice
    out first: 2.2 x the kernels' own time at qwen2-7b widths (ledger, PR
    29). A tree with no such leaf comes back as the same object."""
    from ..models.quant import QuantizedTensor

    held = {}

    def strip(tree, path):
        if not isinstance(tree, dict):
            return tree
        out = {}
        for key, sub in tree.items():
            if isinstance(sub, QuantizedTensor) and sub.q.ndim == 3:
                held[path + (key,)] = sub
            else:
                out[key] = strip(sub, path + (key,))
        return out

    xs = strip(layers, ())
    return (xs, held) if held else (layers, held)


def _layer_at(lp, held, i):
    """Layer ``i``'s parameters: the scan's slice ``lp`` of `_split_stacks`'s
    ``xs`` with a `QuantizedLayerView` at index ``i`` in place of each held
    stack. ``lp`` itself when nothing is held."""
    from ..models.quant import QuantizedLayerView

    def put(tree, path, leaf):
        if not path:
            return leaf
        return {**tree, path[0]: put(tree.get(path[0], {}), path[1:], leaf)}

    for path, stack in held.items():
        lp = put(lp, path, QuantizedLayerView(stack, i))
    return lp


def _scan_layers(layer, h, layers, *xs):
    """``lax.scan(layer, h, (layers, *xs))`` for the prefill programs, with
    ``layer`` handed ``(lp, *xs_l)`` and the int8 stacks of ``layers`` held
    whole (`_split_stacks`): the scan then carries the layer index in its
    ``xs``. A tree with nothing to hold is scanned as it is."""
    rest, held = _split_stacks(layers)
    if not held:
        return jax.lax.scan(layer, h, (layers, *xs))

    def body(h, xs):
        i, lp, *more = xs
        return layer(h, (_layer_at(lp, held, i), *more))

    n = next(iter(held.values())).shape[0]
    return jax.lax.scan(
        body, h, (jnp.arange(n, dtype=jnp.int32), rest, *xs))


class _Period(NamedTuple):
    """The layers behind the leading ones of a family whose layers are of
    two kinds (``cfg.layer_period``): ``full`` ``[P, ..]``, one full layer a
    period, and ``sliding`` ``[P * n, ..]``, the n sliding layers behind
    each, both in their order in the stack. The scans take a period a step:
    the full layer, then an inner scan over its sliding ones."""
    full: Any
    sliding: Any

    @property
    def counts(self) -> Tuple[int, int]:
        """``(P, n)``."""
        p = jax.tree.leaves(self.full)[0].shape[0]
        return p, jax.tree.leaves(self.sliding)[0].shape[0] // p


def _layer_groups(params):
    """The span's layers as the scans take them: ``[(stacked tree, index of
    its first layer among the full layers)]``. ONE stack for every family
    but one with leading dense layers (``cfg.first_k_dense``), whose first
    layers are another kind than the rest and stacked apart
    (``dense_layers``): the same layer body scans each group in turn,
    inside ONE program. Where the rest are of two kinds that alternate
    (``sliding_layers`` beside ``layers``) they are ONE group, a
    `_Period`."""
    if "dense_layers" not in params:
        return [(params["layers"], 0)]
    n = jax.tree.leaves(params["dense_layers"])[0].shape[0]
    rest = params["layers"]
    if "sliding_layers" in params:
        rest = _Period(rest, params["sliding_layers"])
    return [(params["dense_layers"], 0), (rest, n)]


def _layer_of(stack, i):
    """Layer ``i`` (traced) of a stacked tree, as a scan's own slicing of
    its ``xs`` would hand it: a dynamic slice of an array the loop does not
    change, which the compiler reads where it is used. An inner scan's
    layers are taken so and NOT as the outer scan's ``xs`` (a period's
    ``[n, ..]`` slice of a weight stack would be copied out whole before
    the inner loop could start: three layers' held experts a period a
    tick)."""
    return jax.tree.map(
        lambda x: jax.lax.dynamic_index_in_dim(x, i, 0, keepdims=False),
        stack)


def _scan_period(layer, h, period, first, k_in, v_in):
    """`_scan_layers` over a `_Period` for the prefill program: a scan over
    the periods whose body is ``layer(h, (lp, rows_l, keys_l))`` for the
    full layer (the slot's rows of full layer ``first + p`` of ``k_in.rows``
    / ``v_in``) and an inner scan of ``layer(h, (lp, ring_l),
    sliding=True)`` over its sliding layers (their rings of ``k_in.ring``):
    ``(h, (rows, keys, ring))``, each layer's first outputs stacked by
    kind."""
    periods, n = period.counts
    full, held_f = _split_stacks(period.full)
    sliding, held_s = _split_stacks(period.sliding)

    def body(h, xs):
        p, lp_f, rows_l, keys_l = xs
        h, ys = layer(h, (_layer_at(lp_f, held_f, p), rows_l, keys_l))

        def inner(h, j):
            i = p * n + j
            h, ys = layer(h, (_layer_at(_layer_of(sliding, i), held_s, i),
                              _layer_of(k_in.ring, i)), sliding=True)
            return h, ys[0]

        h, ring = jax.lax.scan(inner, h, jnp.arange(n, dtype=jnp.int32))
        return h, (*ys[:2], ring)

    h, (rows, keys, ring) = jax.lax.scan(body, h, (
        jnp.arange(periods, dtype=jnp.int32), full,
        k_in.rows[first:first + periods], v_in[first:first + periods]))
    return h, (rows, keys, ring.reshape(-1, *ring.shape[2:]))


def _scan_groups(params, layer, h, *xs):
    """`_scan_layers` over every group of `_layer_groups` in turn, each
    handed its own layers' share of ``xs``: ``(h, ys)`` with every stacked
    output's first two members (a prefill's new cache rows) concatenated
    over the groups. One group: `_scan_layers` as it is. With a `_Period`
    among them (``xs``: `_LatentStacks` and the index keys) the first
    member comes back as `_LatentStacks`."""
    groups = _layer_groups(params)
    if len(groups) == 1:
        return _scan_layers(layer, h, params["layers"], *xs)
    outs, ring = [], None
    for layers, first in groups:
        if isinstance(layers, _Period):
            h, (*ys, ring) = _scan_period(layer, h, layers, first, *xs)
        else:
            n = jax.tree.leaves(layers)[0].shape[0]
            h, ys = _scan_layers(layer, h, layers, *(
                _full_rows(x)[first:first + n] for x in xs))
        outs.append(ys[:2])
    rows, keys = (jnp.concatenate(part) for part in zip(*outs))
    return h, (rows if ring is None else _LatentStacks(rows, ring), keys)


def _scan_layers_in_place(layer, h, layers, k_all, v_all):
    """``lax.scan`` over the span's layers with the cache stacks as CARRY:
    ``layer(h, (lp, i, k_all, v_all)) -> (h, (k_all, v_all))`` is handed
    the WHOLE ``[L, S, max_len, Hkv, Dh]`` stacks and its own index ``i``,
    writes its new rows into them and reads what it attends over out of
    them, so XLA updates the carried buffers in place and no layer's
    ``[S, max_len, Hkv, Dh]`` slab is ever the operand of an update. What a
    layer returns beside the stacks (an expert layer's held assignments)
    comes back stacked, fourth. ``i``
    indexes the WEIGHTS (``0 .. layers - 1``); which cache layer a weight
    layer writes is the caller's (`_run_passes`: a looped stack's cache is
    ``loop_steps`` times as deep as its weights).

    Two forms this replaced, and what each cost on the v5e. As scan xs/ys
    the stacks are rewritten into a second buffer every step, input and
    output both live: with the ``[.., Hkv, Dh]`` minor dims padded to
    (8,128) tiles (2.6x at gpt2-xl's 25 x 64) each copy is 3 GB at 8
    slots x 1024 rows and the burst program asked for 17.3 GB of a v5e's
    15.75 (chip run, PR 21). As a carry whose layer slab is sliced out,
    appended to and written back, XLA materialised the slice, the updated
    slab, the write-back and one more copy: 72% of the gpt2-xl tick and
    22% of qwen2-7b's (ledger, PR 31)."""

    if isinstance(layers, _Period):
        return _scan_period_in_place(layer, h, layers, k_all, v_all)
    rest, held = _split_stacks(layers)

    def body(carry, xs):
        h, k_all, v_all = carry
        lp, i = xs
        h, (k_all, v_all, *more) = layer(
            h, (_layer_at(lp, held, i), i, k_all, v_all))
        return (h, k_all, v_all), tuple(more)

    n = jax.tree.leaves(layers)[0].shape[0]
    (h, k_all, v_all), more = jax.lax.scan(
        body, (h, k_all, v_all), (rest, jnp.arange(n, dtype=jnp.int32)))
    return h, k_all, v_all, more


def _scan_period_in_place(layer, h, period, k_all, v_all):
    """`_scan_layers_in_place` over a `_Period`: a scan over the periods
    whose body is ``layer(h, (lp, p, k_all, v_all))`` for the full layer
    and an inner scan of ``layer(h, (lp, p * n + j, k_all, v_all),
    sliding=True)`` over its n sliding ones, the stacks carried through
    both. What the layers return beside the stacks comes back stacked in
    the layers' own order, ``[P * (1 + n), ..]``."""
    periods, n = period.counts
    full, held_f = _split_stacks(period.full)
    sliding, held_s = _split_stacks(period.sliding)

    def one_layer(carry, lp, i, **kind):
        h, k_all, v_all = carry
        h, (k_all, v_all, *more) = layer(h, (lp, i, k_all, v_all), **kind)
        return (h, k_all, v_all), tuple(more)

    def body(carry, xs):
        p, lp_f = xs
        carry, first = one_layer(carry, _layer_at(lp_f, held_f, p), p)

        def inner(carry, j):
            i = p * n + j
            return one_layer(
                carry, _layer_at(_layer_of(sliding, i), held_s, i), i,
                sliding=True)

        carry, rest = jax.lax.scan(
            inner, carry, jnp.arange(n, dtype=jnp.int32))
        return carry, tuple(jnp.concatenate([a[None], b])
                            for a, b in zip(first, rest))

    (h, k_all, v_all), more = jax.lax.scan(
        body, (h, k_all, v_all),
        (jnp.arange(periods, dtype=jnp.int32), full))
    return h, k_all, v_all, tuple(m.reshape(-1, *m.shape[2:]) for m in more)


def _run_passes(cfg, params, h, one_pass, k_all, v_all):
    """The span's layers over ``h``, as often as the model runs them:
    ``(h, k_all, v_all, steps)``. ``one_pass(h, base, k_all, v_all) -> (h,
    k_all, v_all)`` is a program's scan over the layers' weights with its
    cache policy; weight layer ``i`` of it reads and writes cache layer
    ``base + i``. A stack that runs once gets ``base`` None (cache layer ==
    weight layer, the program every family had before there was a loop)
    and ``steps`` None, or what ``one_pass`` returns fourth (the held
    assignments of a family with expert layers). A looped stack (``cfg.loop_steps`` passes) scans
    the passes over the SAME stacked weights, pass ``t`` at ``base = t *
    layers`` of the ``loop_steps * layers`` deep stacks, `close_pass`
    after each (the final norm that feeds the next pass, the exit gate and
    rule): ``h`` is then each token's chosen, already NORMED state and
    ``steps`` ``[B, T]`` the passes it took."""
    if cfg.loop_steps == 1:
        return (*one_pass(h, None, k_all, v_all), None)[:4]
    per_pass = k_all.shape[0] // cfg.loop_steps

    def body(carry, t):
        h, k_all, v_all, state = carry
        x, k_all, v_all = one_pass(h, t * per_pass, k_all, v_all)[:3]
        h, state, _ = close_pass(cfg, params, x, t, state)
        return (h, k_all, v_all, state), None

    (_, k_all, v_all, state), _ = jax.lax.scan(
        body, (h, k_all, v_all, exit_state(h)),
        jnp.arange(cfg.loop_steps, dtype=jnp.int32))
    return state[0], k_all, v_all, state[1]


def _at(base, i):
    """Cache layer of weight layer ``i`` in the pass that starts at cache
    layer ``base`` (None: the stack runs once, the two are one index)."""
    return i if base is None else base + i


def _append_rows(stack, i, new, lengths, active):
    """``stack`` (``[L, S, max_len, Hkv, Dh]``) with ``new`` (``[S, T, Hkv,
    Dh]``) at ``stack[i, s, lengths[s] : lengths[s] + T]`` for every slot:
    ONE scatter of S x T rows, so the carried stack is updated in place
    and nothing larger than the rows moves. A slot's start clamps to
    ``max_len - T``, as ``dynamic_update_slice`` clamps. An INACTIVE slot
    writes back the rows already there: a slot parked near ``max_len``
    would clamp its start and clobber that session's last real KV rows, so
    its write value is what the gather reads at the SAME clamped start (T
    rows; the round trip is a no-op, and cheaper than a select over the
    donated buffers). A row (``[Hkv, Dh]``, the scatter's window; `_fold`ed
    first where the stack holds its rows so) at a ``(layer, slot,
    position)`` point is the form the TPU keeps as one native scatter; a
    window that spans the T positions is expanded into a loop over the
    slots."""
    new = _fold(stack, new)
    slots, t = new.shape[:2]
    row = tuple(range(2, new.ndim))         # a row's dims: (2, 3), folded (2,)
    start = jnp.clip(lengths, 0, stack.shape[2] - t)
    at = jnp.stack(jnp.broadcast_arrays(
        i, jnp.arange(slots, dtype=jnp.int32)[:, None],
        start[:, None] + jnp.arange(t, dtype=jnp.int32)), axis=-1)
    old = jax.lax.gather(
        stack, at,
        jax.lax.GatherDimensionNumbers(
            offset_dims=row, collapsed_slice_dims=(0, 1, 2),
            start_index_map=(0, 1, 2)),
        slice_sizes=(1, 1, 1) + new.shape[2:], mode="promise_in_bounds",
        unique_indices=True, indices_are_sorted=True)
    return jax.lax.scatter(
        stack, at,
        jnp.where(active[(slice(None),) + (None,) * (new.ndim - 1)], new,
                  old),
        jax.lax.ScatterDimensionNumbers(
            update_window_dims=row, inserted_window_dims=(0, 1, 2),
            scatter_dims_to_operand_dims=(0, 1, 2)),
        mode="promise_in_bounds", unique_indices=True,
        indices_are_sorted=True)


def _write_rows(stack, i, new, slots, positions, keep):
    """``stack`` with row r of ``new`` (``[n, Hkv, Dh]``) at ``stack[i,
    slots[r], positions[r]]`` where ``keep[r]``: ONE scatter of n row
    points; a row that is not kept goes to a point past ``max_len`` of its
    own, which the scatter drops. The lane's form of `_append_rows`: S + C
    points, no gather of what an inactive slot holds (on the v5e 1.4 ms a
    tick of 192 layer-visits against 2.6 as two appends: PERF.md, PR 34);
    an active slot's position is the caller's to keep inside the slot."""
    new = _fold(stack, new)
    n = new.shape[0]
    out = stack.shape[2] + jnp.arange(n, dtype=jnp.int32)
    at = jnp.stack(jnp.broadcast_arrays(
        i, slots, jnp.where(keep, positions, out)), axis=-1)
    return jax.lax.scatter(
        stack, at, new,
        jax.lax.ScatterDimensionNumbers(
            update_window_dims=tuple(range(1, new.ndim)),
            inserted_window_dims=(0, 1, 2),
            scatter_dims_to_operand_dims=(0, 1, 2)),
        mode="drop", unique_indices=True)


def _decode_span(cfg, spec, params, x, positions, lengths, active, k_all,
                 v_all, rider=None):
    """The span's layers over ``T`` new tokens a slot: ``(h, k_all, v_all,
    steps)`` as `_run_passes` gives them.
    The body of the decode step (T = 1 plain, T = K+1 speculative verify:
    the draft block enters as new tokens, causal within itself) and, at
    T = 1, of every burst tick. ``x``: ids ``[S, T]`` or hidden ``[S, T, D]``
    at ``positions`` (``lengths[:, None]`` + the offset in the block);
    ``pos_grid``: ``arange(max_len)``, the caller's so that a burst builds
    it once for all its ticks.

    ``rider`` (a burst tick of an engine with a rider lane, T = 1): C prompt
    rows of the ONE request that joins during this burst ride the tick
    beside the slots' rows. ``{"ids": [C], "start": their first position,
    "valid": [C] (rows of the prompt; a lane with no rider has none),
    "slot", "rows": R}``: the rows enter the layers FLAT with the slots'
    (``[1, S + C, D]``: one read of every weight for both), are written to
    ``[slot, start : start + C)`` of every cache layer in the ONE scatter
    that writes the slots' rows (`_write_rows`) and attend over that slot's
    first R rows, causally. ``h`` and ``steps`` come back flat, the slots'
    S rows first.

    A family whose older rows are summaries (``cfg.eva_window``; T = 1):
    ``k_all`` and ``v_all`` are `_WindowedStacks`, the policy
    `_append_windowed`.

    A latent family (``cfg.kv_lora_rank``; T = 1): ``k_all`` is the stack
    of latent rows, ``v_all`` that of index keys, a row of each written a
    position a layer by the same `_append_rows`; ``steps`` is then which
    rows chose which held expert, ``[expert layers, S, held]``. One whose
    layers are of two kinds: ``k_all`` is `_LatentStacks`, a full layer
    reads and writes ``rows`` and ``v_all`` as above, a sliding layer its
    ring alone (`_attend_window`), each under its own geometry
    (``cfg.sliding_kind``) and RoPE base."""
    slots = x.shape[0]
    if rider is not None:
        r_pos = rider["start"] + jnp.arange(
            rider["ids"].shape[0], dtype=jnp.int32)
        x = jnp.concatenate([x[:, 0], rider["ids"]])[None]      # [1, S + C]
        positions = jnp.concatenate([positions[:, 0], r_pos])[None]
    with jax.named_scope("embed"):
        h = (embed_tokens(cfg, params["embed"], x, positions)
             if spec.is_first else x)
        rope = make_rope(cfg, positions)
        if isinstance(k_all, _LatentStacks):    # the sliding layers' base
            rope_sliding = make_rope(cfg.sliding_kind, positions)
    if rider is not None:
        positions = positions[0, :slots, None]                  # [S, 1]
    qpos = positions[:, :, None]                            # [S, T, 1]
    # Blocks of a cache layer that hold a row some ACTIVE slot's queries
    # may see: the layers read those and no more (`_attend_cached`).
    # By the kernel (`cache_read`): each slot's own blocks and its row
    # limit, as ONE plan a stack.
    kernel = cache_read(
        cfg, params["layers"], jax.tree.leaves(k_all)[0].ndim == 4,
        qpos.shape[1], rider is not None,
        jax.tree.leaves(k_all)[0].shape[2]) == "kernel"
    if cfg.kv_lora_rank:
        # (latent rows, index keys): the keys are scored to the longest
        # active slot's block; the rows are read as far or, by the kernel,
        # each slot's own blocks under its limit
        max_len = _full_rows(k_all).shape[2]
        scored = index_blocks(lengths, active, max_len, jnp)
        rows = latent_block(max_len)
        blocks = (read_plan(
            attn_blocks(lengths, active, 1, max_len, jnp, per_slot=True,
                        block=rows),
            qpos[:, 0, 0] + 1, max_len // rows) if kernel else scored, scored)
    elif cfg.eva_window:
        rows = (k_all.exact.shape[2], k_all.sums.shape[2])
        blocks = windowed_blocks(cfg, lengths, active, rows, jnp,
                                 per_slot=kernel)
        if kernel:
            p = qpos[:, 0, 0]
            blocks = tuple(
                read_plan(own, limit, n // attn_block(n) if n else 0)
                for own, limit, n in zip(
                    blocks, (p % cfg.eva_window + 1,
                             _summaries_visible(cfg, p)), rows))
    else:
        max_len = k_all.shape[2]
        blocks = attn_blocks(lengths, active, qpos.shape[1], max_len, jnp,
                             per_slot=kernel)
        if kernel:
            blocks = read_plan(blocks, qpos[:, 0, 0] + 1,
                               max_len // attn_block(max_len))
    if rider is not None:
        r_grid = jnp.arange(rider["rows"], dtype=jnp.int32)[None, None, :]
        r_qpos = r_pos[None, :, None]                           # [1, C, 1]
        r_allowed = _visible(cfg, r_qpos, r_grid)
        # Where the flat rows go in a cache layer: (slot, position, kept).
        points = (
            jnp.concatenate([jnp.arange(slots, dtype=jnp.int32),
                             jnp.broadcast_to(rider["slot"], r_pos.shape)]),
            jnp.concatenate([jnp.clip(lengths, 0, k_all.shape[2] - 1),
                             r_pos]),
            jnp.concatenate([active, rider["valid"]]))

    def one_pass(h, base, k_all, v_all):
        def layer(first, h, xs, sliding=False):
            # weight layer ``i`` of the group that starts at layer ``first``
            # (of a family whose layers are of two kinds: among its KIND)
            lp, i, k_all, v_all = xs
            at = _at(base, i + first if first and not sliding else i)

            def ring_append(k, _):
                # a sliding layer: the new row at ``p % R`` of its ring,
                # THEN the slot's whole ring to `_attend_window`
                ring = k_all.ring
                with jax.named_scope("kv_update"):
                    ring = _append_rows(ring, at, k.astype(ring.dtype),
                                        lengths % ring.shape[2], active)
                return (_CacheLayer(ring, at, None), None,
                        (None, qpos, None),
                        (k_all._replace(ring=ring), v_all))

            def per_slot_append(k, v):
                # Write the T new rows a slot into the stacks, THEN hand
                # attention this layer of them to read by blocks.
                if cfg.eva_window:
                    return _append_windowed(cfg, lp, at, k, v, k_all, v_all,
                                            lengths, active, qpos, blocks)
                if rider is None:
                    k_rows = _full_rows(k_all)
                    with jax.named_scope("kv_update"):
                        k_new = _append_rows(
                            k_rows, at, k.astype(k_rows.dtype), lengths,
                            active)
                        v_new = _append_rows(
                            v_all, at, v.astype(v_all.dtype), lengths,
                            active)
                    k_blocks, v_blocks = (
                        blocks if cfg.kv_lora_rank else (blocks, blocks))
                    return (_CacheLayer(k_new, at, k_blocks),
                            _CacheLayer(v_new, at, v_blocks),
                            (None, qpos, None),
                            (k_new if k_rows is k_all
                             else k_all._replace(rows=k_new), v_new))
                new, read = [], []
                for stack, rows in ((k_all, k), (v_all, v)):
                    rows = rows[0].astype(stack.dtype)      # [S + C, ..]
                    with jax.named_scope("kv_update"):
                        stack = _write_rows(stack, at, rows, *points)
                    with jax.named_scope("attention"):
                        mine = _unfold(stack, cfg, jax.lax.dynamic_slice(
                            stack, _origin(stack, at, rider["slot"]),
                            (1, 1, rider["rows"]) + stack.shape[3:])[0])
                    new.append(stack)
                    read.append((_CacheLayer(stack, at, blocks), mine))
                return (read[0], read[1],
                        ((None, qpos, None), (r_allowed, r_qpos, r_grid)),
                        tuple(new))

            if sliding:
                return _decoder_layer(cfg.sliding_kind, lp, h, rope_sliding,
                                      ring_append)
            return _decoder_layer(cfg, lp, h, rope, per_slot_append)

        more = ()
        for layers, first in _layer_groups(params):
            h, k_all, v_all, more = _scan_layers_in_place(
                partial(layer, first), h, layers, k_all, v_all)
        return (h, k_all, v_all, *more)

    return _run_passes(cfg, params, h, one_pass, k_all, v_all)


@dataclasses.dataclass
class _Burst:
    """One burst between its enqueue and its collect
    (`BatchedStageExecutor.burst_enqueue` / `burst_fetch` /
    `burst_collect`): the sessions' rows, the packed result on the device
    and, once fetched, on the host (``flat``; ``error``: what the fetch
    failed with), the rider with its slot, the prompt's result that lay
    ahead of it, the open phase ``device`` and the brackets that timed its
    parts."""

    rows: Dict[str, int]
    packed: Any
    n_ticks: int
    rider: Optional[dict]
    ahead: Any
    fence: contextlib.ExitStack
    timed: tuple
    flat: Optional[np.ndarray] = None
    error: Optional[Exception] = None


class BatchedStageExecutor:
    """One stage span serving up to `slots` sessions with batched decode."""

    # The last burst's seconds by `STALL_PARTS`, where the phase profiler
    # measured them (`decode_burst`); None with it off.
    burst_parts: Optional[Dict[str, float]] = None
    # With telemetry on (the registry's switch), the LAST device result of
    # the prompt's programs a `prefill` enqueued since the last round's
    # dispatch (caller holds the adapter's lock, as for every slot table);
    # None with it off, always.
    _registry = _tm.get_registry()
    _ahead = None
    # Whether the last round's dispatch found that result unfinished: its
    # program sits behind a prompt's on the one in-order device queue.
    behind_prefill = False

    def __init__(
        self,
        cfg: ModelConfig,
        spec: StageSpec,
        params: Params,
        *,
        slots: int = 8,
        max_len: int = 2048,
        dtype=jnp.float32,
        prefix_cache_bytes: int = 0,
        model: Optional[str] = None,
    ):
        if not (spec.is_first and spec.is_last):
            refuse_single_pass(cfg, "a batched engine over part of the "
                                    "stack")
        self.cfg = cfg
        self.spec = spec
        # Model tag for prefix-store digest coords: two models with the same
        # span indices must never share cache entries (multi-model serving).
        self.model = model
        # Engine-side fused-QKV layout (one projection matmul per layer,
        # bitwise-identical — models/transformer.fuse_qkv_params).
        from ..models.transformer import fuse_qkv_params

        self.params = params = fuse_qkv_params(params)
        self.slots = slots
        self.max_len = max_len
        self.dtype = jnp.dtype(dtype)
        # The rider lane (`_decode_span`): a looped stack's prefill program
        # streams the weights `loop_steps` times, as long as a whole tick of
        # every OTHER session's burst, and which rounds pay it is chance;
        # carried by the burst's own ticks the same prompt costs nothing
        # that an idle lane does not. A stack that runs once prefills in a
        # fraction of a tick and keeps the programs it had.
        self.rider_rows = (RIDER_ROWS if cfg.loop_steps > 1
                           and spec.is_first and spec.is_last else 0)
        self._new_stacks()
        self.lengths = np.zeros((slots,), np.int32)   # host-side truth
        self._slot_of: Dict[str, int] = {}
        self._free: List[int] = list(range(slots))
        self.decode_steps = 0                          # batched steps executed
        self._prefill_jit = None
        self._decode_jits: Dict[int, Any] = {}         # step width T -> jit
        # Burst decode (full-span engines only): n_ticks -> jitted scan.
        self._burst_jits: Dict[int, Any] = {}
        self.burst_dispatches = 0          # burst programs executed
        self.burst_tokens = 0              # tokens emitted by bursts
        self._m_burst_ticks = _tm.get("server_burst_ticks")
        self._m_burst_disp = _tm.get("server_burst_dispatches_total")
        self._m_burst_toks = _tm.get("server_burst_tokens_total")
        self._m_transfers = _tm.get("server_burst_transfers_total")
        self._m_sampler = _tm.get("server_sampler_rounds_total")
        self._m_exit_steps = _tm.get("server_loop_exit_steps_total")
        self._m_rows_read = _tm.get("server_attn_rows_read_total")
        self._m_rows_span = _tm.get("server_attn_rows_span_total")
        self._m_sum_rows_read = _tm.get("server_attn_summary_rows_read_total")
        self._m_chunks = _tm.get("server_kv_chunks_summarised_total")
        self._m_written = _tm.get("server_kv_positions_written_total")
        self._m_index_scored = _tm.get("server_index_rows_scored_total")
        self._m_streamed = _tm.get("server_latent_rows_streamed_total")
        self._m_window_read = _tm.get("server_window_rows_read_total")
        self._m_window_span = _tm.get("server_window_rows_span_total")
        self._m_moe = [_tm.get(name) for name in MOE_COUNTERS]
        self._m_rows_held = _tm.get("server_state_rows_held_total")
        self._m_pos_held = _tm.get("server_positions_held_total")
        # Prompt-prefix KV reuse (runtime.prefix_cache), slot-layout
        # variant: entries hold [L, G, Hkv, Dh] KV segments, rows as the
        # stacks hold them (folded: [L, G, W]) (+ [1, G, D] output rows
        # off the final stage). Same grain-chained rolling
        # digests as the session executor's store.
        self.prefix_store = None
        if prefix_cache_bytes > 0:
            if cfg.eva_window or cfg.kv_lora_rank:
                refuse_single_pass(cfg, "the prefix cache (a stored prefix "
                                        "is a slice of rows)")
            from .prefix_cache import PrefixStore

            self.prefix_store = PrefixStore(prefix_cache_bytes)
        self._suffix_jit = None
        self._chain_write_jit = None
        self._grain_split_jits: Dict[tuple, Any] = {}

    def _new_stacks(self) -> None:
        """Zeroed K and V stacks ``[loop_steps * L, S, max_len, Hkv, Dh]``:
        rows of its own for every (pass, layer), pass-major. Where the
        backend would not keep ``Dh`` minor a row is FOLDED: ``[.., W]``
        (`kv_fold_width`); the programs tell by the stack's rank. A family
        whose older rows are summaries holds TWO stacks each
        (`_WindowedStacks`): the current window's exact rows and one
        summary row a chunk of the earlier ones (`windowed_rows`). A latent
        family holds other rows altogether: `_new_latent_stacks`."""
        if self.cfg.kv_lora_rank:
            return self._new_latent_stacks()
        row = (self.cfg.num_kv_heads, self.cfg.head_dim)
        asked = jnp.zeros((1, 1, self.max_len) + row,
                          self.dtype).format.layout
        width = kv_fold_width(asked, *row)
        windowed = bool(self.cfg.eva_window)
        if windowed and width is not None:
            raise NotImplementedError(
                "a summary is pooled per head: rows folded into the lanes "
                f"(head_dim {row[1]} on this backend) are not implemented "
                "for a family whose older rows are summaries")
        depth = max(self.spec.num_layers, 1) * self.cfg.loop_steps
        counts = (windowed_rows(self.cfg, self.max_len) if windowed
                  else (self.max_len,))
        shapes = [(depth, self.slots, n) + (row if width is None
                                            else (width,)) for n in counts]

        def stacks():
            made = [jnp.zeros(shape, self.dtype) for shape in shapes]
            return _WindowedStacks(*made) if windowed else made[0]

        self.k, self.v = stacks(), stacks()
        leaves = jax.tree.leaves((self.k, self.v))
        _tm.get("server_kv_stack_bytes").set(sum(x.nbytes for x in leaves))
        shape, first = shapes[0], leaves[0]
        _ev.emit(
            "kv_layout", shape=list(shape), dtype=str(first.dtype),
            layout=layout_text(first.format.layout), row=list(row),
            row_layout=layout_text(asked), folded_to=width,
            read=self._cache_read(1, bool(self.rider_rows)),
            logical_bytes_a_stack=int(first.nbytes),
            resident_bytes_a_stack=int(first.on_device_size_in_bytes()),
            **({"rows": list(counts), "summary_shape": list(shapes[1])}
               if windowed else {}))

    def _new_latent_stacks(self) -> None:
        """`_new_stacks` for a latent family under a learned selection:
        ``self.k`` is the stack of latent rows ``[L, S, max_len,
        kv_lora_rank + qk_rope_head_dim]``, ``self.v`` that of index keys
        ``[L, S, max_len, index_head_dim]``: TWO rows a position a layer,
        of different widths, neither per head (a row's numbers are its
        minor dim already; the programs read a latent row's first
        ``kv_lora_rank + qk_rope_head_dim`` numbers whatever its pad). A
        family whose layers are of two kinds holds THREE stacks: those two
        over its FULL layers only, and (``self.k`` then `_LatentStacks`)
        the sliding layers' rings, ``[sliding layers, S, R, swa_kv_lora_rank
        + swa_qk_rope_head_dim]`` (`ring_rows`)."""
        cfg = self.cfg
        kinds = cfg.layer_kinds[:max(self.spec.num_layers, 1)]
        depth = kinds.count("full")
        row = cfg.kv_lora_rank + cfg.qk_rope_head_dim

        def asked_of(row: int, rows: int):
            return jnp.zeros((1, 1, rows, row), self.dtype).format.layout

        def width(row: int, rows: int) -> int:
            # Where the backend would not keep a row of that many numbers
            # minor (the v5e holds 576 with ``max_len`` minor, to pad
            # nothing, and every program then re-lays the stack at its
            # edges: PERF.md section 6, PRs 45 and 55), the row is held
            # padded with zeros to whole lane tiles, as a folded row is
            # (`kv_fold_width`: 640; a sliding layer's 1088 as 1152).
            return kv_fold_width(asked_of(row, rows), 1, row) or row

        asked = asked_of(row, self.max_len)
        widths = (width(row, self.max_len), cfg.index_head_dim)
        self.k, self.v = (jnp.zeros((depth, self.slots, self.max_len, w),
                                    self.dtype) for w in widths)
        ring = {}
        if "sliding" in kinds:
            # A sliding layer's slot is a RING and never a row a position:
            # its stack has no axis of ``max_len``.
            kind = cfg.sliding_kind
            ring_row = kind.kv_lora_rank + kind.qk_rope_head_dim
            n_ring = ring_rows(cfg.sliding_window_size, self.max_len)
            rows = self.k
            self.k = _LatentStacks(rows, jnp.zeros(
                (kinds.count("sliding"), self.slots, n_ring,
                 width(ring_row, n_ring)), self.dtype))
            ring = dict(
                ring_shape=list(self.k.ring.shape), ring_row=[ring_row],
                ring_layout=layout_text(self.k.ring.format.layout),
                ring_resident_bytes=int(
                    self.k.ring.on_device_size_in_bytes()),
                ring_rows=n_ring, window=cfg.sliding_window_size,
                ring_read="window", layer_kinds=list(kinds))
        rows = _full_rows(self.k)
        _tm.get("server_kv_stack_bytes").set(
            sum(x.nbytes for x in jax.tree.leaves((self.k, self.v))))
        _ev.emit(
            "kv_layout", shape=list(rows.shape), dtype=str(rows.dtype),
            layout=layout_text(rows.format.layout), row=[row],
            row_layout=layout_text(asked),
            folded_to=widths[0] if widths[0] != row else None,
            read=self._cache_read(1, False),
            logical_bytes_a_stack=int(rows.nbytes),
            resident_bytes_a_stack=int(rows.on_device_size_in_bytes()),
            index_shape=list(self.v.shape), index_row=[widths[1]],
            index_layout=layout_text(self.v.format.layout),
            index_resident_bytes=int(self.v.on_device_size_in_bytes()),
            selected_rows=min(cfg.index_topk, self.max_len), **ring)

    def _cache_read(self, t: int, rider: bool) -> str:
        """`cache_read` of this engine's decode program of ``t`` new rows a
        slot, with or without a ``rider`` group."""
        return cache_read(self.cfg, self.params["layers"],
                          jax.tree.leaves(self.k)[0].ndim == 4, t, rider,
                          self.max_len)

    def _count_attn_rows(self, lengths, active, t: int,
                         rider: bool = False) -> None:
        """Add the ticks whose slots began at ``lengths`` (``[ticks, S]``),
        ``active`` of them taking ``t`` new rows (beside a ``rider`` group
        or not), to the two counters of how much of a cache layer the
        ticks' attention read: the bound is `attn_blocks`, the function the
        programs call, every slot to the longest active one's or, where the
        program reads by the kernel (`cache_read`), each slot to its own
        and an inactive one not at all. A windowed family's
        bounds are `windowed_blocks`' two, shared or per slot likewise: its
        exact rows go to the same counter, its summary rows to one of
        their own, and the chunks its ticks closed and pooled to a third.
        A latent family under a learned selection: the index keys it
        scored to ``server_index_rows_scored_total``, the latent rows it
        SELECTED and read to the rows-read counter, and those it STREAMED
        to read them (the kernel: each active slot's own blocks of
        `latent_block` rows; the gather none) to
        ``server_latent_rows_streamed_total``; those are ONE FULL layer's.
        Where sliding layers alternate with them, ONE sliding layer's: the
        ring rows a tick read (every slot's whole ring) to
        ``server_window_rows_read_total`` and what the active slots'
        windows hold, ``min(length + 1, sliding_window_size)`` each, to
        ``server_window_rows_span_total``."""
        kernel = self._cache_read(t, rider) == "kernel"
        each = 1 if kernel else self.slots      # slots that read a count
        if self.cfg.kv_lora_rank:
            # index keys scored: every slot's blocks to the longest active
            # one's; latent rows read: those SELECTED, an active slot's
            # positions up to ``index_topk``
            self._m_index_scored.inc(
                int(index_blocks(lengths, active, self.max_len).sum())
                * index_block(self.max_len) * self.slots)
            self._m_rows_read.inc(int(np.where(
                active, np.minimum(lengths + 1, self.cfg.index_topk),
                0).sum()))
            if kernel:
                rows = latent_block(self.max_len)
                self._m_streamed.inc(int(attn_blocks(
                    lengths, active, 1, self.max_len, per_slot=True,
                    block=rows).sum()) * rows)
            if isinstance(self.k, _LatentStacks):
                # ONE sliding layer: every slot's whole ring a tick, against
                # what the active slots' windows hold
                self._m_window_read.inc(
                    len(lengths) * self.slots * self.k.ring.shape[2])
                self._m_window_span.inc(int(np.where(active, np.minimum(
                    lengths + 1, self.cfg.sliding_window_size), 0).sum()))
        elif self.cfg.eva_window:
            rows_e, rows_s = rows = windowed_rows(self.cfg, self.max_len)
            exact, sums = windowed_blocks(self.cfg, lengths, active, rows,
                                          per_slot=kernel)
            self._m_rows_read.inc(int(exact.sum()) * attn_block(rows_e) * each)
            if rows_s:
                self._m_sum_rows_read.inc(
                    int(sums.sum()) * attn_block(rows_s) * each)
            c = self.cfg.eva_chunk
            self._m_chunks.inc(int((active & (lengths % c == c - 1)).sum()))
        else:
            blocks = attn_blocks(lengths, active, t, self.max_len,
                                 per_slot=kernel)
            self._m_rows_read.inc(
                int(blocks.sum()) * attn_block(self.max_len) * each)
        self._m_rows_span.inc(len(lengths) * self.slots * self.max_len)
        self._m_written.inc(int(np.sum(active)) * t)

    def _count_rows_held(self, held: Sequence[int]) -> None:
        """Once a round, over the slots in it (``held``): the cache rows a
        layer holds for those sessions against the positions they have
        sent. One row a position everywhere but in a windowed family: the
        rows of the current window and one a chunk of the earlier ones;
        and where sliding layers hold a ring: the MEAN over the layers, a
        row a position in a full one and at most the ring's in a sliding
        one."""
        n = self.lengths[list(held)].astype(np.int64)
        rows = n
        if isinstance(self.k, _LatentStacks):
            full, ring = (x.shape[0] for x in self.k)
            rows = (full * n + ring * np.minimum(n, self.k.ring.shape[2])
                    ) / (full + ring)
        if self.cfg.eva_window:
            last = np.maximum(n - 1, 0)     # the newest position held
            rows = np.where(n > 0, last % self.cfg.eva_window + 1
                            + _summaries_visible(self.cfg, last), 0)
        self._m_rows_held.inc(int(rows.sum()))
        self._m_pos_held.inc(int(n.sum()))

    # ------------------------------------------------------------------
    # Slots
    # ------------------------------------------------------------------

    def slot(self, session_id: str) -> Optional[int]:
        return self._slot_of.get(session_id)

    def _alloc(self, session_id: str) -> int:
        old = self._slot_of.pop(session_id, None)
        if old is not None:                  # re-prefill restarts the session
            self._free.append(old)
        if not self._free:
            raise SlotFull(f"all {self.slots} session slots in use")
        s = self._free.pop()
        self._slot_of[session_id] = s
        return s

    def end_session(self, session_id: str) -> None:
        s = self._slot_of.pop(session_id, None)
        if s is not None:
            self.lengths[s] = 0
            self._free.append(s)

    def rewind(self, session_id: str, pos: int) -> None:
        """Shrink a session's valid KV prefix to `pos` (the
        ``start_from_position`` semantics of petals handler.py:163-168,
        reused as speculative rollback). Host-side only: rows past `pos`
        are never attended (the decode mask allows positions <= length)
        and are overwritten as the session advances."""
        s = self._slot_of.get(session_id)
        if s is None:
            raise KeyError(f"unknown session {session_id}")
        cur = int(self.lengths[s])
        if not 0 <= pos <= cur:
            raise ValueError(f"rewind to {pos} outside [0, {cur}]")
        w = self.cfg.eva_window
        if w and pos < cur and pos // w != (cur - 1) // w:
            # Back inside the current window is a length (its later rows
            # are masked until rewritten, and every chunk past ``pos`` is
            # pooled again as it closes again); an earlier window's exact
            # rows have been overwritten.
            raise WindowGone(
                f"rewind to {pos} from {cur}: the exact rows of window "
                f"{pos // w} ({w} rows a window) are gone, only their "
                "summaries are held; replay the session through prefill")
        if isinstance(self.k, _LatentStacks) and pos < cur:
            # A sliding layer's ring holds the newest R positions. The
            # query at ``pos`` needs those from ``pos - window + 1``: the
            # oldest of them is still there while no position R past it
            # has been written.
            ring, w = self.k.ring.shape[2], self.cfg.sliding_window_size
            if max(0, pos - w + 1) + ring < cur:
                raise WindowGone(
                    f"rewind to {pos} from {cur}: a sliding layer holds "
                    f"the newest {ring} rows and the window at {pos} needs "
                    f"row {max(0, pos - w + 1)}, overwritten since; replay "
                    "the session through prefill")
        self.lengths[s] = pos

    # ------------------------------------------------------------------
    # Prefill: per-session, writes the prompt's KV into the slot's rows
    # ------------------------------------------------------------------

    def _build_prefill(self):
        cfg, spec = self.cfg, self.spec

        @partial(jax.jit, donate_argnums=engine_donation(3, 4))
        def prefill(params, x, slot, k_all, v_all, t_real):
            t = x.shape[1]
            positions = jnp.arange(t, dtype=jnp.int32)[None, :]
            with jax.named_scope("embed"):
                h = (embed_tokens(cfg, params["embed"], x, positions)
                     if spec.is_first else x)
                rope = make_rope(cfg, positions)
            # Causal self-attention over the fresh prompt (prefill restarts
            # the session, so there is no prior cache to attend to). O(T^2)
            # scores — long prompts belong to the sp engine or the chunked
            # per-session executor.
            causal = jnp.tril(jnp.ones((t, t), bool))
            valid = jnp.arange(t)[None, :] < t_real       # mask pad columns
            mask = causal & valid
            rows = jnp.arange(t)[:, None]
            cols = jnp.arange(t)[None, :]
            if cfg.sliding_window:
                # Mistral-style local attention: row i sees cols
                # (i - window, i].
                mask &= cols > rows - cfg.sliding_window

            def fresh_prompt(k, v):
                return k, v, (mask, rows, cols), (k, v)

            def layer(h, xs):
                (lp,) = xs
                h, (k, v) = _decoder_layer(cfg, lp, h, rope, fresh_prompt)
                return h, (k[0], v[0])

            def one_pass(h, base, k_all, v_all):
                h, (ks, vs) = _scan_layers(layer, h, params["layers"])
                # ks/vs: [L, T, Hkv, Dh] -> write rows [slot, 0:T).
                with jax.named_scope("kv_update"):
                    k_all = jax.lax.dynamic_update_slice(
                        k_all, _fold(k_all, ks)[:, None].astype(k_all.dtype),
                        _origin(k_all, _at(base, 0), slot))
                    v_all = jax.lax.dynamic_update_slice(
                        v_all, _fold(v_all, vs)[:, None].astype(v_all.dtype),
                        _origin(v_all, _at(base, 0), slot))
                return h, k_all, v_all

            return _run_passes(cfg, params, h, one_pass, k_all, v_all)[:3]

        return prefill

    def _build_prefill_window(self):
        """The prefill program of a family whose older rows are summaries:
        ONE window's share of a prompt, ``x`` ``[1, T]`` at positions ``win
        * W ..`` (T a bucket, or W for a whole window). Its queries need
        their own causal block and the ``(W / C) * win`` summary rows the
        windows before it left in the slot (`_attend_windowed`), so a
        prompt of any length runs through the bounded set of shapes
        `window_shapes` lists, no mask is larger than ``[512, W + R]``,
        and the cost of a prompt grows as ``T x (W + T / C)``. It writes
        the window's rows to ``[slot, 0 : T)`` of the exact stacks (rows
        past the prompt's end are masked until a later token overwrites
        them) and
        the summaries of its T / C chunks to their rows (those of a chunk
        the prompt leaves open are made again when a decode tick closes
        it, `_append_windowed`; those of the slot's last window have no
        row: the write is a no-op)."""
        cfg, spec = self.cfg, self.spec
        w_rows, c_rows = cfg.eva_window, cfg.eva_chunk
        per = w_rows // c_rows

        @partial(jax.jit, donate_argnums=engine_donation(3, 4))
        def prefill_window(params, x, slot, k_all, v_all, win):
            t = x.shape[1]
            positions = win * w_rows + jnp.arange(t, dtype=jnp.int32)[None, :]
            with jax.named_scope("embed"):
                h = embed_tokens(cfg, params["embed"], x, positions)
                rope = make_rope(cfg, positions)
            qpos = positions[:, :, None]
            with jax.named_scope("attention"):
                k_sums, v_sums = (jax.lax.dynamic_index_in_dim(
                    st.sums, slot, 1, keepdims=False) for st in (k_all, v_all))

            def layer(h, xs):
                lp, k_sum, v_sum = xs               # [R, Hkv, Dh]

                def fresh_window(k, v):
                    k, v = (k.astype(k_all.exact.dtype),
                            v.astype(v_all.exact.dtype))
                    pooled = (() if t < c_rows else _pool_chunks(
                        cfg, lp, *(a[0, :t // c_rows * c_rows].reshape(
                            t // c_rows, c_rows, *a.shape[2:])
                            for a in (k, v))))
                    return (_WindowedRead(k, k_sum[None]),
                            _WindowedRead(v, v_sum[None]),
                            (None, qpos, None), (k[0], v[0], *pooled))

                return _decoder_layer(cfg, lp, h, rope, fresh_window)

            h, (ks, vs, *pooled) = _scan_layers(
                layer, h, params["layers"], k_sums, v_sums)
            with jax.named_scope("kv_update"):
                exact = [jax.lax.dynamic_update_slice(
                    st.exact, rows[:, None], _origin(st.exact, 0, slot))
                    for st, rows in ((k_all, ks), (v_all, vs))]
                sums = [k_all.sums, v_all.sums]
                if pooled and sums[0].shape[2]:
                    # ``dynamic_update_slice`` clamps a start past the end:
                    # there, write back what the clamped slice holds.
                    n = pooled[0].shape[1]
                    at = _origin(sums[0], 0, slot, win * per)
                    fits = win * per + n <= sums[0].shape[2]
                    sums = [jax.lax.dynamic_update_slice(
                        st, jnp.where(fits, new[:, None], jax.lax.dynamic_slice(
                            st, at, (st.shape[0], 1, n) + st.shape[3:])), at)
                        for st, new in zip(sums, pooled)]
            return (h, _WindowedStacks(exact[0], sums[0]),
                    _WindowedStacks(exact[1], sums[1]))

        return prefill_window

    def window_shapes(self) -> List[int]:
        """Every ``T`` the windowed prefill program runs at: the buckets
        under a window's rows, and the window."""
        rows = windowed_rows(self.cfg, self.max_len)[0]
        return [n for n in PREFILL_BUCKETS if n < rows] + [rows]

    def _prefill_windows(self, session_id: str, x) -> jnp.ndarray:
        """`_prefill_full` for a family whose older rows are summaries:
        the prompt's whole windows, then its tail padded to a bucket, each
        through `_build_prefill_window`'s program in turn (window ``w``
        reads the summaries windows ``0 .. w - 1`` have just written)."""
        x = np.asarray(x)
        t = x.shape[1]
        if t > self.max_len:
            raise ValueError(f"prompt {t} exceeds slot max_len {self.max_len}")
        s = self._alloc(session_id)
        if self._prefill_jit is None:
            self._prefill_jit = self._build_prefill_window()
        rows, shapes = self.cfg.eva_window, self.window_shapes()
        outs = []
        try:
            for win in range(-(-t // rows)):
                part = x[:, win * rows:(win + 1) * rows]
                n = part.shape[1]
                tb = round_to_bucket(n, shapes)
                h, self.k, self.v = self._prefill_jit(
                    self.params, np.pad(part, ((0, 0), (0, tb - n))),
                    np.int32(s), self.k, self.v, np.int32(win))
                outs.append(h if tb == n else h[:, :n])
        except Exception:
            self._recover_slot(session_id, s)
            raise
        self.lengths[s] = t
        self._m_chunks.inc(t // self.cfg.eva_chunk)
        self._m_written.inc(t)
        return outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=1)

    def _build_prefill_suffix(self):
        """Prefill CONTINUATION for a prefix-cache hit: the suffix enters at
        position p_len and attends over the slot's cache rows (the copied
        prefix) plus its own fresh keys — the slot-batched analogue of the
        session executor's chunked continuation. For a latent family
        under a learned selection it is THE prefill program: every chunk
        of a prompt is a continuation of the chunks before it
        (`_prefill_chunks`)."""
        cfg, spec = self.cfg, self.spec

        @partial(jax.jit, donate_argnums=engine_donation(3, 4))
        def prefill_suffix(params, x, slot, k_all, v_all, p_len, t_real):
            t = x.shape[1]
            positions = p_len + jnp.arange(t, dtype=jnp.int32)[None, :]
            with jax.named_scope("embed"):
                h = (embed_tokens(cfg, params["embed"], x, positions)
                     if spec.is_first else x)
                rope = make_rope(cfg, positions)
            pos_grid = jnp.arange(_full_rows(k_all).shape[2],
                                  dtype=jnp.int32)[None, :]
            qpos = positions[0][:, None]                     # [T, 1]
            allowed = pos_grid <= qpos                       # [T, M] causal
            if cfg.sliding_window:
                allowed &= pos_grid > qpos - cfg.sliding_window
            with jax.named_scope("kv_update"):
                k_slot = jax.tree.map(
                    lambda st: jax.lax.dynamic_slice_in_dim(st, slot, 1, 1),
                    k_all)
                v_slot = jax.lax.dynamic_slice_in_dim(v_all, slot, 1, 1)
            if isinstance(k_all, _LatentStacks):
                with jax.named_scope("embed"):
                    rope_sliding = make_rope(cfg.sliding_kind, positions)

            def layer(h, xs, sliding=False):
                if sliding:
                    lp, ring_l = xs                          # [1, R, W]

                    def ring_continuation(k, _):
                        # the chunk's queries see the ring as the chunks
                        # before left it, and the chunk's own rows
                        fresh = _fold(k_all.ring, k)
                        with jax.named_scope("kv_update"):
                            new = _write_ring(ring_l, fresh, p_len, t_real)
                        return (_RingChunk(ring_l, fresh, p_len), None,
                                (None, qpos, None), (new,))

                    return _decoder_layer(cfg.sliding_kind, lp, h,
                                          rope_sliding, ring_continuation)
                lp, k_l, v_l = xs       # k_l: [1, M, Hkv, Dh] or [1, M, W]

                def slot_continuation(k, v):
                    with jax.named_scope("kv_update"):
                        k_new = jax.lax.dynamic_update_slice_in_dim(
                            k_l, _fold(_full_rows(k_all), k).astype(
                                k_l.dtype), p_len, 1)
                        v_new = jax.lax.dynamic_update_slice_in_dim(
                            v_l, _fold(v_all, v).astype(v_l.dtype), p_len, 1)
                    if cfg.kv_lora_rank:    # the slot's two rows, as held
                        return (k_new, v_new, (None, qpos, None),
                                (k_new, v_new))
                    return (_unfold(k_all, cfg, k_new),
                            _unfold(v_all, cfg, v_new),
                            (allowed, qpos, pos_grid), (k_new, v_new))

                return _decoder_layer(cfg, lp, h, rope, slot_continuation)

            def one_pass(h, base, k_all, v_all):
                k_in, v_in = k_slot, v_slot
                if base is not None:       # this pass's layers of the slot
                    n = k_slot.shape[0] // cfg.loop_steps
                    k_in = jax.lax.dynamic_slice_in_dim(k_slot, base, n, 0)
                    v_in = jax.lax.dynamic_slice_in_dim(v_slot, base, n, 0)
                h, (ks, vs) = _scan_groups(params, layer, h, k_in, v_in)
                with jax.named_scope("kv_update"):
                    k_all = jax.tree.map(
                        lambda st, new: jax.lax.dynamic_update_slice(
                            st, new, _origin(st, _at(base, 0), slot)),
                        k_all, ks)
                    v_all = jax.lax.dynamic_update_slice(
                        v_all, vs, _origin(v_all, _at(base, 0), slot))
                return h, k_all, v_all

            # ``t_real``: a ring's write alone needs it (`_write_ring`);
            # every other mask is by ``qpos``
            return _run_passes(cfg, params, h, one_pass, k_all, v_all)[:3]

        return prefill_suffix

    def _write_prefix_chain(self, slot: int, chain) -> None:
        """Write a chain's KV segments into the slot's leading cache rows
        in ONE jitted dispatch (specialized per chain length)."""
        if self._chain_write_jit is None:
            @partial(jax.jit, donate_argnums=engine_donation(0, 1))
            def prefix_chain_write(k_all, v_all, slot, segs_k, segs_v):
                kc = (segs_k[0] if len(segs_k) == 1
                      else jnp.concatenate(segs_k, axis=1))
                vc = (segs_v[0] if len(segs_v) == 1
                      else jnp.concatenate(segs_v, axis=1))
                start = _origin(k_all, 0, slot)
                return (jax.lax.dynamic_update_slice(
                            k_all, kc[:, None].astype(k_all.dtype), start),
                        jax.lax.dynamic_update_slice(
                            v_all, vc[:, None].astype(v_all.dtype), start))

            self._chain_write_jit = prefix_chain_write
        self.k, self.v = self._chain_write_jit(
            self.k, self.v, jnp.int32(slot),
            [e.k for e in chain], [e.v for e in chain])

    def _split_grains(self, slot: int, n_grains: int, grain: int):
        """All grain KV segments of a slot's leading rows as one jitted
        call (n outputs, ONE dispatch — eager per-grain slicing would pay
        a device round trip per grain on registration)."""
        key = (n_grains, grain)
        fn = self._grain_split_jits.get(key)
        if fn is None:
            @jax.jit
            def grain_split(k_all, v_all, slot):
                k_s = jax.lax.dynamic_index_in_dim(k_all, slot, 1,
                                                   keepdims=False)
                v_s = jax.lax.dynamic_index_in_dim(v_all, slot, 1,
                                                   keepdims=False)
                return ([k_s[:, g * grain:(g + 1) * grain]
                         for g in range(n_grains)],
                        [v_s[:, g * grain:(g + 1) * grain]
                         for g in range(n_grains)])

            fn = self._grain_split_jits[key] = grain_split
        return fn(self.k, self.v, jnp.int32(slot))

    def prefill(self, session_id: str, x, prefix_len: int = 0) -> jnp.ndarray:
        """Join/restart a session: x = ids [1, T] (first stage) or hidden
        [1, T, D]. Returns hidden rows (pad trimmed): all T rows normally;
        on a prefix-cache hit, the stored prefix rows prepended to the
        computed suffix (final stage: suffix only — it samples from the
        last row and stores no outputs)."""
        if self.prefix_store is not None and prefix_len > 0:
            h = self._prefill_with_store(session_id, x, prefix_len)
        else:
            h = self._prefill_full(session_id, x)
        if self._registry.enabled:
            self._ahead = h
        return h

    def _dispatched(self):
        """A round's program has just been enqueued: the prompt's result
        kept since the last round (`prefill`), taken and cleared, and
        whether this round queues behind it (``behind_prefill``: a
        non-blocking query; a prompt whose programs ran while the sessions
        were on their way back is finished and does not count)."""
        ahead, self._ahead = self._ahead, None
        self.behind_prefill = ahead is not None and not ahead.is_ready()
        return ahead

    def _prefill_with_store(self, session_id: str, x,
                            prefix_len: int) -> jnp.ndarray:
        from .prefix_cache import chain_digests

        x_np = np.asarray(x)
        t = x_np.shape[1]
        grain = self.prefix_store.grain
        n_grains = min(prefix_len, t - 1) // grain
        if n_grains <= 0:
            return self._prefill_full(session_id, x)
        # Batch dim rides the coords because stored segments are [L, G, ...]
        # slices of a fixed-batch slot layout; model tag because digests are
        # content-addressed across sessions, and two models' identical token
        # prefixes must not alias (the session executor's coords already
        # carry req.model — this engine learns it at construction).
        coords = ("slot", self.spec.start, self.spec.end,
                  str(x_np.dtype), str(self.dtype),
                  x_np.shape[0], self.model)
        blocks = [np.ascontiguousarray(x_np[:, g * grain:(g + 1) * grain])
                  .tobytes() for g in range(n_grains)]
        keys = chain_digests(blocks, coords)
        chain = self.prefix_store.lookup_chain(
            keys, need_out=not self.spec.is_last)
        if not chain:
            h = self._prefill_full(session_id, x)
            s = self._slot_of[session_id]
            segs_k, segs_v = self._split_grains(s, n_grains, grain)
            for g in range(n_grains):
                out = (None if self.spec.is_last
                       else h[:, g * grain:(g + 1) * grain])
                self.prefix_store.put(keys[g], segs_k[g], segs_v[g], out)
            return h
        # Hit (possibly partial): copy the chain's KV, compute the rest.
        p = len(chain) * grain
        if t > self.max_len:
            raise ValueError(f"prompt {t} exceeds slot max_len {self.max_len}")
        s = self._alloc(session_id)
        suffix = x_np[:, p:]
        ts = suffix.shape[1]
        tb = (ts if ts > PREFILL_BUCKETS[-1]
              else min(round_to_bucket(ts, PREFILL_BUCKETS),
                       self.max_len - p))
        if tb != ts:
            pad = ((0, 0), (0, tb - ts)) + (((0, 0),) if x_np.ndim == 3
                                            else ())
            suffix = np.pad(suffix, pad)
        if self._suffix_jit is None:
            self._suffix_jit = self._build_prefill_suffix()
        try:
            self._write_prefix_chain(s, chain)
            h, self.k, self.v = self._suffix_jit(
                self.params, jnp.asarray(suffix), jnp.int32(s), self.k,
                self.v, jnp.int32(p), jnp.int32(ts))
        except Exception:
            self._recover_slot(session_id, s)
            raise
        self.lengths[s] = t
        h = h[:, :ts]
        full = (h if self.spec.is_last
                else jnp.concatenate([*(e.out for e in chain), h], axis=1))
        if len(chain) < n_grains:
            # Register the grains the chain didn't cover (and REPAIR chains
            # truncated by LRU eviction of a middle link — the session
            # executor's pfx_register does the same).
            segs_k, segs_v = self._split_grains(s, n_grains, grain)
            for g in range(len(chain), n_grains):
                out = (None if self.spec.is_last
                       else full[:, g * grain:(g + 1) * grain])
                self.prefix_store.put(keys[g], segs_k[g], segs_v[g], out)
        return full

    def _recover_slot(self, session_id: str, s: int) -> None:
        """Shared failure recovery for every prefill path: a failed
        dispatch (e.g. device OOM) must not leak the slot — the session
        was never established, so recycle it with a clean length. The
        jitted calls DONATE self.k/self.v, so a failure DURING execution
        (vs before dispatch) leaves them deleted, which would crash every
        later step with 'Array has been deleted'; rebuild empty caches and
        evict all sessions — their KV is gone either way, and a refused
        decode is retryable (clients fail over and replay) where a
        poisoned engine is not."""
        self._slot_of.pop(session_id, None)
        self.lengths[s] = 0
        self._free.append(s)
        if any(getattr(x, "is_deleted", lambda: False)()
               for x in jax.tree.leaves(self.k)):
            self._new_stacks()
            self._slot_of.clear()
            self.lengths[:] = 0
            self._free = list(range(self.slots))

    def _prefill_chunks(self, session_id: str, x) -> jnp.ndarray:
        """`_prefill_full` for a latent family under a learned selection:
        the prompt in chunks of `LATENT_CHUNK` rows, its tail padded to a
        bucket, each through the suffix program in turn (a chunk's queries
        score and attend over the slot's rows so far, its own among them:
        `_attend_latent`). ONE program shape and the tail's buckets serve
        a prompt of any length, and no array grows with the square of
        it."""
        x = np.asarray(x)
        t = x.shape[1]
        if t > self.max_len:
            raise ValueError(f"prompt {t} exceeds slot max_len {self.max_len}")
        s = self._alloc(session_id)
        if self._suffix_jit is None:
            self._suffix_jit = self._build_prefill_suffix()
        outs = []
        try:
            for p0 in range(0, t, LATENT_CHUNK):
                part = x[:, p0:p0 + LATENT_CHUNK]
                n = part.shape[1]
                tb = (n if n == LATENT_CHUNK else min(
                    round_to_bucket(n, PREFILL_BUCKETS), self.max_len - p0))
                h, self.k, self.v = self._suffix_jit(
                    self.params, np.pad(part, ((0, 0), (0, tb - n))),
                    np.int32(s), self.k, self.v, np.int32(p0), np.int32(n))
                outs.append(h if tb == n else h[:, :n])
        except Exception:
            self._recover_slot(session_id, s)
            raise
        self.lengths[s] = t
        self._m_written.inc(t)
        return outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=1)

    def _prefill_full(self, session_id: str, x) -> jnp.ndarray:
        if self.cfg.kv_lora_rank:
            return self._prefill_chunks(session_id, x)
        if self.cfg.eva_window:
            return self._prefill_windows(session_id, x)
        if not isinstance(x, jax.Array):
            x = np.asarray(x)
        t = x.shape[1]
        if t > self.max_len:
            raise ValueError(f"prompt {t} exceeds slot max_len {self.max_len}")
        s = self._alloc(session_id)
        # Bucket-pad the prompt so an epoch of varied lengths compiles a
        # handful of shapes; beyond the bucket table, exact length (one
        # compile) beats failing. Ids off the wire are a host array: padded
        # there, they go up once, at the bucket's shape; hidden states are
        # on the device already and are padded where they are.
        tb = (t if t > PREFILL_BUCKETS[-1]
              else min(round_to_bucket(t, PREFILL_BUCKETS), self.max_len))
        if tb != t:
            pad = ((0, 0), (0, tb - t)) + (((0, 0),) if x.ndim == 3 else ())
            x = (np if isinstance(x, np.ndarray) else jnp).pad(x, pad)
        x = jnp.asarray(x)
        if self._prefill_jit is None:
            self._prefill_jit = self._build_prefill()
        try:
            h, self.k, self.v = self._prefill_jit(
                self.params, x, jnp.int32(s), self.k, self.v, jnp.int32(t))
        except Exception:
            self._recover_slot(session_id, s)
            raise
        self.lengths[s] = t
        self._m_written.inc(t)
        return h[:, :t]

    # ------------------------------------------------------------------
    # Batched decode: one step for EVERY active slot
    # ------------------------------------------------------------------

    def _build_decode(self, t_step: int):
        """One batched step of `t_step` tokens per active slot. t_step == 1
        is plain decode; t_step == K+1 is a speculative verify round (the
        draft block enters as new tokens, causal within itself)."""
        cfg, spec = self.cfg, self.spec

        @partial(jax.jit, donate_argnums=engine_donation(4, 5))
        def decode_step(params, x, lengths, active, k_all, v_all):
            # x: ids [S, T] or hidden [S, T, D]; lengths/active: [S].
            offs = jnp.arange(t_step, dtype=jnp.int32)
            positions = lengths[:, None] + offs[None, :]       # [S, T]
            h, k_all, v_all, _ = _decode_span(
                cfg, spec, params, x, positions, lengths, active, k_all,
                v_all)
            # Inactive slots produced garbage — zero them so nothing
            # downstream can mistake them for real activations.
            h = jnp.where(active[:, None, None], h, 0.0)
            return h, k_all, v_all

        return decode_step

    def tokens_left(self) -> int:
        """Admission headroom for heartbeats/info (the slot-batched analogue
        of KVArena.tokens_left): free slots at full length plus the unused
        tail of every occupied slot."""
        occupied = set(self._slot_of.values())
        free = self.slots - len(occupied)
        return int(free * self.max_len
                   + sum(self.max_len - int(self.lengths[s])
                         for s in occupied))

    def decode_batch(self, inputs: Dict[str, Any]) -> Dict[str, jnp.ndarray]:
        """One batched step. inputs: {session_id: ids [1,T] or hidden
        [1,T,D]} — every session in the call shares one step width T (T=1
        plain decode, T=K+1 speculative verify). Returns {session_id:
        hidden [1,T,D]}. Sessions not in `inputs` are untouched (masked)."""
        if not inputs:
            return {}
        sids = list(inputs)
        t = int(np.asarray(inputs[sids[0]]).shape[1])
        if t > 1 and self.cfg.kv_lora_rank:
            raise NotImplementedError(
                f"a step of {t} rows a slot on a latent family under a "
                "learned selection (the decode step selects for ONE query "
                "row a slot): speculative verify is not served, and a "
                "journal replay rebuilds the slot through prefill")
        if t > 1 and self.cfg.eva_window:
            raise NotImplementedError(
                f"a step of {t} rows a slot on a family whose older rows "
                "are summaries (a block of rows may cross a window's edge "
                "and overwrite the exact rows its own first rows read): "
                "speculative verify is not served, and a journal replay "
                "rebuilds the slot through prefill")
        rows = []
        for sid in sids:
            if int(np.asarray(inputs[sid]).shape[1]) != t:
                raise ValueError(
                    "all sessions in one batched step share one width "
                    f"(got {np.asarray(inputs[sid]).shape[1]} vs {t})")
            if sid not in self._slot_of:
                raise KeyError(f"unknown session {sid} (prefill first)")
            if self.lengths[self._slot_of[sid]] + t > self.max_len:
                raise RuntimeError(
                    f"session {sid}: {t} tokens past length "
                    f"{int(self.lengths[self._slot_of[sid]])} exceeds "
                    f"max_len {self.max_len}")
            rows.append(self._slot_of[sid])

        first = self.spec.is_first
        d = self.cfg.hidden_size
        if first:
            x = np.zeros((self.slots, t), np.int32)
        else:
            x = np.zeros((self.slots, t, d), np.float32)
        for sid, s in zip(sids, rows):
            x[s] = np.asarray(inputs[sid])[0]
        active = np.zeros((self.slots,), bool)
        active[rows] = True

        step = self._decode_jits.get(t)
        if step is None:
            step = self._decode_jits[t] = self._build_decode(t)
        # lengths is COPIED: jnp.asarray may alias a numpy buffer (the CPU
        # client does, zero-copy, whenever it is 64-byte aligned) and the
        # host bumps self.lengths below while the step is still in flight.
        h, self.k, self.v = step(
            self.params, jnp.asarray(x), jnp.asarray(self.lengths.copy()),
            jnp.asarray(active), self.k, self.v)
        self._dispatched()
        self._count_attn_rows(self.lengths[None], active[None], t)
        for s in rows:
            self.lengths[s] += t
        self._count_rows_held(rows)
        self.decode_steps += 1
        return {sid: h[s:s + 1] for sid, s in zip(sids, rows)}

    # ------------------------------------------------------------------
    # Burst decode: N ticks per dispatch, sampling on device
    # ------------------------------------------------------------------

    def _build_burst(self, n_ticks: int):
        """N decode ticks in one program: ``lax.scan`` over ticks, each tick
        `_decode_span` at T = 1 (what ``_build_decode(1)`` runs) plus the
        final head and per-slot sampling.

        Determinism contract: tick i of a slot whose request shipped
        ``step_seed`` samples with ``PRNGKey(step_seed + i)`` — exactly the
        key the sequential client would ship for that token (its step_seed
        is ``seed + len(generated)``), and the same ``sample_tokens`` /
        ``push_recent`` math as executor._sample_rows, so burst tokens are
        bit-identical to the per-tick baseline.

        Host stop rules are mirrored ON DEVICE, in the host's order (cap
        via the ``left`` budget counter, then eos, then the 5-run repeat
        heuristic), so the emitted count per slot always matches what the
        sequential client would have accepted.

        A looped stack's program (``cfg.loop_steps > 1``) carries one more
        value through the ticks: the passes taken by the tokens the burst
        emitted, summed on the device (``server_loop_exit_steps_total``).
        A family with expert layers that hold a share carries four
        (`MOE_COUNTERS`): the ticks' routed assignments, those to a held
        expert, the held experts some row chose and the held experts there
        were, summed over the burst's ticks and expert layers.
        With a rider lane (``self.rider_rows``) the program takes one more
        ARGUMENT, the rider (`_rider_args`), every tick carries the lane's
        rows through the layers (`_decode_span`), the head and the sampler
        read one more row (the chunk's row at which the prompt ends), and
        the rider's first token is carried too. A lane without a rider
        does the same work on rows that write nothing.

        Arguments and results are PACKED (`BURST_INTS`): the per-slot
        values arrive as one int32 and one float32 array, the rider as one
        int32 vector, and what the host reads leaves as one int32 vector
        beside the stacks: ``toks [N, S]`` flat, ``stop [S]``, the new
        lengths ``[S]``, then the pass count (looped) and the rider's
        token (lane). The ticks compute on the values and dtypes the
        separate arrays held."""
        cfg, spec = self.cfg, self.spec
        looped = cfg.loop_steps > 1
        routed = holds_expert_share(cfg)
        lane = self.rider_rows
        S = self.slots
        N = n_ticks
        from ..models.transformer import lm_head
        from ..ops.sampling import RECENT_WINDOW, push_recent, sample_tokens

        @partial(jax.jit, donate_argnums=engine_donation(3, 4))
        def burst_tick(params, ints, floats, k_all, v_all, *rider):
            (tok, lengths, alive, seeds, nvalid, run, left, eos_id,
             top_k) = ints[:len(BURST_INTS)]
            alive = alive != 0
            recent = ints[len(BURST_INTS):].T                  # [S, window]
            temp, top_p, rp = floats
            if lane:
                (packed,) = rider
                ints_, recent_, knobs_, ids_ = _rider_fields(RECENT_WINDOW)
                rider = dict(zip(RIDER_INTS, packed[ints_]))
                rider["recent"] = packed[recent_]
                rider.update(zip(BURST_FLOATS, jax.lax.bitcast_convert_type(
                    packed[knobs_], jnp.float32)))
                rider["ids"] = packed[ids_].reshape(N, lane)
                last = rider["len"] - 1        # the prompt's last row
                r_knobs = [jnp.concatenate([a, rider[name][None]])
                           for a, name in ((temp, "temp"), (top_p, "top_p"),
                                           (top_k, "top_k"), (rp, "rp"))]

            def tick(carry, i):
                (tok, lengths, alive, recent, nvalid, run, left,
                 stop, k_all, v_all, *more) = carry
                active = alive
                chunk = ()
                if lane:
                    at = i * lane + jnp.arange(lane, dtype=jnp.int32)
                    chunk = ({"ids": rider["ids"][i], "start": i * lane,
                              "valid": at < rider["len"],
                              "slot": rider["slot"],
                              "rows": min(N * lane, k_all.shape[2])},)
                h, k_all, v_all, steps = _decode_span(
                    cfg, spec, params, tok[:, None], lengths[:, None],
                    lengths, active, k_all, v_all, *chunk)
                with jax.named_scope("head"):
                    if lane:
                        # Flat rows: the slots', then the lane's; of those
                        # the head takes the one the prompt may end at.
                        h = jnp.concatenate([
                            jnp.where(active[:, None], h[0, :S], 0.0),
                            h[0, S + jnp.clip(last - i * lane, 0,
                                              lane - 1)][None]])[:, None]
                        steps = steps[0, :S, None]
                    else:
                        h = jnp.where(active[:, None, None], h, 0.0)
                    logits = lm_head(cfg, params, h,
                                     normed=looped)[:, 0]     # [S, V] fp32
                with jax.named_scope("sampler"):
                    keys = jax.vmap(jax.random.PRNGKey)(seeds + i)
                    if lane:
                        sampled = sample_tokens(
                            jnp.concatenate([keys, jax.random.PRNGKey(
                                rider["seed"])[None]]),
                            logits,
                            jnp.concatenate([recent, rider["recent"][None]]),
                            jnp.concatenate([nvalid, rider["nvalid"][None]]),
                            *r_knobs)
                        more[-1] = jnp.where(i == last // lane, sampled[S],
                                             more[-1])
                        sampled = sampled[:S]
                    else:
                        sampled = sample_tokens(
                            keys, logits, recent, nvalid, temp, top_p,
                            top_k, rp)
                # Host stop-rule mirror, in host order: the token is always
                # EMITTED (the host appends before checking eos/repeat);
                # stops only gate the NEXT tick.
                with jax.named_scope("stop_rules"):
                    eos_hit = active & (eos_id >= 0) & (sampled == eos_id)
                    run_next = jnp.where(sampled == tok, run + 1,
                                         jnp.int32(1))
                    run_next = jnp.where(active, run_next, run)
                    rep_hit = active & (run_next >= BURST_REPEAT_STOP)
                    left_next = jnp.where(active, left - 1, left)
                    rec2, nv2 = jax.vmap(push_recent)(recent, nvalid,
                                                      sampled)
                    recent = jnp.where(active[:, None], rec2, recent)
                    nvalid = jnp.where(active, nv2, nvalid)
                    lengths = jnp.where(active, lengths + 1, lengths)
                    first = stop == 0
                    stop = jnp.where(eos_hit & first, jnp.int32(1), stop)
                    stop = jnp.where(rep_hit & ~eos_hit & first,
                                     jnp.int32(2), stop)
                    alive = active & ~eos_hit & ~rep_hit & (left_next > 0)
                    tok = jnp.where(active, sampled, tok)
                    out_tok = jnp.where(active, sampled, jnp.int32(-1))
                    if looped:
                        more[0] = more[0] + jnp.sum(
                            jnp.where(active, steps[:, 0], 0))
                    if routed:      # `MOE_COUNTERS`, this tick's share
                        took = steps & active[None, :, None]
                        n_layers, _, n_held = steps.shape
                        live = active.any().astype(jnp.int32)
                        more[0] = more[0] + jnp.stack([
                            active.sum() * n_layers
                            * cfg.num_experts_per_tok,
                            took.sum(), took.any(1).sum(),
                            live * n_layers * n_held]).astype(jnp.int32)
                return (tok, lengths, alive, recent, nvalid, run_next,
                        left_next, stop, k_all, v_all, *more), out_tok

            stop0 = jnp.zeros((S,), jnp.int32)
            carry, toks = jax.lax.scan(
                tick,
                (tok, lengths, alive, recent, nvalid, run, left, stop0,
                 k_all, v_all, *([jnp.int32(0)] if looped else []),
                 *([jnp.zeros((len(MOE_COUNTERS),), jnp.int32)]
                   if routed else []),
                 *([jnp.int32(-1)] if lane else [])),
                jnp.arange(N, dtype=jnp.int32))
            (_, lengths, _, _, _, _, _, stop, k_all, v_all, *more) = carry
            return (jnp.concatenate([toks.reshape(-1), stop, lengths,
                                     *(m if m.ndim else m[None]
                                       for m in more)]),
                    k_all, v_all)

        return burst_tick

    def _get_burst_jit(self, n_ticks: int):
        fn = self._burst_jits.get(n_ticks)
        if fn is None:
            fn = self._burst_jits[n_ticks] = self._build_burst(n_ticks)
        return fn

    def _burst_prep(self, entries: Dict[str, dict], n_ticks: int):
        """Pack per-session burst specs into the burst program's two
        packed arguments (`BURST_INTS`), as host arrays: they go up where
        the program is called (`decode_burst`), and nothing writes to them
        after (a backend may alias a host buffer it is handed).

        entries[sid]: {token, seed, budget, eos (-1 = none), generated,
        temperature, top_p, top_k, repetition_penalty} — the stateless
        per-burst mirror of what the wire protocol ships every step, so
        failover needs no server-side sampler state."""
        from ..ops.sampling import RECENT_WINDOW, sampler_stages

        if not (self.spec.is_first and self.spec.is_last):
            raise RuntimeError(
                "burst decode requires the full model span (on-device "
                "sampling feeds tokens straight back into the embedding)")
        if n_ticks < 1:
            raise ValueError(f"burst of {n_ticks} ticks")
        S = self.slots
        # The round's two uploads, filled through views: a name's row of
        # the int32 array, the recent window's columns under them, the
        # three rows of the float32 array (`BURST_INTS`).
        ints = np.zeros((len(BURST_INTS) + RECENT_WINDOW, S), np.int32)
        (tok0, lengths, alive, seeds, nvalid, run0, left, eos,
         top_k) = ints[:len(BURST_INTS)]
        recent = ints[len(BURST_INTS):].T                      # [S, window]
        floats = np.ones((len(BURST_FLOATS), S), np.float32)
        temp, top_p, rp = floats
        lengths[:] = self.lengths    # a copy: the host bumps its own below
        eos[:] = -1
        temp[:] = 0.0
        rows: Dict[str, int] = {}
        for sid, e in entries.items():
            s = self._slot_of.get(sid)
            if s is None:
                raise KeyError(f"unknown session {sid} (prefill first)")
            budget = min(int(e["budget"]), n_ticks)
            if budget < 1:
                raise ValueError(f"session {sid}: burst budget must be >= 1")
            if int(self.lengths[s]) + budget > self.max_len:
                raise RuntimeError(
                    f"session {sid}: burst of {budget} past length "
                    f"{int(self.lengths[s])} exceeds max_len {self.max_len}")
            gen = tuple(int(t) for t in e["generated"])
            win = gen[-RECENT_WINDOW:]
            if win:
                recent[s, :len(win)] = win
            nvalid[s] = len(win)
            r = 0
            for t in reversed(gen):
                if t != gen[-1]:
                    break
                r += 1
            run0[s] = r
            tok0[s] = int(e["token"])
            seeds[s] = int(e["seed"])
            left[s] = budget
            eos[s] = int(e.get("eos", -1) if e.get("eos") is not None else -1)
            temp[s] = float(e["temperature"])
            top_p[s] = float(e["top_p"])
            top_k[s] = int(e["top_k"])
            rp[s] = float(e["repetition_penalty"])
            alive[s] = 1
            rows[sid] = s
        self._m_sampler.labels(stages=sampler_stages(
            temp, top_p, top_k, rp, self.cfg.vocab_size)).inc()
        return rows, (ints, floats)

    _BURST_STOPS = {0: None, 1: "eos", 2: "repeat"}

    def _burst_collect(self, rows: Dict[str, int], flat: np.ndarray,
                       n_ticks: int):
        """One burst's packed results (`_build_burst`), read to the host as
        ``flat`` (`burst_fetch`: the only host sync per burst, and ONE
        read), into the slot tables and the counters. Returns the sessions'
        results and the rider's first token (None: an engine without a
        lane). It writes ``lengths`` of its own rows only: a prompt that
        took another slot while the burst ran keeps what it wrote."""
        S = self.slots
        toks_np = flat[:n_ticks * S].reshape(n_ticks, S)
        stop_np, len_np = flat[n_ticks * S:(n_ticks + 2) * S].reshape(2, S)
        tail = flat[(n_ticks + 2) * S:]     # passes (looped), token (lane)
        if self.cfg.loop_steps > 1:
            self._m_exit_steps.inc(int(tail[0]))
        if holds_expert_share(self.cfg):
            for series, n in zip(self._m_moe, tail):
                series.inc(int(n))
        # Tick i began with every slot i rows on, those still emitting
        # active: a slot's emitted ticks are a prefix of the burst's.
        grown = np.zeros((self.slots,), np.int64)     # tokens a slot emitted
        held = list(rows.values())
        grown[held] = len_np[held] - self.lengths[held]
        tick = np.arange(len(toks_np))[:, None]
        self._count_attn_rows(self.lengths[None] + tick, tick < grown[None],
                              1, bool(self.rider_rows))
        out: Dict[str, dict] = {}
        total = 0
        for sid, s in rows.items():
            m = int(grown[s])
            emitted = [int(t) for t in toks_np[:m, s]]
            total += m
            out[sid] = {"tokens": emitted,
                        "stop": self._BURST_STOPS[int(stop_np[s])],
                        "cache_len": int(len_np[s])}
            self.lengths[s] = int(len_np[s])
        self._count_rows_held(held)
        self.burst_tokens += total
        self._m_burst_toks.inc(total)
        return out, (int(tail[-1]) if self.rider_rows else None)

    def can_ride(self, t: int, n_ticks: int) -> bool:
        """Whether a prompt of ``t`` rows fits the rider lane of ONE burst
        of ``n_ticks`` ticks: whole chunks inside the slot (`_append_rows`
        clamps a chunk that would end past ``max_len``)."""
        c = self.rider_rows
        return (bool(c) and 0 < t <= n_ticks * c
                and -(-t // c) * c <= self.max_len)

    def _rider_args(self, rider: Optional[dict], n_ticks: int) -> np.ndarray:
        """The burst program's rider argument, ONE int32 host vector
        (`RIDER_INTS`): the joining request's prompt length, slot, and what
        its first token is sampled with (the key and the knobs
        `executor._sample_rows` gives a prefill's token: seed, the recent
        window and its count, ``top_k``, and the bits of temperature,
        ``top_p`` and the repetition penalty), then its prompt ids as
        ``n_ticks`` chunks of `rider_rows`. None: a lane that carries
        nothing (``len`` 0: no row of it is written). No scalar goes up on
        its own (each would run an eager convert program on the device)."""
        from ..ops.sampling import RECENT_WINDOW

        r = rider or {"ids": (), "slot": 0, "seed": 0, "generated": (),
                      "temperature": 0.0, "top_p": 1.0, "top_k": 0,
                      "repetition_penalty": 1.0}
        win = tuple(int(t) for t in r["generated"])[-RECENT_WINDOW:]
        ints, recent, knobs, ids = _rider_fields(RECENT_WINDOW)
        packed = np.zeros((knobs.stop + n_ticks * self.rider_rows,),
                          np.int32)
        packed[ints] = (len(r["ids"]), r["slot"], r["seed"], len(win),
                        r["top_k"])
        packed[recent][:len(win)] = win
        packed[knobs] = np.asarray(
            [r["temperature"], r["top_p"], r["repetition_penalty"]],
            np.float32).view(np.int32)
        packed[ids][:len(r["ids"])] = r["ids"]
        return packed

    def decode_burst(self, entries: Dict[str, dict], n_ticks: int,
                     rider: Optional[dict] = None) -> Dict[str, dict]:
        """Run up to ``n_ticks`` decode ticks for every session in
        ``entries`` in ONE jitted dispatch. Returns {session_id: {tokens,
        stop, cache_len}} — ``tokens`` are the emitted ids (<= n_ticks;
        device-side eos/repeat/budget stops truncate), ``stop`` is
        None/"eos"/"repeat". Sessions join/leave only between bursts.

        ``rider`` (an engine with a rider lane; `can_ride`): the ONE request
        that joins during this burst, ``{session_id, ids, seed, generated,
        temperature, top_p, top_k, repetition_penalty}``. It gets a slot,
        its prompt's K/V rows are written by the burst's ticks, and its
        entry of the result is ``{token, cache_len}``: the first token,
        sampled on the device as a prefill's is on the host.

        The burst's three steps in turn (`burst_enqueue`, `burst_fetch`,
        `burst_collect`), for a caller that shares the engine with nobody:
        the adapter's round leader calls them itself and lets go of its
        lock for the middle one."""
        if not entries and rider is None:
            return {}
        flight = self.burst_enqueue(entries, n_ticks, rider)
        self.burst_fetch(flight)
        return self.burst_collect(flight)

    def burst_enqueue(self, entries: Dict[str, dict], n_ticks: int,
                      rider: Optional[dict] = None) -> "_Burst":
        """The first half of `decode_burst`: the arguments built, the
        rider's slot taken, the program ENQUEUED and the stacks it will
        return put in place of those it was given, so that whatever is
        enqueued next (another session's prompt) runs behind it on the
        device's one in-order queue. Nothing here waits for the device, so
        the adapter's lock is held for host work only. Returns the burst in
        flight, for `burst_fetch` and `burst_collect`."""
        prof = _get_profiler()
        n = len(entries)
        extra = []
        with prof.phase("burst_build", sessions=n) as built:
            rows, args = self._burst_prep(entries, n_ticks)
            fn = self._get_burst_jit(n_ticks)
            if rider is not None:
                if not self.can_ride(len(rider["ids"]), n_ticks):
                    raise ValueError(
                        f"a prompt of {len(rider['ids'])} rows does not ride "
                        f"a burst of {n_ticks} ticks on this engine")
                rider = {**rider, "slot": self._alloc(rider["session_id"])}
            if self.rider_rows:
                extra = [self._rider_args(rider, n_ticks)]
        # Profiled: a fenced dispatch. The device phase is dispatch-to-ready
        # (`burst_fetch` closes it) and the bubble gauge charges idle time
        # between successive readies.
        try:
            with contextlib.ExitStack() as opened:
                ran = opened.enter_context(prof.device_phase(sessions=n))
                with prof.phase("dispatch", sessions=n) as issued:
                    packed, self.k, self.v = fn(
                        self.params, *args, self.k, self.v, *extra)
                fence = opened.pop_all()      # stays open: `burst_fetch`
        except Exception:
            if rider is not None:
                self._recover_slot(rider["session_id"], rider["slot"])
            raise
        ahead = self._dispatched()
        self._m_transfers.labels(dir="up").inc(len(args) + len(extra))
        self.decode_steps += 1
        self.burst_dispatches += 1
        self._m_burst_disp.inc()
        self._m_burst_ticks.observe(n_ticks)
        return _Burst(rows, packed, n_ticks, rider, ahead, fence,
                      (built, issued, ran))

    def burst_fetch(self, flight: "_Burst") -> None:
        """The one step of a burst that BLOCKS, and the one its caller runs
        with the adapter's lock free: the packed results read to the host
        (``flight.flat``), which takes as long as the burst's ticks and
        whatever lay ahead of them on the device. It touches no slot table.
        Profiled, the two fences first, inside phase ``device``:
        ``device_queued`` is the wait for a prompt's programs enqueued
        AHEAD of the burst (on one in-order queue their end is the burst's
        start; the results complete in order, so no wait is added). A
        failure is kept for `burst_collect`, which recovers under the lock
        what this step may not touch."""
        prof = _get_profiler()
        n = len(flight.rows)
        try:
            with flight.fence:
                with prof.phase("device_queued", sessions=n) as queued:
                    if prof.enabled:
                        jax.block_until_ready(flight.ahead)  # None: no wait
                if prof.enabled:
                    jax.block_until_ready(flight.packed)
            with prof.phase("readback", sessions=n) as read:
                flight.flat = np.asarray(flight.packed)
            flight.timed += (queued, read)
        except Exception as exc:
            flight.error = exc

    def burst_collect(self, flight: "_Burst") -> Dict[str, dict]:
        """The second half of `decode_burst`, under the adapter's lock
        again: what `burst_fetch` read goes into the slot tables and the
        counters, the rider's length is set, and the result is what
        `decode_burst` returns. A failure of the fetch is raised here, the
        rider's slot recovered first (`_recover_slot`)."""
        prof = _get_profiler()
        rider = flight.rider
        try:
            if flight.error is not None:
                raise flight.error
            gone = [sid for sid, s in flight.rows.items()
                    if self._slot_of.get(sid) != s]
            if gone:        # `_recover_slot` took the stacks meanwhile
                raise RuntimeError(
                    f"sessions {gone} lost their slots while their burst "
                    "ran (a failed prefill rebuilt the stacks)")
            self._m_transfers.labels(dir="down").inc()
            with prof.phase("readback", sessions=len(flight.rows)) as read:
                res, token = self._burst_collect(
                    flight.rows, flight.flat, flight.n_ticks)
                if rider is not None:
                    t = len(rider["ids"])
                    self.lengths[rider["slot"]] = t
                    res[rider["session_id"]] = {"token": token,
                                                "cache_len": t}
        except Exception:
            if rider is not None:
                self._recover_slot(rider["session_id"], rider["slot"])
            raise
        # What this burst's wall time was made of, where the profiler has
        # just measured it (``queued`` + ``device``: enqueue returned ->
        # results ready; ``readback``: the read and the tables, not the
        # wait for the lock between them).
        self.burst_parts = None
        if prof.enabled:
            built, issued, ran, queued, fetched = flight.timed
            self.burst_parts = dict(zip(STALL_PARTS, (
                built.seconds, issued.seconds, queued.seconds,
                ran.seconds - issued.seconds - queued.seconds,
                fetched.seconds + read.seconds)))
        return res

    # ------------------------------------------------------------------

    def logits(self, hidden: jnp.ndarray) -> jnp.ndarray:
        """Final-stage head over [1, T, D] -> [1, T, V] (fp32)."""
        from ..models.transformer import lm_head

        return lm_head(self.cfg, self.params, hidden,
                       normed=self.cfg.loop_steps > 1)


# ---------------------------------------------------------------------------
# Transport adapter: serve the batched engine behind the StageRequest
# protocol, coalescing CONCURRENT decode requests into one step.
# ---------------------------------------------------------------------------

class _Round:
    """One coalescing round: requests that arrive while it is open share a
    single batched step. Its leader (whoever created it) holds it open for
    the sessions the last round of its key has just answered, or for
    ``window_s`` where there are none (`BatchingStageAdapter._close_round`).
    Rounds are keyed by step width T (seq_len), so a round's sessions
    always share one compiled step: T=1 plain decode, T=K+1 speculative
    verify."""

    __slots__ = ("reqs", "outs", "err", "bad", "lengths", "spec", "event",
                 "closed", "t_open", "t_exec", "t_done", "rider", "rejoined",
                 "back", "flight")

    def __init__(self):
        self.reqs: Dict[str, Any] = {}
        self.outs: Dict[str, jnp.ndarray] = {}
        self.lengths: Dict[str, int] = {}
        self.spec: Dict[str, Tuple[Tuple[int, ...], int]] = {}  # verified rows
        self.err: Optional[Exception] = None      # whole-round failure
        self.bad: Dict[str, str] = {}             # per-session exclusions
        self.event = threading.Event()
        self.closed = False
        # opened: its first session is in (under a step in flight: that
        # step is collected, `_close_round`)
        self.t_open = time.monotonic()
        self.t_exec = 0.0    # monotonic instant the round's step started
        self.t_done = 0.0    # ... and the instant its results were read
        self.rider = None    # a burst round's ONE joining request (prefill)
        self.flight = None   # a burst round's burst, enqueue -> collect
        # Holds a session that the last round of its key answered (`_join`):
        # only then is the time since that round a PERIOD of the machine.
        self.rejoined = False
        # Sessions whose reply says they ask for the next round
        # (`_answered`): their responses carry ``t_done``.
        self.back: frozenset = frozenset()


class _SlotArenaView:
    """KVArena-shaped facade over the slot tables (tokens_left only).

    Takes the adapter's lock (heartbeat/info threads call this while handler
    threads mutate the slot tables under the same lock — an unlocked dict
    iteration there can raise mid-resize), but with a BOUNDED wait: the
    adapter holds its lock across whole prefill dispatches (including
    compiles), and blocking the heartbeat thread past the registry TTL would
    expire a healthy server. A busy adapter returns the last known value.
    Since a round's leader lets go of the lock while its step runs on the
    device the wait rarely times out: what is left to outlast it is a
    program that compiles under the lock."""

    def __init__(self, inner: BatchedStageExecutor, lock: threading.Lock):
        self._inner = inner
        self._lock = lock
        self._last = inner.slots * inner.max_len

    def tokens_left(self) -> int:
        if self._lock.acquire(timeout=0.5):
            try:
                self._last = self._inner.tokens_left()
            finally:
                self._lock.release()
        return self._last


class BatchingStageAdapter:
    """Drop-in StageExecutor replacement for transports: plain
    prefill/decode AND speculative-verify requests ride the batched engine,
    with concurrent decode calls coalesced — the FIRST arrival leads its
    width's round, holds it open until the sessions that the last round of
    that width answered are back (at most `REJOIN_SHARE` of that round's
    wall time after their reply; ``window_s`` where nobody is on the way:
    `_close_round`), runs ONE `decode_batch`, and every waiter picks up its
    own row. The leader holds the adapter's lock to BUILD and ENQUEUE its
    round's step and to COLLECT the results, and lets go of it while the
    device runs the step: a prompt of another session takes a slot and
    enqueues its programs meanwhile, BEHIND the running step on the device's
    in-order queue and not ahead of the next one. What the held lock used to
    guard is said by ``_flying``, the ONE step in flight and its sessions:
    no other round closes, and nothing frees, gives anew, rewinds or
    validates against the slot of a session in it, before it is collected
    (`_landed_locked`). Draft steps
    (width K+1) coalesce with each other; the final stage verifies each
    row and rewinds its slot past the rejected tail before releasing
    waiters. Beam/training/replay/sub-span requests are refused with a
    retryable stage error so clients route them to a per-session replica
    (the batched path is the common-case fast lane, not the whole protocol
    — see module docstring)."""

    engine = "batched"   # registry capability tag (ServerRecord.engine)

    def __init__(self, inner: BatchedStageExecutor, *,
                 window_s: float = 0.003, peer_id: str = "batched",
                 step_timeout: float = 120.0):
        self.inner = inner
        self.spec = inner.spec
        self.cfg = inner.cfg
        self.window_s = window_s
        self.peer_id = peer_id
        self.step_timeout = step_timeout
        self.requests_served = 0
        self._lock = threading.Lock()
        # What a round's leader waits on while its round is open: a join, a
        # drop and a prefill of a session it waits for notify it; and what
        # whoever needs a step in flight to be over waits on: its collect
        # notifies.
        self._cond = threading.Condition(self._lock)
        # The step in flight, (round key, its sessions): enqueued, the lock
        # let go of, not collected yet. None between a collect and the next
        # enqueue. At most one at a time, whatever the rounds' keys.
        self._flying: Optional[Tuple[Any, frozenset]] = None
        # Open coalescing rounds, keyed by step width T (classic decode /
        # speculative verify) or ('burst', N) (burst rounds never share a
        # compiled program with single-tick rounds).
        self._rounds: Dict[Any, _Round] = {}
        # Per round key: when its last round's results were read, and that
        # round's wall time (t_done - t_exec).
        self._last_round: Dict[Any, Tuple[float, float]] = {}
        # Per session holding a slot: (round key, instant) of its last
        # reply, kept while that reply says the session asks again (a burst
        # or step that did not end its request; a prefill's first token,
        # key None: it may ask for any). Gone once it joins a round, is
        # dropped or sends a new prompt, or its slot went (`_returning`).
        self._replied: Dict[str, Tuple[Any, float]] = {}
        # Ticks of the burst rounds a joining request may ride (`warmup`
        # sets it to the burst it compiles; 0: prefills are programs).
        self.burst_ticks = 0
        # Telemetry (global registry; strict no-op unless enabled). Step
        # latency itself is observed at the serving boundary (LocalTransport
        # / TcpStageServer) — the adapter owns the batching-specific signals.
        self._m_queue_wait = _tm.get("server_queue_wait_seconds")
        self._m_fill = _tm.get("server_batch_fill_sessions")
        self._m_held = _tm.get("server_batch_slots_held")
        # a burst request whose ids came as a device array (`_host_ids`)
        self._m_ids_read = _tm.get("server_burst_transfers_total").labels(
            dir="down")
        self._m_round = _tm.get("server_decode_round_seconds")
        self._m_enqueued = _tm.get("server_prefill_enqueued_total")
        self._m_behind = _tm.get("server_round_behind_prefill_seconds")
        self._m_closed = _tm.get("server_round_closed_total")
        self._m_rejoin = _tm.get("server_round_rejoin_seconds")
        self._m_period = _tm.get("server_round_period_seconds")
        self._m_back = _tm.get("server_round_back_seconds")
        self._m_hold = _tm.get("server_round_hold_seconds")
        self._m_hold_prefill = _tm.get("server_round_hold_prefill_seconds")
        self._m_request_leg = _tm.get("server_request_leg_seconds")
        self._m_stalls = _tm.get("server_round_stalls_total")
        self._m_stall_s = _tm.get("server_round_stall_seconds_total")
        # The flight recorder (on under --telemetry), and the collections
        # the garbage collector had counted when the last round ended
        # while it was on (`_gc_counts`).
        self._events = _ev.get_recorder()
        self._gc_seen: Optional[List[int]] = None
        # TcpStageServer's info verb + heartbeat read `.arena.tokens_left()`
        # on whatever executor they serve; point that surface at the slot
        # tables so a batched server advertises real admission headroom.
        self.arena = _SlotArenaView(inner, self._lock)

    def warmup(self, speculative_k: int = 0, burst: int = 0) -> None:
        """Pre-compile the engine's programs (prefill at the smallest
        bucket + the batched decode step) so the first real session doesn't
        pay compile latency — the serve-mode analogue of StageExecutor.warmup.

        ``speculative_k > 0`` additionally warms every speculative decode
        width 2..K+1 — the n-gram drafter returns VARIABLE-length drafts
        (whatever follow it matched, often < K), so any unwarmed width
        would compile inside the round leader's lock hold on first use,
        stalling every concurrent round and the heartbeat's arena view for
        the compile duration."""
        first = self.spec.is_first
        d = self.cfg.hidden_size
        x = (np.zeros((1, 4), np.int32) if first
             else np.zeros((1, 4, d), np.float32))
        if self.cfg.eva_window:
            # A windowed family's prompts run through a bounded set of
            # shapes (`window_shapes`): build them all now, so that no
            # prompt length compiles a prefill program while serving.
            for n in self.inner.window_shapes():
                self.inner.prefill("__warmup__", np.zeros((1, n), np.int32))
        self.inner.prefill("__warmup__", x)
        widths = [1] + list(range(2, speculative_k + 2))
        for t in widths:
            step = (np.zeros((1, t), np.int32) if first
                    else np.zeros((1, t, d), np.float32))
            self.inner.rewind("__warmup__", 4)
            out = self.inner.decode_batch({"__warmup__": jnp.asarray(step)})
            if self.spec.is_last and t > 1:
                # The verify path's head projection over [n, K+1, D] is its
                # own program shape — warm it too, or the first speculative
                # round compiles it inside the leader's lock.
                self.inner.logits(out["__warmup__"])
        if burst > 0 and self.spec.is_first and self.spec.is_last:
            # The burst scan is by far the largest program (N unrolled-ish
            # ticks under a scan + head + sampler); compiling it inside the
            # first real round's lock hold would stall every session AND
            # the heartbeat's arena view for the whole compile.
            self.inner.rewind("__warmup__", 4)
            self.inner.decode_burst(
                {"__warmup__": {"token": 1, "seed": 0, "budget": burst,
                                "eos": None, "generated": (1,),
                                "temperature": 0.0, "top_p": 1.0,
                                "top_k": 0, "repetition_penalty": 1.0}},
                burst)
            if self.inner.rider_rows:
                self.burst_ticks = burst
        self.inner.end_session("__warmup__")

    # -- protocol ----------------------------------------------------------

    def forward(self, req) -> "StageResponse":
        from .executor import StageExecutionError

        self.requests_served += 1
        if (req.train or req.hypo_ids is not None or req.num_logprobs
                or req.prompts is not None
                or req.start_from_position not in (None, req.cur_len)):
            _ev.emit("task_rejected", session_id=req.session_id,
                     pool="batched", reason="unsupported request kind")
            raise StageExecutionError(
                "batched peer serves plain prefill/decode, speculative "
                "verify, and replay only (route beam/training/deep-prompt "
                "requests to a per-session replica)")
        if req.start_block is not None and (
                req.start_block != self.spec.start
                or (req.end_block or self.spec.end) != self.spec.end):
            _ev.emit("task_rejected", session_id=req.session_id,
                     pool="batched", reason="sub-span request")
            raise StageExecutionError(
                "batched peer serves its full span only")
        if req.is_prefill:
            return self._prefill(req)
        if self.cfg.eva_window and req.seq_len != 1:
            _ev.emit("task_rejected", session_id=req.session_id,
                     pool="batched", reason="rows across a window's edge")
            raise StageExecutionError(
                f"a step of {req.seq_len} rows on a family whose older rows "
                "are summaries (it may cross a window's edge): speculative "
                "verify is not served, and a journal replay rebuilds the "
                "slot through prefill")
        if req.burst_len:
            if not (self.spec.is_first and self.spec.is_last):
                _ev.emit("task_rejected", session_id=req.session_id,
                         pool="batched", reason="burst without full span")
                raise StageExecutionError(
                    "burst decode requires a full-span peer (on-device "
                    "sampling feeds tokens back into the embedding)")
            if req.seq_len != 1 or req.draft_tokens is not None:
                raise StageExecutionError(
                    "a burst step carries exactly the one last accepted "
                    "token")
            return self._decode_burst(req)
        if req.draft_tokens is not None:
            if req.seq_len != len(req.draft_tokens) + 1:
                raise StageExecutionError(
                    f"speculative step carries {req.seq_len} positions for "
                    f"{len(req.draft_tokens)} drafts (want K+1)")
        elif req.seq_len != 1 and not req.is_replay:
            # Replay chunks are plain multi-token KV rebuilds (the client
            # discards the sampled token) — exactly decode_batch's T>1
            # shape, so a replacement batched peer can adopt a failed-over
            # burst session without per-session machinery.
            raise StageExecutionError(
                "batched decode is single-token (chunked continuation "
                "belongs to the per-session executor)")
        return self._decode(req)

    def drop_session(self, session_id: str) -> None:
        with self._lock:
            self._landed_locked(session_id)
            self.inner.end_session(session_id)
            self._forget_locked(session_id)

    # -- when a round closes -------------------------------------------------

    def _forget_locked(self, sid: str) -> None:
        """``sid`` is not on its way back to a round (dropped, or it sent a
        new prompt). Wakes a leader that may be waiting for it."""
        if self._replied.pop(sid, None) is not None:
            self._cond.notify_all()

    def _replied_locked(self, sid: str, key, t: float) -> None:
        """``sid``'s reply left at ``t`` and says it asks for a round of
        ``key`` next (None: of any width)."""
        self._replied[sid] = (key, t)

    def _landed_locked(self, sid: str) -> None:
        """Caller holds the lock and is about to touch ``sid``'s slot: free
        it, give it anew, rewind it, or validate a request against its
        length. Waits (the lock released) while the step in flight has
        ``sid`` in it: its slot's rows are being written and its length is
        the collect's to set."""
        while self._flying is not None and sid in self._flying[1]:
            self._cond.wait()

    def _enqueues_locked(self) -> None:
        """Caller holds the lock and enqueues a prompt's programs next:
        BEHIND the step the device is running, or into the gap between two
        (``server_prefill_enqueued_total{during}``: the share of prompts
        that the free lock engages for)."""
        self._m_enqueued.labels(
            during="gap" if self._flying is None else "burst").inc()

    def _in_a_hold_locked(self) -> float:
        """Now, where a round of any key is open and not closed, and no
        step is in flight: its leader's hold pays for what the caller does
        under the lock (a prefill's program:
        ``server_round_hold_prefill_seconds``). Else 0.0: a round that was
        opened under a running step waits for the collect whatever the
        caller does."""
        return (time.monotonic() if self._rounds and self._flying is None
                else 0.0)

    def _join(self, r: _Round, req, key) -> None:
        """Caller holds the lock and has put ``req`` into ``r``, the open
        round of ``key``: whoever waits for its session is woken. The delay
        is observed where the last round of that key is the one that
        answered the session (in between only a prefill or another width's
        round held the lock): the whole way back, and the part of it from
        the request's frame read off the socket (``req.t_recv``, where the
        serving boundary gave one). Such a join makes ``r`` a round whose
        distance from the last is a period (`_step_starts`)."""
        rec = self._replied.pop(req.session_id, None)
        if rec is None:
            return
        last = self._last_round.get(key)
        if last and rec == (key, last[0]):
            now = time.monotonic()
            self._m_rejoin.observe(now - rec[1])
            if req.t_recv:
                self._m_request_leg.observe(now - req.t_recv)
            r.rejoined = True
        self._cond.notify_all()

    def _returning(self, key, now: float, bound: float) -> Dict[str, float]:
        """Caller holds the lock. The sessions an open round of ``key`` is
        held for, each with the instant it stops being waited for: it holds
        a slot, its last reply says it asks again (`_replied`) and left
        less than ``bound`` ago, and it has not joined (a join takes the
        record)."""
        out = {}
        for s_id, (k, t) in list(self._replied.items()):
            if s_id not in self.inner._slot_of:
                del self._replied[s_id]      # evicted: the record goes too
            elif k in (None, key) and t + bound > now:
                out[s_id] = t + bound
        return out

    def _close_round(self, r: _Round, key, sid: str) -> None:
        """The ONE place a round decides to close (its leader, holding the
        lock; waits release it, so joins and prefills go on). A round that
        was opened while a step is in flight (a session whose first token
        came meanwhile; a rider) first waits for that step's collect, open
        to joins all the while, and counts as opened then: the sessions
        that step answered are waited for as after any round, and no round
        runs with a rider alone beside a running one. The round
        stays open while a session is on its way back (`_returning`) and
        closes the moment the last of them is in: no sleep after it. The
        bound on that wait is the engine's own measurement: `REJOIN_SHARE`
        of the wall time of the last round of this key, never under
        ``window_s``; a session that does not return costs one round that
        much, once (its reply is then older than the bound). Where nobody
        is on the way when the leader gets here (one session in flight, a
        key that has not run yet) the round is open for ``window_s``, as
        it always was. Counted by what closed it
        (``server_round_closed_total{by}``)."""
        with _get_profiler().span("round_window", session=sid):
            if self._flying is not None:
                while self._flying is not None:
                    self._cond.wait()
                r.t_open = time.monotonic()
            now = time.monotonic()
            last = self._last_round.get(key)
            bound = (max(self.window_s, REJOIN_SHARE * last[1])
                     if last else 0.0)
            back = self._returning(key, now, bound)
            by = "joined" if back else "window"
            if not back:
                until = now + self.window_s
                while now < until:          # a notify cuts a wait short
                    self._cond.wait(until - now)
                    now = time.monotonic()
            while back:
                self._cond.wait(max(back.values()) - now)
                now = time.monotonic()
                still = self._returning(key, now, bound)
                if any(s in self._replied for s in back if s not in still):
                    by = "bound"       # somebody's time ran out, still away
                back = still
            # A round of ANOTHER key may have gone up during those waits.
            while self._flying is not None:
                self._cond.wait()
        self._m_closed.labels(by=by).inc()
        r.closed = True
        if self._rounds.get(key) is r:
            del self._rounds[key]

    def _step_starts(self, r: _Round, key) -> None:
        """Caller holds the lock and runs the step of round ``r`` next. The
        round's hold (opened -> now: `_close_round` with its lock waits,
        and the re-validation) is observed for every round; where the round
        holds a session that the LAST round of ``key`` answered, so are the
        period of the round machine (that round's start -> now) and the way
        back (its results on the host -> this round opened): round by
        round, period = that round's wall time + back + hold."""
        r.t_exec = time.monotonic()
        self._m_hold.observe(r.t_exec - r.t_open)
        last = self._last_round.get(key)
        if r.rejoined and last:
            t_done, wall = last
            self._m_period.observe(r.t_exec - (t_done - wall))
            self._m_back.observe(r.t_open - t_done)

    def _answered(self, r: _Round, key, back, parts=None) -> None:
        """Caller holds the lock; the step of round ``r`` has run and its
        results are on the host: time it, and note which sessions (``back``)
        will ask for the next round of ``key``. ``parts``: what the engine
        measured of the step (`BatchedStageExecutor.burst_parts`). A round
        whose program the engine found behind a prompt's
        (``behind_prefill``) is timed a second time, in a series of those
        rounds alone: the clear rounds are the difference of the two."""
        r.t_done = time.monotonic()
        wall = r.t_done - r.t_exec
        self._m_round.observe(wall)
        behind = self.inner.behind_prefill
        if behind:
            self._m_behind.observe(wall)
        last = self._last_round.get(key)
        if last and wall > STALL_FACTOR * last[1]:
            self._stalled(r, key, wall, last[1], parts, behind)
        if self._events.enabled:
            self._gc_seen = _gc_counts()
        self._last_round[key] = (r.t_done, wall)
        r.back = frozenset(back)
        for s_id in back:
            self._replied_locked(s_id, key, r.t_done)

    def _stalled(self, r: _Round, key, wall: float, last_wall: float,
                 parts, behind: bool) -> None:
        """Round ``r`` took over `STALL_FACTOR` x the last round of ``key``:
        count it, add its seconds by part (``other``: what no phase of the
        profiler covers; all of it with the profiler off) and leave ONE
        event that says what it was made of. ``behind``: its program was
        enqueued behind a prompt's, so the stall is the device's queue (a
        long prompt ahead of a short round) and no fault."""
        by_part = {p: (parts or {}).get(p, 0.0) for p in STALL_PARTS}
        by_part["other"] = max(0.0, wall - sum(by_part.values()))
        self._m_stalls.labels(behind_prefill=str(behind).lower()).inc()
        for part, seconds in by_part.items():
            self._m_stall_s.labels(part=part).inc(seconds)
        burst = isinstance(key, tuple)
        self._events.emit(
            "round_stall", wall_s=round(wall, 6),
            last_wall_s=round(last_wall, 6), sessions=len(r.reqs),
            ticks=key[1] if burst else 1, rider=r.rider is not None,
            behind_prefill=behind,
            gc_collections=(None if self._gc_seen is None else [
                now - was for now, was in zip(_gc_counts(), self._gc_seen)]),
            **{p + "_s": round(s, 6) for p, s in by_part.items()})

    @staticmethod
    def _stamped(r: _Round, resp):
        """``resp`` answers a session of round ``r``: where it says the
        session asks for the next round, it carries the instant the round's
        results were on the host, and the serving boundary observes the
        reply's way out from it (``server_reply_leg_seconds``)."""
        if resp.session_id in r.back:
            resp.t_done = r.t_done
        return resp

    # -- phases ------------------------------------------------------------

    def _respond(self, req, hidden_row, cache_len: int):
        from .executor import _sample_last
        from .messages import StageResponse

        if self.spec.is_last:
            # the head over the ONE row the token is sampled from: a
            # 14000-row prompt's other rows are 1.1 GB of float32 logits
            # (and as much again of normed rows) that nothing reads
            logits = self.inner.logits(hidden_row[:, -1:])
            token = _sample_last(logits, 1, req)
            return StageResponse(session_id=req.session_id, token_id=token,
                                 cache_len=cache_len)
        return StageResponse(session_id=req.session_id, hidden=hidden_row,
                             cache_len=cache_len)

    def _prefill(self, req):
        from .executor import StageExecutionError

        prof = _get_profiler()
        sid = req.session_id
        if self._rides(req):
            return self._prefill_riding(req)
        # A request's life up to its first token, as three phases: the wait
        # for the lock (a round leader holds it to build and enqueue its
        # step and to collect the results, NOT while the device runs it:
        # the wait is for a build, a collect or another prompt, or, for a
        # session that is in the step in flight itself, for that step's
        # collect), the prefill under it, the first token after it (and
        # inside that one, profiled, the prompt's programs finishing).
        with prof.phase("prefill_wait", session=sid):
            self._lock.acquire()  # slot tables + cache arrays: shared state
            self._landed_locked(sid)
        t_held = self._in_a_hold_locked()
        try:
            self._enqueues_locked()
            self._forget_locked(sid)   # a new prompt: not on its way back
            with prof.phase("prefill", session=sid):
                try:
                    h = self.inner.prefill(sid, req.hidden,
                                           prefix_len=req.prefix_len)
                except StageExecutionError:
                    raise
                except Exception as exc:
                    # Same taxonomy as decode's whole-round failures: the
                    # engine recovered its slot/caches specifically so the
                    # request is retryable — a raw XlaRuntimeError would
                    # cross the wire as a kind-less error outside the
                    # client's failover taxonomy and crash the generation
                    # instead of re-routing it.
                    raise StageExecutionError(str(exc)) from exc
                cache_len = int(self.inner.lengths[self.inner.slot(sid)])
        finally:
            if t_held:
                self._m_hold_prefill.observe(time.monotonic() - t_held)
            self._lock.release()
        if not self.spec.is_last:
            resp = self._respond(req, h, cache_len)
        else:
            with prof.phase("first_token", session=sid):
                # Profiled: the prompt's own programs first, the head and
                # the sampler's (behind whatever burst came meanwhile) after.
                with prof.phase("prefill_ready", session=sid):
                    if prof.enabled:
                        jax.block_until_ready(h)
                resp = self._respond(req, h, cache_len)
        # The session asks for a round from now on: on record if the lock
        # is free (a leader that is waiting then waits for this one too; one
        # whose round opens under a running step finds the record at that
        # step's collect, unless the session has joined by then). Busy
        # means a build, a collect or a prompt, a few ms: whoever asks then
        # joins the open round or the next anyway, and a first token never
        # waits for the lock.
        if self._lock.acquire(blocking=False):
            try:
                self._replied_locked(sid, None, time.monotonic())
            finally:
                self._lock.release()
        return resp

    def _rides(self, req) -> bool:
        """Whether this prefill joins a burst round as its rider instead of
        running the prefill program: the engine has a lane, the prompt (ids,
        whole, no stored prefix to copy) fits one burst of it, and ANOTHER
        session holds a slot. A program between two rounds is paid by every
        session that is decoding, in the gap it happens to fall into; on an
        engine nobody else is using it is the shorter way to a first token
        (a few tens of ms against a whole burst)."""
        held = self.inner._slot_of
        return (self.burst_ticks > 0
                and np.ndim(req.hidden) == 2
                and self.inner.can_ride(req.seq_len, self.burst_ticks)
                and not (self.inner.prefix_store is not None
                         and req.prefix_len > 0)
                and len(held) > (req.session_id in held))

    def _prefill_riding(self, req):
        """A prefill as the rider of the next burst round (`_burst_round`).
        Its three phases: ``prefill_wait`` from entry until that round's
        step starts (a step in flight, the lane if another request has it,
        the window), ``prefill`` the step from its enqueue to its collect,
        which writes the prompt's rows and samples the token (the lock is
        free while the device runs it), ``first_token`` from the
        results on the host to the response (``prefill_ready`` inside it
        is 0: the prompt has no program of its own to wait for)."""
        from .messages import StageResponse

        prof = _get_profiler()
        t0 = time.monotonic()
        r = self._burst_round(req, self.burst_ticks, rider=True)
        out = r.outs[req.session_id]
        prof.observe("prefill_wait", r.t_exec - t0)
        prof.observe("prefill", r.t_done - r.t_exec)
        prof.observe("first_token", time.monotonic() - r.t_done)
        prof.observe("prefill_ready", 0.0)
        return self._stamped(r, StageResponse(
            session_id=req.session_id, token_id=out["token"],
            cache_len=out["cache_len"]))

    def _validate_rider(self, r: "_Round", n: int) -> Optional[str]:
        """Admission of a round's rider (caller holds the lock)."""
        req = r.rider
        sid = req.session_id
        if sid in r.reqs:
            return f"session {sid}: prefill concurrent with its own decode"
        if not self.inner.can_ride(req.seq_len, n):
            return (f"session {sid}: a prompt of {req.seq_len} rows does "
                    f"not ride a burst of {n} ticks")
        if not self.inner._free and sid not in self.inner._slot_of:
            return f"all {self.inner.slots} session slots in use"
        return None

    def _validate(self, req) -> Optional[str]:
        """Per-session admission (caller holds the lock). Returns a refusal
        reason or None. A bad session must never poison its round-mates."""
        s = self.inner.slot(req.session_id)
        if s is None:
            return (f"session {req.session_id}: decode without a slot "
                    "(prefill first; replay-rebuild is per-session only)")
        cur = int(self.inner.lengths[s])
        spos = req.start_from_position
        if spos is not None and spos != cur:
            # Speculative rollback: the previous round's rejected overhang
            # is still in the slot; shrink the valid prefix before this
            # round appends (petals start_from_position semantics —
            # forward() already pinned spos == req.cur_len).
            if spos > cur:
                return (f"session {req.session_id}: rewind to {spos} beyond "
                        f"cache {cur}")
            self.inner.rewind(req.session_id, spos)
            cur = spos
        if cur + req.seq_len > self.inner.max_len:
            return (f"session {req.session_id}: {req.seq_len} tokens past "
                    f"{cur} exceeds max_len {self.inner.max_len}")
        if req.cur_len != cur:
            # The per-session executor warns and trusts itself
            # (executor.py past-len mismatch); the batched path REFUSES: the
            # main cause here is a retry after a follower timeout whose step
            # actually advanced — continuing would silently desync. Refusal
            # is retryable, so the client fails over to a per-session
            # replica and replays.
            return (f"session {req.session_id}: cur_len {req.cur_len} != "
                    f"server {cur} (stale retry?)")
        return None

    def _round(self, req, key, validate, enqueue, collect,
               rider: bool = False) -> _Round:
        """Take ``req`` through one round of ``key`` (a step's width T, or
        ``("burst", N)``) as its leader (whoever CREATES it) or a follower,
        and return the round once its step has run; raises what the round
        or this session failed with. The ONE leader/follower machine. Its
        caller states ``validate(rq)``, a member's admission under the lock
        (a refusal reason or None; the leader asks again once the round has
        closed: a session may have been dropped since it joined, and an
        exclusion fails ONLY its own waiter), and the step's two halves,
        both under the lock: ``enqueue(r, good, riding)``, the engine's
        call on the admitted members and the rider's entry (or None), which
        returns what the leader calls with the lock FREE and blocks in
        until the results are on the host (None: nothing to wait for), and
        ``collect(r, good, riding)``, the results into ``r.outs``:
        ``(back, parts)``, what `_answered` takes. In between the step is
        on record as the one in flight (``_flying``). ``rider``: ``req`` is
        a PREFILL that joins a burst round as its rider (`_rides`); a round
        carries one, a second waits for that round to run and tries the
        next."""
        from .executor import StageExecutionError

        sid = req.session_id
        t_join = time.monotonic()
        while True:
            with self._lock:
                # a retry of a session in flight waits for the lengths the
                # collect writes (and is then refused, as it always was)
                self._landed_locked(sid)
                reason = None if rider else validate(req)
                if reason is not None:
                    raise StageExecutionError(reason)
                r = self._rounds.get(key)
                leader = r is None or r.closed
                if leader:
                    r = self._rounds[key] = _Round()
                if not rider:
                    if sid in r.reqs:
                        raise StageExecutionError(
                            f"session {sid}: concurrent decode for one "
                            "session")
                    r.reqs[sid] = req
                    self._join(r, req, key)
                    break
                if r.rider is None:
                    r.rider = req
                    self._forget_locked(sid)
                    break
            if not r.event.wait(self.step_timeout):   # the lane is taken
                raise StageExecutionError("batched step timed out")
        if leader:
            # The whole leader path runs under try/finally: an unexpected
            # exception anywhere (not just inside the engine's call) must
            # still release the followers, else they block for step_timeout.
            try:
                self._lead(r, key, sid, validate, enqueue, collect)
            except Exception as exc:  # whole-round failure
                r.err = exc
                with self._lock:  # a dead round must not accept joiners
                    r.closed = True
                    if self._rounds.get(key) is r:
                        del self._rounds[key]
            finally:
                r.event.set()
        elif not r.event.wait(self.step_timeout):
            raise StageExecutionError("batched step timed out")
        if r.t_exec and not rider:
            # Time this session spent parked before its round's step ran —
            # the coalescing window for the leader, window + leader overhead
            # for followers.
            self._m_queue_wait.observe(max(0.0, r.t_exec - t_join))
        if r.err is not None:
            raise StageExecutionError(str(r.err)) from r.err
        if sid in r.bad:
            raise StageExecutionError(r.bad[sid])
        return r

    def _lead(self, r: _Round, key, sid: str, validate, enqueue,
              collect) -> None:
        """The leader's way through round ``r`` (`_round`): close it, admit
        its members and enqueue their step under the lock; wait for the
        results with the lock FREE, the step on record as the one in flight;
        collect them under the lock again, which ends the flight whatever
        either half raised (the engine recovers a rider's slot in its own
        collect), and wakes whoever waited for it."""
        with self._lock:
            self._close_round(r, key, sid)
            good = {}
            for s_id, rq in r.reqs.items():
                reason = validate(rq)
                if reason is None:
                    good[s_id] = rq
                else:
                    r.bad[s_id] = reason
            riding = None
            if r.rider is not None:
                reason = self._validate_rider(r, key[1])
                if reason is None:
                    riding = _rider_entry(r.rider, self._m_ids_read)
                else:
                    r.bad[r.rider.session_id] = reason
            if not (good or riding):
                return
            self._step_starts(r, key)
            if good:
                self._m_fill.observe(len(good))
                self._m_held.observe(len(self.inner._slot_of))
            landed = enqueue(r, good, riding)
            self._flying = (key, frozenset(good).union(
                [riding["session_id"]] if riding else ()))
        try:
            if landed is not None:
                landed()
        finally:
            with self._lock:
                self._flying = None
                self._cond.notify_all()
                back, parts = collect(r, good, riding)
                r.lengths = {
                    s_id: int(self.inner.lengths[self.inner.slot(s_id)])
                    for s_id in good
                }
                self._answered(r, key, back, parts)

    def _decode(self, req):
        """A step of width T = ``req.seq_len`` (plain decode, a speculative
        verify, a replay chunk) through the round of that width."""
        from .messages import StageResponse

        def enqueue(r, good, riding):
            # nothing of a step is read back under the lock: each waiter
            # reads its own row after the round (`_respond`)
            r.outs = self.inner.decode_batch(
                {s_id: rq.hidden for s_id, rq in good.items()})
            if self.spec.is_last:
                self._verify_spec_rows(r, good)

        def collect(r, good, riding):
            return good, None   # a step's reply never says it was the last

        sid = req.session_id
        r = self._round(req, req.seq_len, self._validate, enqueue, collect)
        if sid in r.spec:
            tokens, n_acc = r.spec[sid]
            return self._stamped(r, StageResponse(
                session_id=sid, tokens=tokens, n_accepted=n_acc,
                cache_len=r.lengths[sid]))
        return self._stamped(r, self._respond(req, r.outs[sid],
                                              r.lengths[sid]))

    def _validate_burst(self, req) -> Optional[str]:
        """Burst-specific admission on top of ``_validate`` (caller holds
        the lock): mirror every condition the engine's ``_burst_prep``
        would raise on, so one bad session never poisons its round-mates
        with a whole-round failure."""
        if req.burst_budget < 1:
            return (f"session {req.session_id}: burst budget "
                    f"{req.burst_budget} (want >= 1)")
        s = self.inner.slot(req.session_id)
        cur = int(self.inner.lengths[s])
        budget = min(int(req.burst_budget), int(req.burst_len))
        if cur + budget > self.inner.max_len:
            return (f"session {req.session_id}: burst of {budget} past "
                    f"{cur} exceeds max_len {self.inner.max_len}")
        return None

    def _decode_burst(self, req):
        """Concurrent burst requests coalesce into ONE N-tick dispatch: a
        round keyed by ('burst', N), so that single-tick rounds and bursts
        never mix widths. Sessions join and leave at round boundaries."""
        from .messages import StageResponse

        sid = req.session_id
        r = self._burst_round(req, int(req.burst_len))
        out = r.outs[sid]
        return self._stamped(r, StageResponse(
            session_id=sid, burst_tokens=tuple(out["tokens"]),
            burst_stop=out["stop"], cache_len=r.lengths[sid]))

    def _burst_round(self, req, n: int, rider: bool = False) -> _Round:
        """``req`` through the burst round of ``n`` ticks: a member
        (`_decode_burst`) or, ``rider``, the prefill that rides it
        (`_prefill_riding`)."""

        def validate(rq):
            return self._validate(rq) or self._validate_burst(rq)

        def enqueue(r, good, riding):
            r.flight = self.inner.burst_enqueue(
                {s_id: _burst_entry(rq, self._m_ids_read)
                 for s_id, rq in good.items()}, n, rider=riding)
            return partial(self.inner.burst_fetch, r.flight)

        def collect(r, good, riding):
            r.outs = self.inner.burst_collect(r.flight)
            # Back for the next round: the rider, with its first token, and
            # every session whose burst did not end its request (no stop,
            # and a budget of a whole burst: a client asks for min(burst,
            # tokens still wanted), so a short one is its last).
            back = [s_id for s_id, rq in good.items()
                    if r.outs[s_id]["stop"] is None
                    and rq.burst_budget >= rq.burst_len]
            if riding:
                back.append(riding["session_id"])
            return back, self.inner.burst_parts

        return self._round(req, ("burst", n), validate, enqueue, collect,
                           rider)

    def _verify_spec_rows(self, r: _Round, good: Dict[str, Any]) -> None:
        """Per-row speculative verification on the final stage (caller holds
        the lock, the round's batched step has run): compute each draft
        session's logits over its K+1 positions, accept/reject with the
        SAME math as the per-session executor
        (executor.verify_drafts_from_logits), and rewind the slot past the
        rejected tail so the next round's cur_len validates against the
        accepted prefix."""
        from .executor import verify_drafts_from_logits

        spec_ids = [s_id for s_id, rq in good.items()
                    if rq.draft_tokens is not None]
        if not spec_ids:
            return
        # ONE stacked head projection for the whole round ([n, T, D] ->
        # [n, T, V]) — a per-session loop of [1, T, D] head calls would
        # undo the round's batching and stretch the lock hold linearly
        # with slot count.
        stacked = jnp.concatenate([r.outs[s_id] for s_id in spec_ids], axis=0)
        logits = self.inner.logits(stacked)
        for i, s_id in enumerate(spec_ids):
            rq = good[s_id]
            tokens, n_acc = verify_drafts_from_logits(logits[i], rq)
            self.inner.rewind(s_id, rq.cur_len + n_acc + 1)
            r.spec[s_id] = (tokens, n_acc)
