"""Sparse MoE dispatch: sort-by-expert grouped matmuls (GShard-style).

The dense formulation in ``models.transformer._moe_mlp_dense`` runs every
expert on every token and zero-weights the non-selected ones — MLP FLOPs
scale with ``num_experts``, which makes real Mixtral-scale MoE unaffordable
(ROADMAP item 4; the reference capstone has no runnable MoE at all, only
config guards at ``src/llama_partition.py:82``). This module is the sparse
path, default ON (``MOE_SPARSE=0`` is the dense kill switch):

  router top-k  ->  flatten the (token, choice) slots  ->  stable sort by
  expert id  ->  per-expert segment positions  ->  capacity-bounded
  scatter into a static ``[E_local, C, D]`` dispatch buffer  ->  grouped
  expert matmuls  ->  weighted scatter-combine back to token order.

Every shape is static (capacity ``C`` is a trace-time constant), so the
whole dispatch jits, scans over layers, and shard_maps unchanged — and the
executed MLP FLOPs become ``E * C`` token-slots instead of ``N * E``
(``C ~= N * top_k / E * capacity_factor``), i.e. proportional to
``top_k / num_experts``.

Expert parallelism rides the existing ``tp`` mesh axis: the router is
replicated so the top-k and every capacity/position decision are computed
IDENTICALLY on all devices, each device scatters/computes only its local
expert range, and the closing ``psum`` combines the per-device partial
token outputs (the same collective the dense path already emits). Drop
decisions are therefore bit-identical sharded vs unsharded.

Capacity policy: ``C = min(N, ceil(N * top_k / E * MOE_CAPACITY_FACTOR))``
with factor 2.0 by default (``MOE_CAPACITY_FACTOR=0`` means drop-free:
``C = N``, the hard upper bound since a token contributes each expert at
most one slot). Slots past an expert's capacity are DROPPED — their
contribution is zero, exactly like GShard — and accounted in the
``moe_dropped_total`` counter when telemetry is on.

Quantized experts stay packed on this path (``models.quant.dequant_tree``
``keep_experts=True``): int8 stacks run the scale-folded grouped einsum
(int8 bytes stream straight into the dot, per-expert scale in the
epilogue — the 3-D analogue of ops.int8_kernel), NF4 stacks dequantize
ONE expert at a time under ``lax.map`` instead of materializing the full
``[E, D, I]`` bf16 stack.
"""

from __future__ import annotations

import math
import os
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .config import ModelConfig
from .quant import NF4Tensor, QuantizedTensor, int8_fold_enabled

Params = Dict[str, Any]


def moe_sparse_enabled() -> bool:
    """MOE_SPARSE=1 (default ON) routes MoE layers through the sparse
    sort-and-dispatch path above. MOE_SPARSE=0 restores the dense
    all-expert einsums bit-for-bit (the tiny-model fallback and kill
    switch, same idiom as INT8_FOLD/NF4_KERNEL).

    Trace-time flag (utils/flags.py catalog): resolved while the engine
    traces, so flips after warmup require a retrace."""
    from ..utils.flags import bool_flag

    return bool_flag("MOE_SPARSE")


def moe_capacity_factor() -> float:
    """Per-expert slot budget multiplier over the perfectly-balanced load
    (``MOE_CAPACITY_FACTOR``, default 2.0; <= 0 means drop-free).
    Trace-time flag: baked into the dispatch shapes at trace time."""
    from ..utils.flags import float_flag

    return float_flag("MOE_CAPACITY_FACTOR")


def moe_capacity(n_tokens: int, num_experts: int, top_k: int) -> int:
    """Static per-expert capacity C for a dispatch of `n_tokens` tokens.

    Balanced load is ``n_tokens * top_k / num_experts`` slots per expert;
    C is that times the capacity factor, clamped to [1, n_tokens] — an
    expert can receive at most one slot per token (top-k indices are
    distinct), so ``C = n_tokens`` is structurally drop-free."""
    full = max(1, n_tokens)
    cf = moe_capacity_factor()
    if cf <= 0:
        return full
    c = math.ceil(n_tokens * top_k / num_experts * cf)
    return max(1, min(full, c))


def _route(router: jnp.ndarray, xf: jnp.ndarray, top_k: int):
    """Replicated global routing: f32 logits -> top-k -> softmax weights.

    xf: [N, D] flattened tokens. Returns (e_flat, w_flat, t_flat), each
    [N*K]: expert id, combine weight, and source token of every slot."""
    n = xf.shape[0]
    logits = xf.astype(jnp.float32) @ router.astype(jnp.float32)  # [N, E]
    topv, topi = jax.lax.top_k(logits, top_k)
    weights = jax.nn.softmax(topv, axis=-1)
    e_flat = topi.reshape(-1)
    w_flat = weights.reshape(-1)
    t_flat = jnp.arange(n * top_k, dtype=jnp.int32) // top_k
    return e_flat, w_flat, t_flat


def _sort_and_position(e_flat: jnp.ndarray, num_experts: int):
    """Stable sort by expert id + within-segment positions.

    Returns (order, seg_pos, counts): `order` permutes slots into
    expert-sorted order, `seg_pos[i]` is sorted slot i's rank within its
    expert's segment (the dispatch row it would occupy), `counts[e]` the
    total slots routed to expert e."""
    nk = e_flat.shape[0]
    order = jnp.argsort(e_flat, stable=True)
    se = e_flat[order]
    counts = jnp.bincount(e_flat, length=num_experts)
    seg_start = jnp.cumsum(counts) - counts
    seg_pos = jnp.arange(nk, dtype=jnp.int32) - seg_start[se].astype(jnp.int32)
    return order, se, seg_pos, counts


def _expert_dot(x: jnp.ndarray, w) -> jnp.ndarray:
    """Grouped matmul over the leading expert axis: [e,C,a] @ [e,a,b].

    Quantized stacks never materialize whole: int8 streams packed bytes
    into a mixed-dtype einsum with the per-expert scale applied to the f32
    accumulator (exact per output channel — same contract as
    ops.int8_kernel; INT8_FOLD=0 restores dequant-materialize), NF4
    dequantizes one expert per ``lax.map`` step so a single expert's bf16
    weights are resident at a time."""
    if isinstance(w, QuantizedTensor):
        if int8_fold_enabled():
            y = jnp.einsum("eca,eab->ecb", x, w.q,
                           preferred_element_type=jnp.float32)
            return (y * w.s).astype(x.dtype)
        return jnp.einsum("eca,eab->ecb", x, w.dequant().astype(x.dtype))
    if isinstance(w, NF4Tensor):
        def one(args):
            xe, we = args
            return xe @ we.dequant().astype(xe.dtype)

        return jax.lax.map(one, (x, w))
    return jnp.einsum("eca,eab->ecb", x, w)


def sparse_moe_mlp(cfg: ModelConfig, p: Params, x: jnp.ndarray,
                   tp_axis: Optional[str]) -> jnp.ndarray:
    """Capacity-bounded sparse dispatch of a top-k routed SwiGLU MoE layer.

    x: [B, T, D]. p holds `router` (replicated [D, E]) and expert stacks
    `wg`/`wu`/`wd` ([E_local, ...] — the local shard when the expert axis
    is sharded over `tp_axis`, the full stack otherwise). Token-identical
    to the dense formulation whenever no expert overflows its capacity
    (combine order differs, so identical means allclose/argmax, not
    bitwise)."""
    e_total = cfg.num_experts
    top_k = cfg.num_experts_per_tok
    b, t, d = x.shape
    n = b * t
    xf = x.reshape(n, d)

    e_flat, w_flat, t_flat = _route(p["router"], xf, top_k)
    order, se, seg_pos, counts = _sort_and_position(e_flat, e_total)
    sw = w_flat[order]
    st = t_flat[order]

    cap = moe_capacity(n, e_total, top_k)
    keep = seg_pos < cap

    # Local expert range under EP: routing math above is replicated, so
    # every device agrees on positions and drops; each device dispatches
    # only the slots of its own expert shard.
    e_local = p["wg"].shape[0]
    if tp_axis is not None and e_local != e_total:
        offset = jax.lax.axis_index(tp_axis) * e_local
    else:
        offset = 0
    le = se - offset
    valid = keep & (le >= 0) & (le < e_local)
    le_c = jnp.clip(le, 0, e_local - 1)
    pos_c = jnp.clip(seg_pos, 0, cap - 1)

    # Dispatch: masked scatter-add into the static [E_local, C, D] buffer.
    # Each (expert, position) cell receives at most one real row (segment
    # positions are unique per expert); masked-out slots add zeros.
    xs = jnp.where(valid[:, None], xf[st], 0).astype(x.dtype)
    buf = jnp.zeros((e_local, cap, d), x.dtype).at[le_c, pos_c].add(xs)

    gate = jax.nn.silu(_expert_dot(buf, p["wg"]))
    up = _expert_dot(buf, p["wu"])
    y = _expert_dot(gate * up, p["wd"])            # [E_local, C, D]

    # Combine: gather each slot's expert output, weight, scatter-add back
    # to token order. Dropped and remote slots contribute zero.
    comb_w = jnp.where(valid, sw, 0.0).astype(x.dtype)
    ys = y[le_c, pos_c] * comb_w[:, None]
    out = jnp.zeros((n, d), x.dtype).at[st].add(ys).reshape(b, t, d)

    # Expert-load observability (default OFF): only when the registry is
    # already enabled at trace time, and never inside shard_map (host
    # callbacks from collectives-carrying bodies are not portable).
    if tp_axis is None and _registry_enabled():
        jax.debug.callback(_record_load, counts, jnp.sum(keep),
                           ordered=False)

    if tp_axis is not None:
        out = jax.lax.psum(out, tp_axis)
    return out


# Rows a held expert takes in ONE round of `held_moe_mlp`, at least: a step
# of up to this many rows runs every held expert over every row (one round,
# each expert's weights streamed once: a decode tick); a longer one gives
# each expert this many row slots a round, or twice its balanced share
# where that is more, and takes as many rounds as the fullest expert needs.
MOE_ROUND_ROWS = 128


def route_sigmoid(cfg: ModelConfig, p: Params, xf: jnp.ndarray):
    """Sigmoid routing over ALL ``cfg.num_experts`` (the ``noaux_tc``
    method with one group): scores ``sigmoid(x W_g)``, the
    ``num_experts_per_tok`` largest of ``score + bias`` chosen, the chosen
    SCORES (without the bias) normalised to sum 1 and scaled by
    ``routed_scaling_factor``. float32 at the highest matmul precision: a
    near-tie for the last place decides which expert runs. xf ``[N, D]``
    -> ``(expert ids [N, K], weights [N, K])``. The ONE place the
    ``router`` scope is opened."""
    with jax.named_scope("router"):
        scores = jax.nn.sigmoid(jnp.dot(
            xf.astype(jnp.float32), p["router"].astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST))
        _, topi = jax.lax.top_k(
            scores + p["router_bias"].astype(jnp.float32),
            cfg.num_experts_per_tok)
        w = jnp.take_along_axis(scores, topi, axis=-1)
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
        return topi, w * cfg.routed_scaling_factor


def _swiglu_experts(buf: jnp.ndarray, p: Params) -> jnp.ndarray:
    """``[E, C, D]`` through the ``E`` SwiGLU experts of ``p``."""
    gate = jax.nn.silu(_expert_dot(buf, p["wg"]))
    return _expert_dot(gate * _expert_dot(buf, p["wu"]), p["wd"])


def held_moe_mlp(cfg: ModelConfig, p: Params, x: jnp.ndarray):
    """The expert layer of ONE chip of a deployment that shares a layer's
    routed experts among chips: ``(y, assigned)``. ``p`` holds the router
    over ALL experts (``router`` ``[D, E]``, ``router_bias`` ``[E]``), the
    stacks of the experts THIS chip holds (``wg``/``wu``/``wd``, ``[held,
    ..]``: experts ``cfg.held_experts[0] ..``) and the ``shared`` expert.
    Every token is routed over all ``E``; ``y`` is its held experts' part
    (weights as routed: the other chips' parts add to it where they live)
    plus the shared expert, which every chip's data-parallel tokens take
    whole. ``assigned`` ``[N, held]``: which rows chose which held expert
    (the burst program's counters).

    Drop-free at static shapes. A step of at most `MOE_ROUND_ROWS` rows
    runs every held expert over every row, weighted: each expert's
    weights are read ONCE whatever the routing, so a decode tick's bytes
    do not follow its tokens. A longer step (a prefill) compacts: row
    ``n``'s rank among the rows that chose expert ``e`` is a running count,
    round ``r`` takes ranks ``[r C, (r + 1) C)`` of every expert through
    one-hot dispatch and combine products (MXU work, no scatter), and the
    rounds run until the fullest expert is done (a traced trip count: one
    round at balanced routing, more under skew, never a dropped row)."""
    b, t, d = x.shape
    n = b * t
    xf = x.reshape(n, d)
    first, held = cfg.held_experts
    topi, w = route_sigmoid(cfg, p, xf)
    with jax.named_scope("experts"):
        hit = topi[:, :, None] == first + jnp.arange(held, dtype=topi.dtype)
        assigned = hit.any(1)                                   # [N, held]
        held_w = jnp.where(hit, w[:, :, None], 0.0).sum(1)      # [N, held]
        if n <= MOE_ROUND_ROWS:
            y = _swiglu_experts(jnp.broadcast_to(xf, (held, n, d)), p)
            out = jnp.einsum("ne,end->nd", held_w.astype(x.dtype), y,
                             preferred_element_type=jnp.float32)
        else:
            cap = max(MOE_ROUND_ROWS, -(-2 * n * cfg.num_experts_per_tok
                                        // cfg.num_experts))
            rank = jnp.cumsum(assigned.astype(jnp.int32), axis=0) - 1
            rounds = -(-assigned.sum(0).max() // cap)
            lane = jnp.arange(cap, dtype=jnp.int32)

            def one_round(r, acc):
                slot = rank - r * cap
                onehot = (assigned[:, :, None]
                          & (slot[:, :, None] == lane)).astype(x.dtype)
                y = _swiglu_experts(
                    jnp.einsum("nec,nd->ecd", onehot, xf), p)
                return acc + jnp.einsum(
                    "nec,ecd->nd",
                    onehot * held_w[:, :, None].astype(x.dtype), y,
                    preferred_element_type=jnp.float32)

            out = jax.lax.fori_loop(0, rounds, one_round,
                                    jnp.zeros((n, d), jnp.float32))
    with jax.named_scope("shared_expert"):
        from .transformer import _dot

        sh = p["shared"]
        shared = _dot(jax.nn.silu(_dot(xf, sh["wg"])) * _dot(xf, sh["wu"]),
                      sh["wd"])
    return ((out + shared.astype(jnp.float32)).astype(x.dtype)
            .reshape(b, t, d), assigned)


def dispatch_stats(cfg: ModelConfig, router: jnp.ndarray, x: jnp.ndarray
                   ) -> Tuple[jnp.ndarray, int, int]:
    """Host-visible routing stats for a batch — the SAME math the sparse
    path traces, exposed for tests and capacity tuning.

    Returns (counts[E], kept_slots, capacity)."""
    b, t, _ = x.shape
    n = b * t
    xf = x.reshape(n, -1)
    e_flat, _, _ = _route(router, xf, cfg.num_experts_per_tok)
    _, _, seg_pos, counts = _sort_and_position(e_flat, cfg.num_experts)
    cap = moe_capacity(n, cfg.num_experts, cfg.num_experts_per_tok)
    kept = int(jnp.sum(seg_pos < cap))
    return counts, kept, cap


# -- expert-load telemetry (host side) ---------------------------------------


def _registry_enabled() -> bool:
    from ..telemetry.metrics import get_registry

    return get_registry().enabled


def _record_load(counts, kept) -> None:
    """jax.debug.callback target: fold one dispatch's routing histogram
    into the registry. counts: [E] slots routed per expert; kept: slots
    within capacity."""
    import numpy as np

    from ..telemetry import catalog
    from ..telemetry.metrics import get_registry

    reg = get_registry()
    if not reg.enabled:
        return
    c = np.asarray(counts, dtype=np.float64)
    total = float(c.sum())
    if total <= 0:
        return
    e = c.shape[0]
    hist = catalog.get("moe_expert_load", reg)
    for share in c * (e / total):
        hist.observe(float(share))
    catalog.get("moe_tokens_total", reg).inc(total)
    catalog.get("moe_dropped_total", reg).inc(max(0.0, total - float(kept)))
    catalog.get("moe_max_expert_share", reg).set(float(c.max()) / total)
