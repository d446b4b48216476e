"""Ring attention: sequence-parallel exact attention over a mesh axis.

The reference has NO long-context story beyond single-server chunked prefill
(SURVEY.md §5.7: no ring/Ulysses/blockwise anywhere; chunking at
``petals/server/backend.py:129-143`` just bounds one GPU's peak memory). On
TPU the natural long-context design is to shard the SEQUENCE across an
intra-stage mesh axis: each device holds a slice of queries and a slice of
keys/values, and the KV slices rotate around the ring via ``ppermute`` while
every device accumulates its queries' attention with an online (flash-style)
softmax. P devices => P× longer context at the same per-device HBM, with
compute/communication overlap on ICI.

Causality: query chunk q on device i covers absolute positions
[i·C, i·C + C); after s ring steps a device holds the KV chunk of device
(i - s) mod P. Blocks wholly in the future are masked out; the diagonal
block applies the usual triangular mask.

Causal skip (VERDICT r3 item 4): the KV rotation is always full-ring (the
ppermute is a collective — every device must participate every step), but
a device whose incoming block is WHOLLY in its future skips the
score/value compute for it via ``lax.cond`` (a runtime branch, per
device). Summed over the ring, causal prefill does P(P+1)/2 block
computes instead of P² — the step-work ratio (P+1)/2P → ~0.5 at large P.
This cuts total FLOPs/energy; single-ring LATENCY is still P-1 rotations
because the last device computes at every step (balancing that needs a
zigzag chunk layout — two half-chunks per device, one low one high —
which would change sp_stage's on-device sequence layout;
`zigzag_ring_attention` below is that layout, docs/PERFORMANCE.md "Scale
features").

Numerics: scores and the softmax accumulator run in float32 regardless of the
activation dtype (matching ops.attention's fp32-softmax contract); the output
returns to the input dtype.
"""

from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def _block_scores(q, k, scale):
    # q: [B, Tq, Hkv, G, Dh]; k: [B, Tk, Hkv, Dh] -> [B, Hkv, G, Tq, Tk] f32
    return jnp.einsum(
        "bthgd,bshd->bhgts", q * scale, k, preferred_element_type=jnp.float32
    )


# ---------------------------------------------------------------------------
# Shared online-softmax primitives (used here conceptually and directly by
# parallel.sp_stage's decode): partials are (m, l, o) with o UN-normalized
# fp32. The NEG_INF/2 guards keep fully-masked blocks exactly zero instead
# of exp(-inf - -inf) = 1 garbage.
# ---------------------------------------------------------------------------

def online_partial(qg, k, v, mask, scale):
    """Partial over one KV block. qg: [B, 1, Hkv, G, Dh]; k/v: [B, S, Hkv,
    Dh]; mask: [B, S] (True = attendable). Returns (m, l, o), o [B,Hkv,G,Dh]."""
    scores = jnp.einsum("bthgd,bshd->bhgs", qg * scale, k,
                        preferred_element_type=jnp.float32)
    scores = jnp.where(mask[:, None, None, :], scores, NEG_INF)
    m = jnp.max(scores, axis=-1)
    safe_m = jnp.where(m <= NEG_INF / 2, 0.0, m)
    probs = jnp.exp(scores - safe_m[..., None])
    probs = jnp.where(scores <= NEG_INF / 2, 0.0, probs)
    l = probs.sum(axis=-1)
    o = jnp.einsum("bhgs,bshd->bhgd", probs.astype(jnp.float32),
                   v.astype(jnp.float32))
    return m, l, o


def online_combine(a, b):
    """Merge two online-softmax partials (m, l, o)."""
    ma, la, oa = a
    mb, lb, ob = b
    m = jnp.maximum(ma, mb)
    safe_m = jnp.where(m <= NEG_INF / 2, 0.0, m)
    ca = jnp.where(ma <= NEG_INF / 2, 0.0, jnp.exp(ma - safe_m))
    cb = jnp.where(mb <= NEG_INF / 2, 0.0, jnp.exp(mb - safe_m))
    return m, la * ca + lb * cb, oa * ca[..., None] + ob * cb[..., None]


def ring_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    axis_name: str,
    *,
    q_offset: Optional[jnp.ndarray] = None,
    chunk_positions: Optional[jnp.ndarray] = None,
    causal: bool = True,
) -> jnp.ndarray:
    """Exact attention with sequence sharded over `axis_name`.

    Must be called inside shard_map/pjit manual context. Per-device views:
      q: [B, C, H, Dh] — this device's query chunk;
      k, v: [B, C, Hkv, Dh] — this device's KV chunk (same C).
    q_offset: absolute position of this device's first query (defaults to
    axis_index · C). Returns [B, C, H, Dh] in q.dtype.
    """
    del chunk_positions  # reserved for ragged chunks
    b, c, h, dh = q.shape
    hkv = k.shape[2]
    groups = h // hkv
    p = jax.lax.psum(1, axis_name)
    idx = jax.lax.axis_index(axis_name)
    scale = dh ** -0.5

    if q_offset is None:
        q_offset = idx * c
    q_pos = q_offset + jnp.arange(c, dtype=jnp.int32)          # [C]

    qg = q.reshape(b, c, hkv, groups, dh)
    perm = [(i, (i + 1) % p) for i in range(p)]

    def accumulate(s, k_blk, v_blk, m, l, o):
        src = (idx - s) % p                                     # owner of k_blk
        k_pos = src * c + jnp.arange(c, dtype=jnp.int32)        # [C]

        scores = _block_scores(qg, k_blk, scale)                # [B,Hkv,G,C,C]
        if causal:
            allowed = k_pos[None, :] <= q_pos[:, None]          # [C, C]
            scores = jnp.where(allowed[None, None, None], scores, NEG_INF)

        blk_max = jnp.max(scores, axis=-1)                      # [B,Hkv,G,C]
        m_new = jnp.maximum(m, blk_max)
        # Guard fully-masked rows: exp(NEG_INF - NEG_INF) would be 1.
        safe_m = jnp.where(m_new <= NEG_INF / 2, 0.0, m_new)
        corr = jnp.exp(m - safe_m)
        probs = jnp.exp(scores - safe_m[..., None])
        probs = jnp.where(scores <= NEG_INF / 2, 0.0, probs)
        l = l * corr + probs.sum(axis=-1)
        pv = jnp.einsum(
            "bhgts,bshd->bthgd", probs.astype(v_blk.dtype), v_blk,
            preferred_element_type=jnp.float32,
        )                                                        # [B,C,Hkv,G,Dh]
        o = o * corr.transpose(0, 3, 1, 2)[..., None] + pv
        return m_new, l, o

    def step(s, carry):
        # Rotate FIRST, then accumulate: with the local block (s=0) peeled
        # out of the loop, p-1 rotations cover all p blocks — rotating after
        # the final accumulation would ship one dead ring hop of KV traffic.
        k_blk, v_blk, m, l, o = carry
        k_blk = jax.lax.ppermute(k_blk, axis_name, perm)
        v_blk = jax.lax.ppermute(v_blk, axis_name, perm)
        if causal:
            # Causal skip: if the incoming block is WHOLLY in this device's
            # future (its first key position is past our last query), every
            # score would be masked — skip the block's compute entirely.
            # The rotation above still ran (collective); only the local
            # einsum/softmax work is branched out.
            src = (idx - s) % p
            wholly_future = src * c > q_offset + (c - 1)
            m, l, o = jax.lax.cond(
                wholly_future,
                lambda m, l, o: (m, l, o),
                lambda m, l, o: accumulate(s, k_blk, v_blk, m, l, o),
                m, l, o)
        else:
            m, l, o = accumulate(s, k_blk, v_blk, m, l, o)
        return k_blk, v_blk, m, l, o

    def vary(x):
        return jax.lax.pcast(x, axis_name, to="varying")

    m0 = vary(jnp.full((b, hkv, groups, c), NEG_INF, jnp.float32))
    l0 = vary(jnp.zeros((b, hkv, groups, c), jnp.float32))
    o0 = vary(jnp.zeros((b, c, hkv, groups, dh), jnp.float32))
    m, l, o = accumulate(0, k, v, m0, l0, o0)                   # local block
    _, _, m, l, o = jax.lax.fori_loop(1, p, step, (k, v, m, l, o))

    denom = jnp.maximum(l, 1e-20).transpose(0, 3, 1, 2)[..., None]
    out = (o / denom).reshape(b, c, h, dh)
    return out.astype(q.dtype)


def zigzag_ring_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    axis_name: str,
) -> jnp.ndarray:
    """Causal ring attention with the ZIGZAG chunk layout: device i holds
    half-chunks i (low) and 2P-1-i (high) of a sequence cut into 2P
    half-chunks, so every device owns one early and one late piece.

    Why: the contiguous layout's causal skip leaves a skewed LATENCY
    profile — device P-1's queries attend every block, so it computes at
    all P ring steps while device 0 computes only its own (VERDICT r4
    weak item 6). Under zigzag, for the incoming KV of source s a device
    computes exactly
        [s <= i] qLow x kLow  +  qHigh x kLow (always)  +  [s >= i] qHigh x kHigh
    = 2 half-pairs per step (3 when s == i; qLow x kHigh is NEVER causal
    and is omitted statically) — per-device per-step work is uniform, so
    the slowest-device critical path drops from P block-computes to
    ~(2P+1)/4 block-equivalents while TOTAL work stays the causal ~half:
    P*(2P+1) half-pairs vs 4P^2 full-ring = (2P+1)/4P -> 0.5.

    Per-device views (inside shard_map): q [B, 2*C2, H, Dh], k/v
    [B, 2*C2, Hkv, Dh] in zigzag order (low half first). Use
    `make_zigzag_ring_attention_fn` for the full-array wrapper that
    applies the layout permutation.
    """
    b, c2x2, h, dh = q.shape
    c2 = c2x2 // 2
    hkv = k.shape[2]
    groups = h // hkv
    p = jax.lax.psum(1, axis_name)
    idx = jax.lax.axis_index(axis_name)
    scale = dh ** -0.5

    ar = jnp.arange(c2, dtype=jnp.int32)
    q_pos_lo = idx * c2 + ar
    q_pos_hi = (2 * p - 1 - idx) * c2 + ar
    qg = q.reshape(b, 2 * c2, hkv, groups, dh)
    qlo, qhi = qg[:, :c2], qg[:, c2:]
    perm = [(i, (i + 1) % p) for i in range(p)]

    def pair(qh, q_pos, k_blk, v_blk, k_pos, m, l, o):
        """Accumulate one (query-half x key-half) pair into (m, l, o)."""
        scores = _block_scores(qh, k_blk, scale)        # [B,Hkv,G,C2,C2]
        allowed = k_pos[None, :] <= q_pos[:, None]
        scores = jnp.where(allowed[None, None, None], scores, NEG_INF)
        blk_max = jnp.max(scores, axis=-1)
        m_new = jnp.maximum(m, blk_max)
        safe_m = jnp.where(m_new <= NEG_INF / 2, 0.0, m_new)
        corr = jnp.exp(m - safe_m)
        probs = jnp.exp(scores - safe_m[..., None])
        probs = jnp.where(scores <= NEG_INF / 2, 0.0, probs)
        l = l * corr + probs.sum(axis=-1)
        pv = jnp.einsum("bhgts,bshd->bthgd", probs.astype(v_blk.dtype),
                        v_blk, preferred_element_type=jnp.float32)
        o = o * corr.transpose(0, 3, 1, 2)[..., None] + pv
        return m_new, l, o

    def accumulate(src, k_blk, v_blk, st_lo, st_hi):
        kl, vl = k_blk[:, :c2], v_blk[:, :c2]           # src's low chunk
        kh, vh = k_blk[:, c2:], v_blk[:, c2:]           # src's high chunk
        k_pos_lo = src * c2 + ar
        k_pos_hi = (2 * p - 1 - src) * c2 + ar
        # qLow x kLow: only when src <= i (past or diagonal).
        st_lo = jax.lax.cond(
            src <= idx,
            lambda st: pair(qlo, q_pos_lo, kl, vl, k_pos_lo, *st),
            lambda st: st, st_lo)
        # qHigh x kLow: always causal (every low chunk precedes any high).
        st_hi = pair(qhi, q_pos_hi, kl, vl, k_pos_lo, *st_hi)
        # qHigh x kHigh: only when src >= i (high chunks order-reverse).
        st_hi = jax.lax.cond(
            src >= idx,
            lambda st: pair(qhi, q_pos_hi, kh, vh, k_pos_hi, *st),
            lambda st: st, st_hi)
        # qLow x kHigh: statically never causal (2P-1-src > i for every
        # src < P <= 2P-1-i) — omitted.
        return st_lo, st_hi

    def step(s, carry):
        k_blk, v_blk, st_lo, st_hi = carry
        k_blk = jax.lax.ppermute(k_blk, axis_name, perm)
        v_blk = jax.lax.ppermute(v_blk, axis_name, perm)
        st_lo, st_hi = accumulate((idx - s) % p, k_blk, v_blk, st_lo, st_hi)
        return k_blk, v_blk, st_lo, st_hi

    def vary(x):
        return jax.lax.pcast(x, axis_name, to="varying")

    def init():
        return (vary(jnp.full((b, hkv, groups, c2), NEG_INF, jnp.float32)),
                vary(jnp.zeros((b, hkv, groups, c2), jnp.float32)),
                vary(jnp.zeros((b, c2, hkv, groups, dh), jnp.float32)))

    st_lo, st_hi = accumulate(idx, k, v, init(), init())   # local block
    _, _, st_lo, st_hi = jax.lax.fori_loop(
        1, p, step, (k, v, st_lo, st_hi))

    def finish(st):
        m, l, o = st
        denom = jnp.maximum(l, 1e-20).transpose(0, 3, 1, 2)[..., None]
        return o / denom

    out = jnp.concatenate([finish(st_lo), finish(st_hi)], axis=1)
    return out.reshape(b, 2 * c2, h, dh).astype(q.dtype)


def zigzag_order(t: int, p: int) -> "jnp.ndarray":
    """Permutation taking natural sequence order to zigzag-sharded order:
    device i's shard_map slice holds half-chunks [i, 2P-1-i]."""
    if t % (2 * p):
        raise ValueError(
            f"zigzag layout needs T divisible by 2*P: T={t}, P={p} "
            "(pad the sequence; a truncating take would silently drop "
            "tokens)")
    c2 = t // (2 * p)
    idx = []
    for i in range(p):
        idx.extend(range(i * c2, (i + 1) * c2))
        idx.extend(range((2 * p - 1 - i) * c2, (2 * p - i) * c2))
    return jnp.asarray(idx, jnp.int32)


def make_zigzag_ring_attention_fn(mesh, axis_name: str = "sp"):
    """shard_map-wrapped zigzag ring attention over full natural-order
    arrays: applies the zigzag layout permutation, runs the balanced ring,
    and inverse-permutes the output. T must divide by 2*P. (A production
    sp serving path would keep the whole session IN zigzag layout and pay
    the permutation never — this wrapper prices it per call, which is fine
    for the structural comparison and parity tests.)"""
    from jax.sharding import PartitionSpec as P

    spec = P(None, axis_name)
    p = mesh.shape[axis_name]

    @jax.jit
    def fn(q, k, v):
        t = q.shape[1]
        order = zigzag_order(t, p)
        inv = jnp.argsort(order)
        sharded = jax.shard_map(
            lambda q_, k_, v_: zigzag_ring_attention(q_, k_, v_, axis_name),
            mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        )
        out = sharded(jnp.take(q, order, axis=1),
                      jnp.take(k, order, axis=1),
                      jnp.take(v, order, axis=1))
        return jnp.take(out, inv, axis=1)

    return fn


def make_ring_attention_fn(mesh, axis_name: str = "sp"):
    """shard_map-wrapped ring attention over full arrays.

    q: [B, T, H, Dh]; k/v: [B, T, Hkv, Dh]; T must divide by the axis size.
    Returns the full [B, T, H, Dh] output (sequence re-assembled).
    """
    from jax.sharding import PartitionSpec as P

    spec = P(None, axis_name)

    @jax.jit
    @partial(
        jax.shard_map, mesh=mesh,
        in_specs=(spec, spec, spec), out_specs=spec,
    )
    def fn(q, k, v):
        return ring_attention(q, k, v, axis_name)

    return fn
