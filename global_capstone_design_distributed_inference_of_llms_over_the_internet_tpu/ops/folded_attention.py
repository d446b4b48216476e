"""Decode attention over ONE layer of a FOLDED cache stack, each slot read
up to its own last block.

The batched engine holds a cache row's KV heads side by side in one minor
dim where the backend would not keep ``head_dim`` minor (``[L, S, max_len,
W]``: `runtime.batching.kv_fold_width`). A decode step has one query row a
slot, and the slots differ in length: XLA can bound a read by a value only
through a loop whose trips each cost a few microseconds and whose running
sum goes through HBM, so its read stops where the LONGEST active slot
stops, for every slot, active or not. This kernel takes the slots' OWN
block counts (`read_plan`) and reads, for each slot, that slot's blocks
and no others:

  * the K and V stacks stay WHOLE in HBM, as the layer scan carries them;
    the layer index and the plan are scalar-prefetch operands, so nothing
    slices or stages a layer for the call (`ops.int8_kernel`'s stacked
    form does the same with its weights);
  * ONE invocation walks the plan's (slot, block) pairs in a loop whose
    trip count is the plan's total, a value: a slot with no block costs
    nothing, and a pair's K and V rows (``[block, W]`` each, dense, in the
    layout they rest in) arrive through a double-buffered
    ``make_async_copy`` started one pair ahead;
  * a head's query sits in the lanes of its own KV head and is zero in all
    others (block-diagonal: the zeros add nothing to a score), so every
    head's scores against a block are one MXU product ``[H, W] x [block,
    W]^T``; the causal mask is the slot's own position; the softmax is
    online, its running max, denominator and weighted sum float32 in VMEM
    scratch, never in HBM; at a slot's last block each head's own lanes of
    the sum over the denominator are written out and the rest dropped.

The arithmetic is `runtime.batching._attend`'s: operands in their own
dtype, float32 scores, statistics and sums; a row past a query's position
has probability exactly 0 there, so leaving it unread drops no term.

Off the TPU (a test that folds on the CPU) the call runs through the Pallas
interpreter. PERF.md section 6, PR 52.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from .attention import NEG_INF

VMEM_LIMIT = 32 * 1024 * 1024

# None: the interpreter wherever the backend is not a TPU. A rehearsal that
# compiles for a described chip from a CPU process sets False.
_INTERPRET: Optional[bool] = None


def read_plan(blocks, q_pos, most: int):
    """The kernel's walk over a layer, as ONE int32 vector made once a step
    (every layer of the step reads by it): ``[total, slot of pair i (S x
    most), block of pair i (S x most), blocks (S), q_pos (S)]``. ``blocks``
    ``[S]`` are the slots' own block counts (0: the slot is not read),
    ``q_pos`` ``[S]`` their queries' positions, ``most`` the blocks of a
    whole slot. Pairs ``0 .. total - 1`` are slot-major, a slot's blocks in
    order; the entries past ``total`` are never read."""
    slots = blocks.shape[0]
    blocks = blocks.astype(jnp.int32)
    ends = jnp.cumsum(blocks)
    i = jnp.arange(slots * most, dtype=jnp.int32)
    slot = jnp.minimum((i[:, None] >= ends[None, :]).sum(-1), slots - 1)
    block = i - (ends - blocks)[slot]
    return jnp.concatenate(
        [ends[-1:], slot, block, blocks, q_pos]).astype(jnp.int32)


def _padded_heads(heads: int, dtype) -> int:
    """Query rows of the kernel: the heads, up to whole sublane tiles of
    ``dtype`` (8 rows of 32 bits, 16 of 16)."""
    tile = 8 * max(4 // jnp.dtype(dtype).itemsize, 1)
    return -(-heads // tile) * tile


def _precision(dtype):
    """A product of 16-bit operands is exact in the float32 it is summed
    in, and Mosaic refuses a higher precision asked of it (a process that
    pins ``jax_default_matmul_precision``): DEFAULT there, the process's
    own for float32."""
    return (None if jnp.dtype(dtype).itemsize >= 4
            else jax.lax.Precision.DEFAULT)


def _kernel(at_ref, plan_ref, q_ref, k_hbm, v_hbm, out_ref, k_buf, v_buf,
            sem, q_wide, m_ref, l_ref, acc_ref, own_ref, *, pairs, rows, hkv,
            groups, dh, dtype):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    slots = q_ref.shape[0]
    total, at = plan_ref[0], at_ref[0]

    # The plan by part (`read_plan`): pair i's slot and block, a slot's own
    # block count and its query's position.
    def slot_of(i):
        return plan_ref[1 + i]

    def block_of(i):
        return plan_ref[1 + pairs + i]

    def blocks_of(slot):
        return plan_ref[1 + 2 * pairs + slot]

    def q_pos_of(slot):
        return plan_ref[1 + 2 * pairs + slots + slot]

    def copies(i, buf):
        """Pair i's K and V rows into buffer ``buf``."""
        at_rows = pl.ds(pl.multiple_of(block_of(i) * rows, rows), rows)
        return [pltpu.make_async_copy(
            stack.at[at, slot_of(i), at_rows], dst.at[buf], sem.at[n, buf])
            for n, (stack, dst) in enumerate(((k_hbm, k_buf),
                                              (v_hbm, v_buf)))]

    @pl.when(total > 0)
    def _():
        for copy in copies(0, 0):
            copy.start()

    # The kernel's rows are the heads, KV head k's j-th query head at row
    # ``j * Hkv + k`` (then zero rows up to whole tiles). Which group j a
    # lane of a row belongs to: the row's own in the lanes of its KV head,
    # -1 everywhere else (another head's lanes, the pad lanes, the pad
    # rows). A slot's queries arrive as ``[G, W]``, head ``(j, k)`` in row
    # j at KV head k's lanes: row ``j * Hkv + k`` of the block-diagonal
    # operand is row j of that where ``own == j``, and each output row j
    # collects the sums' lanes where ``own == j``.
    row = jax.lax.broadcasted_iota(jnp.int32, own_ref.shape, 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, own_ref.shape, 1)
    own = jnp.full(own_ref.shape, -1, jnp.int32)
    for j in range(groups):
        first = (row - j * hkv) * dh
        own = jnp.where((row >= j * hkv) & (row < (j + 1) * hkv)
                        & (lane >= first) & (lane < first + dh), j, own)
    own_ref[...] = own
    # A slot that is not read: zeros, which the caller discards.
    out_ref[...] = jnp.zeros_like(out_ref)

    def pair(i, _):
        buf = jax.lax.rem(i, 2)
        slot, block = slot_of(i), block_of(i)

        @pl.when(i + 1 < total)
        def _():
            for copy in copies(i + 1, 1 - buf):
                copy.start()

        @pl.when(block == 0)
        def _():
            mine = q_ref[slot].astype(jnp.float32)              # [G, W]
            wide = jnp.zeros(own_ref.shape, jnp.float32)
            for j in range(groups):
                wide = jnp.where(own_ref[...] == j, mine[j:j + 1], wide)
            q_wide[...] = wide.astype(q_wide.dtype)
            m_ref[...] = jnp.full_like(m_ref, NEG_INF)
            l_ref[...] = jnp.zeros_like(l_ref)
            acc_ref[...] = jnp.zeros_like(acc_ref)

        for copy in copies(i, buf):
            copy.wait()
        q = q_wide[...]                                         # [HP, W]
        scores = jax.lax.dot_general(
            q, k_buf[buf].astype(q.dtype), (((1,), (1,)), ((), ())),
            precision=_precision(q.dtype),
            preferred_element_type=jnp.float32)                 # [HP, rows]
        k_pos = block * rows + jax.lax.broadcasted_iota(
            jnp.int32, scores.shape, 1)
        scores = jnp.where(k_pos <= q_pos_of(slot), scores, NEG_INF)
        m = m_ref[...]
        m2 = jnp.maximum(m, scores.max(-1, keepdims=True))
        corr = jnp.exp(m - m2)
        w = jnp.exp(scores - m2)
        v = v_buf[buf]
        m_ref[...] = m2
        l_ref[...] = l_ref[...] * corr + w.sum(-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jnp.dot(
            w.astype(v.dtype).astype(dtype), v.astype(dtype),
            precision=_precision(dtype),
            preferred_element_type=jnp.float32)                 # [HP, W]

        @pl.when(block == blocks_of(slot) - 1)
        def _():
            out = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
            for j in range(groups):
                out_ref[slot, pl.ds(j, 1), :] = jnp.where(
                    own_ref[...] == j, out, 0.0).sum(
                        0, keepdims=True).astype(out_ref.dtype)

    jax.lax.fori_loop(0, total, pair, None)


def folded_attention(q, k_stack, v_stack, at, plan, *, rows: int, hkv: int):
    """Attention of one query row a slot, ``q`` ``[S, H, Dh]`` (rotated and
    scaled), over layer ``at`` of the folded stacks ``[L, S, max_len, W]``
    by ``plan`` (`read_plan`, blocks of ``rows`` rows): ``[S, H * Dh]`` in
    `runtime.batching._attend`'s output dtype, zeros for a slot the plan
    does not read."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    slots, heads, dh = q.shape
    groups = heads // hkv
    width = k_stack.shape[-1]
    pairs = slots * (k_stack.shape[2] // rows)
    dtype = jnp.promote_types(v_stack.dtype, q.dtype)
    mine = jnp.pad(
        q.reshape(slots, hkv, groups, dh).transpose(0, 2, 1, 3).reshape(
            slots, groups, hkv * dh),
        ((0, 0), (0, 0), (0, width - hkv * dh)))
    padded = _padded_heads(heads, q.dtype)
    interpret = (jax.default_backend() != "tpu" if _INTERPRET is None
                 else _INTERPRET)
    out = pl.pallas_call(
        functools.partial(_kernel, pairs=pairs, rows=rows, hkv=hkv,
                          groups=groups, dh=dh, dtype=dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(1,),
            in_specs=[pl.BlockSpec(mine.shape, lambda i, *_: (0, 0, 0)),
                      pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((slots, groups, width),
                                   lambda i, *_: (0, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, rows, width), k_stack.dtype),
                pltpu.VMEM((2, rows, width), v_stack.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.VMEM((padded, width), q.dtype),
                pltpu.VMEM((padded, 1), jnp.float32),
                pltpu.VMEM((padded, 1), jnp.float32),
                pltpu.VMEM((padded, width), jnp.float32),
                pltpu.VMEM((padded, width), jnp.int32)]),
        out_shape=jax.ShapeDtypeStruct((slots, groups, width), dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name="folded_attention",
    )(jnp.asarray(at, jnp.int32).reshape(1), plan, mine, k_stack, v_stack)
    # [S, G, Hkv x Dh (+ pad)] -> heads in their own order, k * G + j.
    out = out[:, :, :hkv * dh].reshape(slots, groups, hkv, dh)
    return out.transpose(0, 2, 1, 3).reshape(slots, heads * dh)
