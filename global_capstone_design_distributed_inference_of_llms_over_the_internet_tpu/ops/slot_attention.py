"""Decode attention over ONE layer of a slot-major cache stack, each slot
read up to its own last block.

A decode step has one query row a slot, and the slots differ in length. XLA
bounds a read by a value through a loop whose trips each cost a few
microseconds and whose running sum goes through HBM
(`runtime.batching._block_stats`), and its read stops where the LONGEST
active slot stops, for every slot, active or not. This kernel takes
the slots' OWN block counts and row limits (`read_plan`) and reads, for each
slot, that slot's blocks and no others:

  * the K and V stacks stay WHOLE in HBM, as the layer scan carries them;
    the layer index and the plan are scalar-prefetch operands, so nothing
    slices or stages a layer for the call (`ops.int8_kernel`'s stacked
    form does the same with its weights);
  * ONE invocation walks the plan's (slot, block) pairs in a loop whose
    trip count is the plan's total, a value: a slot with no block costs
    nothing, and a pair's K and V rows (dense, in the layout they rest in)
    arrive through a double-buffered ``make_async_copy`` started one pair
    ahead;
  * a block is ONE matrix operand of the MXU, whichever way the stack holds
    a row. A FOLDED row (``[W]``, the KV heads side by side in the lanes:
    `runtime.batching.kv_fold_width`): the block is ``[block, W]``, a
    head's query sits in the lanes of its own KV head and is zero in all
    others (block-diagonal: the zeros add nothing to a score), and every
    head's scores against the block are one product ``[H, W] x [block,
    W]^T``. A row that stays ``[Hkv, Dh]`` (``Dh`` whole lane tiles, the KV
    heads down the sublanes): the block's ``[block, Hkv, Dh]`` rows are, as
    they rest, the ``[block x Hkv, Dh]`` operand of ``[H, Dh] x [block x
    Hkv, Dh]^T``, whose column ``(r, k)`` is row r's score for the heads of
    KV head k and is masked for every other head; the probabilities' zeros
    there drop the other heads' values from ``p x V``. Either way the MXU
    multiplies ``Hkv`` times what it must, which hides behind the block's
    DMA, and no row is re-laid;
  * the mask is ONE comparison, a row's index in its slot against the
    slot's LIMIT (the plan's: a query at position p of a plain stack sees
    ``p + 1`` rows; a stack that holds something else, a window of
    positions or summaries, states its own); the softmax is online, its
    running max, denominator and weighted sum float32 in VMEM scratch,
    never in HBM; at a slot's last block each head's sum over the
    denominator is written out, or (``stats``) the three as they stand,
    for a caller that merges two stacks' reads into one softmax.

The arithmetic is `runtime.batching._attend`'s: operands in their own
dtype, float32 scores, statistics and sums; a row past a query's limit has
probability exactly 0 there, so leaving it unread drops no term.

Off the TPU the call runs through the Pallas interpreter: where the stack
is folded always (a test that folds on the CPU), where it is not only for
a test that asks (`engaged`). PERF.md section 6, PRs 52 and 53.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from .attention import NEG_INF

VMEM_LIMIT = 32 * 1024 * 1024

# None: the interpreter wherever the backend is not a TPU. A rehearsal that
# compiles for a described chip from a CPU process sets False; a test that
# wants the kernel where only a TPU takes it by itself (`engaged`) sets True.
_INTERPRET: Optional[bool] = None

# A row index no limit reaches: the columns of another KV head's rows.
_NEVER = 2 ** 30


def engaged() -> bool:
    """Whether a program that reads by the kernel only where the kernel is
    the chip's (rows that stay ``[Hkv, Dh]``: every other backend keeps the
    program it had) does so in this process: on a TPU, or where a test has
    set the hook."""
    return jax.default_backend() == "tpu" or _INTERPRET is not None


def read_plan(blocks, limit, most: int):
    """The kernel's walk over a layer, as ONE int32 vector made once a step
    (every layer of the step reads by it): ``[total, slot of pair i (S x
    most), block of pair i (S x most), blocks (S), limit (S)]``. ``blocks``
    ``[S]`` are the slots' own block counts (0: the slot is not read),
    ``limit`` ``[S]`` how many of a slot's rows its query may see (rows
    ``0 .. limit - 1``), ``most`` the blocks of a whole slot. Pairs ``0 ..
    total - 1`` are slot-major, a slot's blocks in order; the entries past
    ``total`` are never read."""
    slots = blocks.shape[0]
    blocks = blocks.astype(jnp.int32)
    ends = jnp.cumsum(blocks)
    i = jnp.arange(slots * most, dtype=jnp.int32)
    slot = jnp.minimum((i[:, None] >= ends[None, :]).sum(-1), slots - 1)
    block = i - (ends - blocks)[slot]
    return jnp.concatenate(
        [ends[-1:], slot, block, blocks, limit]).astype(jnp.int32)


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


def _kernel(at_ref, plan_ref, q_ref, k_hbm, v_hbm, *refs, pairs, rows, hkv,
            folded, groups, dh, dtype, stats):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n_out = 3 if stats else 1           # the sums (, the max, the denominator)
    outs, scratch = refs[:n_out], refs[n_out:]
    out_ref = outs[0]
    k_buf, v_buf, sem, q_wide, m_ref, l_ref, acc_ref, seen_ref = scratch[:8]
    slots = q_ref.shape[0]
    total, at = plan_ref[0], at_ref[0]

    # The plan by part (`read_plan`): pair i's slot and block, a slot's own
    # block count and its row limit.
    def slot_of(i):
        return plan_ref[1 + i]

    def block_of(i):
        return plan_ref[1 + pairs + i]

    def blocks_of(slot):
        return plan_ref[1 + 2 * pairs + slot]

    def limit_of(slot):
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
    # ``j * Hkv + k`` (then zero rows up to whole tiles). Which row of its
    # block a column of a head's scores is. Folded rows: column r is row r,
    # for every head. Rows that stay ``[Hkv, Dh]``: column ``r * Hkv + k``
    # is row r for the heads of KV head k and, for every other head,
    # `_NEVER` (that product means nothing).
    seen = jax.lax.broadcasted_iota(jnp.int32, seen_ref.shape, 1)
    if not folded:
        head = jax.lax.broadcasted_iota(jnp.int32, seen_ref.shape, 0)
        seen = jnp.where(
            jax.lax.rem(seen, hkv) == jax.lax.rem(head, hkv),
            jax.lax.div(seen, hkv), _NEVER)
    seen_ref[...] = seen
    if folded:
        # Which group j a lane of a kernel row belongs to: the row's own in
        # the lanes of its KV head, -1 everywhere else (another head's
        # lanes, the pad lanes, the pad rows). A slot's queries arrive as
        # ``[G, W]``, head ``(j, k)`` in row j at KV head k's lanes: row
        # ``j * Hkv + k`` of the block-diagonal operand is row j of that
        # where ``own == j``, and each output row j collects the sums'
        # lanes where ``own == j``.
        own_ref = scratch[8]
        row = jax.lax.broadcasted_iota(jnp.int32, own_ref.shape, 0)
        lane = jax.lax.broadcasted_iota(jnp.int32, own_ref.shape, 1)
        own = jnp.full(own_ref.shape, -1, jnp.int32)
        for j in range(groups):
            first = (row - j * hkv) * dh
            own = jnp.where((row >= j * hkv) & (row < (j + 1) * hkv)
                            & (lane >= first) & (lane < first + dh), j, own)
        own_ref[...] = own
    # A slot that is not read: zeros, which the caller discards; as
    # statistics, a read that saw nothing (it weighs nothing in a merge).
    out_ref[...] = jnp.zeros_like(out_ref)
    if stats:
        outs[1][...] = jnp.full_like(outs[1], NEG_INF)
        outs[2][...] = jnp.zeros_like(outs[2])

    def operand(buf_ref, buf):
        """A block as it rests: ``[block, W]``, or ``[block x Hkv, Dh]``."""
        got = buf_ref[buf]
        return got.reshape(-1, got.shape[-1])

    def pair(i, _):
        buf = jax.lax.rem(i, 2)
        slot, block = slot_of(i), block_of(i)

        @pl.when(i + 1 < total)
        def _():
            for copy in copies(i + 1, 1 - buf):
                copy.start()

        @pl.when(block == 0)
        def _():
            if folded:
                mine = q_ref[slot].astype(jnp.float32)          # [G, W]
                wide = jnp.zeros(own_ref.shape, jnp.float32)
                for j in range(groups):
                    wide = jnp.where(own_ref[...] == j, mine[j:j + 1], wide)
                q_wide[...] = wide.astype(q_wide.dtype)
            else:
                q_wide[...] = q_ref[slot]                       # [HP, Dh]
            m_ref[...] = jnp.full_like(m_ref, NEG_INF)
            l_ref[...] = jnp.zeros_like(l_ref)
            acc_ref[...] = jnp.zeros_like(acc_ref)

        for copy in copies(i, buf):
            copy.wait()
        q = q_wide[...]                                         # [HP, C]
        scores = jax.lax.dot_general(
            q, operand(k_buf, buf).astype(q.dtype), (((1,), (1,)), ((), ())),
            precision=_precision(q.dtype),
            preferred_element_type=jnp.float32)     # [HP, block (x Hkv)]
        scores = jnp.where(
            seen_ref[...] < limit_of(slot) - block * rows, scores, NEG_INF)
        m = m_ref[...]
        m2 = jnp.maximum(m, scores.max(-1, keepdims=True))
        corr = jnp.exp(m - m2)
        w = jnp.exp(scores - m2)
        v = operand(v_buf, buf)
        m_ref[...] = m2
        l_ref[...] = l_ref[...] * corr + w.sum(-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jnp.dot(
            w.astype(v.dtype).astype(dtype), v.astype(dtype),
            precision=_precision(dtype),
            preferred_element_type=jnp.float32)                 # [HP, C]

        @pl.when(block == blocks_of(slot) - 1)
        def _():
            out = acc_ref[...]
            if stats:
                outs[1][slot] = m_ref[...]
                outs[2][slot] = l_ref[...]
            else:
                out = out / jnp.maximum(l_ref[...], 1e-30)
            if folded:
                for j in range(groups):
                    out_ref[slot, pl.ds(j, 1), :] = jnp.where(
                        own_ref[...] == j, out, 0.0).sum(
                            0, keepdims=True).astype(out_ref.dtype)
            else:
                out_ref[slot] = out.astype(out_ref.dtype)

    jax.lax.fori_loop(0, total, pair, None)


def slot_attention(q, k_stack, v_stack, at, plan, *, rows: int, hkv: int,
                   stats: bool = False):
    """Attention of one query row a slot, ``q`` ``[S, H, Dh]`` (rotated and
    scaled), over layer ``at`` of the stacks ``[L, S, n, Hkv, Dh]`` (folded:
    ``[L, S, n, W]``) by ``plan`` (`read_plan`, blocks of ``rows`` rows):
    ``[S, H * Dh]`` in `runtime.batching._attend`'s output dtype, zeros for
    a slot the plan does not read. ``stats``: the softmax's float32
    statistics instead, ``(max [S, H], denominator [S, H], weighted sum
    [S, H, Dh])``, not yet divided; ``(NEG_INF, 0, 0)`` for a slot that is
    not read."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    slots, heads, dh = q.shape
    groups = heads // hkv
    folded = k_stack.ndim == 4
    width = k_stack.shape[-1]
    pairs = slots * (k_stack.shape[2] // rows)
    dtype = jnp.promote_types(v_stack.dtype, q.dtype)
    padded = _padded_heads(heads, q.dtype)
    # Head (j, k) of KV head k: [S, G, Hkv, Dh].
    mine = q.reshape(slots, hkv, groups, dh).transpose(0, 2, 1, 3)
    if folded:
        mine = jnp.pad(mine.reshape(slots, groups, hkv * dh),
                       ((0, 0), (0, 0), (0, width - hkv * dh)))
    else:
        mine = jnp.pad(mine.reshape(slots, heads, dh),
                       ((0, 0), (0, padded - heads), (0, 0)))
    out_rows = groups if folded else padded
    interpret = (jax.default_backend() != "tpu" if _INTERPRET is None
                 else _INTERPRET)
    whole = lambda shape: pl.BlockSpec(shape, lambda i, *_: (0,) * len(shape))
    out_shapes = [jax.ShapeDtypeStruct(
        (slots, out_rows, width), jnp.float32 if stats else dtype)]
    if stats:
        out_shapes += [jax.ShapeDtypeStruct((slots, padded, 1),
                                            jnp.float32)] * 2
    got = pl.pallas_call(
        functools.partial(_kernel, pairs=pairs, rows=rows, hkv=hkv,
                          folded=folded, groups=groups, dh=dh, dtype=dtype,
                          stats=stats),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(1,),
            in_specs=[whole(mine.shape),
                      pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=[whole(s.shape) for s in out_shapes],
            scratch_shapes=[
                pltpu.VMEM((2, rows) + k_stack.shape[3:], k_stack.dtype),
                pltpu.VMEM((2, rows) + v_stack.shape[3:], v_stack.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.VMEM((padded, width), q.dtype),
                pltpu.VMEM((padded, 1), jnp.float32),
                pltpu.VMEM((padded, 1), jnp.float32),
                pltpu.VMEM((padded, width), jnp.float32),
                pltpu.VMEM((padded, rows * (1 if folded else hkv)),
                           jnp.int32)]
            + ([pltpu.VMEM((padded, width), jnp.int32)] if folded else [])),
        out_shape=out_shapes,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name="slot_attention",
    )(jnp.asarray(at, jnp.int32).reshape(1), plan, mine, k_stack, v_stack)

    def by_head(x, last):
        """Kernel rows ``j * Hkv + k`` -> heads in their own order, ``k * G
        + j``: ``[S, H] + last``."""
        return x[:, :heads].reshape(slots, groups, hkv, *last).swapaxes(
            1, 2).reshape(slots, heads, *last)

    out = got[0]
    if folded:      # [S, G, Hkv x Dh (+ pad)]: rows j, KV heads in the lanes
        out = out[:, :, :hkv * dh].reshape(slots, groups * hkv, dh)
    out = by_head(out, (dh,))
    if stats:
        return by_head(got[1], ()), by_head(got[2], ()), out
    return out.reshape(slots, heads * dh)
