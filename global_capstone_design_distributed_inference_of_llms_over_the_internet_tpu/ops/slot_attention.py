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

A LATENT row under a learned selection (`runtime.batching._attend_latent`'s
decode read; ``v_stack=None``, ``select``) is the same walk with one operand
fewer and one mask term more:

  * a latent row is ONE row every head shares, its keys the row and its
    values the row's first lanes: as it rests in ``[L, S, n, W]`` it is the
    row of one KV head, so ONE stack is keys and values both, a pair's
    block is copied once and is the operand of both products, and the
    caller keeps the value lanes of the float32 sums it is handed;
  * the selection is a MASK, made here: the slots' float32 index scores
    ``[S, n]`` arrive whole in VMEM and, while the first block is on its
    way, `_threshold` finds every slot's k-th largest score by bisection on
    the scores' integer image and, among the entries equal to it, the
    position up to which they are admitted. That is ``jax.lax.top_k``'s
    pick entry for entry, ties to the lower position, with no sort and no
    index. A row is in iff it is under the slot's limit AND picked; an
    unselected row has probability exactly 0, as in the sum over the
    gathered rows. XLA's gather moves a 1280-byte row in ~15 ns, a tenth
    of the HBM rate, whoever issues it: a slot's own blocks, contiguous,
    arrive at eight times that, so while a slot holds under ~16 times the
    rows it selects the dense read is the shorter one
    (`runtime.batching.LATENT_DENSE`);
  * the call names the stack FIRST among its array operands and has one
    result: a trace knows an operation by the first characters of its text,
    and this read is found there by the stack it reads.

The arithmetic is `runtime.batching._attend`'s: operands in their own
dtype, float32 scores, statistics and sums; a row past a query's limit has
probability exactly 0 there, so leaving it unread drops no term.

Off the TPU the call runs through the Pallas interpreter: where the stack
is folded always (a test that folds on the CPU), where it is not only for
a test that asks (`engaged`). PERF.md section 6, PRs 52, 53 and 56.
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


def _threshold(pick_ref, sel_ref, topk: int):
    """``pick_ref`` ``[S, n]`` int32 := which ``topk`` entries of every row
    of the float32 scores ``sel_ref`` are its largest, 1 or 0: EXACTLY
    ``jax.lax.top_k``'s pick, ties to the lower position
    (`runtime.batching.select_topk`'s definition, and its method). The
    k-th largest value by bisection on the scores' monotone integer image
    (32 compares and counts, all slots at once: a slot is a sublane), then,
    among the entries EQUAL to it, the position up to which the first
    ``topk - (number above)`` of them lie (a second bisection, over
    positions). No sort, and no index is made."""
    slots, n = sel_ref.shape
    low = jnp.int32(-2 ** 31)

    def count(cond):                                 # [S, 1], exact in f32
        return jnp.where(cond, 1.0, 0.0).sum(-1, keepdims=True)

    bits = jax.lax.bitcast_convert_type(sel_ref[...], jnp.int32)
    # monotone as a SIGNED number; ``image ^ low`` is the same order unsigned
    pick_ref[...] = jnp.where(bits >= 0, bits, bits ^ jnp.int32(2 ** 31 - 1))

    def value_bit(i, kth):                           # kth: unsigned, as bits
        cand = kth | jax.lax.shift_left(jnp.int32(1), jnp.int32(31) - i)
        enough = count(pick_ref[...] >= (cand ^ low)) >= topk
        return jnp.where(enough, cand, kth)

    kth = jax.lax.fori_loop(0, 32, value_bit,
                            jnp.zeros((slots, 1), jnp.int32)) ^ low
    wanted = topk - count(pick_ref[...] > kth)       # ties to admit: >= 1
    width = max((n - 1).bit_length(), 1)

    def position_bit(i, cut):
        # the largest position with fewer than ``wanted`` ties BEFORE it:
        # that of the last tie admitted
        cand = cut | jax.lax.shift_left(jnp.int32(1), jnp.int32(width - 1) - i)
        at = jax.lax.broadcasted_iota(jnp.int32, (slots, n), 1)
        few = count((pick_ref[...] == kth) & (at < cand)) < wanted
        return jnp.where(few, cand, cut)

    cut = jax.lax.fori_loop(0, width, position_bit,
                            jnp.zeros((slots, 1), jnp.int32))
    image = pick_ref[...]
    at = jax.lax.broadcasted_iota(jnp.int32, (slots, n), 1)
    pick_ref[...] = ((image > kth) | ((image == kth) & (at <= cut))).astype(
        jnp.int32)


def _kernel(at_ref, plan_ref, *refs, pairs, rows, hkv, folded, groups, dh,
            dtype, stats, shared, topk):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    # The operands: the queries and the two stacks; ONE stack that is keys
    # and values both (``shared``) comes first, then the queries; the
    # selection's scores (``topk``) last.
    refs = list(refs)
    if shared:
        stacks, q_ref = (refs.pop(0),), refs.pop(0)
    else:
        q_ref, stacks = refs.pop(0), (refs.pop(0), refs.pop(0))
    sel_ref = refs.pop(0) if topk else None
    n_out = 3 if stats else 1           # the sums (, the max, the denominator)
    outs, scratch = refs[:n_out], refs[n_out:]
    out_ref = outs[0]
    k_buf = scratch.pop(0)
    v_buf = k_buf if shared else scratch.pop(0)
    sem, q_wide, m_ref, l_ref, acc_ref, seen_ref = scratch[:6]
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
        """Pair i's K and V rows into buffer ``buf`` (ONE copy where one
        stack is both)."""
        at_rows = pl.ds(pl.multiple_of(block_of(i) * rows, rows), rows)
        return [pltpu.make_async_copy(
            stack.at[at, slot_of(i), at_rows], dst.at[buf], sem.at[n, buf])
            for n, (stack, dst) in enumerate(zip(stacks, (k_buf, v_buf)))]

    @pl.when(total > 0)
    def _():
        for copy in copies(0, 0):
            copy.start()

    if topk:
        # Which rows of a slot the selection admits, for all slots at once
        # while the first block is on its way. A slot whose limit is at most
        # ``topk`` sees every causal row: where no slot is past it, nothing
        # is searched.
        pick_ref = scratch[-1]
        past = functools.reduce(
            jnp.logical_or, [(blocks_of(s) > 0) & (limit_of(s) > topk)
                             for s in range(slots)])

        @pl.when(past)
        def _():
            _threshold(pick_ref, sel_ref, topk)

        @pl.when(jnp.logical_not(past))
        def _():
            pick_ref[...] = jnp.ones_like(pick_ref)

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
        own_ref = scratch[6]
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
        ok = seen_ref[...] < limit_of(slot) - block * rows
        if topk:    # a row is in iff under the limit AND of the selection
            ok &= pick_ref[pl.ds(slot, 1), pl.ds(
                pl.multiple_of(block * rows, rows), rows)] != 0
        scores = jnp.where(ok, scores, NEG_INF)
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
                   stats: bool = False, select=None):
    """Attention of one query row a slot, ``q`` ``[S, H, Dh]`` (rotated and
    scaled), over layer ``at`` of the stacks ``[L, S, n, Hkv, Dh]`` (folded:
    ``[L, S, n, W]``) by ``plan`` (`read_plan`, blocks of ``rows`` rows):
    ``[S, H * Dh]`` in `runtime.batching._attend`'s output dtype, zeros for
    a slot the plan does not read. ``stats``: the softmax's float32
    statistics instead, ``(max [S, H], denominator [S, H], weighted sum
    [S, H, Dh])``, not yet divided; ``(NEG_INF, 0, 0)`` for a slot that is
    not read.

    ``v_stack=None``: ONE stack ``[L, S, n, W]`` whose row every head
    shares (``hkv`` 1) is keys and values both, ``q`` ``[S, H, W]`` against
    the row as it rests: ``[S, H, W]`` float32, the normalised sums as the
    scratch holds them (the caller keeps the lanes that are values and
    projects them on). ``select=(scores, k)``: of a slot's rows under its
    limit only the ``k`` whose ``scores`` (``[S, n]`` float32) are largest,
    as ``jax.lax.top_k`` picks them (`_threshold`)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    slots, heads, dh = q.shape
    groups = heads // hkv
    shared = v_stack is None
    # A row of ONE KV head is both forms at once: the row as it rests is the
    # ``[block, W]`` operand and no lane is another head's, so it takes the
    # walk of rows that stay ``[Hkv, Dh]``, with nothing block-diagonal.
    folded = k_stack.ndim == 4 and not shared
    width = k_stack.shape[-1]
    pairs = slots * (k_stack.shape[2] // rows)
    dtype = jnp.promote_types((k_stack if shared else v_stack).dtype, q.dtype)
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
        (slots, out_rows, width),
        jnp.float32 if stats or shared else dtype)]
    if stats:
        out_shapes += [jax.ShapeDtypeStruct((slots, padded, 1),
                                            jnp.float32)] * 2
    stacks = [k_stack] if shared else [k_stack, v_stack]
    scores, topk = select or (None, 0)
    # (operand, its spec): the stack that is both goes FIRST (a trace names
    # a call by its first operands, and this read is found by the stack it
    # reads), the selection's scores last
    ins = [(x, pl.BlockSpec(memory_space=pl.ANY)) for x in stacks]
    ins.insert(len(ins) if shared else 0, (mine, whole(mine.shape)))
    if topk:
        ins.append((scores, whole(scores.shape)))
    operands, in_specs = zip(*ins)
    got = pl.pallas_call(
        functools.partial(_kernel, pairs=pairs, rows=rows, hkv=hkv,
                          folded=folded, groups=groups, dh=dh, dtype=dtype,
                          stats=stats, shared=shared, topk=topk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(1,),
            in_specs=list(in_specs),
            out_specs=[whole(s.shape) for s in out_shapes],
            scratch_shapes=[
                pltpu.VMEM((2, rows) + x.shape[3:], x.dtype) for x in stacks]
            + [pltpu.SemaphoreType.DMA((len(stacks), 2)),
               pltpu.VMEM((padded, width), q.dtype),
               pltpu.VMEM((padded, 1), jnp.float32),
               pltpu.VMEM((padded, 1), jnp.float32),
               pltpu.VMEM((padded, width), jnp.float32),
               pltpu.VMEM((padded, rows * (1 if folded else hkv)),
                          jnp.int32)]
            + ([pltpu.VMEM((padded, width), jnp.int32)] if folded else [])
            + ([pltpu.VMEM(scores.shape, jnp.int32)] if topk else [])),
        out_shape=out_shapes,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name="slot_attention",
    )(jnp.asarray(at, jnp.int32).reshape(1), plan, *operands)

    def by_head(x, last):
        """Kernel rows ``j * Hkv + k`` -> heads in their own order, ``k * G
        + j``: ``[S, H] + last``."""
        return x[:, :heads].reshape(slots, groups, hkv, *last).swapaxes(
            1, 2).reshape(slots, heads, *last)

    out = got[0]
    if shared:
        return out[:, :heads]
    if folded:      # [S, G, Hkv x Dh (+ pad)]: rows j, KV heads in the lanes
        out = out[:, :, :hkv * dh].reshape(slots, groups * hkv, dh)
    out = by_head(out, (dh,))
    if stats:
        return by_head(got[1], ()), by_head(got[2], ()), out
    return out.reshape(slots, heads * dh)
