"""A decode tick's attention reads each cache layer only up to the longest
ACTIVE slot, by blocks of `runtime.batching.ATTN_BLOCK` rows
(`_attend_cached`, `attn_blocks`): the same sums as the full read that
`_attend` makes under a mask, with the rows past the bound never touched.

The tests run at 8-row blocks on 32-row slots (`small_blocks`), so that the
block edges, a slot at ``max_len - T`` and a slot parked near ``max_len``
all fit a tiny engine. The oracle of the engine-level cases is the full
read as it was: `test_batching.slab_policy_decode_span(full_read=True)`."""

import types
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu import (
    telemetry,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.ops import (
    slot_attention,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime import (
    batching as B,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.telemetry import (
    catalog,
)

from engines import (
    all_eqns,
    both_policies,
    cache_writes_and_slabs,
    FAMILIES,
    family_engine,
    greedy_entry as greedy,
    ids_of,
    looped,
    LOOPED_HF,
    program_args,
    PROMPTS,
    rel_rms,
    rider_of,
    SLAB_POLICIES,
    two_decoding,
)

BLOCK, MAX_LEN = 8, 32


@pytest.fixture
def small_blocks(monkeypatch):
    monkeypatch.setattr(B, "ATTN_BLOCK", BLOCK)


def head_cfg(groups=1, **kw):
    """What `_attend` reads of a configuration."""
    return types.SimpleNamespace(**{
        "num_kv_heads": 2, "num_heads": 2 * groups, "head_dim": 8,
        "query_scale": 0.0, "attn_softcap": 0.0, "sliding_window": None,
        **kw})


def full_read(cfg, lp, q, k_layer, v_layer, q_pos):
    k_pos = jnp.arange(k_layer.shape[1], dtype=jnp.int32)[None, None, :]
    return B._attend(cfg, lp, q, k_layer, v_layer,
                     (B._visible(cfg, q_pos, k_pos), q_pos, k_pos))


def cached_read(cfg, lp, q, k, v, at, lengths, active, q_pos):
    blocks = B.attn_blocks(lengths, active, q.shape[1], k.shape[2], jnp)
    return B._attend_cached(cfg, lp, q, B._CacheLayer(k, at, blocks),
                            B._CacheLayer(v, at, blocks), q_pos)


# The slots of the unit cases: lengths at the block's edges, one that ends
# at max_len exactly, and an INACTIVE one parked past every active bound.
def slots_for(t):
    lengths = [BLOCK - 1, BLOCK, BLOCK + 1, 0, 2 * BLOCK - t, MAX_LEN - 1]
    return (jnp.asarray(lengths, jnp.int32),
            jnp.asarray([True] * 5 + [False]))


VARIANTS = {
    "plain": ({}, {}),
    "seven-queries-a-kv-head": ({"groups": 7}, {}),
    "sliding-window": ({"sliding_window": 5}, {}),
    "window-leaf": ({}, {"window": jnp.asarray(6.0, jnp.bfloat16)}),
    "window-leaf-global": ({"sliding_window": 5}, {"window": jnp.int32(0)}),
    "softcap-and-scale": ({"attn_softcap": 2.0, "query_scale": 0.3}, {}),
}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("t", [1, 4])
@pytest.mark.parametrize("variant", sorted(VARIANTS))
def test_the_bounded_read_is_the_full_read(small_blocks, variant, t, dtype):
    """`_attend_cached` against `_attend` over the whole layer, on every
    ACTIVE slot: float32 to 1e-6, bfloat16 to its rounding."""
    kw, lp = VARIANTS[variant]
    cfg = head_cfg(**kw)
    lengths, active = slots_for(t)
    s = lengths.shape[0]
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(t), 3)
    q = jax.random.normal(kq, (s, t, cfg.num_heads, 8)).astype(dtype)
    k = jax.random.normal(kk, (3, s, MAX_LEN, 2, 8)).astype(dtype)
    v = jax.random.normal(kv, (3, s, MAX_LEN, 2, 8)).astype(dtype)
    q_pos = (lengths[:, None] + jnp.arange(t))[:, :, None]
    got = jax.jit(partial(cached_read, cfg))(
        lp, q, k, v, jnp.int32(1), lengths, active, q_pos)
    want = full_read(cfg, lp, q, k[1], v[1], q_pos)
    assert got.shape == want.shape and got.dtype == want.dtype
    live = np.asarray(active)
    tol = 1e-6 if dtype == "float32" else 2e-2
    np.testing.assert_allclose(
        np.asarray(got, np.float32)[live], np.asarray(want, np.float32)[live],
        atol=tol, rtol=tol)


@pytest.mark.parametrize("t", [1, 3])
def test_rows_past_the_bound_are_never_read(small_blocks, t):
    """NaN in every row past the blocks the bound covers: the bounded read
    returns what it returns on clean stacks, bit for bit; the full read
    returns NaN (probability 0 times NaN). With NO active slot nothing is
    read at all: a stack of NaN gives finite rows."""
    cfg = head_cfg()
    lengths, active = slots_for(t)
    s = lengths.shape[0]
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(7), 3)
    q = jax.random.normal(kq, (s, t, 2, 8))
    k = jax.random.normal(kk, (2, s, MAX_LEN, 2, 8))
    v = jax.random.normal(kv, (2, s, MAX_LEN, 2, 8))
    q_pos = (lengths[:, None] + jnp.arange(t))[:, :, None]
    bound = int(B.attn_blocks(np.asarray(lengths), np.asarray(active), t,
                              MAX_LEN)) * BLOCK
    assert bound == 2 * BLOCK < MAX_LEN
    k_bad, v_bad = k.at[:, :, bound:].set(jnp.nan), v.at[:, :, bound:].set(
        jnp.nan)
    run = jax.jit(partial(cached_read, cfg, {}))
    clean = run(q, k, v, jnp.int32(0), lengths, active, q_pos)
    dirty = run(q, k_bad, v_bad, jnp.int32(0), lengths, active, q_pos)
    live = np.asarray(active)
    assert np.isfinite(np.asarray(dirty)[live]).all()
    np.testing.assert_array_equal(np.asarray(dirty)[live],
                                  np.asarray(clean)[live])
    assert np.isnan(np.asarray(
        full_read(cfg, {}, q, k_bad[0], v_bad[0], q_pos))[live]).any()
    nobody = run(q, k * jnp.nan, v * jnp.nan, jnp.int32(0), lengths,
                 jnp.zeros_like(active), q_pos)
    assert np.isfinite(np.asarray(nobody)).all()


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("slots", ["one-idle", "none-active"])
def test_the_loop_s_statistics_are_the_full_read_s(small_blocks, slots,
                                                   dtype):
    """`_block_stats`, the one read beside the kernel, at ONE query row a
    KV head, over stacks that are NaN past the bound: the maximum, the
    denominator and the weighted sum of `_attend`'s softmax over the dense
    rows, undivided (float32 to 2e-6, bfloat16 to its rounding), on every
    active slot beside an idle one parked past the bound; with NO active
    slot the loop makes no trip: ``NEG_INF``, zeros, and nothing read."""
    cfg = head_cfg()
    lengths, active = slots_for(1)
    if slots == "none-active":
        active = jnp.zeros_like(active)
    s = lengths.shape[0]
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(11), 3)
    q = jax.random.normal(kq, (s, 1, 2, 8)).astype(dtype)
    k = jax.random.normal(kk, (2, s, MAX_LEN, 2, 8)).astype(dtype)
    v = jax.random.normal(kv, (2, s, MAX_LEN, 2, 8)).astype(dtype)
    q_pos = lengths[:, None, None]
    bound = int(B.attn_blocks(np.asarray(lengths), np.asarray(active), 1,
                              MAX_LEN)) * BLOCK
    assert bound == (2 * BLOCK if slots == "one-idle" else 0)

    qg = q.reshape(s, 1, 2, 1, 8) * B._qscale(cfg)

    @jax.jit
    def stats(qg, k, v):
        blocks = B.attn_blocks(lengths, active, 1, MAX_LEN, jnp)
        return B._block_stats(
            cfg, {}, qg, B._CacheLayer(k, jnp.int32(1), blocks),
            B._CacheLayer(v, jnp.int32(1), blocks), q_pos,
            lambda k_pos: B._visible(cfg, q_pos, k_pos))

    m, l, acc = stats(qg, k.at[:, :, bound:].set(jnp.nan),
                      v.at[:, :, bound:].set(jnp.nan))
    assert m.shape == l.shape == (s, 2, 1, 1) and acc.shape == (s, 2, 1, 1, 8)
    assert m.dtype == l.dtype == acc.dtype == jnp.float32
    if slots == "none-active":
        assert (np.asarray(m) == B.NEG_INF).all()
        assert not np.asarray(l).any() and not np.asarray(acc).any()
        return
    live = np.asarray(active)
    tol = 2e-6 if dtype == "float32" else 2e-2
    k_pos = jnp.arange(MAX_LEN, dtype=jnp.int32)[None, None, :]
    sc = np.asarray(B._masked_scores(
        cfg, {}, qg, k[1],
        (B._visible(cfg, q_pos, k_pos), q_pos, k_pos)))      # [S, 2, 1, 1, 32]
    np.testing.assert_array_equal(np.asarray(m)[live], sc.max(-1)[live])
    np.testing.assert_allclose(
        np.asarray(l)[live], np.exp(sc - sc.max(-1, keepdims=True))
        .sum(-1)[live], atol=tol, rtol=tol)
    want = full_read(cfg, {}, q, k[1], v[1], q_pos)          # [S, 1, 2 * 8]
    got = (acc / l[..., None]).transpose(0, 3, 1, 2, 4).reshape(s, 1, -1)
    np.testing.assert_allclose(
        np.asarray(got)[live], np.asarray(want, np.float32)[live],
        atol=tol, rtol=tol)


@pytest.mark.parametrize("per_slot", [False, True])
def test_the_bound_is_the_longest_active_slot(small_blocks, per_slot):
    """`attn_blocks`, the one statement of the bound, on the host's arrays
    and on the device's: blocks up to the last new row of the longest
    ACTIVE slot, never more than the slot holds, none with nobody active;
    a leading axis of ticks is reduced row by row. ``per_slot`` (the
    kernel's bound): each slot up to its OWN last new row, an inactive one
    0, the same clamp."""
    lengths = np.asarray([7, 8, 9, 31], np.int32)
    on = np.asarray([True, True, True, False])
    if per_slot:
        for t, want in ((1, [1, 2, 2, 0]), (7, [2, 2, 2, 0]),
                        (8, [2, 2, 3, 0])):
            for xp, wrap in ((np, np.asarray), (jnp, jnp.asarray)):
                got = B.attn_blocks(wrap(lengths), wrap(on), t, MAX_LEN, xp,
                                    per_slot=True)
                assert got.shape == (4,) and list(np.asarray(got)) == want
        own = partial(B.attn_blocks, per_slot=True)
        assert list(own(lengths, ~on, 1, MAX_LEN)) == [0, 0, 0, 4]
        assert list(own(lengths, ~on, 5, MAX_LEN)) == [0, 0, 0, 4]  # clamped
        assert not own(lengths, on & False, 1, MAX_LEN).any()
        ticks = np.arange(3)[:, None]
        lengths = np.asarray([1, 8, 9, 31], np.int32)
        np.testing.assert_array_equal(
            own(lengths[None] + ticks, ticks < np.asarray([3, 1, 0, 0]), 1,
                MAX_LEN), [[1, 2, 0, 0], [1, 0, 0, 0], [1, 0, 0, 0]])
        return
    for t, want in ((1, 2), (7, 2), (8, 3)):
        assert B.attn_blocks(lengths, on, t, MAX_LEN) == want
        assert int(B.attn_blocks(jnp.asarray(lengths), jnp.asarray(on), t,
                                 MAX_LEN, jnp)) == want
    assert B.attn_blocks(lengths, ~on, 1, MAX_LEN) == 4
    assert B.attn_blocks(lengths, ~on, 5, MAX_LEN) == 4      # clamped
    assert B.attn_blocks(lengths, on & False, 1, MAX_LEN) == 0
    ticks = np.arange(3)[:, None]
    lengths = np.asarray([1, 8, 9, 31], np.int32)
    np.testing.assert_array_equal(
        B.attn_blocks(lengths[None] + ticks, ticks < np.asarray([3, 1, 0, 0]),
                      1, MAX_LEN), [2, 1, 1])
    # the block divides the slot: 128 at the served lengths
    assert [B.attn_block(n) for n in (32, 8, 6, 36)] == [8, 8, 6, 6]
    # ... and where nothing near the block does, the slot is one block
    assert [B.attn_block(n) for n in (37, 34, 2)] == [37, 34, 2]


def test_served_lengths_read_by_blocks_of_128():
    assert B.ATTN_BLOCK == 128
    assert [B.attn_block(n) for n in (1024, 512, 2048, 64, 1000, 24, 1021)
            ] == [128, 128, 128, 64, 125, 24, 1021]


# -- the engine's programs against the full read as it was -------------------

@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("family", FAMILIES)
def test_decode_steps_are_the_full_read_s(small_blocks, monkeypatch, family,
                                          dtype):
    """Hidden rows and the whole K and V stacks after a plain step (T = 1),
    a verify step (T = 3) that takes one slot to ``max_len`` exactly, with
    one slot parked past the bound and sitting out, against the slab's
    round trip (a full read): gpt2, qwen2 (GQA, rotary), a sliding window,
    gemma-2 (a ``window`` leaf, softcap, a query scale)."""

    def drive():
        ex = family_engine(family, dtype, MAX_LEN)
        ex.lengths[ex._slot_of["d"]] = MAX_LEN - 1          # parked, out
        one = ex.decode_batch({"a": jnp.asarray([[3]], jnp.int32),
                               "b": jnp.asarray([[4]], jnp.int32)})
        ex.lengths[ex._slot_of["c"]] = MAX_LEN - 3
        three = ex.decode_batch({"a": jnp.asarray([[3, 9, 1]], jnp.int32),
                                 "c": jnp.asarray([[4, 8, 2]], jnp.int32)})
        return {"one.a": one["a"], "one.b": one["b"], "three.a": three["a"],
                "three.c": three["c"], "k": ex.k, "v": ex.v}

    want, got = both_policies(monkeypatch, drive, full_read=True)
    tol = 2e-5 if dtype == "float32" else 8e-2
    for name in want:
        np.testing.assert_allclose(
            np.asarray(got[name], np.float32),
            np.asarray(want[name], np.float32), atol=tol, rtol=tol,
            err_msg=name)


def test_a_burst_never_reads_past_its_bound(small_blocks, monkeypatch):
    """NaN in rows ``[16, 32)`` of every slot of every layer: a 4-tick
    burst and a decode step of sessions 3-7 rows long return the clean
    engine's tokens and finite, equal hidden rows. Under the full read
    (the slab's round trip) the same stacks give NaN."""

    def drive(poison):
        ex = family_engine("qwen2", "float32", MAX_LEN)
        if poison:
            ex.k = ex.k.at[:, :, 2 * BLOCK:].set(jnp.nan)
            ex.v = ex.v.at[:, :, 2 * BLOCK:].set(jnp.nan)
        out = ex.decode_burst({"a": greedy(3), "b": greedy(4)}, 4)
        h = ex.decode_batch({"c": jnp.asarray([[5, 6]], jnp.int32)})["c"]
        return out, np.asarray(h)

    clean, dirty = drive(False), drive(True)
    assert dirty[0] == clean[0]
    assert len(clean[0]["a"]["tokens"]) == 4
    assert np.isfinite(dirty[1]).all()
    np.testing.assert_array_equal(dirty[1], clean[1])
    with monkeypatch.context() as m:
        m.setattr(B, "_decode_span", SLAB_POLICIES[True])
        assert np.isnan(drive(True)[1]).any()


@pytest.mark.parametrize("kind", ["float32", "bfloat16"])
def test_a_looped_stack_with_a_rider_reads_by_blocks(monkeypatch, kind):
    """A looped stack's burst with a 21-row rider beside two decoding
    sessions (64-row slots), at 8-row blocks against one 64-row block (a
    full read through the same program): the same tokens, the rider's
    token, and K and V stacks that agree to rounding."""
    engines = []
    for block in (BLOCK, 64):
        with monkeypatch.context() as m:
            m.setattr(B, "ATTN_BLOCK", block)
            _, _, eng = looped(kind)
            got = eng.decode_burst(two_decoding(eng), 4,
                                   rider=rider_of("r", ids_of(21, 3)))
            engines.append((eng, got))
    (a, got), (b, want) = engines
    assert got == want
    loose = {"float32": 1e-5, "bfloat16": 2e-2}[kind]
    assert rel_rms(a.k, b.k) <= loose and rel_rms(a.v, b.v) <= loose


# -- the two counters ---------------------------------------------------------

@pytest.mark.parametrize("folded", [False, True])
def test_the_counters_against_a_hand_count(small_blocks, monkeypatch, folded):
    """4 slots of 32 rows, 8-row blocks. A 4-tick burst of "a" (5 rows,
    budget 4) and "b" (3 rows, budget 2: it stops after tick 1): the ticks
    begin at longest active lengths 5, 6, 7, 8, so they read 1, 1, 1 and 2
    blocks of every layer = 5 x 8 rows x 4 slots of 4 x 4 x 32. A verify
    step of 3 rows on "a" (now 9 rows: 2 blocks) adds 2 x 8 x 4 of 4 x 32.
    A burst in which nobody is left after tick 0 reads one block and spans
    two ticks.

    A ``folded`` engine's bursts read by the kernel, each slot its OWN
    blocks and an idle slot none: "a" 1 + 1 + 1 + 2 and "b" 1 + 1 = 7
    blocks of 8 rows, no factor of the slots; the verify step (three rows a
    slot) keeps the loop and the shared bound; the last burst reads ONE
    slot's one block."""
    if folded:
        monkeypatch.setattr(B, "kv_fold_width", lambda layout, hkv, dh: 128)
    telemetry.enable()
    try:
        ex = family_engine("gpt2", "float32", MAX_LEN)
        assert (ex.k.ndim == 4) == folded
        read = catalog.get("server_attn_rows_read_total")
        span = catalog.get("server_attn_rows_span_total")
        r0, s0 = read.value, span.value
        assert [len(PROMPTS[s]) for s in "ab"] == [5, 3]
        out = ex.decode_burst({"a": greedy(3), "b": {**greedy(4),
                                                      "budget": 2}}, 4)
        assert [len(out[s]["tokens"]) for s in "ab"] == [4, 2]
        burst = 7 * 8 if folded else 5 * 8 * 4
        assert (read.value - r0, span.value - s0) == (burst, 4 * 4 * 32)
        ex.decode_batch({"a": jnp.asarray([[3, 9, 1]], jnp.int32)})
        assert (read.value - r0, span.value - s0) == (
            burst + 2 * 8 * 4, 4 * 4 * 32 + 4 * 32)
        r1, s1 = read.value, span.value
        ex.decode_burst({"b": {**greedy(4), "budget": 1}}, 2)
        assert (read.value - r1, span.value - s1) == (
            1 * 8 if folded else 1 * 8 * 4, 2 * 4 * 32)
    finally:
        telemetry.disable()


def test_a_looped_burst_with_a_rider_reads_its_slots_by_the_kernel(
        small_blocks, monkeypatch):
    """A looped stack whose heads fill the lanes (``head_dim`` 128), with
    the kernel engaged: the slots' group of a burst tick goes by the kernel
    BESIDE the rider's rows, which keep their slice of one slot. The tokens
    of the two decoding sessions and the rider's first are those of the
    step path (a twin engine that reads by the loop), the stacks agree, and the counter reads the slots' OWN blocks: x begins the four
    ticks at 8..11 rows and y at 12..15, two 8-row blocks each a tick = 16
    blocks of 8 rows, where the shared bound reads 2 x 8 x 3 slots x 4."""
    hf = {**LOOPED_HF, "hidden_size": 256, "num_attention_heads": 2,
          "num_key_value_heads": 2, "head_dim": 128}
    telemetry.enable()
    try:
        read = catalog.get("server_attn_rows_read_total")
        seen = []
        for hook in (True, None):
            monkeypatch.setattr(slot_attention, "_INTERPRET", hook)
            _, _, eng = looped(hf=hf)
            assert eng._cache_read(1, True) == (
                "kernel" if hook else "loop")
            r0 = read.value
            got = eng.decode_burst(two_decoding(eng), 4,
                                   rider=rider_of("r", ids_of(21, 3)))
            seen.append((eng, got, read.value - r0))
    finally:
        telemetry.disable()
    (a, got, by_kernel), (b, want, shared) = seen
    assert got == want and set(got) == {"x", "y", "r"}
    assert rel_rms(a.k, b.k) <= 1e-5 and rel_rms(a.v, b.v) <= 1e-5
    assert (by_kernel, shared) == (16 * 8, 2 * 8 * 3 * 4)


# -- the lowered programs -------------------------------------------------------

def _in_scans(jaxpr, names, depth=0):
    """``(depth of enclosing scans, equation)`` of every equation whose
    primitive is one of ``names``."""
    for e in jaxpr.eqns:
        if e.primitive.name in names:
            yield depth, e
        for v in e.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else (v,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _in_scans(
                        sub, names, depth + (e.primitive.name == "scan"))


@pytest.mark.parametrize("family,program", [
    ("qwen2", "burst_tick"), ("qwen2", "decode_step-1"),
    ("gpt2", "decode_step-3"), ("gpt2", "burst_tick"),
    ("gpt2", "decode_step-1"), ("looped", "burst_tick")])
def test_the_program_bounds_its_read_inside_the_layer_scan(
        small_blocks, family, program):
    """ONE program a tick count or a step width, whatever the lengths. In
    its jaxpr the read of a cache layer sits inside the layer scan (inside
    the tick scan in a burst, inside the pass scan of a looped stack) and
    is bounded by a TRACED value there, whether several query rows share a
    KV head (grouped queries, T > 1) or each has its own (gpt2, a looped
    stack beside its rider lane): ONE loop with a traced trip count that
    carries the softmax statistics and never a stack or a layer, around two
    block-sized slices of the carried stacks; no conditional with a branch
    a block count, and no equation anywhere takes or yields a layer's
    ``[S, max_len, Hkv, Dh]``. No write takes a slab."""
    if family == "looped":
        _, _, ex = looped()
    else:
        ex = family_engine(family, "float32", MAX_LEN)
    S = ex.slots
    depth = (2 + (family == "looped")) if program == "burst_tick" else 1
    fn, args = program_args(ex, program)
    jaxpr = jax.make_jaxpr(fn)(*args).jaxpr
    reads = sorted(
        e.outvars[0].aval.shape[2] for e in all_eqns(jaxpr)
        if e.primitive.name == "dynamic_slice"
        and e.invars[0].aval.shape == ex.k.shape
        and e.outvars[0].aval.shape[1] == S)
    writes, slabs = cache_writes_and_slabs(jaxpr, ex.k.shape)
    assert {name for name, _ in writes} == {"scatter"}
    whiles = list(_in_scans(jaxpr, ("while",)))
    conds = [d for d, _ in _in_scans(jaxpr, ("cond",)) if d >= depth]
    assert [d for d, _ in whiles] == [depth] and not conds
    carried = [v.aval.shape for v in whiles[0][1].outvars]
    assert all(len(shape) <= 5 and ex.max_len not in shape
               for shape in carried), carried
    assert reads == [BLOCK, BLOCK] and slabs == []
    layer = ex.k.shape[1:]
    for e in all_eqns(jaxpr):
        assert all(getattr(v.aval, "shape", None) != layer
                   for v in e.invars), e.primitive.name


def test_one_program_for_every_length(small_blocks):
    """Bursts and steps at lengths that need 1, 2 and 4 blocks run the
    programs compiled for the first: one burst program a tick count, one
    decode step a width."""
    ex = family_engine("gpt2", "float32", MAX_LEN,   # its own: the programs
                       make=B.BatchedStageExecutor)  # are counted
    for at in (0, 9, 25):
        ex.lengths[ex._slot_of["a"]] = max(at, len(PROMPTS["a"]))
        ex.decode_burst({"a": greedy(3)}, 2)
        ex.decode_batch({"a": jnp.asarray([[3]], jnp.int32)})
    assert list(ex._burst_jits) == [2] and list(ex._decode_jits) == [1]
    assert ex._burst_jits[2]._cache_size() == 1
    assert ex._decode_jits[1]._cache_size() == 1
