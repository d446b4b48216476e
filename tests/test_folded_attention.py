"""`ops.slot_attention`: a decode step's attention over one layer of a
cache stack, FOLDED or with its rows as ``[Hkv, Dh]``, each slot read up to
its own last block.

On the CPU the kernel runs through the Pallas interpreter; the oracle is
`runtime.batching._attend` over the dense rows of the same layer. The
shapes are tiny (16-row blocks, 64-row slots) and every case of a shape
runs ONE compiled program: the whole file stays well under a minute. The
last tests compile the kernel at the five cells' served widths for a
DESCRIBED v5e (no chip): what Mosaic refuses, it refuses there."""

import functools
import re
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.ops import (
    slot_attention as FA,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime import (
    batching as B,
)

BLOCK, MAX_LEN, SLOTS, LAYERS = 16, 64, 4, 3

# heads, KV heads, head_dim, lanes of a folded row
SHAPES = {
    "five-heads-padded": (5, 5, 8, 128),        # H no multiple of 8
    "grouped-queries": (4, 2, 16, 32),          # two query heads a KV head
    "nothing-to-pad": (8, 8, 8, 64),
}


def head_cfg(heads, hkv, dh):
    """What `_attend` reads of a configuration."""
    return types.SimpleNamespace(
        num_kv_heads=hkv, num_heads=heads, head_dim=dh, query_scale=0.0,
        attn_softcap=0.0, sliding_window=None, kv_lora_rank=0)


def stacks(shape, dtype="float32", seed=0):
    """``(q [S, 1, H, Dh], K and V [L, S, max_len, W])``, zeros in the pad
    lanes as the engine keeps them."""
    heads, hkv, dh, width = SHAPES[shape]
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(kq, (SLOTS, 1, heads, dh)).astype(dtype)
    lanes = jnp.arange(width) < hkv * dh
    k = jax.random.normal(kk, (LAYERS, SLOTS, MAX_LEN, width)) * lanes
    v = jax.random.normal(kv, (LAYERS, SLOTS, MAX_LEN, width)) * lanes
    return q, k.astype(dtype), v.astype(dtype)


@functools.lru_cache(maxsize=None)
def plan_read(heads, hkv, dh):
    """`_attend_cached` over stacks of either form by the kernel's plan,
    jitted once a configuration."""
    cfg = head_cfg(heads, hkv, dh)

    @jax.jit
    def read(q, k, v, at, lengths, active):
        own = B.attn_blocks(lengths, active, 1, MAX_LEN, jnp, per_slot=True)
        plan = FA.read_plan(own, lengths + 1, MAX_LEN // BLOCK)
        return B._attend_cached(cfg, {}, q, B._CacheLayer(k, at, plan),
                                B._CacheLayer(v, at, plan),
                                lengths[:, None, None])

    return read


def kernel_read(shape):
    return plan_read(*SHAPES[shape][:3])


def dense_read(shape, q, k_layer, v_layer, lengths):
    """`_attend` under the causal mask over the layer's whole rows."""
    heads, hkv, dh, _ = SHAPES[shape]
    cfg = head_cfg(heads, hkv, dh)
    q_pos = lengths[:, None, None]
    k_pos = jnp.arange(MAX_LEN, dtype=jnp.int32)[None, None, :]
    unfold = lambda x: x[..., :hkv * dh].reshape(SLOTS, MAX_LEN, hkv, dh)
    return B._attend(cfg, {}, q, unfold(k_layer), unfold(v_layer),
                     (B._visible(cfg, q_pos, k_pos), q_pos, k_pos))


@pytest.fixture(autouse=True)
def blocks_of_16(monkeypatch):
    monkeypatch.setattr(B, "ATTN_BLOCK", BLOCK)


# A slot of length n has its query at row n and reads ceil((n + 1) / 16)
# blocks: rows b - 1, b and b + 1 are lengths 14, 15, 16.
CASES = {
    "a-row-short-of-the-edge": ([BLOCK - 2, 0, 2 * BLOCK - 2, 5], [1, 1, 1, 1]),
    "at-the-edge": ([BLOCK - 1, 2 * BLOCK - 1, 3 * BLOCK - 1, 1], [1, 1, 1, 1]),
    "a-row-past-the-edge": ([BLOCK, 2 * BLOCK, 3 * BLOCK, 40], [1, 1, 1, 1]),
    "a-slot-at-max-len": ([MAX_LEN - 1, 3, MAX_LEN - 1, 20], [1, 1, 1, 1]),
    "an-inactive-slot": ([9, MAX_LEN - 1, 30, 17], [1, 0, 1, 0]),
    "nobody-active": ([9, MAX_LEN - 1, 30, 17], [0, 0, 0, 0]),
}


@pytest.mark.parametrize("shape", sorted(SHAPES))
@pytest.mark.parametrize("case", sorted(CASES))
def test_the_kernel_is_attend_over_the_dense_rows(case, shape):
    """Every active slot's row against `_attend` over the whole layer under
    the causal mask (float32: the sums' order is another); an inactive
    slot's row is finite, and nobody reads it."""
    lengths, active = (jnp.asarray(x, jnp.int32) for x in CASES[case])
    active = active.astype(bool)
    q, k, v = stacks(shape)
    got = kernel_read(shape)(q, k, v, jnp.int32(1), lengths, active)
    want = dense_read(shape, q, k[1], v[1], lengths)
    assert got.shape == want.shape and got.dtype == want.dtype
    assert np.isfinite(np.asarray(got)).all()
    live = np.asarray(active)
    np.testing.assert_allclose(np.asarray(got)[live], np.asarray(want)[live],
                               atol=2e-6, rtol=2e-6)


def test_bfloat16_operands_to_their_rounding():
    lengths = jnp.asarray([BLOCK - 1, BLOCK, 50, 3], jnp.int32)
    active = jnp.asarray([True, True, True, False])
    q, k, v = stacks("five-heads-padded", "bfloat16")
    got = kernel_read("five-heads-padded")(q, k, v, jnp.int32(2), lengths,
                                           active)
    want = dense_read("five-heads-padded", q, k[2], v[2], lengths)
    assert got.dtype == want.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(got, np.float32)[:3],
                               np.asarray(want, np.float32)[:3],
                               atol=2e-2, rtol=2e-2)


@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_no_row_past_a_slot_s_own_blocks_is_read(shape):
    """NaN in every row PAST each slot's own blocks (so: in blocks that the
    longest active slot's bound covers), in every layer: the kernel returns
    what it returns on clean stacks, bit for bit. The bound is per slot."""
    lens = [BLOCK - 2, 3 * BLOCK + 1, BLOCK, 2 * BLOCK + 3]
    on = [1, 1, 1, 0]
    lengths, active = jnp.asarray(lens, jnp.int32), jnp.asarray(on, bool)
    q, k, v = stacks(shape, seed=3)
    own = B.attn_blocks(np.asarray(lens), np.asarray(on, bool), 1, MAX_LEN,
                        per_slot=True)
    assert list(own) == [1, 4, 2, 0]
    rows = np.arange(MAX_LEN)[None, :] >= (own * BLOCK)[:, None]    # [S, n]
    bad = jnp.asarray(rows)[None, :, :, None]
    k_bad, v_bad = jnp.where(bad, jnp.nan, k), jnp.where(bad, jnp.nan, v)
    read = kernel_read(shape)
    clean = read(q, k, v, jnp.int32(0), lengths, active)
    dirty = read(q, k_bad, v_bad, jnp.int32(0), lengths, active)
    assert np.isfinite(np.asarray(dirty)).all()
    np.testing.assert_array_equal(np.asarray(dirty), np.asarray(clean))
    # the shared bound (4 blocks for every slot) would have read them
    assert np.isnan(np.asarray(
        dense_read(shape, q, k_bad[0], v_bad[0], lengths))[:3]).any()


def test_the_plan_by_hand():
    """Three slots of up to 4 blocks with 2, 0 and 3 of their own: five
    pairs, slot-major, then the counts and the positions."""
    plan = np.asarray(FA.read_plan(jnp.asarray([2, 0, 3], jnp.int32),
                                   jnp.asarray([20, 63, 40], jnp.int32), 4))
    assert plan.dtype == np.int32 and plan.shape == (1 + 12 + 12 + 3 + 3,)
    assert plan[0] == 5
    assert list(plan[1:6]) == [0, 0, 2, 2, 2]           # slot of pair i
    assert list(plan[13:18]) == [0, 1, 0, 1, 2]         # block of pair i
    assert list(plan[25:28]) == [2, 0, 3] and list(plan[28:]) == [20, 63, 40]
    none = np.asarray(FA.read_plan(jnp.zeros((3,), jnp.int32),
                                   jnp.zeros((3,), jnp.int32), 4))
    assert none[0] == 0


def test_which_programs_read_by_the_kernel(monkeypatch):
    """`cache_read`, the one rule, two answers. The kernel: one new row a
    slot, no softcap and no window of the masked kind; a folded stack on
    every backend and with no rider group, rows that stay ``[Hkv, Dh]``
    where the kernel is the chip's (here: the tests' hook) and ``Dh`` is
    whole lane tiles, a rider group or not. Every other input: the loop,
    one query row a KV head or several."""
    cfg = head_cfg(4, 2, 16)
    cfg.eva_window = 0
    assert B.cache_read(cfg, {}, True) == "kernel"
    assert B.cache_read(cfg, {}, True, t=3) == "loop"
    assert B.cache_read(cfg, {}, True, rider=True) == "loop"
    assert B.cache_read(cfg, {"window": 0}, True) == "loop"
    for key in ("attn_softcap", "sliding_window"):
        other = types.SimpleNamespace(**{**vars(cfg), key: 4})
        assert B.cache_read(other, {}, True) == "loop"
    assert B.cache_read(cfg, {}, False) == "loop"           # grouped queries
    cfg.num_kv_heads = 4
    assert B.cache_read(cfg, {}, False) == "loop"   # one query row a head
    assert B.cache_read(cfg, {}, False, t=2) == "loop"
    # rows that stay [Hkv, Dh], a head filling the lanes
    wide = types.SimpleNamespace(**{**vars(cfg), "head_dim": 128})
    grouped = types.SimpleNamespace(**{**vars(wide), "num_kv_heads": 2})
    windowed = types.SimpleNamespace(**{**vars(wide), "eva_window": 32})
    assert not FA.engaged()                                 # not a TPU
    for off_the_chip in (wide, grouped, windowed):
        assert B.cache_read(off_the_chip, {}, False) == "loop"
    monkeypatch.setattr(FA, "_INTERPRET", True)
    assert FA.engaged()
    for rider in (False, True):
        assert B.cache_read(wide, {}, False, rider=rider) == "kernel"
    assert B.cache_read(windowed, {}, False) == "kernel"
    assert B.cache_read(grouped, {}, False) == "kernel"
    assert B.cache_read(grouped, {}, False, t=2) == "loop"
    assert B.cache_read(wide, {}, False, t=2) == "loop"     # a verify step
    assert B.cache_read(wide, {"window": 0}, False) == "loop"
    for key in ("attn_softcap", "sliding_window"):
        other = types.SimpleNamespace(**{**vars(wide), key: 4})
        assert B.cache_read(other, {}, False) == "loop"
    assert B.cache_read(cfg, {}, False) == "loop"           # head_dim 16
    assert B.cache_read(cfg, {}, True, rider=True) == "loop"


# -- rows that stay [Hkv, Dh]: the same kernel, a block as it rests --------------

# heads, KV heads of the three cells whose stacks are not folded, in small
# (``head_dim`` 128 as theirs: a row fills its lanes).
WIDE = {"ouro": (16, 16), "evabyte": (32, 32), "qwen2": (28, 4)}
DH = 128


def wide_stacks(shape, dtype="float32", seed=0, rows=MAX_LEN):
    """``(q [S, 1, H, Dh], K and V [L, S, rows, Hkv, Dh])``."""
    heads, hkv = WIDE[shape]
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(kq, (SLOTS, 1, heads, DH)).astype(dtype)
    k = jax.random.normal(kk, (LAYERS, SLOTS, rows, hkv, DH)).astype(dtype)
    v = jax.random.normal(kv, (LAYERS, SLOTS, rows, hkv, DH)).astype(dtype)
    return q, k, v


def wide_read(shape):
    return plan_read(*WIDE[shape], DH)


def wide_dense(shape, q, k_layer, v_layer, lengths):
    cfg = head_cfg(*WIDE[shape], DH)
    q_pos = lengths[:, None, None]
    k_pos = jnp.arange(k_layer.shape[1], dtype=jnp.int32)[None, None, :]
    return B._attend(cfg, {}, q, k_layer, v_layer,
                     (B._visible(cfg, q_pos, k_pos), q_pos, k_pos))


@pytest.mark.parametrize("shape", sorted(WIDE))
@pytest.mark.parametrize("case", sorted(CASES))
def test_rows_as_they_rest_against_attend_over_the_dense_rows(case, shape):
    """The cells' three shapes (16 KV heads of one query head, 32 of one, 4
    of seven): every active slot's row is `_attend`'s over the whole layer
    to float32's last digits; an idle slot's row is zeros."""
    lengths, active = (jnp.asarray(x, jnp.int32) for x in CASES[case])
    active = active.astype(bool)
    q, k, v = wide_stacks(shape)
    got = wide_read(shape)(q, k, v, jnp.int32(1), lengths, active)
    want = wide_dense(shape, q, k[1], v[1], lengths)
    assert got.shape == want.shape and got.dtype == want.dtype
    live = np.asarray(active)
    np.testing.assert_allclose(np.asarray(got)[live], np.asarray(want)[live],
                               atol=2e-6, rtol=2e-6)
    assert not np.asarray(got)[~live].any()


@pytest.mark.parametrize("shape", sorted(WIDE))
def test_rows_as_they_rest_in_bfloat16_to_its_rounding(shape):
    lengths = jnp.asarray([BLOCK - 1, BLOCK, 50, 3], jnp.int32)
    active = jnp.asarray([True, True, True, False])
    q, k, v = wide_stacks(shape, "bfloat16")
    got = wide_read(shape)(q, k, v, jnp.int32(2), lengths, active)
    want = wide_dense(shape, q, k[2], v[2], lengths)
    assert got.dtype == want.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(got, np.float32)[:3],
                               np.asarray(want, np.float32)[:3],
                               atol=2e-2, rtol=2e-2)


@pytest.mark.parametrize("shape", sorted(WIDE))
def test_no_row_past_a_slot_s_own_blocks_is_read_as_it_rests(shape):
    """`test_no_row_past_a_slot_s_own_blocks_is_read` on unfolded stacks:
    NaN past each slot's OWN blocks changes no bit, and the idle slot's
    output is zeros."""
    lens, on = [BLOCK - 2, 3 * BLOCK + 1, BLOCK, 2 * BLOCK + 3], [1, 1, 1, 0]
    lengths, active = jnp.asarray(lens, jnp.int32), jnp.asarray(on, bool)
    q, k, v = wide_stacks(shape, seed=3)
    own = B.attn_blocks(np.asarray(lens), np.asarray(on, bool), 1, MAX_LEN,
                        per_slot=True)
    bad = jnp.asarray(np.arange(MAX_LEN)[None, :]
                      >= (own * BLOCK)[:, None])[None, :, :, None, None]
    k_bad, v_bad = jnp.where(bad, jnp.nan, k), jnp.where(bad, jnp.nan, v)
    read = wide_read(shape)
    clean = read(q, k, v, jnp.int32(0), lengths, active)
    dirty = read(q, k_bad, v_bad, jnp.int32(0), lengths, active)
    np.testing.assert_array_equal(np.asarray(dirty), np.asarray(clean))
    assert not np.asarray(dirty)[3].any()
    assert np.isnan(np.asarray(
        wide_dense(shape, q, k_bad[0], v_bad[0], lengths))[:3]).any()


# A family whose older rows are summaries: a 32-row window of exact rows and
# one summary row a chunk of 4 earlier positions, TWO stacks under ONE
# softmax. Positions: in its first window (no summary), at its window's
# last row, at the first row of its second window, deep in its fourth, and
# an idle slot past everything.
EVA_W, EVA_C, EVA_R = 32, 4, 32
EVA_AT = ([5, EVA_W - 1, EVA_W, 3 * EVA_W + 4, 4 * EVA_W + 20],
          [1, 1, 1, 1, 0])


def eva_cfg():
    cfg = head_cfg(*WIDE["evabyte"], DH)
    cfg.eva_window, cfg.eva_chunk = EVA_W, EVA_C
    return cfg


@functools.lru_cache(maxsize=None)
def eva_read():
    cfg = eva_cfg()

    @jax.jit
    def read(q, ke, ve, ks, vs, at, p, active):
        rows = (EVA_W, EVA_R)
        own = B.windowed_blocks(cfg, p, active, rows, jnp, per_slot=True)
        limits = (p % EVA_W + 1, B._summaries_visible(cfg, p))
        plan = [FA.read_plan(o, lim, n // BLOCK)
                for o, lim, n in zip(own, limits, rows)]
        layer = lambda ex, su: B._WindowedRead(             # noqa: E731
            B._CacheLayer(ex, at, plan[0]), B._CacheLayer(su, at, plan[1]))
        return B._attend_windowed(cfg, {}, q, layer(ke, ks), layer(ve, vs),
                                  p[:, None, None])

    return read


def eva_stacks(dtype, seed=0):
    s = len(EVA_AT[0])
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    shape = lambda n: (LAYERS, s, n) + (WIDE["evabyte"][1], DH)  # noqa: E731
    q = jax.random.normal(ks[0], (s, 1, WIDE["evabyte"][0], DH))
    made = [jax.random.normal(key, shape(n)) for key, n in zip(
        ks[1:], (EVA_W, EVA_W, EVA_R, EVA_R))]
    return [x.astype(dtype) for x in [q] + made]


@pytest.mark.parametrize("dtype,tol", [("float32", 2e-6), ("bfloat16", 2e-2)])
def test_a_window_stack_and_a_summary_stack_under_one_softmax(dtype, tol):
    """Each stack by the kernel with its statistics out, the two merged by
    `_attend_windowed`: `_attend` over the window's rows up to the query's
    own beside the summaries of EARLIER windows, in one softmax. A slot in
    its first window reads no summary block and weighs none; NaN past each
    slot's own blocks of either stack is never read."""
    cfg = eva_cfg()
    p, active = (np.asarray(x) for x in EVA_AT)
    active = active.astype(bool)
    q, ke, ve, ks, vs = eva_stacks(dtype)
    own = B.windowed_blocks(cfg, p, active, (EVA_W, EVA_R), per_slot=True)
    assert [list(x) for x in own] == [[1, 2, 1, 1, 0], [0, 0, 1, 2, 0]]
    cols = jnp.arange(EVA_W + EVA_R, dtype=jnp.int32)[None, None, :]
    at = jnp.asarray(p, jnp.int32)[:, None, None]
    mask = jnp.where(cols < EVA_W, cols <= at % EVA_W,
                     cols - EVA_W < B._summaries_visible(cfg, at))
    want = B._attend(cfg, {}, q, jnp.concatenate([ke[1], ks[1]], 1),
                     jnp.concatenate([ve[1], vs[1]], 1), (mask, at, cols))

    def poisoned(stack, blocks):
        bad = np.arange(stack.shape[2])[None, :] >= (blocks * BLOCK)[:, None]
        return jnp.where(jnp.asarray(bad)[None, :, :, None, None], jnp.nan,
                         stack)

    got = eva_read()(q, poisoned(ke, own[0]), poisoned(ve, own[0]),
                     poisoned(ks, own[1]), poisoned(vs, own[1]),
                     jnp.int32(1), jnp.asarray(p, jnp.int32),
                     jnp.asarray(active))
    assert got.shape == want.shape and got.dtype == want.dtype
    np.testing.assert_allclose(np.asarray(got, np.float32)[active],
                               np.asarray(want, np.float32)[active],
                               atol=tol, rtol=tol)
    assert not np.asarray(got, np.float32)[~active].any()


def test_a_slot_the_plan_leaves_out_has_statistics_that_weigh_nothing():
    q, k, v = wide_stacks("ouro")
    lengths = jnp.asarray([9, 40, 0, 17], jnp.int32)
    own = jnp.asarray([1, 3, 0, 0], jnp.int32)
    plan = FA.read_plan(own, lengths + 1, MAX_LEN // BLOCK)
    m, l, acc = FA.slot_attention(q[:, 0], k, v, 0, plan, rows=BLOCK,
                                  hkv=WIDE["ouro"][1], stats=True)
    assert m.shape == l.shape == (SLOTS, 16) and acc.shape == (SLOTS, 16, DH)
    assert (np.asarray(m)[2:] == FA.NEG_INF).all()
    assert not np.asarray(l)[2:].any() and not np.asarray(acc)[2:].any()
    whole = FA.slot_attention(q[:, 0], k, v, 0, plan, rows=BLOCK,
                              hkv=WIDE["ouro"][1])
    np.testing.assert_allclose(
        np.asarray(acc / l[..., None])[:2].reshape(2, -1),
        np.asarray(whole)[:2], atol=1e-6, rtol=1e-6)


# -- the kernel the three accepted forms build, pinned -----------------------

# The jaxpr of `slot_attention` (the wrapper's re-laying and the kernel's
# body, locations stripped), by form: what PR 53 left. A later form of the
# call (PR 56: one stack for keys and values, a selection as a mask) is a
# Python branch on an argument these calls do not pass, and must leave them
# the program they had.
KERNEL_FORMS = {
    "folded": (dict(hkv=5), (4, 5, 8), (3, 4, 64, 128), "float32",
               "8ae36b07ee0efbcf"),
    "rows": (dict(hkv=2), (4, 4, 128), (3, 4, 64, 2, 128), "bfloat16",
             "b4734738f6ea91f8"),
    "stats": (dict(hkv=4, stats=True), (4, 4, 128), (3, 4, 64, 4, 128),
              "bfloat16", "f3e216c8e5134d37"),
}


@pytest.mark.parametrize("form", sorted(KERNEL_FORMS))
def test_the_accepted_forms_build_the_kernel_they_had(form, monkeypatch):
    import hashlib
    import re

    monkeypatch.setattr(FA, "_INTERPRET", False)
    kw, q, stack, dtype, want = KERNEL_FORMS[form]
    sd = jax.ShapeDtypeStruct
    text = str(jax.make_jaxpr(
        lambda q, k, v, at, plan: FA.slot_attention(
            q, k, v, at, plan, rows=BLOCK, **kw))(
                sd(q, dtype), sd(stack, dtype), sd(stack, dtype),
                sd((), jnp.int32),
                sd((1 + 2 * q[0] * (MAX_LEN // BLOCK) + 2 * q[0],),
                   jnp.int32)))
    text = re.sub(r" at [^\s:]+:\d+", "", text)
    assert "/" + "tests" not in text and "slot_attention.py" not in text
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == want


# -- the served widths, compiled for the chip without the chip ----------------

@pytest.fixture(scope="module")
def one_chip():
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:              # no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def test_the_kernel_compiles_for_the_v5e_at_gpt2_xl_s_widths(
        one_chip, monkeypatch):
    """S 8, ``max_len`` 1024, W 1664, 25 heads of 64, bfloat16, 48 layers:
    Mosaic takes the kernel (aligned slices, VMEM), the program is the
    custom call and holds no temporary the size of a layer, let alone of a
    stack."""
    monkeypatch.setattr(FA, "_INTERPRET", False)
    monkeypatch.setattr(B, "ATTN_BLOCK", 128)
    s, heads, dh, layers, max_len, width = 8, 25, 64, 48, 1024, 1664

    def read(q, k, v, at, lengths, active):
        own = B.attn_blocks(lengths, active, 1, max_len, jnp, per_slot=True)
        plan = FA.read_plan(own, lengths + 1, max_len // 128)
        return FA.slot_attention(q, k, v, at, plan, rows=128, hkv=heads)

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    stack = arg((layers, s, max_len, width), jnp.bfloat16)
    compiled = jax.jit(read).lower(
        arg((s, heads, dh), jnp.bfloat16), stack, stack, arg((), jnp.int32),
        arg((s,), jnp.int32), arg((s,), bool)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert "slot_attention" in compiled.as_text()
    assert (compiled.memory_analysis().temp_size_in_bytes
            < s * max_len * width * 2)


# slots, heads, KV heads, cache layers, rows a slot, statistics out: the
# three cells whose rows stay [Hkv, 128], as served (bfloat16).
SERVED = {
    "ouro": (8, 16, 16, 192, 512, False),
    "evabyte-window": (8, 32, 32, 16, 2048, True),
    "evabyte-summaries": (8, 32, 32, 16, 896, True),
    "qwen2": (16, 28, 4, 28, 1024, False),
}


@pytest.mark.parametrize("cell", sorted(SERVED))
def test_the_kernel_compiles_for_the_v5e_on_rows_as_they_rest(
        cell, one_chip, monkeypatch):
    """The stacks in the layout the v5e holds them in (a row one or two
    whole bfloat16 tiles; qwen2's four heads a tile of four sublanes), a
    block landed as ``[128, Hkv, 128]`` and read as ``[128 x Hkv, 128]``:
    Mosaic takes the kernel, and the program around it holds no temporary
    the size of a layer."""
    monkeypatch.setattr(FA, "_INTERPRET", False)
    monkeypatch.setattr(B, "ATTN_BLOCK", 128)
    s, heads, hkv, layers, n, stats = SERVED[cell]

    def read(q, k, v, at, lengths, active):
        own = B.attn_blocks(lengths, active, 1, n, jnp, per_slot=True)
        plan = FA.read_plan(own, lengths + 1, n // 128)
        return FA.slot_attention(q, k, v, at, plan, rows=128, hkv=hkv,
                                 stats=stats)

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    stack = arg((layers, s, n, hkv, 128), jnp.bfloat16)
    compiled = jax.jit(read).lower(
        arg((s, heads, 128), jnp.bfloat16), stack, stack, arg((), jnp.int32),
        arg((s,), jnp.int32), arg((s,), bool)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert "slot_attention" in compiled.as_text()
    assert (compiled.memory_analysis().temp_size_in_bytes
            < s * n * hkv * 128 * 2)


def test_the_kernel_compiles_for_the_v5e_on_a_latent_layer_under_its_selection(
        one_chip, monkeypatch):
    """glm-5 as served: 8 slots of 16384 latent rows of 640 lanes, 6
    layers, 64 heads, the top 2048 of a slot's float32 index scores, blocks
    of `LATENT_BLOCK` rows. Mosaic takes the kernel with its threshold
    search; the call names the stack FIRST among its array operands and
    has ONE float32 result whose leading dimension is the slots (a trace
    finds the read by both: `perfbench/layer_metrics/
    sparse_attn_roofline_share.json`); nothing the size of a layer is
    staged around it."""
    monkeypatch.setattr(FA, "_INTERPRET", False)
    s, heads, layers, n, width, topk = 8, 64, 6, 16384, 640, 2048
    rows = B.latent_block(n)

    def read(q, stack, scores, at, lengths, active):
        own = B.attn_blocks(lengths, active, 1, n, jnp, per_slot=True,
                            block=rows)
        plan = FA.read_plan(own, lengths + 1, n // rows)
        return FA.slot_attention(q, stack, None, at, plan, rows=rows, hkv=1,
                                 select=(scores, topk))

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = jax.jit(read).lower(
        arg((s, heads, width), jnp.bfloat16),
        arg((layers, s, n, width), jnp.bfloat16), arg((s, n), jnp.float32),
        arg((), jnp.int32), arg((s,), jnp.int32), arg((s,), bool)).compile()
    call = next(line for line in compiled.as_text().splitlines()
                if "tpu_custom_call" in line and "slot_attention" in line)
    assert f" = f32[{s},{heads},{width}]" in call
    operands = call[call.index("operand_layout_constraints={"):]
    assert operands.index(f"bf16[{layers},{s},{n},{width}]") \
        < operands.index(f"bf16[{s},{heads},{width}]") \
        < operands.index(f"f32[{s},{n}]")
    assert (compiled.memory_analysis().temp_size_in_bytes
            < s * n * width * 2)


# -- glm-5's burst program: every weight of a latent layer read where it rests

_ELEMENT_BYTES = {"bf16": 2, "f16": 2, "f32": 4, "s32": 4, "u32": 4,
                  "s8": 1, "u8": 1, "pred": 1}


def large_results(text, least, vmem=False):
    """``[(name, shape with layout, opcode)]`` of every operation of an
    optimised HLO text that WRITES at least ``least`` bytes: outside fused
    computations (what a fusion computes inside is never written out) and
    the custom calls (the kernels), and no view of another buffer (a
    parameter, a tuple's member, a bitcast, a loop). To the device's main
    memory only, unless ``vmem``: a result the compiler placed in VMEM
    (``S(1)`` in its layout) is the staging of an operand, its one read."""
    fused = set(re.findall(r"fusion\([^\n]*?calls=(%[\w.\-]+)", text))
    views = {"parameter", "get-tuple-element", "bitcast", "tuple", "while",
             "custom-call"}
    inside, out = None, []
    for line in text.splitlines():
        head = re.match(r"(?:ENTRY )?(%[\w.\-]+) \(.*\) -> .* \{$", line)
        if head:
            inside = head.group(1)
            continue
        op = re.match(r"\s*(?:ROOT )?(%[\w.\-]+) = (\w+)\[([\d,]+)\]"
                      r"(\{[^}]*\})? ([\w\-]+)\(", line)
        if not op or inside in fused:
            continue
        name, dtype, dims, layout, opcode = op.groups()
        size = _ELEMENT_BYTES.get(dtype, 0)
        for d in dims.split(","):
            size *= int(d)
        if (size >= least and opcode not in views
                and (vmem or "S(1)" not in (layout or ""))):
            out.append((name, f"{dtype}[{dims}]{layout or ''}", opcode))
    return out


def test_glm5_s_burst_program_reads_every_latent_weight_where_it_rests(
        one_chip, monkeypatch):
    """`BatchedStageExecutor._build_burst(16)` as `glm5-doc-sat8` serves it
    (six layers at the published widths, 8 slots of 16384 rows, bfloat16,
    the decode read by the kernel), compiled for a described v5e: outside
    the kernel NO operation writes a result the size of a layer's ``wiq_t``
    (the smallest of the three weights that rest by head, 16.8 MB) to the
    device's memory but the two cache stacks' own in-place updates, no
    weight is re-laid by a ``copy`` anywhere, VMEM included, and the
    program's temporaries are smaller than that slice. Until PR 57 the
    ``[in, out]`` forms of ``wqb`` / ``wkvb`` / ``wiq`` were re-laid whole
    once a burst (`copy` ``bf16[5,2048,16384]{1,2,0}`` ..: 0.73 GB of
    temporaries) and ``wkva``'s ``[6144, 576]`` stack, which the v5e holds
    with 6144 minor, likewise."""
    import dataclasses

    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.models import (
        config,
        transformer,
    )
    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.models.partition import (
        ROLE_FULL,
        StageSpec,
    )
    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.ops.sampling import (
        RECENT_WINDOW,
    )

    monkeypatch.setattr(FA, "_INTERPRET", False)
    monkeypatch.setattr(B, "kernel_engaged", lambda: True)
    layers, s, n, ticks = 6, 8, 16384, 16
    cfg = dataclasses.replace(config.get_config("glm5"), num_layers=layers)

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree.map(
        lambda x: arg(x.shape, x.dtype),
        jax.eval_shape(lambda: transformer.init_params(
            jax.random.PRNGKey(0), cfg, jnp.bfloat16)))
    attn = params["layers"]["attn"]
    assert attn["wqb_t"].shape == (layers - 1, 64, 256, 2048)
    assert attn["wkvb_t"].shape == (layers - 1, 64, 448, 512)
    assert attn["wiq_t"].shape == (layers - 1, 32, 128, 2048)
    least = 32 * 128 * 2048 * 2
    stacks = (arg((layers, s, n, 640), jnp.bfloat16),
              arg((layers, s, n, 128), jnp.bfloat16))
    burst = B.BatchedStageExecutor._build_burst(types.SimpleNamespace(
        cfg=cfg, spec=StageSpec(0, ROLE_FULL, 0, layers), rider_rows=0,
        slots=s), ticks)
    compiled = burst.lower(
        params, arg((len(B.BURST_INTS) + RECENT_WINDOW, s), jnp.int32),
        arg((3, s), jnp.float32), *stacks).compile()
    text = compiled.as_text()
    assert "slot_attention" in text and "tpu_custom_call" in text
    of_a_stack = tuple(
        "bf16[" + ",".join(str(d) for d in x.shape) + "]" for x in stacks)
    large = [r for r in large_results(text, least, vmem=True)
             if not r[1].startswith(of_a_stack)]
    assert [r for r in large if "S(1)" not in r[1]] == []    # written to HBM
    assert [r for r in large if r[2] == "copy"] == []        # re-laid at all
    assert compiled.memory_analysis().temp_size_in_bytes < least
