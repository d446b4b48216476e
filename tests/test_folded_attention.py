"""`ops.folded_attention`: a decode step's attention over one layer of a
FOLDED cache stack, each slot read up to its own last block.

On the CPU the kernel runs through the Pallas interpreter; the oracle is
`runtime.batching._attend` over the dense rows of the same layer. The
shapes are tiny (16-row blocks, 64-row slots) and every case of a shape
runs ONE compiled program: the whole file stays well under 30 s. The last
test compiles the kernel at gpt2-xl's served widths for a DESCRIBED v5e (no
chip): what Mosaic refuses, it refuses there."""

import functools
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.ops import (
    folded_attention as FA,
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
    return types.SimpleNamespace(
        num_kv_heads=hkv, num_heads=heads, head_dim=dh, query_scale=0.0,
        attn_softcap=0.0, sliding_window=None)


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
def kernel_read(shape):
    """`_attend_cached` over folded stacks by the kernel's plan, jitted
    once a shape."""
    cfg = head_cfg(*SHAPES[shape][:3])

    @jax.jit
    def read(q, k, v, at, lengths, active):
        own = B.attn_blocks(lengths, active, 1, MAX_LEN, jnp, per_slot=True)
        plan = FA.read_plan(own, lengths, MAX_LEN // BLOCK)
        return B._attend_cached(cfg, {}, q, B._CacheLayer(k, at, plan),
                                B._CacheLayer(v, at, plan),
                                lengths[:, None, None])

    return read


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


def test_which_programs_read_by_the_kernel():
    """`cache_read`, the one rule: a folded stack, one new row a slot, no
    rider group, no softcap and no window of any kind."""
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
    assert B.cache_read(cfg, {}, False) == "switch"
    assert B.cache_read(cfg, {}, False, t=2) == "loop"


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
        plan = FA.read_plan(own, lengths, max_len // 128)
        return FA.folded_attention(q, k, v, at, plan, rows=128, hkv=heads)

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    stack = arg((layers, s, max_len, width), jnp.bfloat16)
    compiled = jax.jit(read).lower(
        arg((s, heads, dh), jnp.bfloat16), stack, stack, arg((), jnp.int32),
        arg((s,), jnp.int32), arg((s,), bool)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert "folded_attention" in compiled.as_text()
    assert (compiled.memory_analysis().temp_size_in_bytes
            < s * max_len * width * 2)
