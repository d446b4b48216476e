"""What the batched engine's programs do to the cache stacks
(runtime.batching `_append_rows`, `_split_stacks` / `_layer_at`): int8 layer
stacks reach the Pallas kernel whole, and a decode step appends its rows in
place, bit for bit what the slab's round trip left (`engines`: the slab
policy, kept as the oracle)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.models import (
    init_params,
    mixtral_config,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.models.quant import (
    quantize_params,
    QuantizedLayerView,
    QuantizedTensor,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime.batching import (
    _append_rows,
    _layer_at,
    _split_stacks,
)

from engines import (
    all_eqns,
    bits,
    both_policies,
    cache_writes_and_slabs,
    check_clamped_slot,
    engine,
    FAMILIES,
    family_engine,
    full_spec,
    kernel_cfg,
    program_args,
    slot_rows,
    tiny_cfg,
)

# ---------------------------------------------------------------------------
# int8 layer stacks reach the Pallas kernel WHOLE (runtime.batching
# _split_stacks / _layer_at): no program slices a layer's int8 weight out
# of its stack for the call.
# ---------------------------------------------------------------------------


def _stacked_tree(kind):
    cfg = (mixtral_config(
        vocab_size=128, hidden_size=64, num_layers=2, num_heads=4,
        num_kv_heads=2, intermediate_size=96, num_experts=4,
        num_experts_per_tok=2, max_position_embeddings=32)
        if kind == "int8-moe" else kernel_cfg())
    params = init_params(jax.random.PRNGKey(0), cfg)
    return quantize_params(params, kind.split("-")[0])["layers"]


@pytest.mark.parametrize("kind", ["none", "nf4", "int8", "int8-moe"])
def test_split_stacks_holds_dense_int8_stacks_only(kind):
    """`_split_stacks` takes the dense [L, K, N] int8 stacks out of what
    lax.scan slices, and nothing else: a bf16 or an NF4 tree comes back
    as the SAME object with nothing held; MoE expert stacks ([L, E, K,
    N]) stay in xs. `_layer_at` puts a view of layer i where each held
    stack was, so the body sees the tree's own structure."""
    layers = _stacked_tree(kind)
    xs, held = _split_stacks(layers)
    if kind in ("none", "nf4"):
        assert xs is layers and held == {}
        lp = jax.tree.map(lambda a: a[1], layers)
        assert _layer_at(lp, held, 1) is lp
        return
    dense = {("attn", k) for k in ("wq", "wk", "wv", "wo")}
    if kind == "int8":
        dense |= {("mlp", k) for k in ("wg", "wu", "wd")}
    assert set(held) == dense
    assert all(w.q.ndim == 3 for w in held.values())
    is_q = lambda v: isinstance(v, QuantizedTensor)          # noqa: E731
    left = [v for v in jax.tree.leaves(xs, is_leaf=is_q) if is_q(v)]
    assert all(v.q.ndim == 4 for v in left)                  # expert stacks
    assert len(left) == (3 if kind == "int8-moe" else 0)
    lp = _layer_at(jax.tree.map(lambda a: a[1], xs), held, 1)
    assert jax.tree.structure(
        jax.tree.map(lambda a: 0, lp, is_leaf=lambda v: isinstance(
            v, (QuantizedTensor, QuantizedLayerView)))
    ) == jax.tree.structure(jax.tree.map(lambda a: 0, layers, is_leaf=is_q))
    for path, stack in held.items():
        view = lp[path[0]][path[1]]
        assert isinstance(view, QuantizedLayerView)
        assert view.stack is stack and view.index == 1
        np.testing.assert_array_equal(np.asarray(view.layer().q),
                                      np.asarray(stack.q[1]))


@pytest.mark.parametrize(
    "program", ["burst_tick", "decode_step-1", "prefill", "prefill_suffix"])
def test_int8_programs_hand_the_kernel_the_whole_stack(monkeypatch, program):
    """In each device program of an int8 llama-shaped engine, every
    pallas_call takes a rank-3 int8 operand (the layer stack itself) and
    NO equation (dynamic_slice, dynamic_index, gather, anything) yields
    an int8 array of rank 2 or more: nothing is there for XLA to write
    out as a staging copy before the custom call."""
    import global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.ops.int8_kernel as IK
    monkeypatch.setattr(IK, "_INTERPRET", True)
    cfg = kernel_cfg()
    qp = quantize_params(init_params(jax.random.PRNGKey(0), cfg), "int8")
    ex = engine(cfg, full_spec(cfg), qp, slots=2, max_len=16)
    fn, args = program_args(ex, program)
    eqns = list(all_eqns(jax.make_jaxpr(fn)(*args).jaxpr))
    calls = [e for e in eqns if e.primitive.name == "pallas_call"]
    assert len(calls) == 4                        # wqkv, wo, wgu, wd
    for e in calls:
        int8_in = [v.aval for v in e.invars if v.aval.dtype == jnp.int8]
        assert [a.ndim for a in int8_in] == [3], int8_in
        assert int8_in[0].shape[0] == cfg.num_layers
    made = [(e.primitive.name, v.aval) for e in eqns for v in e.outvars
            if getattr(v.aval, "dtype", None) == jnp.int8
            and v.aval.ndim >= 2]
    assert not made, made


def slab_append(slab, new, start, active):
    """The append as it was: a vmap'd `dynamic_update_slice` of T rows a
    slot on one layer's ``[S, max_len, Hkv, Dh]`` slab; an inactive slot
    writes back what it reads at the same (clamped) start."""
    t = new.shape[1]
    return jax.vmap(
        lambda cache, rows, at, act: jax.lax.dynamic_update_slice_in_dim(
            cache, jnp.where(act, rows, jax.lax.dynamic_slice_in_dim(
                cache, at, t, 0)), at, 0))(slab, new, start, active)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("t_step", [1, 3])
def test_append_rows_is_the_slab_append_in_place(t_step, dtype):
    """`_append_rows` on the whole stack at a traced layer index leaves
    what `slab_append` (a vmap'd `dynamic_update_slice`) leaves on that
    layer's slab, bit for bit, and touches no other layer. Every start is
    there active and inactive: 0, mid-cache, the exact fit ``max_len - T``,
    ``max_len - 1`` (for T = 3 it clamps back to ``max_len - 3``: an
    inactive slot parked there must keep its last rows) and ``max_len``."""
    layers, max_len, hkv, dh = 3, 16, 2, 8
    starts = [0, 5, max_len - t_step, max_len - 1, max_len]
    lengths = jnp.asarray(starts + starts, jnp.int32)
    active = jnp.asarray([True] * len(starts) + [False] * len(starts))
    slots = len(starts) * 2
    ks, kn = jax.random.split(jax.random.PRNGKey(t_step))
    stack = jax.random.normal(
        ks, (layers, slots, max_len, hkv, dh)).astype(dtype)
    new = jax.random.normal(kn, (slots, t_step, hkv, dh)).astype(dtype)
    for i in range(layers):
        got = jax.jit(_append_rows)(stack, jnp.int32(i), new, lengths, active)
        want = stack.at[i].set(slab_append(stack[i], new, lengths, active))
        np.testing.assert_array_equal(bits(got), bits(want))
        assert np.any(bits(got)[i] != bits(stack)[i])
        parked = bits(got)[i, len(starts):]
        np.testing.assert_array_equal(parked, bits(stack)[i, len(starts):])


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("family", FAMILIES)
def test_decode_steps_bit_equal_to_slab_round_trip(monkeypatch, family, dtype):
    """Hidden states and the WHOLE K and V stacks after a plain decode
    step (T = 1) and a speculative-verify step (T = 3), with one session
    sitting both out, are bit for bit what the slab's round trip leaves."""

    def drive():
        ex = family_engine(family, dtype)
        one = ex.decode_batch({"a": jnp.asarray([[3]], jnp.int32),
                               "b": jnp.asarray([[4]], jnp.int32)})
        three = ex.decode_batch({"a": jnp.asarray([[3, 9, 1]], jnp.int32),
                                 "c": jnp.asarray([[4, 8, 2]], jnp.int32)})
        return {"one.a": one["a"], "one.b": one["b"], "three.a": three["a"],
                "three.c": three["c"], "k": ex.k, "v": ex.v}

    want, got = both_policies(monkeypatch, drive)
    assert np.any(bits(want["k"]))
    for name in want:
        np.testing.assert_array_equal(bits(got[name]), bits(want[name]),
                                      err_msg=name)


@pytest.mark.parametrize("t_step", [1, 3])
@pytest.mark.parametrize("case", ["parked-inactive", "active-to-max-len"])
def test_decode_append_clamps_as_the_slab_append_did(monkeypatch, case,
                                                     t_step):
    """The two ends of the clamp. A slot parked at ``max_len - 1`` that
    sits a step out has its start clamped to ``max_len - T``: it must
    write back the rows it read there, so its last rows stay bit for bit
    while the others decode. A slot at ``max_len - T`` that takes the step
    reaches exactly ``max_len``: its rows land at ``[max_len - T,
    max_len)``, where `slab_append` puts them."""
    max_len = 32

    def drive():
        ex = family_engine("qwen2", "float32", max_len)
        d, before = slot_rows(ex, "d")
        ids = np.asarray([[3, 9, 1][:t_step]], np.int32)
        if case == "parked-inactive":
            ex.lengths[d] = max_len - 1
            ex.decode_batch({"a": ids, "b": ids})
        else:
            ex.lengths[d] = max_len - t_step
            ex.decode_batch({"a": ids, "d": ids})
        return {"k": ex.k, "v": ex.v, "before": before, "slot": d}

    want, got = both_policies(monkeypatch, drive)
    np.testing.assert_array_equal(bits(got["k"]), bits(want["k"]))
    np.testing.assert_array_equal(bits(got["v"]), bits(want["v"]))
    check_clamped_slot(got, case, max_len - t_step)


@pytest.mark.parametrize("tree", ["int8", "bfloat16"])
@pytest.mark.parametrize("program", ["burst_tick", "decode_step-1",
                                     "decode_step-3"])
def test_tick_writes_rows_and_never_a_slab(program, tree):
    """In the jaxpr of the burst tick and of the decode step, every write
    into a cache stack is a `scatter` whose update holds T rows a slot
    (``[S, T, Hkv, Dh]``: one for K, one for V), and the only equations that
    yield a layer's ``[S, max_len, Hkv, Dh]`` slab are the two reads that
    feed attention: the `squeeze` of `dynamic_index_in_dim` on the stack,
    once for K and once for V in the one layer body. A slab that is never
    an update's operand is one XLA need not copy."""
    cfg = tiny_cfg("qwen2")
    params = init_params(jax.random.PRNGKey(0), cfg)
    if tree == "int8":
        params, dtype = quantize_params(params, "int8"), jnp.float32
    else:
        dtype = jnp.bfloat16
        params = jax.tree.map(lambda a: a.astype(dtype), params)
    S, M = 3, 24
    ex = engine(cfg, full_spec(cfg), params, slots=S, max_len=M, dtype=dtype)
    T = 1 if program == "burst_tick" else int(program[-1])
    fn, args = program_args(ex, program)
    writes, slabs = cache_writes_and_slabs(
        jax.make_jaxpr(fn)(*args).jaxpr, ex.k.shape)
    rows = (S, T, cfg.num_kv_heads, cfg.head_dim)
    assert writes == [("scatter", rows)] * 2, writes
    assert slabs == ["squeeze", "squeeze"], slabs
