"""Folded K/V stacks (`runtime.batching.kv_fold_width`, `_fold`, the folded
form of `_attend_cached`): where the backend would not keep ``head_dim``
minor, a cache row holds its KV heads side by side in ONE minor dim
(``[L, S, max_len, W]``) and the decode programs attend with block-diagonal
queries against one wide head.

The CPU keeps every array dense and major to minor, so no engine folds
here by itself (`test_the_cpu_folds_nothing`); these tests hand an engine
a width in place of the backend's answer and hold it to a twin that was
told nothing: the same tokens, a prompt's rows in the cache bit for bit, every later row and
hidden row to float32 rounding."""

import functools

import jax
import numpy as np
import pytest
from jax.experimental.layout import Layout

from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.models import (
    config as config_mod,
    init_params,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.models.partition import (
    slice_stage_params,
    StagePlan,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime import (
    batching,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime.batching import (
    kv_fold_width,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.telemetry import (
    events as events_mod,
)

from engines import all_eqns, engine as shared_engine, program_args

GRAIN = 8
SLOTS = 4
MAX_LEN = 64
TICKS = 4

_TINY = dict(vocab_size=131, hidden_size=64, num_layers=2, num_heads=4,
             max_position_embeddings=128)
FAMILIES = {
    # one query head a KV head: unfolded, the loop over blocks
    "gpt2": lambda: config_mod.gpt2_config(**_TINY),
    # grouped queries: two query heads share a KV head's lanes
    "qwen2": lambda: config_mod.qwen2_config(
        num_kv_heads=2, intermediate_size=96, **_TINY),
    # softcapped scores, a query scale of its own, every other layer
    # windowed (4 rows: it truncates at these lengths)
    "gemma2": lambda: config_mod.gemma2_config(
        num_kv_heads=2, intermediate_size=96, head_dim=32, sliding_window=4,
        query_pre_attn_scalar=16.0, attn_softcap=2.0, final_softcap=3.0,
        **_TINY),
    # layers run three times a token: the burst carries a rider lane
    "looped": lambda: config_mod.ouro_config(
        loop_steps=3, head_dim=16, num_kv_heads=4, intermediate_size=96,
        **_TINY),
}
# The v5e's answers (chip runs, PR 45).
V5E_GPT2_XL = Layout((0, 1, 3, 4, 2), ((8, 128), (2, 1)))   # [.., 25, 64]
V5E_QWEN2 = Layout((0, 1, 2, 3, 4), ((4, 128), (2, 1)))     # [.., 4, 128]


@functools.lru_cache(maxsize=None)
def _fold_to(lanes):
    """ONE function a lane count, so that the engines told the same share
    their programs (`engines`)."""
    return lambda layout, hkv, dh: -(-hkv * dh // lanes) * lanes


def told(monkeypatch, lanes):
    """Engines built from here on fold their rows to whole ``lanes``."""
    monkeypatch.setattr(batching, "kv_fold_width", _fold_to(lanes))


def engine(family, *, span=None, prefix_cache=True):
    cfg = FAMILIES[family]()
    params = init_params(jax.random.PRNGKey(3), cfg)
    plan = StagePlan.even(cfg.num_layers, 1 if span is None else 2)
    spec = plan.stages[0 if span is None else span]
    ex = shared_engine(
        cfg, spec, slice_stage_params(cfg, params, spec), slots=SLOTS,
        max_len=MAX_LEN, prefix_cache_bytes=(1 << 20) * prefix_cache)
    if prefix_cache:
        ex.prefix_store.grain = GRAIN
    return ex


def ids_of(n, seed):
    return np.random.default_rng(seed).integers(
        1, 131, (1, n)).astype(np.int32)


def entry(token, seed=5, temperature=0.8):
    return {"token": int(token), "seed": seed, "budget": TICKS, "eos": None,
            "generated": (int(token),), "temperature": temperature,
            "top_p": 0.95, "top_k": 0, "repetition_penalty": 1.0}


def rows_of(ex):
    """Both stacks as ``[L, S, max_len, Hkv, Dh]`` whatever they are held
    as, and the lanes past a folded row's heads (zeros, always)."""
    hkv, dh = ex.cfg.num_kv_heads, ex.cfg.head_dim
    out, pad = [], []
    for stack in (np.asarray(ex.k), np.asarray(ex.v)):
        if stack.ndim == 4:
            pad.append(stack[..., hkv * dh:])
            stack = stack[..., :hkv * dh].reshape(
                *stack.shape[:3], hkv, dh)
        out.append(stack)
    return out, pad


# The decode programs that read a folded layer by the kernel
# (`ops.slot_attention`): one new row a slot, no rider group beside them,
# no softcap, no window. A verify step (three rows a slot), gemma2 and a
# burst with a rider lane keep the loop over the blocks.
KERNEL_READS = {"gpt2": {"burst", "step"}, "qwen2": {"burst", "step"},
                "gemma2": set(), "looped": {"step"}}


def reads_by_kernel(ex):
    """Which of the engine's compiled decode programs hold the kernel: the
    burst, the single step, the verify step (`drive` ran all three)."""
    programs = {"burst": program_args(ex, "burst_tick", TICKS),
                "step": program_args(ex, "decode_step-1"),
                "verify": program_args(ex, "decode_step-3")}
    # By the equation, not by a name in the text: a cached inner jaxpr
    # (`jnp.pad`'s) keeps the source line of whoever traced it first.
    return {name for name, (fn, args) in programs.items()
            if any(e.primitive.name == "pallas_call"
                   and e.params["name"] == "slot_attention"
                   for e in all_eqns(jax.make_jaxpr(fn)(*args).jaxpr))}


def drive(ex):
    """prefill -> burst -> rewind -> suffix prefill (a prefix-chain write
    and a grain split on the way) -> burst, eight bursts in all, a verify
    step and a single step: (what, tokens or hidden rows, the stacks)."""
    seen = []

    def note(what, tokens=None, hidden=None):
        seen.append((what, tokens, None if hidden is None
                     else np.asarray(hidden, np.float32), rows_of(ex)))

    a, b = ids_of(21, 1), ids_of(13, 2)
    note("prefill a", hidden=ex.prefill("a", a, prefix_len=16))
    note("prefill b", hidden=ex.prefill("b", b))
    toks = {"a": [7], "b": [9]}
    for _ in range(3):
        out = ex.decode_burst({s: entry(toks[s][-1]) for s in toks}, TICKS)
        for s in toks:
            toks[s] += out[s]["tokens"]
        note("burst", {s: list(t) for s, t in toks.items()})
    ex.rewind("a", 21 + 2)                  # speculative rollback
    out = ex.decode_burst({"a": entry(toks["a"][2])}, TICKS)
    note("burst after rewind", out["a"]["tokens"])
    # c shares a's first 16 tokens: the chain is written into its slot and
    # the suffix program computes the rest.
    c = np.concatenate([a[:, :16], ids_of(9, 4)], axis=1)
    note("suffix prefill", hidden=ex.prefill("c", c, prefix_len=24))
    assert ex.prefix_store.hits > 0
    toks["c"] = [11]
    for _ in range(4):
        out = ex.decode_burst({s: entry(toks[s][-1], temperature=0.0)
                               for s in ("b", "c")}, TICKS)
        for s in ("b", "c"):
            toks[s] += out[s]["tokens"]
        note("burst", {s: list(t) for s, t in toks.items()})
    h = ex.decode_batch({"b": np.asarray([[toks["b"][-1]]], np.int32)})
    note("decode step", hidden=h["b"])
    h = ex.decode_batch({"c": ids_of(3, 6)})        # a verify step, T = 3
    note("verify step", hidden=h["c"])
    assert ex.burst_dispatches == 8
    return seen


@pytest.mark.parametrize("lanes", [128, 16])
@pytest.mark.parametrize("family", list(FAMILIES))
def test_a_folded_engine_is_the_unfolded_one(monkeypatch, family, lanes):
    """Every token of eight bursts, every row any program wrote into the
    cache (a prompt's bit for bit: writing is data movement; a later row
    of a deeper layer to the rounding of what it was computed from) and
    every hidden row (float32 rounding: the block-diagonal's zeros add
    nothing, the order of the sums is another), through prefill, bursts
    with and without a
    rider lane, a rewind, a prefix-chain write with its suffix prefill, a
    single step and a verify step. 128 lanes pad a row (64 -> 128); 16
    fold it with nothing to pad."""
    want = drive(engine(family))
    told(monkeypatch, lanes)
    ex = engine(family)
    width = -(-ex.cfg.num_kv_heads * ex.cfg.head_dim // lanes) * lanes
    assert ex.k.shape == ex.v.shape == (
        ex.cfg.num_layers * ex.cfg.loop_steps, SLOTS, MAX_LEN, width)
    assert (ex.rider_rows > 0) == (family == "looped")
    got = drive(ex)
    assert reads_by_kernel(ex) == KERNEL_READS[family]
    assert [w for w, *_ in got] == [w for w, *_ in want]
    for (what, tokens, hidden, (rows, pad)), (_, t0, h0, (r0, _)) in zip(
            got, want):
        assert tokens == t0, what
        if hidden is not None:
            np.testing.assert_allclose(hidden, h0, rtol=2e-4, atol=2e-5,
                                       err_msg=what)
        for mine, theirs in zip(rows, r0):
            if what.startswith("prefill"):      # no cached row was read yet
                np.testing.assert_array_equal(mine, theirs, err_msg=what)
            np.testing.assert_allclose(mine, theirs, rtol=2e-4, atol=2e-5,
                                       err_msg=what)
        assert not any(p.any() for p in pad), what


def test_a_span_s_engine_folds_too(monkeypatch):
    """An engine that holds the second half of the layers (hidden rows in,
    hidden rows out; no burst): prefill and decode steps over folded
    stacks are the unfolded ones."""
    x = np.random.default_rng(0).standard_normal((1, 9, 64)).astype(
        np.float32)
    step = x[:, :1] * 0.5

    def run():
        ex = engine("qwen2", span=1, prefix_cache=False)
        return ex, (np.asarray(ex.prefill("a", x)),
                    np.asarray(ex.decode_batch({"a": step})["a"]))

    _, want = run()
    told(monkeypatch, 128)
    ex, got = run()
    assert ex.k.ndim == 4
    for mine, theirs in zip(got, want):
        np.testing.assert_allclose(mine, theirs, rtol=2e-4, atol=2e-5)


def test_recovery_makes_folded_stacks_again(monkeypatch):
    """`_recover_slot` after a donated stack was lost: the new stacks are
    folded as the first were, and the compiled programs still run."""
    told(monkeypatch, 128)
    ex = engine("gpt2", prefix_cache=False)
    ex.prefill("a", ids_of(9, 1))
    shape = ex.k.shape
    ex.k.delete()
    ex._recover_slot("a", ex.slot("a"))
    assert ex.k.shape == ex.v.shape == shape and not np.asarray(ex.k).any()
    ex.prefill("a", ids_of(9, 1))
    assert len(ex.decode_burst({"a": entry(3)}, TICKS)["a"]["tokens"]) == TICKS


@pytest.mark.parametrize("layout, hkv, dh, width", [
    (V5E_GPT2_XL, 25, 64, 1664),        # 1600 -> 13 x 128 lanes
    (V5E_GPT2_XL, 12, 64, 768),         # a whole number already
    (V5E_QWEN2, 4, 128, None),          # the device keeps Dh minor
    (Layout((0, 1, 2, 3, 4), ((8, 128), (2, 1))), 16, 128, None),
    (Layout((0, 1, 2, 3, 4)), 25, 64, None),    # a backend of one layout
])
def test_the_width_is_read_off_the_backend_s_answer(layout, hkv, dh, width):
    assert kv_fold_width(layout, hkv, dh) == width


@pytest.mark.parametrize("family", list(FAMILIES))
def test_the_cpu_folds_nothing(family):
    """The CPU holds ``[.., Hkv, Dh]`` with ``Dh`` minor: the stacks keep
    their five dims and no program of the engine sees a folded row (the
    folded forms are all behind the stack's rank)."""
    ex = engine(family, prefix_cache=False)
    hkv, dh = ex.cfg.num_kv_heads, ex.cfg.head_dim
    assert ex.k.shape[3:] == ex.v.shape[3:] == (hkv, dh)
    assert kv_fold_width(ex.k.format.layout, hkv, dh) is None


def test_no_model_is_named_and_no_field_added():
    """The fold reads the backend's answer and the row's size: no
    `ModelConfig` field, no model's name in the runtime."""
    import dataclasses
    import inspect

    names = {f.name for f in dataclasses.fields(config_mod.ModelConfig)}
    assert not {n for n in names if "fold" in n or "layout" in n}
    text = inspect.getsource(batching).lower()
    assert "gpt2_xl" not in text and "gpt2xl" not in text


def test_one_event_says_how_the_stacks_are_held(monkeypatch):
    """`kv_layout`, where the stacks are made: the shape, the layout as XLA
    spells it, what the backend said of a ``[Hkv, Dh]`` row, the fold."""
    rec = events_mod.EventRecorder(enabled=True)
    monkeypatch.setattr(batching._ev, "emit", rec.emit)
    plain = engine("gpt2", prefix_cache=False)
    told(monkeypatch, 128)
    ex = engine("gpt2", prefix_cache=False)
    first, second = [e.fields for e in rec.events() if e.name == "kv_layout"]
    assert first["shape"] == list(plain.k.shape) and first["folded_to"] is None
    assert first["layout"] == first["row_layout"] == "{4,3,2,1,0}"
    assert second["shape"] == list(ex.k.shape) == [2, SLOTS, MAX_LEN, 128]
    assert second["folded_to"] == 128 and second["row"] == [4, 16]
    # how the burst ticks read a layer: the loop over unfolded rows (not
    # a TPU), the kernel over folded ones
    assert (first["read"], second["read"]) == ("loop", "kernel")
    assert second["layout"] == "{3,2,1,0}"
    assert second["logical_bytes_a_stack"] == ex.k.nbytes
    assert second["resident_bytes_a_stack"] >= ex.k.nbytes
    assert batching.layout_text(V5E_GPT2_XL) == "{2,4,3,1,0:T(8,128)(2,1)}"


def test_a_stated_layout_is_lost_when_an_executable_is_serialized():
    """WHY the shape carries the layout and no program states one at its
    edges (PERF.md section 6, PR 45): jax / jaxlib 0.9.0 drop a program's
    entry layouts when they serialize its executable, so a server that
    loads its programs from the persistent compile cache gets the
    backend's default back and a program pinned to the stated layout
    raises. A probe to rerun after a jax upgrade: when it FAILS, stated
    layouts survive the cache, and an engine may state one again."""
    from jax.experimental import serialize_executable as se
    from jax.experimental.layout import Format
    from jax.sharding import SingleDeviceSharding

    fmt = Format(Layout((0, 2, 1)), SingleDeviceSharding(jax.devices()[0]))
    x = jax.device_put(np.arange(512, dtype=np.float32).reshape(4, 8, 16),
                       fmt)
    compiled = jax.jit(lambda a: a * 2, in_shardings=(fmt,),
                       out_shardings=fmt).lower(x).compile()
    assert compiled(x).format.layout.major_to_minor == (0, 2, 1)
    loaded = se.deserialize_and_load(*se.serialize(compiled),
                                     execution_devices=jax.devices()[:1])
    assert loaded(x).format.layout.major_to_minor == (0, 1, 2)
