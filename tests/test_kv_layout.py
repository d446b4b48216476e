"""The K/V stacks' device layout (`BatchedStageExecutor._ask_kv_formats`,
`_stack_program`, `_keep`): chosen once, pinned on every edge a stack
crosses, and never seen by anything that reads the stacks' VALUES.

The CPU compiler keeps arrays dense and major to minor, so the engine asks it
nothing here (`layout_pin_refused`) and builds the programs it always
built. The CPU client does HONOUR a layout it is told, though, so these
tests hand an engine another order (``max_len`` minor, what the v5e holds a
gpt2-xl stack in by default) in place of the compiler's answer and hold it to
a twin engine that was told nothing: the parent's programs."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.layout import Format, Layout
from jax.sharding import SingleDeviceSharding

from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.models import (
    config as config_mod,
    init_params,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.models.partition import (
    StagePlan,
    slice_stage_params,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime import (
    batching,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime.batching import (
    BatchedStageExecutor,
    layout_text,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.telemetry import (
    catalog,
    events as events_mod,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.telemetry.metrics import (
    MetricsRegistry,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.utils.platform import (
    engine_donation,
)

GRAIN = 8
SLOTS = 4
MAX_LEN = 64
TICKS = 4
# ``max_len`` minor: [L, S, max_len, Hkv, Dh] held as XLA's {2,4,3,1,0}.
OTHER = Layout(major_to_minor=(0, 1, 3, 4, 2))
DENSE = "{4,3,2,1,0}"

_TINY = dict(vocab_size=131, hidden_size=64, num_layers=2, num_heads=4,
             max_position_embeddings=128)
FAMILIES = {
    "gpt2": lambda: config_mod.gpt2_config(**_TINY),
    "qwen2": lambda: config_mod.qwen2_config(
        num_kv_heads=2, intermediate_size=96, **_TINY),
    # layers run three times a token: the burst carries a rider lane
    "looped": lambda: config_mod.ouro_config(
        loop_steps=3, head_dim=16, num_kv_heads=4, intermediate_size=96,
        **_TINY),
}


def told(monkeypatch, layout=OTHER):
    """Engines built from here on are handed ``layout`` for both stacks as
    the compiler's answer."""
    fmt = Format(layout, SingleDeviceSharding(jax.devices()[0]))
    monkeypatch.setattr(BatchedStageExecutor, "_ask_kv_formats",
                        lambda self: (fmt, fmt))
    return fmt


def engine(family, *, span=None, prefix_cache=True):
    cfg = FAMILIES[family]()
    params = init_params(jax.random.PRNGKey(3), cfg)
    plan = StagePlan.even(cfg.num_layers, 1 if span is None else 2)
    spec = plan.stages[0 if span is None else span]
    ex = BatchedStageExecutor(
        cfg, spec, slice_stage_params(cfg, params, spec), slots=SLOTS,
        max_len=MAX_LEN, prefix_cache_bytes=(1 << 20) * prefix_cache)
    if prefix_cache:
        ex.prefix_store.grain = GRAIN
    # the gauge in a registry that is switched on (the process's is off)
    ex._m_relaid = catalog.get("server_kv_layout_mismatch_programs",
                               MetricsRegistry(enabled=True))
    return ex


def ids_of(n, seed):
    return np.random.default_rng(seed).integers(
        1, 131, (1, n)).astype(np.int32)


def entry(token, seed=5, temperature=0.8):
    return {"token": int(token), "seed": seed, "budget": TICKS, "eos": None,
            "generated": (int(token),), "temperature": temperature,
            "top_p": 0.95, "top_k": 0, "repetition_penalty": 1.0}


def checksum(ex):
    """The stacks' VALUES, whatever they are laid out as."""
    return (np.asarray(ex.k, np.float64).sum(),
            np.asarray(ex.v, np.float64).sum(),
            np.asarray(ex.k).tobytes(), np.asarray(ex.v).tobytes())


def drive(ex):
    """prefill -> burst -> rewind -> suffix prefill (a prefix-chain write
    and a grain split on the way) -> burst, eight bursts in all; what every
    step produced and the stacks' values after it."""
    seen = []

    def note(what):
        seen.append((what, checksum(ex)))

    a, b = ids_of(21, 1), ids_of(13, 2)
    ex.prefill("a", a, prefix_len=16)       # a miss: registers two grains
    ex.prefill("b", b)
    note("prefills")
    toks = {"a": [7], "b": [9]}
    for _ in range(3):
        out = ex.decode_burst({s: entry(toks[s][-1]) for s in toks}, TICKS)
        for s in toks:
            toks[s] += out[s]["tokens"]
        note(("burst", {s: list(t) for s, t in toks.items()}))
    ex.rewind("a", 21 + 2)                  # speculative rollback
    out = ex.decode_burst({"a": entry(toks["a"][2])}, TICKS)
    note(("burst after rewind", out["a"]["tokens"]))
    # c shares a's first 16 tokens: the chain is written into its slot and
    # the suffix program computes the rest.
    c = np.concatenate([a[:, :16], ids_of(9, 4)], axis=1)
    h = ex.prefill("c", c, prefix_len=24)
    note(("suffix prefill", np.asarray(h).tobytes()))
    toks["c"] = [11]
    for _ in range(4):
        out = ex.decode_burst({s: entry(toks[s][-1], temperature=0.0)
                               for s in ("b", "c")}, TICKS)
        for s in ("b", "c"):
            toks[s] += out[s]["tokens"]
        note(("burst", {s: list(t) for s, t in toks.items()}))
    h = ex.decode_batch({"b": np.asarray([[toks["b"][-1]]], np.int32)})
    note(("decode step", np.asarray(h["b"]).tobytes()))
    assert ex.burst_dispatches == 8
    return seen


def six_programs(ex):
    return {"prefill": ex._prefill_jit, "prefill_suffix": ex._suffix_jit,
            "prefix_chain_write": ex._chain_write_jit,
            "grain_split": next(iter(ex._grain_split_jits.values())),
            "decode_step": ex._decode_jits[1],
            "burst_tick": ex._burst_jits[TICKS]}


@pytest.mark.parametrize("family", list(FAMILIES))
def test_all_six_programs_hold_the_stacks_in_the_one_layout(
        monkeypatch, family):
    """Told another layout than the default, the engine makes its stacks in
    it, every program takes and returns them in it (so the gauge reads 0),
    and every value any step produces (tokens, hidden rows, the stacks
    after each of 8 bursts, a rewind, a suffix prefill over a prefix-chain
    write) is the twin's that was told nothing."""
    plain = engine(family)
    want = drive(plain)
    told(monkeypatch)
    ex = engine(family)
    assert layout_text(ex._kv_layouts[0]) == "{2,4,3,1,0}"
    assert ex.k.shape == plain.k.shape      # the logical shape is the same
    got = drive(ex)
    assert [w for w, _ in got] == [w for w, _ in want]
    for (what, mine), (_, theirs) in zip(got, want):
        assert mine == theirs, what
    programs = six_programs(ex)
    assert all(p is not None for p in programs.values())
    assert (ex.k.format.layout, ex.v.format.layout) == ex._kv_layouts
    assert ex._m_relaid.value == 0 and not ex._relaid
    assert plain._m_relaid.value == 0
    assert (ex.rider_rows > 0) == (family == "looped")


@pytest.mark.parametrize("program, stacks, results", [
    ("prefill", 3, 1), ("prefill_suffix", 3, 1), ("prefix_chain_write", 0, 0),
    ("grain_split", 0, None), ("decode_step", 4, 1), ("burst_tick", 14, 10)])
def test_a_compiled_program_s_stack_edges_are_the_resident_layout(
        monkeypatch, program, stacks, results):
    """What the compiler was told for each of the six: the stack arguments
    (and results, where the program returns them) in the engine's layout."""
    told(monkeypatch)
    ex = engine("looped")
    drive(ex)
    fn = six_programs(ex)[program]
    sds = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)    # noqa: E731
    i32 = jnp.int32(0)
    seg = [sds(ex.k[:, 0, :GRAIN])]
    rows, burst = ex._burst_prep({"b": entry(3)}, TICKS)
    args = {
        "prefill": (ex.params, ids_of(16, 0), i32, ex.k, ex.v, i32),
        "prefill_suffix": (ex.params, ids_of(16, 0), i32, ex.k, ex.v, i32,
                           i32),
        "prefix_chain_write": (ex.k, ex.v, i32, seg, seg),
        "grain_split": (ex.k, ex.v, i32),
        "decode_step": (ex.params, ids_of(SLOTS, 0).T, ex.lengths,
                        np.ones((SLOTS,), bool), ex.k, ex.v),
        "burst_tick": (ex.params, *burst, ex.k, ex.v,
                       ex._rider_args(None, TICKS)),
    }[program]
    compiled = fn.lower(*args).compile()
    for fmt in compiled.input_formats[0][stacks:stacks + 2]:
        assert fmt.layout == ex._kv_layouts[0]
    if results is not None:
        out = compiled.output_formats
        for fmt in out[results:results + 2]:
            assert fmt.layout == ex._kv_layouts[0]


def test_a_program_without_the_pin_is_counted(monkeypatch):
    """The regression the gauge exists for: a program built with a bare
    ``jax.jit`` takes the stacks as they are and returns them in the
    device's default layout, so a whole stack is re-laid on its way out."""
    told(monkeypatch)
    ex = engine("gpt2", prefix_cache=False)
    ex.prefill("a", ids_of(9, 1))
    ex.decode_batch({"a": np.asarray([[5]], np.int32)})
    assert ex._m_relaid.value == 0
    ex._decode_jits[1] = jax.jit(ex._build_decode(1).__wrapped__)
    ex.decode_batch({"a": np.asarray([[6]], np.int32)})
    assert ex._m_relaid.value == 1
    assert layout_text(ex.k.format.layout) == DENSE
    ex.decode_batch({"a": np.asarray([[7]], np.int32)})
    assert ex._m_relaid.value == 1          # programs, not calls
    # ... and the next program that IS pinned refuses what it is handed
    # instead of compiling itself anew around a copy.
    with pytest.raises(ValueError, match="[Ll]ayout"):
        ex.decode_burst({"a": entry(3)}, TICKS)


def lowerings(ex):
    """The four programs of the tick and the first token, lowered on the
    engine's own arguments: name -> (program, args, donated)."""
    rows, burst = ex._burst_prep({"a": entry(3)}, TICKS)
    i32 = jnp.int32(1)
    return {
        "burst_tick": (ex._get_burst_jit(TICKS),
                       (ex.params, *burst, ex.k, ex.v), (14, 15)),
        "decode_step": (ex._build_decode(1),
                        (ex.params, jnp.zeros((SLOTS, 1), jnp.int32),
                         jnp.asarray(ex.lengths), jnp.ones((SLOTS,), bool),
                         ex.k, ex.v), (4, 5)),
        "prefill": (ex._build_prefill(),
                    (ex.params, jnp.zeros((1, 8), jnp.int32), i32, ex.k,
                     ex.v, i32), (3, 4)),
        "prefill_suffix": (ex._build_prefill_suffix(),
                           (ex.params, jnp.zeros((1, 8), jnp.int32), i32,
                            ex.k, ex.v, i32, i32), (3, 4)),
    }


@pytest.mark.parametrize("program", ["burst_tick", "decode_step", "prefill",
                                     "prefill_suffix"])
def test_on_the_cpu_a_program_lowers_to_the_parent_s_text(program):
    """Nothing is asked of the CPU's compiler and nothing pinned: the
    program is ``jax.jit(fn, donate_argnums=engine_donation(..))``, the
    parent's, to the letter of its lowered text."""
    ex = engine("gpt2", prefix_cache=False)
    assert ex.kv_formats == (None, None)
    ex.prefill("a", ids_of(9, 1))
    fn, args, donated = lowerings(ex)[program]
    parent = jax.jit(fn.__wrapped__, donate_argnums=engine_donation(*donated))
    text = fn.lower(*args).as_text()
    assert text == parent.lower(*args).as_text()
    assert "layout_mode" not in text and "mhlo.sharding" not in text


def test_the_pin_is_what_states_a_layout_in_a_program_s_text(monkeypatch):
    """The same lowering with a layout told: the two stack arguments and
    results carry it and nothing else does."""
    told(monkeypatch)
    ex = engine("gpt2", prefix_cache=False)
    ex.prefill("a", ids_of(9, 1))
    fn, args, _ = lowerings(ex)["burst_tick"]
    text = fn.lower(*args).as_text()
    assert text.count('mhlo.layout_mode = "{2,4,3,1,0}"') == 4


@pytest.mark.parametrize("family, span", [("gpt2", None), ("looped", None),
                                          ("qwen2", 1)])
def test_the_compiler_is_asked_before_the_stacks_are_made(
        monkeypatch, family, span):
    """Where a program may state a layout (as on a TPU without the compile
    cache) the engine lowers the program that reads the stacks most with
    ``Layout.AUTO`` over shapes, takes the compiler's answer (the CPU's:
    dense) and pins it: a burst where it holds the whole model, rider lane
    or not, the decode step where it holds a span. One ``kv_layout`` event
    says what was chosen."""
    monkeypatch.setattr(batching, "layout_pin_refused", lambda: None)
    made = []
    real = BatchedStageExecutor._new_stacks
    monkeypatch.setattr(
        BatchedStageExecutor, "_new_stacks",
        lambda self: (made.append(self.kv_formats), real(self))[1])
    rec = events_mod.EventRecorder(enabled=True)
    monkeypatch.setattr(batching._ev, "emit", rec.emit)
    ex = engine(family, span=span, prefix_cache=False)
    (k_fmt, v_fmt), = made              # known when the stacks were made
    assert isinstance(k_fmt, Format) and k_fmt == v_fmt
    assert layout_text(k_fmt.layout) == DENSE
    (event,) = [e for e in rec.events() if e.name == "kv_layout"]
    said = event.fields
    assert said["shape"] == list(ex.k.shape) and said["asked"]
    assert said["not_asked_because"] is None
    assert said["k_layout"] == said["v_layout"] == DENSE
    assert said["logical_bytes_a_stack"] == ex.k.nbytes
    assert said["resident_bytes_a_stack"] >= ex.k.nbytes
    x = (ids_of(7, 1) if span is None else
         np.zeros((1, 7, ex.cfg.hidden_size), np.float32))
    ex.prefill("a", x)
    if span is None:
        ex.decode_burst({"a": entry(3)}, TICKS)
    assert ex._m_relaid.value == 0


def test_nothing_is_asked_where_a_layout_would_not_hold(monkeypatch):
    """On the CPU, and in a process whose programs come from the persistent
    compile cache (a serialized executable has lost its entry layouts), the
    engine asks nothing, pins nothing and says why."""
    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.utils import (
        platform,
    )

    assert platform.layout_pin_refused() == "cpu"
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    was = (jax.config.jax_enable_compilation_cache,
           jax.config.jax_compilation_cache_dir)
    assert was == (False, None)                         # tests/conftest.py
    assert platform.layout_pin_refused() is None
    try:
        jax.config.update("jax_compilation_cache_dir", "/nowhere")
        assert platform.layout_pin_refused() is None    # a place, unused
        jax.config.update("jax_enable_compilation_cache", True)
        assert platform.layout_pin_refused() == "compile_cache"
        jax.config.update("jax_compilation_cache_dir", None)
        assert platform.layout_pin_refused() is None    # no place for it
    finally:
        jax.config.update("jax_enable_compilation_cache", was[0])
        jax.config.update("jax_compilation_cache_dir", was[1])
    monkeypatch.undo()
    asked = []
    monkeypatch.setattr(jax.stages.Lowered, "compile",
                        lambda *a, **k: asked.append(a))
    rec = events_mod.EventRecorder(enabled=True)
    monkeypatch.setattr(batching._ev, "emit", rec.emit)
    ex = engine("gpt2", prefix_cache=False)
    assert ex.kv_formats == (None, None) and not asked
    (event,) = [e for e in rec.events() if e.name == "kv_layout"]
    assert not event.fields["asked"]
    assert event.fields["not_asked_because"] == "cpu"


def test_layout_text_is_xla_s():
    assert layout_text(Layout((0, 1, 2, 3, 4), ((8, 128), (2, 1)))) == (
        "{4,3,2,1,0:T(8,128)(2,1)}")
    assert layout_text(Layout((0, 1, 3, 4, 2))) == "{2,4,3,1,0}"
    assert layout_text(jnp.zeros((2, 3)).format.layout) == "{1,0}"


def test_recovery_makes_the_stacks_in_the_same_layout(monkeypatch):
    """`_recover_slot` after a donated stack was lost: the new stacks are in
    the engine's layout, and the programs compiled for it still run."""
    told(monkeypatch)
    ex = engine("gpt2", prefix_cache=False)
    ex.prefill("a", ids_of(9, 1))
    ex.k.delete()
    ex._recover_slot("a", ex.slot("a"))
    assert layout_text(ex.k.format.layout) == "{2,4,3,1,0}"
    assert not np.asarray(ex.k).any()
    ex.prefill("a", ids_of(9, 1))
    ex.decode_burst({"a": entry(3)}, TICKS)
    assert ex._m_relaid.value == 0


def test_dataclass_fields_unchanged():
    """No `ModelConfig` field came with the layout: the choice reads the
    stack's shape and dtype and the backend's answer."""
    names = {f.name for f in dataclasses.fields(config_mod.ModelConfig)}
    assert not {n for n in names if "layout" in n}
