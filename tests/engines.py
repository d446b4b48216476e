"""The batched stage engines the test files drive, built in ONE place. Holds
no test, so a test file can be split without breaking another's import.

`engine` is `BatchedStageExecutor` with one difference: every engine it makes
for the same ``(cfg, spec, slots)`` runs the SAME jitted programs. The engine
keeps its programs as `jax.jit` closures over ``cfg``, ``spec`` and the slot
count alone (weights, stacks and lengths are arguments), so a second engine's
prefill, decode and burst reuse the first's executables where the shapes
agree and compile beside them where they do not. The tests keep the
persistent compile cache off, so this is the only sharing there is: a case
pays for its assertion and no longer for sixty compiles of one program.

A program's TRACE also reads module-level names (``batching.ATTN_BLOCK``,
``batching._decode_span``, ``slot_attention._INTERPRET`` ...) that tests
patch, and the environment flags `utils.flags` marks ``trace_time``, that
tests set. `_traced_names` is part of the key, so an engine built under a patch
never runs a program traced without it; patch with a module-level object
(not a new lambda a test) where the patched programs should be shared too.

A case that counts compiles, replaces a program slot or reads
``list(ex._burst_jits)`` builds a `BatchedStageExecutor` of its own and says
so. `tests/conftest.py` forgets the programs at the end of every module."""

import functools
import os
import random
import types

import jax
import jax.numpy as jnp
import numpy as np

from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.models import (  # noqa: E501
    config as config_mod,
    hf_import,
    moe,
    partition,
    quant,
    transformer,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.models.partition import (  # noqa: E501
    ROLE_FULL,
    StagePlan,
    StageSpec,
    parse_splits,
    slice_stage_params,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.ops import (  # noqa: E501
    int8_kernel,
    nf4_kernel,
    sampling as sampling_mod,
    slot_attention,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime import (  # noqa: E501
    batching,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime.batching import (  # noqa: E501
    BatchedStageExecutor,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime.client import (  # noqa: E501
    PipelineClient,
    make_server_record,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime.executor import (  # noqa: E501
    StageExecutor,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime.transport import (  # noqa: E501
    LocalTransport,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.scheduling.registry import (  # noqa: E501
    PlacementRegistry,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.utils import (  # noqa: E501
    flags,
)
from perfbench.harness.manifest import load_module

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_TRACED = (batching, partition, transformer, moe, quant, slot_attention,
           int8_kernel, nf4_kernel, sampling_mod)
_PROGRAMS: dict = {}


def _traced_names():
    """Every function and upper-case constant of the modules a program's
    trace reads, BY OBJECT (the key holds a patched lambda, so its id is
    never another's), and every environment flag the catalog says is read
    at trace time (``INT8_FOLD``, ``MOE_SPARSE`` ...)."""
    plain = (int, float, bool, str, tuple, type(None))
    return tuple(
        val for mod in _TRACED for name, val in sorted(vars(mod).items())
        if isinstance(val, (types.FunctionType, functools.partial))
        or name.lstrip("_").isupper() and isinstance(val, plain)) + tuple(
        flags.raw_flag(f.name) for f in flags.FLAGS.values() if f.trace_time)


def _shared(slot):
    """A program slot the engine assigns (``self._prefill_jit = ...``), kept
    in the programs of its key. The constructor's ``= None`` comes before
    the engine has its key and is dropped."""

    def get(self):
        return (self.__dict__.get("programs") or {}).get(slot)

    def put(self, fn):
        if "programs" in self.__dict__:
            self.programs[slot] = fn

    return property(get, put)


class _Engine(BatchedStageExecutor):
    _prefill_jit = _shared("prefill")
    _suffix_jit = _shared("suffix")
    _chain_write_jit = _shared("chain_write")

    def __init__(self, cfg, spec, params, *, slots=8, **kw):
        super().__init__(cfg, spec, params, slots=slots, **kw)
        key = (cfg, spec, slots, _traced_names())
        self.programs = _PROGRAMS.setdefault(key, {})
        for slot in ("_decode_jits", "_burst_jits", "_grain_split_jits"):
            setattr(self, slot, self.programs.setdefault(slot, {}))


engine = _Engine
_STEPS: dict = {}


class _Stage(StageExecutor):
    """`StageExecutor` whose plain step programs (`jax.jit` closures over
    ``cfg`` and the sub-span's spec; the weights are arguments) are those of
    every other executor of the same span in this module. An offloaded or a
    tensor-parallel step holds its weights and stays its executor's own."""

    def _get_subspan(self, a, b):
        fresh = (a, b) not in self._subspans
        sub_spec, sub_params, step = entry = super()._get_subspan(a, b)
        if fresh and hasattr(step, "lower"):
            key = (self.cfg, sub_spec, _traced_names())
            entry = self._subspans[(a, b)] = (
                sub_spec, sub_params, _STEPS.setdefault(key, step))
        return entry


stage_executor = _Stage


def forget_programs():
    _PROGRAMS.clear()
    _STEPS.clear()
    _forward.cache_clear()


def full_spec(cfg):
    return StageSpec(index=0, role=ROLE_FULL, start=0, end=cfg.num_layers)


@functools.lru_cache(maxsize=None)
def reference(family):
    """The benchmark's plain reference of a family
    (``perfbench/references/<family>_plain.py``): nothing of the program."""
    return load_module(os.path.join(ROOT, "perfbench", "references",
                                    family + "_plain.py"))


_MADE: dict = {}


def reference_weights(family, hf, layers, seed, dtype=jnp.float32):
    """``make_weights`` of a family's reference, made once a process: every
    call of it compiles its own draw. The arrays are immutable and shared;
    the dict is the caller's."""
    key = (family, repr(sorted(hf.items())), layers, seed,
           jnp.dtype(dtype).name)
    if key not in _MADE:
        _MADE[key] = reference(family).make_weights(hf, layers, seed, dtype)
    return dict(_MADE[key])


def reference_logits(family, hf, layers, weights, ids, bucket=64):
    """Rows ``[0, len(ids))`` of the reference's logits. The reference runs
    op by op, and every new length compiles each of its ops again, so the
    sequence is padded to a ``bucket``: a causal model's row depends on no
    later one."""
    ids = np.asarray(ids)
    padded = np.zeros((-(-len(ids) // bucket) * bucket,), ids.dtype)
    padded[:len(ids)] = ids
    return np.asarray(reference(family).forward(
        hf, layers, weights, jnp.asarray(padded)))[:len(ids)]


def imported(cfg, weights, dtype=jnp.float32, quantise=None):
    """A reference's checkpoint through the program's importer, quantised
    where asked, sliced to the whole span: what a server hands its engine."""
    params = hf_import.convert_state_dict(cfg, weights, dtype=dtype)
    if quantise:
        params = quant.quantize_params(params, quantise)
    return slice_stage_params(cfg, params, full_spec(cfg))


def reference_engine(cfg, weights, dtype=jnp.float32, quantise=None,
                     make=engine, **engine_kw):
    """The full-span engine over `imported`, its cache rows in ``dtype``
    (``make=BatchedStageExecutor``: one that shares no program)."""
    return make(cfg, full_spec(cfg), imported(cfg, weights, dtype, quantise),
                dtype=dtype, **engine_kw)


# -- the tiny models most files serve, their cluster and the oracle -----------

@functools.lru_cache(maxsize=None)
def _forward(cfg):
    return jax.jit(functools.partial(transformer.full_forward, cfg))


def full_forward(cfg, *args, **kw):
    """`models.full_forward`, the unpartitioned oracle, as ONE program a
    configuration and a shape. Called op by op its scans and conditionals
    compile again on EVERY call: 3.8 s a 12-token greedy loop at the tiny
    sizes against 0.4 s (PR 59), in every test that has an oracle."""
    return _forward(cfg)(*args, **kw)


def kernel_cfg():
    """Llama-shaped, every matmul site eligible for the Pallas kernels (K
    and N multiples of 128 in the engine-fused layout)."""
    return config_mod.llama_config(
        vocab_size=128, hidden_size=128, num_layers=2, num_heads=4,
        num_kv_heads=2, intermediate_size=256, max_position_embeddings=32)


def tiny_cfg(family="llama"):
    tiny = dict(vocab_size=257, hidden_size=64, num_layers=8, num_heads=4,
                max_position_embeddings=256)
    gqa = dict(tiny, num_kv_heads=2, intermediate_size=128)
    if family == "gpt2":
        return config_mod.gpt2_config(**tiny)
    if family == "qwen2":
        return config_mod.qwen2_config(**gqa)
    if family == "gemma2":
        # Small softcaps so dropping them would change tokens (the
        # production 50/30 sit in tanh's linear region on tiny models);
        # window=4 actually truncates at these sequence lengths.
        return config_mod.gemma2_config(
            **dict(gqa, num_layers=4), head_dim=32, sliding_window=4,
            query_pre_attn_scalar=16.0, attn_softcap=2.0, final_softcap=3.0)
    if family == "mistral-window":
        # One sliding window for every layer; 4 truncates at these lengths.
        return config_mod.mistral_config(sliding_window=4, **gqa)
    return config_mod.llama_config(**gqa)


def tiny_engine(seed, family="llama", **engine_kw):
    """(cfg, params, engine): a full-span `engine` over `tiny_cfg(family)`
    with weights drawn from ``seed``."""
    cfg = tiny_cfg(family)
    params = transformer.init_params(jax.random.PRNGKey(seed), cfg)
    return cfg, params, engine(cfg, full_spec(cfg), params, **engine_kw)


def build_cluster(cfg, splits="3,6", replicas=1, seed=0):
    params = transformer.init_params(jax.random.PRNGKey(seed), cfg)
    plan = StagePlan.from_splits(cfg.num_layers, parse_splits(splits))
    transport = LocalTransport()
    registry = PlacementRegistry(rng=random.Random(seed))
    for spec in plan.stages[1:]:
        for r in range(replicas):
            peer = f"peer-s{spec.index}-r{r}"
            ex = stage_executor(cfg, spec,
                                slice_stage_params(cfg, params, spec),
                                peer_id=peer)
            transport.add_peer(peer, ex)
            registry.register(make_server_record(peer, spec))
    stage0 = stage_executor(cfg, plan.stages[0],
                            slice_stage_params(cfg, params, plan.stages[0]),
                            peer_id="client-local")
    client = PipelineClient(cfg, plan, stage0, transport, registry,
                            settle_seconds=0.0, seed=seed)
    return client, transport, registry, params, plan


def oracle_generate(cfg, params, prompt_ids, max_new_tokens, sampling, seed=0,
                    max_len=256):
    """Unpartitioned reference loop with identical sampling semantics."""
    kc, vc = transformer.init_kv_cache(cfg, cfg.num_layers, 1, max_len)
    ids = jnp.asarray(np.asarray(prompt_ids, np.int32)[None, :])
    generated = []
    cache_len = jnp.int32(0)
    logits, kc, vc = full_forward(cfg, params, ids, kc, vc, cache_len)
    cur_len = len(prompt_ids)

    def pick(logits_last, step):
        recent = np.zeros((sampling_mod.RECENT_WINDOW,), np.int32)
        n = min(len(generated), sampling_mod.RECENT_WINDOW)
        if n:
            recent[:n] = np.asarray(generated[-n:], np.int32)
        return int(sampling_mod.sample_token(
            jax.random.PRNGKey(seed + step),
            logits_last,
            jnp.asarray(recent), jnp.asarray(n, jnp.int32),
            jnp.asarray(sampling.temperature, jnp.float32),
            jnp.asarray(sampling.top_p, jnp.float32),
            jnp.asarray(sampling.top_k, jnp.int32),
            jnp.asarray(sampling.repetition_penalty, jnp.float32),
        ))

    generated.append(pick(logits[0, cur_len - 1], 0))
    for step in range(1, max_new_tokens):
        if len(generated) >= 5 and len(set(generated[-5:])) == 1:
            break
        nxt = jnp.asarray([[generated[-1]]], jnp.int32)
        logits, kc, vc = full_forward(cfg, params, nxt, kc, vc,
                                      jnp.int32(cur_len))
        generated.append(pick(logits[0, 0], step))
        cur_len += 1
    return generated


# -- what the files' drives share ---------------------------------------------

def greedy_entry(token, budget=4, generated=None, **knobs):
    """One session's entry of a greedy burst."""
    return {"token": int(token), "seed": 0, "budget": budget, "eos": None,
            "generated": ((int(token),) if generated is None
                          else tuple(generated)),
            "temperature": 0.0, "top_p": 1.0, "top_k": 0,
            "repetition_penalty": 1.0, **knobs}


def rel_rms(got, want):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def bits(a):
    """An array's bytes, for comparisons that a NaN or a -0.0 cannot fool."""
    a = np.asarray(a)
    return a.view(np.uint8 if a.dtype.itemsize == 1 else
                  {2: np.uint16, 4: np.uint32}[a.dtype.itemsize])


def transfer_counts(eng, ad=None):
    """The round path's transfer and dispatch counters on a registry that
    counts (the process's own is off in tests), for an engine and, where
    given, its adapter: a function that reads (up, down, dispatches)."""
    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.telemetry import (  # noqa: E501
        catalog,
    )
    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.telemetry.metrics import (  # noqa: E501
        MetricsRegistry,
    )

    reg = MetricsRegistry(enabled=True)
    moved = eng._m_transfers = catalog.get("server_burst_transfers_total",
                                           reg)
    rounds = eng._m_burst_disp = catalog.get("server_burst_dispatches_total",
                                             reg)
    if ad is not None:
        ad._m_ids_read = moved.labels(dir="down")

    def read():
        by = {dict(c.labels)["dir"]: int(c.value) for c in moved.children()}
        return by.get("up", 0), by.get("down", 0), int(rounds.value)

    return read


def all_eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs nested in it (scan,
    cond, pjit bodies), except the bodies of Pallas kernels."""
    for e in jaxpr.eqns:
        yield e
        if e.primitive.name == "pallas_call":
            continue
        for v in e.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else (v,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from all_eqns(sub)


def program_args(ex, program, ticks=2):
    """``(function, arguments)`` to trace one of an engine's programs by, on
    zeros: ``"burst_tick"``, ``"decode_step-<T>"``, ``"prefill"`` (8 rows,
    5 real) or ``"prefill_suffix"`` (8 rows, 3 real, after 4 stored)."""
    s = ex.slots
    i32 = lambda *shape: jnp.zeros(shape, jnp.int32)         # noqa: E731
    if program == "burst_tick":
        args = [ex.params,
                i32(len(batching.BURST_INTS) + sampling_mod.RECENT_WINDOW, s),
                jnp.ones((len(batching.BURST_FLOATS), s), jnp.float32),
                ex.k, ex.v]
        if ex.rider_rows:
            args.append(ex._rider_args(None, ticks))
        return ex._build_burst(ticks), args
    if program == "prefill":
        return ex._build_prefill(), [ex.params, i32(1, 8), 0, ex.k, ex.v, 5]
    if program == "prefill_suffix":
        return ex._build_prefill_suffix(), [ex.params, i32(1, 8), 0, ex.k,
                                            ex.v, 4, 3]
    t = int(program.rsplit("-", 1)[1])
    return ex._build_decode(t), [ex.params, i32(s, t), i32(s),
                                 jnp.ones((s,), bool), ex.k, ex.v]


# -- the tiny looped stack (test_looped_stack, test_looped_rider,
# test_bounded_attention): L = 3 layers and T = 4 passes, unlike on purpose --

LAYERS, PASSES, VOCAB = 3, 4, 97
LOOPED_HF = {"model_type": "ouro", "hidden_size": 64, "intermediate_size": 96,
             "num_attention_heads": 4, "num_key_value_heads": 4,
             "head_dim": 16, "num_hidden_layers": LAYERS, "vocab_size": VOCAB,
             "max_position_embeddings": 128, "rms_norm_eps": 1e-6,
             "rope_theta": 1000000, "rope_scaling": None,
             "tie_word_embeddings": False, "total_ut_steps": PASSES,
             "early_exit_threshold": 1, "use_sliding_window": False,
             "sliding_window": None}

# How far the engine may lie from the float32 reference, as relative RMS
# over a row of logits. float32: rounding only. bfloat16: 12 layer-visits
# of bf16 activations and cache rows, each sublayer re-scaled by its
# sandwich norm (the CPU reads 0.02-0.04 at these sizes). int8: weight-only
# quantisation with a scale per output column; at K = 64 a column's 64
# weights share one scale, so a matrix adds ~1.5% (the CPU reads 0.05-0.07
# over the 12 layer-visits). A wrong cache layer reads 0.3 and more (the
# two tests below that break the index on purpose).
TOLERANCE = {"float32": 1e-4, "bfloat16": 8e-2, "int8": 0.12}


def looped_config(hf=LOOPED_HF):
    return hf_import.config_from_hf(types.SimpleNamespace(**hf))


def looped(kind="float32", hf=LOOPED_HF, seed=5, gate_bias=None,
           gate_scale=1.0, **engine_kw):
    """(cfg, weights, engine) at the tiny sizes: the reference's seeded
    checkpoint through the program's importer, 3 slots of 64 rows."""
    dtype = jnp.bfloat16 if kind == "bfloat16" else jnp.float32
    weights = reference_weights("ouro", hf, LAYERS, seed, dtype)
    if gate_bias is not None:
        weights["model.early_exit_gate.bias"] = jnp.full((1,), gate_bias,
                                                         dtype)
    weights["model.early_exit_gate.weight"] = (
        weights["model.early_exit_gate.weight"] * gate_scale).astype(dtype)
    cfg = looped_config(hf)
    return cfg, weights, reference_engine(
        cfg, weights, dtype, "int8" if kind == "int8" else None, slots=3,
        max_len=64, **engine_kw)


def ids_of(n, seed=0, vocab=VOCAB):
    return np.random.default_rng(seed).integers(0, vocab, (n,)).astype(
        np.int32)


def looped_logits(weights, ids, hf=LOOPED_HF):
    return reference_logits("ouro", hf, LAYERS, weights, ids)


def rider_of(sid, ids, **knobs):
    return {"session_id": sid, "ids": np.asarray(ids), "seed": 7,
            "generated": (), "temperature": 0.0, "top_p": 1.0, "top_k": 0,
            "repetition_penalty": 1.0, **knobs}


def two_decoding(eng):
    """Sessions x and y prefilled on ``eng``; their entries of a burst."""
    seqs = {"x": ids_of(9, 1), "y": ids_of(13, 2)}
    for sid, ids in seqs.items():
        eng.prefill(sid, ids[None, :-1])
    return {sid: greedy_entry(ids[-1]) for sid, ids in seqs.items()}


# -- the tiny families (test_batching, test_cache_append, test_burst,
# test_bounded_attention) and the slab policy they hold the engine to ---------
#
# The decode step and the burst tick append their T new rows a slot to the
# carried [L, S, max_len, Hkv, Dh] stacks IN PLACE and attend over what they
# read out of the stacks after the write. Until PR 32 each layer's whole
# [S, max_len, Hkv, Dh] slab was sliced out, appended to and written back:
# 72% of the gpt2-xl tick on the v5e. That policy stays HERE, as the oracle:
# `slab_policy_decode_span` is the old ROUND TRIP of a slab around one layer,
# with the rows appended by `_append_rows` (XLA's CPU backend contracts the
# rotary multiply-add differently when the fresh rows feed a row scatter than
# when they feed a dynamic_update_slice, so the old append itself is held
# apart, as plain data movement: tests/test_cache_append.py `slab_append`).

PROMPTS = {
    "a": [5, 9, 23, 7, 81],
    "b": [44, 2, 3],
    "c": [100, 11, 12, 13, 14, 15, 16],
    "d": [7, 7, 9],
}
FAMILIES = ["gpt2", "qwen2", "mistral-window", "gemma2"]


def slab_policy_decode_span(cfg, spec, params, x, positions, lengths, active,
                            k_all, v_all, full_read=False):
    """`runtime.batching._decode_span` with the slab's round trip as it
    was: slab out of the stack, rows appended to the SLAB, attention over
    the new slab, slab written back. Same signature and results (a stack
    that runs once: `_run_passes`'s ``steps`` is None), same
    `_decoder_layer`, same `_append_rows` (on a stack of one layer).

    The READ of the new slab is the engine's own (since PR 35 by blocks up
    to the longest active slot, `_attend_cached`, here over the slab as a
    stack of one layer), so that what the two policies can differ in is
    the write alone; ``full_read`` reads all ``max_len`` rows under a mask
    as every tick did until then: the oracle of the bounded read
    (tests/test_bounded_attention.py)."""
    h = (batching.embed_tokens(cfg, params["embed"], x, positions)
         if spec.is_first else x)
    rope = batching.make_rope(cfg, positions)
    qpos = positions[:, :, None]
    pos_grid = jnp.arange(k_all.shape[2], dtype=jnp.int32)
    allowed = pos_grid[None, None, :] <= qpos
    if cfg.sliding_window:
        allowed &= pos_grid[None, None, :] > qpos - cfg.sliding_window
    blocks = batching.attn_blocks(lengths, active, qpos.shape[1],
                                  k_all.shape[2], jnp)
    rest, held = batching._split_stacks(params["layers"])

    def body(carry, xs):
        h, k_all, v_all = carry
        lp, i = xs
        k_l = jax.lax.dynamic_index_in_dim(k_all, i, 0, keepdims=True)
        v_l = jax.lax.dynamic_index_in_dim(v_all, i, 0, keepdims=True)

        def slab_round_trip(k, v):
            k_new = batching._append_rows(k_l, 0, k.astype(k_l.dtype), lengths,
                                   active)[0]
            v_new = batching._append_rows(v_l, 0, v.astype(v_l.dtype), lengths,
                                   active)[0]
            if full_read:
                return (k_new, v_new,
                        (allowed, qpos, pos_grid[None, None, :]),
                        (k_new, v_new))
            return (batching._CacheLayer(k_new[None], 0, blocks),
                    batching._CacheLayer(v_new[None], 0, blocks),
                    (None, qpos, None), (k_new, v_new))

        h, (k_new, v_new) = batching._decoder_layer(
            cfg, batching._layer_at(lp, held, i), h, rope, slab_round_trip)
        return (h, jax.lax.dynamic_update_index_in_dim(k_all, k_new, i, 0),
                jax.lax.dynamic_update_index_in_dim(v_all, v_new, i, 0)), None

    (h, k_all, v_all), _ = jax.lax.scan(
        body, (h, k_all, v_all),
        (rest, jnp.arange(k_all.shape[0], dtype=jnp.int32)))
    return h, k_all, v_all, None    # one pass: no passes to count


# Module-level, so that every case under a policy shares its programs.
SLAB_POLICIES = {False: slab_policy_decode_span,
                 True: functools.partial(slab_policy_decode_span,
                                         full_read=True)}


def family_engine(family, dtype, max_len=32, make=engine):
    """A tiny full-span engine of ``family`` with weights and cache in
    ``dtype``, its four slots prefilled with `PROMPTS`."""
    dtype = jnp.dtype(dtype)
    cfg = tiny_cfg(family)
    params = transformer.init_params(jax.random.PRNGKey(3), cfg)
    params = jax.tree.map(
        lambda a: a.astype(dtype) if a.dtype == jnp.float32 else a, params)
    ex = make(cfg, full_spec(cfg), params, slots=4, max_len=max_len,
              dtype=dtype)
    for sid, prompt in PROMPTS.items():
        ex.prefill(sid, np.asarray(prompt, np.int32)[None, :])
    return ex


def slot_rows(ex, sid):
    """``(slot, (K bits, V bits))`` of a session's rows in every layer."""
    d = ex._slot_of[sid]
    return d, (bits(ex.k)[:, d].copy(), bits(ex.v)[:, d].copy())


def check_clamped_slot(got, case, first_new):
    """What the clamp tests assert of slot ``got["slot"]`` beyond equality
    with the oracle: parked and left out, every row is as it was
    (``got["before"]``); taking the step up to exactly ``max_len``, rows
    ``[first_new, max_len)`` are new in every layer and the rest as it was."""
    d = got["slot"]
    for stack, was in zip((got["k"], got["v"]), got["before"]):
        now = bits(stack)[:, d]
        if case == "parked-inactive":
            np.testing.assert_array_equal(now, was)
        else:
            assert np.all(np.any(now[:, first_new:] != was[:, first_new:],
                                 axis=(2, 3)))
            np.testing.assert_array_equal(now[:, :first_new],
                                          was[:, :first_new])


def both_policies(monkeypatch, drive, full_read=False):
    """``drive()`` under the slab's round trip (``full_read``: and the read
    of all ``max_len`` rows) and under the engine's own: ``(oracle's
    result, engine's result)``. Each run builds its engine and its
    programs inside ``drive``, so each traces the policy in force."""
    with monkeypatch.context() as m:
        m.setattr(batching, "_decode_span", SLAB_POLICIES[full_read])
        want = drive()
    return want, drive()



def cache_writes_and_slabs(jaxpr, stack_shape):
    """Of every equation under ``jaxpr``: the updates written into an
    operand shaped like the cache stack, and the equations whose output
    is one layer's ``[S, max_len, Hkv, Dh]`` slab."""
    writes, slabs = [], []
    for e in all_eqns(jaxpr):
        name = e.primitive.name
        if (name in ("dynamic_update_slice", "scatter")
                and e.invars[0].aval.shape == stack_shape):
            upd = e.invars[1 if name == "dynamic_update_slice" else 2]
            writes.append((name, upd.aval.shape))
        slabs += [name for v in e.outvars
                  if getattr(v.aval, "shape", None) == stack_shape[1:]]
    return writes, slabs
