"""A looped stack (``ModelConfig.loop_steps > 1``: the same layers run
several times a token over one copy of their weights, a K/V cache of its
own for every pass) on the batched stage engine, against the benchmark's
plain reference of the family (``perfbench/references/ouro_plain.py``: no
cache, no pass index, nothing of the program).

Tiny sizes with L = 3 layers and T = 4 passes, unlike on purpose: a cache
index built from the wrong one of the two shows. The rider lane's cases are
``test_looped_rider.py``; both build their engines by `engines.looped`."""

import dataclasses
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.models import (
    config as config_mod,
    init_kv_cache,
    init_params,
)
from engines import full_forward
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.models.hf_import import (
    convert_state_dict,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.models.partition import (
    StagePlan,
    slice_stage_params,
    stage_forward,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.models.transformer import (
    looped_forward,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime import (
    batching,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime.batching import (
    BatchedStageExecutor,
)

from engines import (
    LAYERS,
    LOOPED_HF as HF,
    PASSES,
    TOLERANCE,
    VOCAB,
    engine,
    greedy_entry,
    ids_of,
    looped,
    looped_config,
    looped_logits,
    reference,
    reference_weights,
    rel_rms,
)

ref = reference("ouro")

# -- the five ways rows reach the cache, each against the reference ----------

def drive_prefill_decode(eng, ids):
    """prefill 12, then 5 single steps through the cache: rows 0..16."""
    h = eng.prefill("s", ids[None, :12])
    rows = [np.asarray(eng.logits(h))[0]]
    for j in range(12, 17):
        out = eng.decode_batch({"s": ids[None, j:j + 1]})
        rows.append(np.asarray(eng.logits(out["s"]))[0])
    return np.concatenate(rows), 0


def drive_verify_step(eng, ids):
    """prefill 10, one speculative verify step of K + 1 = 5 tokens."""
    eng.prefill("s", ids[None, :10])
    out = eng.decode_batch({"s": ids[None, 10:15]})
    return np.asarray(eng.logits(out["s"]))[0], 10


def drive_rewind(eng, ids):
    """prefill 10, a 5-token step, rewind past 3 of them, then the rows
    again one by one: the second visit overwrites every pass's rows."""
    eng.prefill("s", ids[None, :10])
    eng.decode_batch({"s": np.asarray([[1, 2, 3, 4, 5]], np.int32)})
    eng.rewind("s", 10)
    rows = []
    for j in range(10, 15):
        out = eng.decode_batch({"s": ids[None, j:j + 1]})
        rows.append(np.asarray(eng.logits(out["s"]))[0])
    return np.concatenate(rows), 10


def drive_suffix_prefill(eng, ids):
    """A prefix-store hit: session a registers the first 8 ids' grains,
    session b shares them and prefills only its suffix over the copied
    rows of EVERY pass."""
    other = ids.copy()
    other[8:] = (other[8:] + 1) % VOCAB
    eng.prefill("a", other[None, :14], prefix_len=8)
    h = eng.prefill("b", ids[None, :14], prefix_len=8)
    assert eng.prefix_store.hits > 0
    out = eng.decode_batch({"b": ids[None, 14:15]})
    return np.concatenate([np.asarray(eng.logits(h))[0],
                           np.asarray(eng.logits(out["b"]))[0]]), 8


DRIVES = {"prefill_decode": drive_prefill_decode,
          "verify_step": drive_verify_step, "rewind": drive_rewind,
          "suffix_prefill": drive_suffix_prefill}


@pytest.mark.parametrize("kind", ["float32", "bfloat16", "int8"])
@pytest.mark.parametrize("path", sorted(DRIVES))
def test_engine_rows_match_the_reference(path, kind):
    kw = ({"prefix_cache_bytes": 1 << 22} if path == "suffix_prefill"
          else {})
    _, weights, eng = looped(kind, **kw)
    if path == "suffix_prefill":
        eng.prefix_store.grain = 4
    ids = ids_of(20)
    got, first = DRIVES[path](eng, ids)
    want = looped_logits(weights, ids)[first:first + len(got)]
    worst = max(rel_rms(g, w) for g, w in zip(got, want))
    assert worst <= TOLERANCE[kind], (path, kind, worst)


@pytest.mark.parametrize("kind", ["float32", "bfloat16", "int8"])
def test_the_16_tick_burst_emits_the_reference_s_tokens(kind):
    """Greedy, two sessions of unlike lengths in one program: every token
    the burst emits is judged on the reference's row at its position (the
    benchmark's burst_gap)."""
    _, weights, eng = looped(kind)
    seqs = {"a": ids_of(9, 1), "b": ids_of(13, 2)}
    for sid, ids in seqs.items():
        eng.prefill(sid, ids[None, :-1])
    res = eng.decode_burst({sid: greedy_entry(ids[-1], 16)
                            for sid, ids in seqs.items()}, 16)
    for sid, ids in seqs.items():
        toks = res[sid]["tokens"]
        assert len(toks) >= 5       # a greedy repeat may stop it, no sooner
        consumed = np.concatenate([ids, toks[:-1]]).astype(np.int32)
        want = looped_logits(weights, consumed)[len(ids) - 1:]
        gaps = [(row.max() - row[t]) / np.sqrt((row * row).mean())
                for row, t in zip(want, toks)]
        assert np.mean(gaps) <= {"float32": 1e-5, "bfloat16": 0.05,
                                 "int8": 0.05}[kind], (kind, gaps)


def test_burst_returns_the_passes_only_for_a_looped_stack():
    """The looped program's packed result ends, after the tokens, the stops
    and the lengths, in the passes its tokens took and its rider's token
    (none: -1), and it takes the rider as one more argument; a one-pass
    program's ends at the lengths, and it has no lane."""
    _, _, eng = looped()
    eng.prefill("s", ids_of(6)[None])
    rows, args = eng._burst_prep({"s": greedy_entry(3, 4)}, 4)
    out = eng._get_burst_jit(4)(eng.params, *args, eng.k, eng.v,
                                eng._rider_args(None, 4))
    packed = np.asarray(out[0])
    assert len(out) == 3 and packed.shape == ((4 + 2) * eng.slots + 2,)
    assert packed[-2] == PASSES * 4
    assert packed[-1] == -1 and eng.rider_rows == batching.RIDER_ROWS
    _, _, once = looped(hf=dict(HF, total_ut_steps=1))
    once.prefill("s", ids_of(6)[None])
    rows, args = once._burst_prep({"s": greedy_entry(3, 4)}, 4)
    out = once._get_burst_jit(4)(once.params, *args, once.k, once.v)
    assert len(out) == 3 and out[0].shape == ((4 + 2) * once.slots,)
    assert once.rider_rows == 0 and not once.can_ride(8, 4)


# -- the gate and the exit rule ----------------------------------------------

def test_gates_match_the_reference():
    cfg, weights, eng = looped()
    ids = ids_of(18)
    kc, vc = init_kv_cache(cfg, cfg.num_layers, 1, 32)
    assert kc.shape[0] == PASSES * LAYERS
    _, _, _, gates, steps = looped_forward(
        cfg, eng.params, jnp.asarray(ids[None]), kc, vc, jnp.int32(0))
    _, want = ref.passes(HF, LAYERS, weights, jnp.asarray(ids))
    assert gates.shape == (PASSES, 1, 18)
    np.testing.assert_allclose(np.asarray(gates[:, 0]), np.asarray(want),
                               atol=2e-5)
    assert np.asarray(steps).tolist() == [[PASSES] * 18]


@pytest.mark.parametrize("bias", [0.0, -1.0, 3.0])
def test_a_forced_early_exit_chooses_the_reference_s_pass(bias):
    """Threshold 0.5, the gate's weight drawn 5 x wider and its bias set so
    that tokens leave at different passes (0.0: at the first or the
    second; -1.0: later; 3.0: all at the first). The engine's logits are
    the reference's, which picks each token's pass itself, through
    prefill, decode and the burst, and the passes the burst counts on the
    device are the reference's."""
    hf = dict(HF, early_exit_threshold=0.5)
    cfg, weights, eng = looped(hf=hf, gate_bias=bias, gate_scale=5.0)
    assert cfg.exit_threshold == 0.5
    ids = ids_of(20, 3)
    _, gates = ref.passes(hf, LAYERS, weights, jnp.asarray(ids))
    at = np.asarray(ref.exit_pass(hf, gates))
    assert len(set(at.tolist())) >= (1 if bias == 3.0 else 2), at
    got, _ = drive_prefill_decode(eng, ids)
    want = looped_logits(weights, ids, hf)[:len(got)]
    assert max(rel_rms(g, w) for g, w in zip(got, want)) <= 1e-4
    # and NOT the last pass's logits, where the reference left earlier
    last = looped_logits(weights, ids,
                            dict(hf, early_exit_threshold=2.0))[:len(got)]
    early = at[:len(got)] < PASSES - 1
    assert early.any()
    assert min(rel_rms(g, w) for g, w in zip(got[early], last[early])) > 1e-3
    # the burst, straight from its program: greedy tokens, passes counted
    eng.rewind("s", 12)
    rows, args = eng._burst_prep({"s": greedy_entry(ids[12], 6)}, 6)
    out = eng._get_burst_jit(6)(eng.params, *args, eng.k, eng.v,
                                eng._rider_args(None, 6))
    packed, eng.k, eng.v = out
    packed = np.asarray(packed)
    toks = packed[:6 * eng.slots].reshape(6, eng.slots)[:, rows["s"]]
    toks = toks[toks >= 0]              # a greedy repeat may end it early
    consumed = np.concatenate([ids[:13], toks[:-1]]).astype(np.int32)
    _, g2 = ref.passes(hf, LAYERS, weights, jnp.asarray(consumed))
    at2 = np.asarray(ref.exit_pass(hf, g2))[12:]
    assert len(at2) == len(toks) >= 5
    assert int(packed[-2]) == int((at2 + 1).sum())
    rows_ref = looped_logits(weights, consumed, hf)[12:]
    assert toks.tolist() == rows_ref.argmax(-1).tolist()


def test_a_cache_shared_across_passes_fails_the_comparison(monkeypatch):
    """The check that the comparison has teeth: with every pass reading and
    writing pass 0's cache layers (weight index for cache index) the
    decode rows leave the reference by far more than any tolerance; the
    prefill's own rows, which attend over fresh keys, do not."""
    monkeypatch.setattr(batching, "_at",
                        lambda base, i: i if base is None else 0 * base + i)
    _, weights, eng = looped()
    ids = ids_of(20)
    got, _ = drive_prefill_decode(eng, ids)
    want = looped_logits(weights, ids)
    assert max(rel_rms(g, w) for g, w in zip(got[:12], want[:12])) <= 1e-4
    assert min(rel_rms(g, w) for g, w in zip(got[12:], want[12:17])) > 1e-2


def test_a_pass_reading_the_pass_before_fails_the_comparison(
                                                             monkeypatch):
    """Pass t at pass t-1's cache layers (pass 0 at its own): passes 0 and
    1 then share rows."""
    per = LAYERS

    def shifted(base, i):
        return i if base is None else jnp.maximum(base - per, 0) + i

    monkeypatch.setattr(batching, "_at", shifted)
    _, weights, eng = looped()
    ids = ids_of(20)
    got, _ = drive_prefill_decode(eng, ids)
    want = looped_logits(weights, ids)
    assert min(rel_rms(g, w) for g, w in zip(got[12:], want[12:17])) > 1e-2


# -- one pass is the model the repo already ran -------------------------------

def test_one_pass_is_the_sandwich_norm_llama():
    """total_ut_steps 1: no gate leaf, the head norms once, and oracle,
    engine and reference agree with a llama layer with sandwich norms
    (what ``post_norms`` has meant since gemma-2)."""
    hf = dict(HF, total_ut_steps=1)
    cfg, weights, eng = looped(hf=hf)
    assert cfg.loop_steps == 1 and cfg.post_norms
    sd = {k: v for k, v in weights.items() if "early_exit" not in k}
    params = convert_state_dict(cfg, sd, dtype=jnp.float32)
    assert "exit_gate" not in params
    assert "exit_gate" not in init_params(jax.random.PRNGKey(0), cfg)
    ids = ids_of(16)
    kc, vc = init_kv_cache(cfg, cfg.num_layers, 1, 32)
    assert kc.shape[0] == LAYERS
    oracle, _, _ = full_forward(cfg, params, jnp.asarray(ids[None]), kc, vc,
                                jnp.int32(0))
    want = looped_logits(weights, ids, hf)
    assert rel_rms(oracle[0], want) <= 1e-5
    h = eng.prefill("s", ids[None])
    assert rel_rms(np.asarray(eng.logits(h))[0], want) <= 1e-5
    # the same layers as a plain llama config with the post_norms switch
    plain = dataclasses.replace(
        config_mod.llama_config(
            vocab_size=VOCAB, hidden_size=64, num_layers=LAYERS, num_heads=4,
            num_kv_heads=4, intermediate_size=96,
            max_position_embeddings=128, rope_theta=1e6, norm_eps=1e-6),
        post_norms=True)
    again, _, _ = full_forward(plain, params, jnp.asarray(ids[None]), kc, vc,
                               jnp.int32(0))
    np.testing.assert_array_equal(np.asarray(again), np.asarray(oracle))


def test_the_looped_oracle_matches_the_reference():
    cfg, weights, eng = looped()
    ids = ids_of(16)
    kc, vc = init_kv_cache(cfg, cfg.num_layers, 1, 32)
    got, kc, vc = full_forward(cfg, eng.params, jnp.asarray(ids[None, :11]),
                               kc, vc, jnp.int32(0))
    rows = [np.asarray(got[0])]
    for j in range(11, 16):          # T == 1 steps through the cache
        got, kc, vc = full_forward(cfg, eng.params,
                                   jnp.asarray(ids[None, j:j + 1]), kc, vc,
                                   jnp.int32(j))
        rows.append(np.asarray(got[0]))
    assert rel_rms(np.concatenate(rows),
                   looped_logits(weights, ids)) <= 1e-5
    with pytest.raises(ValueError, match="cache layers"):
        full_forward(cfg, eng.params, jnp.asarray(ids[None]),
                     kc[:LAYERS], vc[:LAYERS], jnp.int32(0))


# -- the importer --------------------------------------------------------------

def test_hf_import_round_trip_of_the_published_names():
    weights = reference_weights("ouro", HF, LAYERS, 9)
    cfg = looped_config()
    assert (cfg.model_type, cfg.loop_steps, cfg.exit_threshold,
            cfg.head_dim, cfg.post_norms, cfg.tie_word_embeddings,
            cfg.use_bias, cfg.attn_qkv_bias) == (
        "ouro", PASSES, 1.0, 16, True, False, False, False)
    p = convert_state_dict(cfg, weights, dtype=jnp.float32)
    w = {k: np.asarray(v) for k, v in weights.items()}
    pre = "model.layers.%d."
    for i in range(LAYERS):
        lay = jax.tree.map(lambda x: np.asarray(x[i]), p["layers"])
        for ours, theirs in (("ln1", "input_layernorm"),
                             ("ln3", "input_layernorm_2"),
                             ("ln2", "post_attention_layernorm"),
                             ("ln4", "post_attention_layernorm_2")):
            np.testing.assert_array_equal(
                lay[ours]["w"], w[pre % i + theirs + ".weight"])
        for ours, theirs in (("wq", "q_proj"), ("wk", "k_proj"),
                             ("wv", "v_proj"), ("wo", "o_proj")):
            np.testing.assert_array_equal(
                lay["attn"][ours],
                w[pre % i + f"self_attn.{theirs}.weight"].T)
        for ours, theirs in (("wg", "gate_proj"), ("wu", "up_proj"),
                             ("wd", "down_proj")):
            np.testing.assert_array_equal(
                lay["mlp"][ours], w[pre % i + f"mlp.{theirs}.weight"].T)
        assert set(lay["attn"]) == {"wq", "wk", "wv", "wo"}   # no biases
    np.testing.assert_array_equal(np.asarray(p["exit_gate"]["w"]),
                                  w["model.early_exit_gate.weight"].T)
    np.testing.assert_array_equal(np.asarray(p["exit_gate"]["b"]),
                                  w["model.early_exit_gate.bias"])
    np.testing.assert_array_equal(np.asarray(p["final_norm"]["w"]),
                                  w["model.norm.weight"])
    np.testing.assert_array_equal(np.asarray(p["lm_head"]["w"]),
                                  w["lm_head.weight"].T)
    # random init has the same tree, and the full-span slice keeps the gate
    init = init_params(jax.random.PRNGKey(0), cfg)
    assert (jax.tree.structure(init) == jax.tree.structure(p)
            and jax.tree.map(jnp.shape, init) == jax.tree.map(jnp.shape, p))
    spec = StagePlan.even(cfg.num_layers, 1).stages[0]
    assert "exit_gate" in slice_stage_params(cfg, p, spec)
    with pytest.raises(ValueError, match="sliding"):
        looped_config(dict(HF, use_sliding_window=True))


def test_the_preset_holds_the_published_keys():
    cfg = config_mod.get_config("ouro-2.6b")
    assert (cfg.hidden_size, cfg.num_layers, cfg.num_heads,
            cfg.num_kv_heads, cfg.head_dim, cfg.intermediate_size,
            cfg.vocab_size, cfg.max_position_embeddings, cfg.rope_theta,
            cfg.norm_eps, cfg.loop_steps, cfg.exit_threshold) == (
        2048, 48, 16, 16, 128, 5632, 49152, 65536, 1e6, 1e-6, 4, 1.0)
    assert cfg.post_norms and not cfg.tie_word_embeddings
    assert cfg.rope_scaling is None and cfg.sliding_window is None
    assert config_mod.get_config("ByteDance/Ouro-2.6B") == cfg
    # every other preset runs its stack once
    for name, make in config_mod.PRESETS.items():
        assert (make().loop_steps > 1) == (name == "ouro-2.6b"), name


def test_a_server_frees_what_the_fused_copies_replace():
    """`main.run_serve` hands its batched engine the fused tree and owns the
    staged one: the unfused projection stacks go before the engine is
    built, the leaves the fused tree still holds stay, and the engine, built
    as every other caller builds it, runs."""
    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu import (
        main as main_mod,
    )

    weights = reference_weights("ouro", HF, LAYERS, 5)
    cfg = looped_config()
    params = convert_state_dict(cfg, weights, dtype=jnp.float32)
    spec = StagePlan.even(cfg.num_layers, 1).stages[0]
    staged = slice_stage_params(cfg, params, spec)
    fused = main_mod._fused_stage_params(
        types.SimpleNamespace(quant="none", lora=None), cfg, params, spec)
    gone = [staged["layers"]["attn"][k] for k in ("wq", "wk", "wv")] + [
        staged["layers"]["mlp"][k] for k in ("wg", "wu")]
    assert all(x.is_deleted() for x in gone)
    assert {"wqkv", "wo"} == set(fused["layers"]["attn"])
    assert not any(x.is_deleted() for x in jax.tree.leaves(fused))
    eng = engine(cfg, spec, fused, slots=2, max_len=32)
    assert eng.params["layers"] is fused["layers"]
    ids = ids_of(10)
    h = eng.prefill("s", ids[None])
    assert rel_rms(np.asarray(eng.logits(h))[0],
                   looped_logits(weights, ids)) <= 1e-4


# -- everything that would run one pass refuses --------------------------------

def looped_tiny():
    cfg = looped_config()
    return cfg, init_params(jax.random.PRNGKey(0), cfg)


def refuse_executor(cfg, params):
    from engines import stage_executor as StageExecutor

    spec = StagePlan.even(cfg.num_layers, 1).stages[0]
    StageExecutor(cfg, spec, params)


def refuse_offload(cfg, params):
    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime.offload import (
        OffloadedSpanRunner,
    )

    OffloadedSpanRunner(cfg, StagePlan.even(cfg.num_layers, 1).stages[0],
                        params)


def refuse_fused_greedy(cfg, params):
    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime.fused_decode import (
        make_fused_decode,
    )

    make_fused_decode(cfg, 4, 1)


def refuse_fused_sampled(cfg, params):
    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime.fused_decode import (
        make_fused_sample_decode,
    )

    make_fused_sample_decode(cfg, 4)


def refuse_sp(cfg, params):
    from jax.sharding import Mesh

    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.parallel.sp_stage import (
        SpStageRunner,
    )

    mesh = Mesh(np.asarray(jax.devices()[:2]), ("sp",))
    SpStageRunner(cfg, StagePlan.even(cfg.num_layers, 1).stages[0], params,
                  mesh)


def refuse_tp(cfg, params):
    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.parallel.tensor_parallel import (
        validate_tp,
    )

    validate_tp(cfg, 2)


def refuse_pipeline(cfg, params):
    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.parallel.pipeline import (
        IciPipeline,
    )

    IciPipeline.build(cfg, params, num_stages=1)


def refuse_trainer(cfg, params):
    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.parallel.trainer import (
        PipelineTrainer,
    )

    PipelineTrainer.build(cfg, params, num_stages=1)


def refuse_training_forward(cfg, params):
    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.parallel.trainer import (
        single_device_loss,
    )

    ids = jnp.zeros((1, 1, 4), jnp.int32)
    single_device_loss(cfg, params, ids, ids)


def refuse_finetune(cfg, params):
    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime.finetune import (
        DistributedFineTuner,
    )

    DistributedFineTuner(cfg, None, {})


def refuse_stage_forward(cfg, params):
    spec = StagePlan.even(cfg.num_layers, 1).stages[0]
    kc, vc = init_kv_cache(cfg, cfg.num_layers, 1, 8)
    stage_forward(cfg, spec, params, jnp.zeros((1, 4), jnp.int32), kc, vc,
                  jnp.int32(0))


def refuse_partial_batched(cfg, params):
    spec = StagePlan.even(cfg.num_layers, 3).stages[1]
    BatchedStageExecutor(cfg, spec, slice_stage_params(cfg, params, spec),
                         slots=2, max_len=16)


def refuse_split_route(cfg, params):
    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime.client import (
        PipelineClient,
    )

    client = object.__new__(PipelineClient)
    client.cfg = cfg
    next(PipelineClient._generate_steps(
        client, [1, 2, 3], 2, sampling=None, eos_token_id=None,
        session_id="s", max_length=None, speculative_k=0, draft_fn=None))


REFUSALS = [refuse_executor, refuse_offload, refuse_fused_greedy,
            refuse_fused_sampled, refuse_sp, refuse_tp, refuse_pipeline,
            refuse_trainer, refuse_training_forward, refuse_finetune,
            refuse_stage_forward, refuse_partial_batched, refuse_split_route]


@pytest.mark.parametrize("attempt", REFUSALS,
                         ids=[f.__name__[7:] for f in REFUSALS])
def test_what_would_run_one_pass_refuses(attempt):
    """One message, naming the mechanism and not the model."""
    cfg, params = looped_tiny()
    with pytest.raises((NotImplementedError, ValueError)) as err:
        attempt(cfg, params)
    text = str(err.value)
    assert "layers run several times" in text and "one pass" in text
    assert "ouro" not in text.lower()
    assert config_mod.single_pass_unsupported(
        dataclasses.replace(cfg, loop_steps=1), "x") is None


def test_the_gate_leaf_has_a_replication_row():
    import re

    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.parallel.tensor_parallel import (
        REPLICATED_LEAVES,
    )

    rows = [(rx, why) for rx, why in REPLICATED_LEAVES
            if re.search(rx, "exit_gate/w") and re.search(rx, "exit_gate/b")]
    assert len(rows) == 1 and len(rows[0][1]) > 20
