"""Sequence-parallel stage serving (parallel.sp_stage): the KV prefix cache
sharded across the mesh, decode via cross-device softmax combine — asserted
token-identical to the single-device oracle.

The reference has no long-context mechanism beyond single-server chunked
prefill (SURVEY.md §5.7); this engine is the exceed-the-reference
capability: P devices hold P× the context at fixed per-device HBM.
"""

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.models import (
    gpt2_config,
    init_kv_cache,
    init_params,
    llama_config,
    qwen2_config,
)
from engines import full_forward
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.models.partition import (
    ROLE_FULL,
    StagePlan,
    StageSpec,
    parse_splits,
    slice_stage_params,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.parallel.sp_stage import (
    SpStageRunner,
)

P_DEV = 8


def sp_mesh():
    return Mesh(np.array(jax.devices()[:P_DEV]), ("sp",))


def tiny(family="llama"):
    kw = dict(vocab_size=257, hidden_size=64, num_layers=4, num_heads=4,
              max_position_embeddings=256)
    if family == "gpt2":
        return gpt2_config(**kw)
    kw.update(num_kv_heads=2, intermediate_size=128)
    if family == "qwen2":
        return qwen2_config(**kw)
    return llama_config(**kw)


def full_spec(cfg):
    return StageSpec(index=0, role=ROLE_FULL, start=0, end=cfg.num_layers)


def oracle_tokens(cfg, params, prompt, n_new):
    kc, vc = init_kv_cache(cfg, cfg.num_layers, 1, 128)
    ids = jnp.asarray(np.asarray(prompt, np.int32)[None, :])
    logits, kc, vc = full_forward(cfg, params, ids, kc, vc, jnp.int32(0))
    out = [int(jnp.argmax(logits[0, -1]))]
    cur = len(prompt)
    for _ in range(n_new - 1):
        logits, kc, vc = full_forward(
            cfg, params, jnp.asarray([[out[-1]]], jnp.int32), kc, vc,
            jnp.int32(cur))
        out.append(int(jnp.argmax(logits[0, -1])))
        cur += 1
    return out


def sp_generate(runner, prompt, n_new):
    h = runner.prefill(np.asarray(prompt, np.int32)[None, :])
    tok = int(jnp.argmax(runner.logits_at(h, len(prompt) - 1)[0]))
    out = [tok]
    for _ in range(n_new - 1):
        h = runner.decode(jnp.asarray([[out[-1]]], jnp.int32))
        tok = int(jnp.argmax(runner.logits_at(h, 0)[0]))
        out.append(tok)
    return out


def test_sp_full_model_matches_oracle_llama():
    cfg = tiny()
    params = init_params(jax.random.PRNGKey(0), cfg)
    runner = SpStageRunner(cfg, full_spec(cfg), params, sp_mesh())
    prompt = [5, 9, 23, 7, 81, 2, 14, 3, 19, 44, 6, 77, 8, 1, 90, 33,
              12, 4, 56, 21, 9, 100, 41, 2]          # T=24 -> chunk 3
    ref = oracle_tokens(cfg, params, prompt, 6)
    got = sp_generate(runner, prompt, 6)
    assert got == ref


def test_sp_full_model_matches_oracle_gpt2_and_qwen2():
    for family in ("gpt2", "qwen2"):
        cfg = tiny(family)
        params = init_params(jax.random.PRNGKey(1), cfg)
        runner = SpStageRunner(cfg, full_spec(cfg), params, sp_mesh())
        prompt = list(range(7, 7 + 16))               # T=16 -> chunk 2
        ref = oracle_tokens(cfg, params, prompt, 5)
        got = sp_generate(runner, prompt, 5)
        assert got == ref, family


def test_sp_prefix_cache_is_actually_sharded():
    cfg = tiny()
    params = init_params(jax.random.PRNGKey(0), cfg)
    runner = SpStageRunner(cfg, full_spec(cfg), params, sp_mesh())
    runner.prefill(np.arange(32, dtype=np.int32)[None, :] % cfg.vocab_size)
    shards = runner.pk.addressable_shards
    assert len(shards) == P_DEV
    # Each device holds T/P of the sequence axis — the whole point.
    assert shards[0].data.shape[2] == 32 // P_DEV
    # Padded prompt: T=30 pads to 32, real length tracked separately.
    runner.prefill(np.arange(30, dtype=np.int32)[None, :] % cfg.vocab_size)
    assert runner.prefix_pad == 32 and runner.prefix_len == 30


def test_sp_unaligned_prompt_matches_oracle():
    # T=21 pads to 24; the padded garbage KV must be masked out of decode.
    cfg = tiny()
    params = init_params(jax.random.PRNGKey(2), cfg)
    runner = SpStageRunner(cfg, full_spec(cfg), params, sp_mesh())
    prompt = [(7 * i + 3) % cfg.vocab_size for i in range(21)]
    ref = oracle_tokens(cfg, params, prompt, 6)
    got = sp_generate(runner, prompt, 6)
    assert got == ref


def test_sp_two_stage_pipeline_matches_oracle():
    """Two sp runners chained like pipeline stages: stage0 (embed + first
    span) feeds its sequence-sharded hidden into the last stage (span +
    norm + head) — sequence parallelism INSIDE each pipeline stage."""
    cfg = tiny()
    params = init_params(jax.random.PRNGKey(3), cfg)
    plan = StagePlan.from_splits(cfg.num_layers, parse_splits("2"))
    mesh = sp_mesh()
    s0 = SpStageRunner(cfg, plan.stages[0],
                       slice_stage_params(cfg, params, plan.stages[0]), mesh)
    s1 = SpStageRunner(cfg, plan.stages[1],
                       slice_stage_params(cfg, params, plan.stages[1]), mesh)
    prompt = [5, 9, 23, 7, 81, 2, 14, 3, 19, 44, 6, 77, 8, 1, 90, 33]
    ref = oracle_tokens(cfg, params, prompt, 5)

    h0 = s0.prefill(np.asarray(prompt, np.int32)[None, :])
    h1 = s1.prefill(h0)
    tok = int(jnp.argmax(s1.logits_at(h1, len(prompt) - 1)[0]))
    out = [tok]
    for _ in range(4):
        h0 = s0.decode(jnp.asarray([[out[-1]]], jnp.int32))
        h1 = s1.decode(h0)
        tok = int(jnp.argmax(s1.logits_at(h1, 0)[0]))
        out.append(tok)
    assert out == ref


def test_sp_nonunit_final_norm_matches_oracle():
    """Regression: final_norm must be applied exactly ONCE on the sp path.
    Random init sets norm weights to ones, where a double RMSNorm is the
    identity and hides the bug — perturb them like a trained checkpoint."""
    cfg = tiny()
    params = init_params(jax.random.PRNGKey(4), cfg)
    params = dict(params)
    params["final_norm"] = {
        "w": 1.0 + 0.5 * jax.random.normal(jax.random.PRNGKey(9),
                                           params["final_norm"]["w"].shape)}
    runner = SpStageRunner(cfg, full_spec(cfg), params, sp_mesh())
    prompt = [(7 * i + 3) % cfg.vocab_size for i in range(16)]
    assert sp_generate(runner, prompt, 6) == oracle_tokens(cfg, params,
                                                           prompt, 6)


def test_sp_rejects_sliding_window():
    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.models import (
        mistral_config,
    )

    cfg = mistral_config(vocab_size=257, hidden_size=64, num_layers=2,
                         num_heads=4, num_kv_heads=2, intermediate_size=128,
                         sliding_window=8)
    try:
        SpStageRunner(cfg, full_spec(cfg),
                      init_params(jax.random.PRNGKey(0), cfg), sp_mesh())
    except ValueError as exc:
        assert "sliding" in str(exc)
    else:
        raise AssertionError("sliding-window config must be rejected")


def test_sp_zigzag_layout_matches_oracle():
    """zigzag=True is a pure WORK-BALANCE change (device i holds one early
    + one late half-chunk; the prefix KV lives zigzag-resident): tokens
    must match the oracle exactly — aligned, unaligned, and across the
    prefill/decode boundary."""
    cfg = tiny()
    params = init_params(jax.random.PRNGKey(0), cfg)
    runner = SpStageRunner(cfg, full_spec(cfg), params, sp_mesh(),
                           zigzag=True)
    # T=24: pads to 32 (multiple of 2P=16), zigzag half-chunks of 2.
    prompt = [5, 9, 23, 7, 81, 2, 14, 3, 19, 44, 6, 77, 8, 1, 90, 33,
              12, 4, 56, 21, 9, 100, 41, 2]
    ref = oracle_tokens(cfg, params, prompt, 6)
    got = sp_generate(runner, prompt, 6)
    assert got == ref
    # Unaligned (T=13) exercises the 2P padding path.
    prompt2 = list(range(3, 16))
    ref2 = oracle_tokens(cfg, params, prompt2, 5)
    runner2 = SpStageRunner(cfg, full_spec(cfg), params, sp_mesh(),
                            zigzag=True)
    got2 = sp_generate(runner2, prompt2, 5)
    assert got2 == ref2
