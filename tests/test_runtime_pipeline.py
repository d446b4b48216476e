"""End-to-end runtime: distributed pipeline == single-process oracle.

This is the in-process integration rig the reference lacked (SURVEY.md §4 —
its 'test' was ``scripts/run_all.py`` spawning real subprocesses and a human
comparing logs). Here the whole 4-stage pipeline runs in one process over
`LocalTransport` and every token is asserted against the unpartitioned
`full_forward` oracle (the ``scripts/single_gpu_check.py`` role, automated).
"""

import random

import jax
import jax.numpy as jnp
import numpy as np

from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.models import (
    full_forward,
    gpt2_config,
    init_kv_cache,
    init_params,
    llama_config,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.models.partition import (
    StagePlan,
    parse_splits,
    slice_stage_params,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.ops.sampling import (
    RECENT_WINDOW,
    SamplingParams,
    sample_token,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime.client import (
    PipelineClient,
    make_server_record,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime.executor import (
    StageExecutor,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime.transport import (
    LocalTransport,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.scheduling.registry import (
    PlacementRegistry,
)


def kernel_cfg():
    """Llama-shaped, every matmul site eligible for the Pallas kernels (K
    and N multiples of 128 in the engine-fused layout)."""
    return llama_config(vocab_size=128, hidden_size=128, num_layers=2,
                        num_heads=4, num_kv_heads=2, intermediate_size=256,
                        max_position_embeddings=32)


def tiny_cfg(family="llama"):
    if family == "gpt2":
        return gpt2_config(vocab_size=257, hidden_size=64, num_layers=8,
                           num_heads=4, max_position_embeddings=256)
    if family == "qwen2":
        from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.models import (
            qwen2_config,
        )

        return qwen2_config(vocab_size=257, hidden_size=64, num_layers=8,
                            num_heads=4, num_kv_heads=2, intermediate_size=128,
                            max_position_embeddings=256)
    if family == "gemma2":
        from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.models import (
            gemma2_config,
        )

        # Small softcaps so dropping them would change tokens (the
        # production 50/30 sit in tanh's linear region on tiny models);
        # window=4 actually truncates at these sequence lengths.
        return gemma2_config(vocab_size=257, hidden_size=64, num_layers=4,
                             num_heads=4, num_kv_heads=2,
                             intermediate_size=128, head_dim=32,
                             sliding_window=4, query_pre_attn_scalar=16.0,
                             attn_softcap=2.0, final_softcap=3.0,
                             max_position_embeddings=256)
    if family == "mistral-window":
        from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.models import (
            mistral_config,
        )

        # One sliding window for every layer; 4 truncates at these lengths.
        return mistral_config(sliding_window=4, vocab_size=257,
                              hidden_size=64, num_layers=8, num_heads=4,
                              num_kv_heads=2, intermediate_size=128,
                              max_position_embeddings=256)
    return llama_config(vocab_size=257, hidden_size=64, num_layers=8,
                        num_heads=4, num_kv_heads=2, intermediate_size=128,
                        max_position_embeddings=256)


def build_cluster(cfg, splits="3,6", replicas=1, seed=0):
    params = init_params(jax.random.PRNGKey(seed), cfg)
    plan = StagePlan.from_splits(cfg.num_layers, parse_splits(splits))
    transport = LocalTransport()
    registry = PlacementRegistry(rng=random.Random(seed))
    for spec in plan.stages[1:]:
        for r in range(replicas):
            peer = f"peer-s{spec.index}-r{r}"
            ex = StageExecutor(cfg, spec, slice_stage_params(cfg, params, spec),
                               peer_id=peer)
            transport.add_peer(peer, ex)
            registry.register(make_server_record(peer, spec))
    stage0 = StageExecutor(cfg, plan.stages[0],
                           slice_stage_params(cfg, params, plan.stages[0]),
                           peer_id="client-local")
    client = PipelineClient(cfg, plan, stage0, transport, registry,
                            settle_seconds=0.0, seed=seed)
    return client, transport, registry, params, plan


def oracle_generate(cfg, params, prompt_ids, max_new_tokens, sampling, seed=0,
                    max_len=256):
    """Unpartitioned reference loop with identical sampling semantics."""
    kc, vc = init_kv_cache(cfg, cfg.num_layers, 1, max_len)
    ids = jnp.asarray(np.asarray(prompt_ids, np.int32)[None, :])
    generated = []
    cache_len = jnp.int32(0)
    logits, kc, vc = full_forward(cfg, params, ids, kc, vc, cache_len)
    cur_len = len(prompt_ids)

    def pick(logits_last, step):
        recent = np.zeros((RECENT_WINDOW,), np.int32)
        n = min(len(generated), RECENT_WINDOW)
        if n:
            recent[:n] = np.asarray(generated[-n:], np.int32)
        return int(sample_token(
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
        logits, kc, vc = full_forward(cfg, params, nxt, kc, vc, jnp.int32(cur_len))
        generated.append(pick(logits[0, 0], step))
        cur_len += 1
    return generated


def test_pipeline_greedy_matches_oracle():
    cfg = tiny_cfg()
    client, _, _, params, _ = build_cluster(cfg, splits="2,4,6")
    sampling = SamplingParams(temperature=0.0)
    prompt = [5, 9, 23, 7, 81]
    res = client.generate(prompt, max_new_tokens=8, sampling=sampling)
    ref = oracle_generate(cfg, params, prompt, 8, sampling)
    assert res.tokens == ref
    assert res.ttft_s > 0
    assert set(client.last_prefill_stage_times) == {"stage1", "stage2", "stage3"}


def test_pipeline_qwen2_matches_oracle():
    # Qwen2 (llama + q/k/v biases) through the full distributed pipeline.
    cfg = tiny_cfg("qwen2")
    client, _, _, params, _ = build_cluster(cfg, splits="3,6")
    sampling = SamplingParams(temperature=0.0)
    prompt = [5, 9, 23, 7, 81]
    res = client.generate(prompt, max_new_tokens=8, sampling=sampling)
    assert res.tokens == oracle_generate(cfg, params, prompt, 8, sampling)


def test_pipeline_sampled_matches_oracle():
    cfg = tiny_cfg("gpt2")
    client, _, _, params, _ = build_cluster(cfg, splits="4")
    sampling = SamplingParams(temperature=0.8, top_p=0.9, top_k=20,
                              repetition_penalty=1.5)
    prompt = [11, 42, 7]
    res = client.generate(prompt, max_new_tokens=10, sampling=sampling)
    ref = oracle_generate(cfg, params, prompt, 10, sampling)
    assert res.tokens == ref


def test_failover_mid_generation_preserves_tokens():
    """Kill the pinned stage-2 server mid-decode; the client must fail over to
    the replica, replay the journal, and produce IDENTICAL tokens (the
    reference's manual kill_stage.py protocol, automated with assertions)."""
    cfg = tiny_cfg()
    client, transport, _, params, _ = build_cluster(cfg, splits="2,4,6", replicas=2)
    sampling = SamplingParams(temperature=0.0)
    prompt = [5, 9, 23, 7, 81]

    # Kill the pinned stage-2 peer after the 3rd decode step.
    seen_decode_steps = [0]
    pinned = {}

    def on_call(peer_id, req):
        if not req.is_prefill and not req.is_replay and "s2" in peer_id:
            seen_decode_steps[0] += 1
            pinned.setdefault("peer", peer_id)
            if seen_decode_steps[0] == 3:
                transport.kill(peer_id)

    transport.on_call = on_call
    res = client.generate(prompt, max_new_tokens=8, sampling=sampling)
    ref = oracle_generate(cfg, params, prompt, 8, sampling)
    assert res.tokens == ref
    assert client.recoveries >= 1
    # The replacement actually served traffic.
    killed = pinned["peer"]
    others = [p for p in transport.peers() if "s2" in p and p != killed]
    assert any(transport.executor(p).requests_served > 0 for p in others)


def test_failover_total_outage_raises():
    cfg = tiny_cfg()
    client, transport, _, _, _ = build_cluster(cfg, splits="2,4,6", replicas=1)
    for p in transport.peers():
        if "s3" in p:
            transport.kill(p)
    try:
        client.generate([1, 2, 3], max_new_tokens=4,
                        sampling=SamplingParams(temperature=0.0))
        raised = False
    except RuntimeError:
        raised = True
    assert raised


def test_transient_flake_recovers_without_replacement_pool():
    """fail_next models a transient network partition: same peer pool, the
    retry loop must eventually succeed via the replica."""
    cfg = tiny_cfg()
    client, transport, _, params, _ = build_cluster(cfg, splits="2,4,6", replicas=2)
    # Flake every stage-1 peer once: first call fails, rediscovery picks the
    # replica (also flaked once) -> second attempt inside recovery succeeds.
    for p in transport.peers():
        if "s1" in p:
            transport.fail_next(p, 1)
    res = client.generate([5, 9, 23], max_new_tokens=6,
                          sampling=SamplingParams(temperature=0.0))
    ref = oracle_generate(cfg, params, [5, 9, 23], 6,
                          SamplingParams(temperature=0.0))
    assert res.tokens == ref


def test_module_routing_covers_pipeline():
    """Module-mode routing: greedy max-end_block cover (rpc_transport.py:393-493)."""
    cfg = tiny_cfg()
    client, transport, registry, params, plan = build_cluster(cfg, splits="2,4,6")
    client.use_module_routing = True
    hops = client.route(refresh=True)
    assert [(h.start_block, h.end_block) for h in hops] == [(2, 4), (4, 6), (6, 8)]
    assert hops[-1].expect_token
    res = client.generate([5, 9, 23], max_new_tokens=5,
                          sampling=SamplingParams(temperature=0.0))
    ref = oracle_generate(cfg, params, [5, 9, 23], 5,
                          SamplingParams(temperature=0.0))
    assert res.tokens == ref


def test_repeat_stop():
    cfg = tiny_cfg()
    client, _, _, _, _ = build_cluster(cfg)
    # Force degenerate repetition by zero temperature on a tiny model with a
    # fixed-point argmax: not guaranteed, so instead assert the stop logic via
    # the result flag when it happens; otherwise max_tokens.
    res = client.generate([3, 3, 3], max_new_tokens=12,
                          sampling=SamplingParams(temperature=0.0))
    assert res.stopped_by in ("repeat", "max_tokens", "eos")
    assert len(res.tokens) <= 12


def test_remote_sessions_freed_after_generation():
    """Regression: every generate() must release its KV lease on all remote
    peers — otherwise repeated generations exhaust the server arenas."""
    cfg = tiny_cfg()
    client, transport, _, _, _ = build_cluster(cfg, splits="2,4,6")
    for _ in range(3):
        client.generate([5, 9, 23], max_new_tokens=3,
                        sampling=SamplingParams(temperature=0.0))
    for p in transport.peers():
        assert transport.executor(p).arena.active_sessions() == ()
    assert client.stage0.arena.active_sessions() == ()
