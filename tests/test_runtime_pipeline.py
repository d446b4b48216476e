"""End-to-end runtime: distributed pipeline == single-process oracle.

This is the in-process integration rig the reference lacked (SURVEY.md §4 —
its 'test' was ``scripts/run_all.py`` spawning real subprocesses and a human
comparing logs). Here the whole 4-stage pipeline runs in one process over
`LocalTransport` and every token is asserted against the unpartitioned
`full_forward` oracle (the ``scripts/single_gpu_check.py`` role, automated).
"""

from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.ops.sampling import (
    SamplingParams,
)

from engines import build_cluster, oracle_generate, tiny_cfg


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
