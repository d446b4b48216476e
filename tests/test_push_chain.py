"""Server→server push chain (petals handler.py:310-350 semantics).

In chain mode the client makes ONE call per step; servers relay activations
hop-to-hop and the final token returns along the relay chain. Tokens must be
IDENTICAL to per-hop mode (same executors, same sampling), failover must
blame the right downstream peer and rebuild every hop's KV via chain replay.
"""

import random

import jax

from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.models import (
    init_params,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.models.partition import (
    StagePlan,
    parse_splits,
    slice_stage_params,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.ops.sampling import (
    SamplingParams,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime.client import (
    PipelineClient,
    make_server_record,
)
from engines import stage_executor as StageExecutor
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.scheduling.registry import (
    PlacementRegistry,
)

from engines import build_cluster, oracle_generate, tiny_cfg


def test_push_chain_matches_oracle():
    cfg = tiny_cfg()
    client, transport, _, params, _ = build_cluster(cfg, splits="2,4,6")
    client.use_push_chain = True
    sampling = SamplingParams(temperature=0.0)
    prompt = [5, 9, 23, 7, 81]
    res = client.generate(prompt, max_new_tokens=8, sampling=sampling)
    ref = oracle_generate(cfg, params, prompt, 8, sampling)
    assert res.tokens == ref
    # one chain timing entry, not per-hop entries
    assert set(client.last_prefill_stage_times) == {"chain"}


def test_push_chain_single_client_call_per_step():
    cfg = tiny_cfg()
    client, transport, _, params, _ = build_cluster(cfg, splits="2,4,6")
    client.use_push_chain = True
    first_hop_calls = [0]

    def on_call(peer_id, req):
        # transport.call recursion fires on_call per hop; count only requests
        # that still carry the full downstream chain (client entry calls).
        if len(req.next_servers) == 2:
            first_hop_calls[0] += 1

    transport.on_call = on_call
    res = client.generate([5, 9, 23], max_new_tokens=4,
                          sampling=SamplingParams(temperature=0.0))
    assert len(res.tokens) == 4
    # prefill + 3 decode steps = 4 client entry calls
    assert first_hop_calls[0] == 4


def test_push_chain_failover_blames_downstream_peer():
    """Kill the MIDDLE hop: the chain error must blacklist that peer (not the
    entry hop), re-route to the replica, replay, and keep tokens identical."""
    cfg = tiny_cfg()
    client, transport, _, params, _ = build_cluster(cfg, splits="2,4,6",
                                                    replicas=2)
    client.use_push_chain = True
    sampling = SamplingParams(temperature=0.0)
    prompt = [5, 9, 23, 7, 81]

    seen = [0]
    killed = {}

    def on_call(peer_id, req):
        if not req.is_prefill and not req.is_replay and "s2" in peer_id:
            seen[0] += 1
            killed.setdefault("peer", peer_id)
            if seen[0] == 3:
                transport.kill(peer_id)

    transport.on_call = on_call
    res = client.generate(prompt, max_new_tokens=8, sampling=sampling)
    ref = oracle_generate(cfg, params, prompt, 8, sampling)
    assert res.tokens == ref
    assert client.recoveries >= 1
    # the blacklist names the downstream peer, not the entry hop
    assert killed["peer"] in client.failed_peers.get("stage2", set())
    # entry hop peer was NOT blamed
    entry_peers = {p for p in transport.peers() if "s1" in p}
    assert not (client.failed_peers.get("stage1", set()) & entry_peers)


def test_push_chain_transient_failure_without_replicas_recovers():
    """Regression: one transient flake with NO spare replicas must not wedge
    the client — the chain walk grants blacklist amnesty (like the per-hop
    path's _rediscover) and retries the same peer."""
    cfg = tiny_cfg()
    client, transport, _, params, _ = build_cluster(cfg, splits="2,4,6",
                                                    replicas=1)
    client.use_push_chain = True
    sampling = SamplingParams(temperature=0.0)
    for p in transport.peers():
        if "s2" in p:
            transport.fail_next(p, 1)
    res = client.generate([5, 9, 23], max_new_tokens=4, sampling=sampling)
    ref = oracle_generate(cfg, params, [5, 9, 23], 4, sampling)
    assert res.tokens == ref
    # and the client is still healthy for the NEXT generation
    res2 = client.generate([7, 1, 2], max_new_tokens=3, sampling=sampling)
    ref2 = oracle_generate(cfg, params, [7, 1, 2], 3, sampling)
    assert res2.tokens == ref2


def test_push_chain_over_tcp():
    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime.net import (
        TcpStageServer,
        TcpTransport,
    )

    cfg = tiny_cfg()
    params = init_params(jax.random.PRNGKey(0), cfg)
    plan = StagePlan.from_splits(cfg.num_layers, parse_splits("2,4,6"))
    registry = PlacementRegistry(rng=random.Random(0))
    servers = []
    try:
        for spec in plan.stages[1:]:
            peer = f"tcp-s{spec.index}"
            ex = StageExecutor(cfg, spec, slice_stage_params(cfg, params, spec),
                               peer_id=peer)
            srv = TcpStageServer(ex, wire_dtype="f32")
            srv.start()
            servers.append(srv)
            rec = make_server_record(peer, spec)
            rec.address = srv.address
            registry.register(rec)
        stage0 = StageExecutor(cfg, plan.stages[0],
                               slice_stage_params(cfg, params, plan.stages[0]),
                               peer_id="client-local")
        transport = TcpTransport(registry, wire_dtype="f32")
        client = PipelineClient(cfg, plan, stage0, transport, registry,
                                settle_seconds=0.0, use_push_chain=True)
        sampling = SamplingParams(temperature=0.0)
        prompt = [5, 9, 23]
        res = client.generate(prompt, max_new_tokens=6, sampling=sampling)
        ref = oracle_generate(cfg, params, prompt, 6, sampling)
        assert res.tokens == ref
    finally:
        for srv in servers:
            srv.stop()


def test_push_chain_over_tcp_sampled_stream_window():
    """Push chain + persistent streams + temperature>0: the first hop's
    stream must append tokens that were sampled DOWNSTREAM and only relayed
    through it, or the final stage's repetition-penalty window freezes at
    stream_open contents (review finding). Parity with the oracle sampler
    over enough steps that the window materially matters proves the relay
    append works."""
    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime.net import (
        TcpStageServer,
        TcpTransport,
    )

    cfg = tiny_cfg()
    params = init_params(jax.random.PRNGKey(0), cfg)
    plan = StagePlan.from_splits(cfg.num_layers, parse_splits("2,4,6"))
    registry = PlacementRegistry(rng=random.Random(0))
    servers = []
    try:
        for spec in plan.stages[1:]:
            peer = f"tcp-sw-s{spec.index}"
            ex = StageExecutor(cfg, spec, slice_stage_params(cfg, params, spec),
                               peer_id=peer)
            srv = TcpStageServer(ex, wire_dtype="f32")
            srv.start()
            servers.append(srv)
            rec = make_server_record(peer, spec)
            rec.address = srv.address
            registry.register(rec)
        stage0 = StageExecutor(cfg, plan.stages[0],
                               slice_stage_params(cfg, params, plan.stages[0]),
                               peer_id="client-local")
        transport = TcpTransport(registry, wire_dtype="f32")
        assert transport.use_streams
        client = PipelineClient(cfg, plan, stage0, transport, registry,
                                settle_seconds=0.0, use_push_chain=True)
        sampling = SamplingParams(temperature=0.8, top_p=0.95, top_k=50,
                                  repetition_penalty=1.6)
        prompt = [5, 9, 23]
        res = client.generate(prompt, max_new_tokens=10, sampling=sampling)
        ref = oracle_generate(cfg, params, prompt, 10, sampling)
        assert res.tokens == ref
        # And the stream actually carried the steps (one open per hop).
        assert servers[0].stream_opens >= 1 and servers[0].stream_steps >= 9
    finally:
        for srv in servers:
            srv.stop()
