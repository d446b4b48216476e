"""Multi-model swarm: two models share one registry without cross-routing.

Every reference DHT key embeds the model name (``src/dht_utils.py:20-31``;
``petals/server/server.py:738-744`` keeps a per-model registry) — so a
registry serving two models must never route a client of model A through a
server of model B. Round 1's ServerRecord had no model field; these tests
pin the fixed behavior end to end (discovery, generation, elastic span
choice, and the wire registry).
"""

import random

import jax
import jax.numpy as jnp
import pytest

from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.models import (
    init_params,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.models.partition import (
    parse_splits,
    slice_stage_params,
    StagePlan,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.ops.sampling import (
    SamplingParams,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime.client import (
    make_server_record,
    PipelineClient,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime.executor import (
    StageExecutionError,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime.messages import (
    StageRequest,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime.net import (
    RegistryServer,
    RemoteRegistry,
    TcpStageServer,
    TcpTransport,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime.server import (
    ElasticStageServer,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime.transport import (
    LocalTransport,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.scheduling.registry import (
    PlacementRegistry,
    ServerRecord,
)

from engines import oracle_generate, stage_executor as StageExecutor, tiny_cfg


def _register_swarm(cfg, params, registry, transport, model, seed):
    """Fixed-split stage servers for one model on a SHARED registry+transport."""
    plan = StagePlan.from_splits(cfg.num_layers, parse_splits("3,6"))
    for spec in plan.stages[1:]:
        peer = f"{model}-s{spec.index}"
        ex = StageExecutor(cfg, spec, slice_stage_params(cfg, params, spec),
                           peer_id=peer)
        transport.add_peer(peer, ex)
        registry.register(make_server_record(peer, spec, model=model))
    return plan


def test_two_models_one_registry_no_cross_routing():
    cfg_a = tiny_cfg("llama")
    cfg_b = tiny_cfg("gpt2")
    params_a = init_params(jax.random.PRNGKey(0), cfg_a)
    params_b = init_params(jax.random.PRNGKey(1), cfg_b)
    # Long TTL: this test's subject is model isolation, not liveness — a
    # cold-compile run of two swarms can exceed the default 45 s, expiring
    # the unrefreshed records before the final route assertions.
    registry = PlacementRegistry(rng=random.Random(0), ttl=3600.0)
    transport = LocalTransport()
    plan_a = _register_swarm(cfg_a, params_a, registry, transport, "llama", 0)
    plan_b = _register_swarm(cfg_b, params_b, registry, transport, "gpt2", 1)

    sampling = SamplingParams(temperature=0.0)
    prompt = [5, 9, 23, 7]
    for cfg, params, plan, model in ((cfg_a, params_a, plan_a, "llama"),
                                     (cfg_b, params_b, plan_b, "gpt2")):
        stage0 = StageExecutor(cfg, plan.stages[0],
                               slice_stage_params(cfg, params, plan.stages[0]),
                               peer_id=f"client-{model}")
        client = PipelineClient(cfg, plan, stage0, transport, registry,
                                settle_seconds=0.0, seed=0, model=model)
        got = client.generate(prompt, max_new_tokens=5,
                              sampling=sampling).tokens
        want = oracle_generate(cfg, params, prompt, 5, sampling)
        assert got == want, model
        # Route never touches the other model's peers.
        for hop in client.route():
            assert hop.peer_id.startswith(model)


def test_discovery_filters_by_model():
    registry = PlacementRegistry(rng=random.Random(0))
    registry.register(ServerRecord(peer_id="a0", start_block=0, end_block=4,
                                   stage_index=1, model="m-a"))
    registry.register(ServerRecord(peer_id="b0", start_block=0, end_block=4,
                                   stage_index=1, model="m-b"))
    registry.register(ServerRecord(peer_id="legacy", start_block=0,
                                   end_block=4, stage_index=1))  # untagged
    # Model-scoped queries see their model + legacy untagged records only.
    for _ in range(16):
        assert registry.discover_stage(1, model="m-a") in ("a0", "legacy")
    got = {r.peer_id for r in registry.discover_block(2, model="m-b")}
    assert got == {"b0", "legacy"}
    # Unscoped query sees everything (single-model swarm compatibility).
    got = {r.peer_id for r in registry.discover_block(2)}
    assert got == {"a0", "b0", "legacy"}
    # Coverage is scoped too (feeds load balancing / elastic span choice).
    cov = registry.coverage(4, model="m-a")
    assert all({r.peer_id for r in blk} == {"a0", "legacy"} for blk in cov)


def test_elastic_server_ignores_other_models_coverage():
    """An elastic server balancing model A must not count model B's span as
    coverage — otherwise it would leave A's blocks unserved."""
    cfg = tiny_cfg()
    params = init_params(jax.random.PRNGKey(2), cfg)
    registry = PlacementRegistry(rng=random.Random(0))
    transport = LocalTransport()
    # Model B fully covers [0, 8) — bait for an unscoped rule-1.
    registry.register(ServerRecord(peer_id="other-model", start_block=0,
                                   end_block=8, final_stage=True, model="b"))

    def provider(spec):
        return slice_stage_params(cfg, params, spec)

    es = ElasticStageServer("elastic-a", cfg, provider, registry, transport,
                            num_blocks=4, total_blocks=8, model="a",
                            rng=random.Random(0))
    spec = es.choose_span()
    # With no model-A servers live, rule 1 must behave as on an EMPTY swarm:
    # start at block 0 (the least-covered prefix), not skip past B's span.
    assert spec.start == 0
    es.load_span(spec)
    rec = registry.get("elastic-a")
    assert rec.model == "a"
    es.shutdown()


def test_remote_registry_model_roundtrip():
    """The model field survives the TCP registry wire schema."""
    srv = RegistryServer(port=0, ttl=30.0)
    srv.start()
    try:
        reg = RemoteRegistry(srv.address)
        reg.register(ServerRecord(peer_id="x", start_block=0, end_block=4,
                                  stage_index=1, final_stage=True, model="mx"))
        reg.register(ServerRecord(peer_id="y", start_block=0, end_block=4,
                                  stage_index=1, final_stage=True, model="my"))
        assert reg.get("x").model == "mx"
        assert {r.peer_id for r in reg.live_servers(model="mx")} == {"x"}
        assert reg.discover_stage(1, model="my") == "y"
        cov = reg.coverage(4, model="mx")
        assert all({r.peer_id for r in blk} == {"x"} for blk in cov)
    finally:
        srv.stop()


def test_data_plane_rejects_model_mismatch():
    """The model id is echoed in every request and the server rejects a
    mismatch BEFORE touching the executor (ADVICE r2: registry-side scoping
    alone cannot stop a mis-constructed client from shipping model-A
    activations into model-B blocks). The error is kind="stage" (retryable),
    so the client's failover taxonomy blacklists the peer and re-discovers."""
    import jax.numpy as jnp

    cfg = tiny_cfg()
    params = init_params(jax.random.PRNGKey(0), cfg)
    plan = StagePlan.from_splits(cfg.num_layers, parse_splits("3,6"))
    spec = plan.stages[1]
    ex = StageExecutor(cfg, spec, slice_stage_params(cfg, params, spec),
                       peer_id="srv-a")
    reg_server = RegistryServer()
    reg_server.start()
    srv = TcpStageServer(ex, wire_dtype="f32", model="model-a")
    srv.start()
    try:
        rec = make_server_record("srv-a", spec, model="model-a")
        rec.address = srv.address
        reg_server.registry.register(rec)
        registry = RemoteRegistry(reg_server.address)
        hidden = jnp.zeros((1, 2, cfg.hidden_size), jnp.float32)

        def _req():
            return StageRequest(session_id="s", hidden=hidden, seq_len=2,
                                cur_len=0, is_prefill=True, max_length=8)

        # Wrong model: rejected on both the stream path (stream_open) and
        # the classic full-metadata frame path.
        for streams in (True, False):
            tx_bad = TcpTransport(registry, wire_dtype="f32",
                                  model="model-b", use_streams=streams)
            with pytest.raises(StageExecutionError, match="model mismatch"):
                tx_bad.call("srv-a", _req())
            tx_bad.close()
        # Matching model and legacy untagged client both pass.
        for model in ("model-a", None):
            tx = TcpTransport(registry, wire_dtype="f32", model=model)
            resp = tx.call("srv-a", _req())
            assert resp.hidden is not None
            tx.end_session("srv-a", "s")
            tx.close()
    finally:
        srv.stop()
        reg_server.stop()


def test_relay_propagates_client_model_tag():
    """An UNTAGGED legacy hop relaying a push chain must forward the
    originating client's model tag, not strip it — the tagged downstream
    server is the one that can still catch the mis-route."""
    import jax.numpy as jnp

    cfg = tiny_cfg()
    params = init_params(jax.random.PRNGKey(0), cfg)
    plan = StagePlan.from_splits(cfg.num_layers, parse_splits("2,4,6"))
    registry = PlacementRegistry(rng=random.Random(0))
    servers = []
    try:
        # Hop A: legacy untagged. Hop B (final): tagged model-a.
        for spec, model in ((plan.stages[1], None),
                            (plan.stages[2], "model-a")):
            peer = f"relay-s{spec.index}"
            ex = StageExecutor(cfg, spec, slice_stage_params(cfg, params, spec),
                               peer_id=peer)
            srv = TcpStageServer(ex, wire_dtype="f32", model=model)
            srv.start()
            servers.append(srv)
            rec = make_server_record(peer, spec)  # records untagged: the
            rec.address = srv.address             # mis-route must be possible
            registry.register(rec)
        tx = TcpTransport(registry, wire_dtype="f32", model="model-b",
                          use_streams=False)
        b_rec = registry.get("relay-s2")
        with pytest.raises(StageExecutionError, match="model mismatch") as ei:
            tx.call("relay-s1", StageRequest(
                session_id="s", seq_len=2, cur_len=0, is_prefill=True,
                max_length=8,
                hidden=jnp.zeros((1, 2, cfg.hidden_size), jnp.float32),
                next_servers=({"peer_id": "relay-s2",
                               "address": b_rec.address,
                               "start_block": 4, "end_block": 6},)))
        assert ei.value.peer_id == "relay-s2"  # blame lands downstream
        tx.close()
    finally:
        for srv in servers:
            srv.stop()
