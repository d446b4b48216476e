"""RTT publication + latency-aware route planning.

Covers the _ping_next_servers parity surface (petals/server/server.py:760-767:
servers ping their likely next hops and publish the RTTs) and the
latency-aware client routing built on it (scheduling.routing): the planner
minimizes estimated per-step latency  Σ [rtt(prev→s) + span/throughput]
where the greedy router (src/rpc_transport.py:440-449) only maximizes span
coverage.
"""

import random

import jax

from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.models import (
    init_params,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.models.partition import (
    ROLE_LAST,
    ROLE_SEGMENT,
    StagePlan,
    StageSpec,
    parse_splits,
    slice_stage_params,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.ops.sampling import (
    SamplingParams,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime.client import (
    PipelineClient,
)
from engines import stage_executor as StageExecutor
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime.server import (
    measure_next_server_rtts,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime.transport import (
    LocalTransport,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.scheduling.registry import (
    PlacementRegistry,
    ServerRecord,
    ServerState,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.scheduling.routing import (
    plan_min_latency_route,
    route_cost,
)

from engines import oracle_generate, tiny_cfg


def rec(peer, start, end, *, thr=1.0, final=False, rtts=None,
        state=ServerState.ONLINE):
    return ServerRecord(peer_id=peer, start_block=start, end_block=end,
                        throughput=thr, state=state, final_stage=final,
                        next_server_rtts=rtts)


# ---------------------------------------------------------------------------
# Pure planner
# ---------------------------------------------------------------------------

def test_planner_prefers_fast_links_over_max_coverage():
    # One server covers the whole remainder but sits behind a 1s link; a
    # two-hop chain of fast links is cheaper end-to-end. Greedy (max
    # end_block) would take the big span; the planner must not.
    records = [
        rec("big", 2, 8, final=True),
        rec("a", 2, 5, rtts={"b": 0.001}),
        rec("b", 5, 8, final=True),
    ]
    route = plan_min_latency_route(
        records, 2, 8,
        client_rtts={"big": 1.0, "a": 0.001}, default_rtt=0.5)
    assert [h.record.peer_id for h in route] == ["a", "b"]
    assert (route[0].entry, route[0].end) == (2, 5)
    assert (route[1].entry, route[1].end) == (5, 8)


def test_planner_takes_single_hop_when_links_are_equal():
    # Same topology, uniform latency: fewer hops ⇒ fewer RTTs ⇒ single hop.
    records = [
        rec("big", 2, 8, final=True),
        rec("a", 2, 5, rtts={"b": 0.01}),
        rec("b", 5, 8, final=True),
    ]
    route = plan_min_latency_route(
        records, 2, 8, client_rtts={"big": 0.01, "a": 0.01})
    assert [h.record.peer_id for h in route] == ["big"]


def test_planner_uses_published_next_hop_rtts():
    # Second hop has two equal-throughput candidates; the first hop's
    # published RTT table must decide between them.
    records = [
        rec("a", 2, 5, rtts={"slow": 2.0, "fast": 0.001}),
        rec("slow", 5, 8, final=True),
        rec("fast", 5, 8, final=True),
    ]
    route = plan_min_latency_route(records, 2, 8, client_rtts={"a": 0.001})
    assert [h.record.peer_id for h in route] == ["a", "fast"]


def test_planner_charges_default_rtt_for_unmeasured_links():
    # "fast" was never pinged: it gets default_rtt (0.1), not zero — so the
    # measured 0.05 link must win.
    records = [
        rec("a", 2, 5, rtts={"m": 0.05}),
        rec("m", 5, 8, final=True),
        rec("fast", 5, 8, final=True),
    ]
    route = plan_min_latency_route(records, 2, 8, client_rtts={"a": 0.0},
                                   default_rtt=0.1)
    assert [h.record.peer_id for h in route] == ["a", "m"]


def test_planner_weighs_throughput_against_latency():
    # Fast link to a slow server vs slow link to a fast server.
    records = [
        rec("slowcompute", 0, 4, thr=1.0, final=True),   # 4 blocks / 1 rps = 4s
        rec("fastcompute", 0, 4, thr=100.0, final=True),  # 0.04s compute
    ]
    route = plan_min_latency_route(
        records, 0, 4, client_rtts={"slowcompute": 0.01, "fastcompute": 1.0})
    assert route[0].record.peer_id == "fastcompute"  # 1.04 < 4.01


def test_planner_requires_final_stage_and_exclusion():
    records = [rec("a", 0, 4)]  # covers everything but is not final
    assert plan_min_latency_route(records, 0, 4) is None
    records = [rec("a", 0, 4, final=True), rec("b", 0, 4, final=True)]
    route = plan_min_latency_route(records, 0, 4, exclude=("a",))
    assert [h.record.peer_id for h in route] == ["b"]
    assert plan_min_latency_route(records, 0, 4, exclude=("a", "b")) is None


def test_planner_can_enter_span_mid_block():
    # Coverage requires entering "wide" at block 3 (mid-span) after "head".
    records = [
        rec("head", 0, 3, rtts={"wide": 0.001}),
        rec("wide", 1, 6, final=True),
    ]
    route = plan_min_latency_route(records, 0, 6, client_rtts={"head": 0.001})
    assert [(h.record.peer_id, h.entry, h.end) for h in route] == [
        ("head", 0, 3), ("wide", 3, 6)]


def test_route_cost_is_the_minimized_objective():
    records = [
        rec("a", 2, 5, rtts={"b": 0.25}),
        rec("b", 5, 8, thr=2.0, final=True),
    ]
    route = plan_min_latency_route(records, 2, 8, client_rtts={"a": 0.5})
    got = route_cost(route, client_rtts={"a": 0.5})
    # 0.5 + 3/1.0 + 0.25 + 3/2.0
    assert abs(got - (0.5 + 3.0 + 0.25 + 1.5)) < 1e-9


# ---------------------------------------------------------------------------
# Server-side measurement + registry round trip
# ---------------------------------------------------------------------------

def test_measure_next_server_rtts_pings_successors_only():
    reg = PlacementRegistry(rng=random.Random(0))
    reg.register(rec("me", 0, 4))
    reg.register(rec("next1", 4, 8))
    reg.register(rec("next2", 2, 6))          # covers block 4 too
    reg.register(rec("unrelated", 6, 8))      # does not serve block 4
    pings = {"next1": 0.02, "next2": 0.05}
    rtts = measure_next_server_rtts(
        reg, lambda r: pings.get(r.peer_id), "me", 4)
    assert rtts == {"next1": 0.02, "next2": 0.05}


def test_measure_skips_unreachable_peers():
    reg = PlacementRegistry(rng=random.Random(0))
    reg.register(rec("me", 0, 4))
    reg.register(rec("dead", 4, 8))
    rtts = measure_next_server_rtts(reg, lambda r: None, "me", 4)
    assert rtts == {}


def test_heartbeat_carries_rtts_into_registry_record():
    reg = PlacementRegistry(rng=random.Random(0))
    reg.register(rec("a", 0, 4))
    assert reg.heartbeat("a", next_server_rtts={"b": 0.01})
    assert reg.get("a").next_server_rtts == {"b": 0.01}
    # absent -> preserved, not cleared
    assert reg.heartbeat("a", throughput=2.0)
    assert reg.get("a").next_server_rtts == {"b": 0.01}


def test_empty_sweep_retracts_stale_rtts():
    # {} must CLEAR previously published RTTs (None means "no update") —
    # otherwise a dead link's 5ms measurement is advertised forever.
    reg = PlacementRegistry(rng=random.Random(0))
    reg.register(rec("a", 0, 4))
    assert reg.heartbeat("a", next_server_rtts={"b": 0.005})
    assert reg.heartbeat("a", next_server_rtts={})
    assert reg.get("a").next_server_rtts == {}


def test_sweep_budget_bounds_heartbeat_stretch():
    reg = PlacementRegistry(rng=random.Random(0))
    reg.register(rec("me", 0, 4))
    for i in range(5):
        reg.register(rec(f"n{i}", 4, 8))
    calls = []

    def slow_ping(r):
        calls.append(r.peer_id)
        import time as t
        t.sleep(0.05)
        return 0.05

    rtts = measure_next_server_rtts(reg, slow_ping, "me", 4, budget_s=0.08)
    # Budget cuts the sweep short: strictly fewer than all 5 candidates.
    assert 1 <= len(calls) < 5
    assert set(rtts) == set(calls)


def test_remote_registry_restores_freshness_ordering():
    import time as t

    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime.net import (
        RegistryServer,
        RemoteRegistry,
    )

    srv = RegistryServer(port=0)
    srv.start()
    try:
        remote = RemoteRegistry(srv.address)
        remote.register(rec("old", 0, 4))
        t.sleep(0.25)
        remote.register(rec("new", 0, 4))
        got = {r.peer_id: r.timestamp for r in remote.live_servers()}
        # Raw monotonic timestamps are meaningless across hosts; the wire
        # carries age_s so newest-first ordering survives deserialization.
        assert got["new"] > got["old"]
        assert got["new"] - got["old"] > 0.1
    finally:
        srv.stop()


def test_rtts_survive_the_tcp_registry_wire():
    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime.net import (
        RegistryServer,
        RemoteRegistry,
    )

    srv = RegistryServer(port=0)
    srv.start()
    try:
        remote = RemoteRegistry(srv.address)
        remote.register(rec("a", 0, 4, rtts={"b": 0.125}))
        remote.heartbeat("a", next_server_rtts={"b": 0.25, "c": 0.5})
        got = remote.get("a")
        assert got.next_server_rtts == {"b": 0.25, "c": 0.5}
    finally:
        srv.stop()


# ---------------------------------------------------------------------------
# Client integration: route choice + token parity
# ---------------------------------------------------------------------------

def _spec(start, end, total):
    role = ROLE_LAST if end >= total else ROLE_SEGMENT
    return StageSpec(index=start, role=role, start=start, end=end)


def test_latency_client_picks_fast_replica_and_matches_oracle():
    cfg = tiny_cfg()
    params = init_params(jax.random.PRNGKey(0), cfg)
    plan = StagePlan.from_splits(cfg.num_layers, parse_splits("4"))
    total = cfg.num_layers
    transport = LocalTransport()
    registry = PlacementRegistry(rng=random.Random(0))

    # Two replicas of the remote span [4, 8): one behind a slow link.
    for peer, link in (("fast", 0.0), ("slow", 0.35)):
        spec = _spec(4, total, total)
        ex = StageExecutor(cfg, spec, slice_stage_params(cfg, params, spec),
                           peer_id=peer)
        transport.add_peer(peer, ex)
        transport.rtts[peer] = link
        registry.register(rec(peer, 4, total, final=True))

    stage0 = StageExecutor(cfg, plan.stages[0],
                           slice_stage_params(cfg, params, plan.stages[0]),
                           peer_id="client-local")
    client = PipelineClient(cfg, plan, stage0, transport, registry,
                            use_module_routing=True, route_by_latency=True,
                            settle_seconds=0.0, seed=0)
    route = client.route()
    assert [h.peer_id for h in route] == ["fast"]

    sampling = SamplingParams(temperature=0.0)
    prompt = [5, 9, 23, 7, 81]
    res = client.generate(prompt, max_new_tokens=6, sampling=sampling)
    assert res.tokens == oracle_generate(cfg, params, prompt, 6, sampling)


def test_latency_client_falls_back_to_greedy_without_final_coverage():
    # Planner dead-ends (no final-stage server), greedy raises NoRouteError
    # identically — but with a PARTIAL coverage the greedy path still works;
    # here we give greedy a valid route that the planner also finds, plus a
    # failed peer the planner must exclude.
    cfg = tiny_cfg()
    params = init_params(jax.random.PRNGKey(0), cfg)
    plan = StagePlan.from_splits(cfg.num_layers, parse_splits("4"))
    total = cfg.num_layers
    transport = LocalTransport()
    registry = PlacementRegistry(rng=random.Random(0))
    for peer in ("r0", "r1"):
        spec = _spec(4, total, total)
        ex = StageExecutor(cfg, spec, slice_stage_params(cfg, params, spec),
                           peer_id=peer)
        transport.add_peer(peer, ex)
        registry.register(rec(peer, 4, total, final=True))
    stage0 = StageExecutor(cfg, plan.stages[0],
                           slice_stage_params(cfg, params, plan.stages[0]),
                           peer_id="client-local")
    client = PipelineClient(cfg, plan, stage0, transport, registry,
                            use_module_routing=True, route_by_latency=True,
                            settle_seconds=0.0, seed=0)
    client.failed_peers["blocks4"] = {"r0"}
    route = client.route(refresh=True)
    assert [h.peer_id for h in route] == ["r1"]


def test_elastic_server_publishes_next_hop_rtts():
    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime.server import (
        FixedStageServer,
    )

    cfg = tiny_cfg()
    params = init_params(jax.random.PRNGKey(0), cfg)
    total = cfg.num_layers
    transport = LocalTransport()
    registry = PlacementRegistry(rng=random.Random(0))

    front_spec = _spec(2, 5, total)
    back_spec = _spec(5, total, total)
    front = FixedStageServer("front", cfg, front_spec,
                             slice_stage_params(cfg, params, front_spec),
                             registry, transport)
    back = FixedStageServer("back", cfg, back_spec,
                            slice_stage_params(cfg, params, back_spec),
                            registry, transport)
    front.start_serving()
    back.start_serving()
    transport.rtts["back"] = 0.07

    front.heartbeat_once()          # measures after refreshing
    front.heartbeat_once()          # publishes last beat's measurement
    assert registry.get("front").next_server_rtts == {"back": 0.07}
    # The final stage never publishes RTTs (no next hop).
    back.heartbeat_once()
    back.heartbeat_once()
    assert registry.get("back").next_server_rtts is None
    # Next hop dies -> the sweep comes back empty -> the stale 0.07 must be
    # RETRACTED, not pinned forever.
    transport.kill("back")
    front.heartbeat_once()          # measures {} after refreshing with stale
    front.heartbeat_once()          # publishes the retraction
    assert registry.get("front").next_server_rtts == {}
