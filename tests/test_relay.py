"""NAT relay data plane (petals/server/reachability.py parity surface).

A server that fails the dial-back reachability vote attaches to a reachable
VOLUNTEER and serves through it: clients dial the volunteer and stamp frames
with relay_to; the volunteer forwards verbatim over a pooled circuit. These
tests pin the full story over real TCP: a relay-only server serving
end-to-end with oracle-identical tokens, failover when its relay dies
mid-generation, gossip re-discovery of the relay_via record with every seed
registry dead, routing deprioritization of relayed peers, and the blame
split (routing blames the hop; the circuit breaker blames whichever
component actually died — one dead relay must not blacklist every peer
behind it).
"""

import random

import jax
import numpy as np
import pytest

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
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime.messages import (
    StageRequest,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime.net import (
    RegistryServer,
    RemoteRegistry,
    TcpStageServer,
    TcpTransport,
    attach_via_relay,
    check_direct_reachability,
    gossip_exchange,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime.transport import (
    PeerUnavailable,
    PushChainError,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.scheduling.gossip import (
    GossipNode,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.scheduling.registry import (
    PlacementRegistry,
    ServerRecord,
    rec_to_dict,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.scheduling.routing import (
    DEFAULT_RTT,
    RouteHop,
    plan_min_latency_route,
    route_cost,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.scheduling.throughput import (
    RELAY_PENALTY,
    get_server_throughput,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.telemetry import (
    events,
)

from engines import build_cluster, oracle_generate, tiny_cfg

# An address nothing listens on: direct dials fail instantly (ECONNREFUSED),
# which is both the NAT model for these tests (advertised-but-unroutable)
# and the proof that a completed generation rode the relay.
UNROUTABLE = "127.0.0.1:9"


def _volunteer(peer_id, capacity, registry, **kw):
    """A relay volunteer: executor-less stage server (forwarding is a
    socket-plane capability) plus its empty-span registry record."""
    srv = TcpStageServer(None, wire_dtype="f32", peer_id=peer_id,
                        relay_capacity=capacity, **kw)
    srv.start()
    rec = ServerRecord(peer_id=peer_id, start_block=0, end_block=0,
                       address=srv.address, relay_capacity=capacity)
    registry.register(rec)
    return srv, rec


def _nat_stage(cfg, params, spec, peer_id, registry):
    """A stage server that is NAT'd by construction: binds locally but
    advertises an address nothing can dial."""
    ex = StageExecutor(cfg, spec, slice_stage_params(cfg, params, spec),
                       peer_id=peer_id)
    srv = TcpStageServer(ex, wire_dtype="f32")
    srv.start()
    rec = make_server_record(peer_id, spec)
    rec.address = UNROUTABLE
    registry.register(rec)
    return srv, rec


# ---------------------------------------------------------------------------
# Relay-only serving, end to end over real TCP
# ---------------------------------------------------------------------------

def test_relay_only_server_serves_end_to_end():
    """The tentpole bar: a server that FAILS the dial-back vote joins
    relay-only and serves a full generation with oracle-identical tokens —
    provably through the volunteer, since its advertised address is a
    closed port."""
    cfg = tiny_cfg()
    params = init_params(jax.random.PRNGKey(0), cfg)
    plan = StagePlan.from_splits(cfg.num_layers, parse_splits("4"))
    registry = PlacementRegistry(rng=random.Random(0))
    vsrv, _ = _volunteer("vol-1", 2, registry)
    nsrv, nrec = _nat_stage(cfg, params, plan.stages[1], "nat-s1", registry)
    transport = TcpTransport(registry, wire_dtype="f32")
    try:
        # The vote: the volunteer dials the advertised address back and
        # reports it dead. (A reachable address would vote True.)
        assert check_direct_reachability(
            transport, registry, UNROUTABLE) is False

        got = attach_via_relay(transport, registry, "nat-s1", nsrv.address)
        assert got is not None and got["relay"] == "vol-1"
        assert got["ttl"] == TcpStageServer.RELAY_CIRCUIT_TTL
        nrec.relay_via = "vol-1"
        registry.register(nrec)
        assert "nat-s1" in vsrv._relay_targets

        stage0 = StageExecutor(cfg, plan.stages[0],
                               slice_stage_params(cfg, params,
                                                  plan.stages[0]),
                               peer_id="client-local")
        client = PipelineClient(cfg, plan, stage0, transport, registry,
                                settle_seconds=0.0)
        sampling = SamplingParams(temperature=0.0)
        prompt = [5, 9, 23, 7]
        res = client.generate(prompt, max_new_tokens=6, sampling=sampling)
        assert res.tokens == oracle_generate(cfg, params, prompt, 6, sampling)
    finally:
        transport.close()
        vsrv.stop()
        nsrv.stop()


def test_relay_attach_sheds_when_saturated():
    """Capacity is enforced at attach: a saturated volunteer answers with an
    error frame (surfaced as PeerUnavailable) and the picker moves on to
    the next candidate, so load spreads across volunteers."""
    registry = PlacementRegistry(rng=random.Random(0))
    v1, _ = _volunteer("vol-1", 2, registry)
    v2, _ = _volunteer("vol-2", 1, registry)
    transport = TcpTransport(registry, wire_dtype="f32")
    try:
        # Fill vol-1 (capacity 2; it sorts first on spare capacity).
        assert attach_via_relay(transport, registry, "p1",
                                "127.0.0.1:5001")["relay"] == "vol-1"
        assert attach_via_relay(transport, registry, "p2",
                                "127.0.0.1:5002")["relay"] == "vol-1"
        # Direct attach to the saturated volunteer is refused...
        with pytest.raises(PeerUnavailable, match="capacity"):
            transport.relay_attach("vol-1", "p3", "127.0.0.1:5003")
        # ...re-attach (lease renewal) of an EXISTING circuit still works...
        transport.relay_attach("vol-1", "p1", "127.0.0.1:5001")
        # ...and the picker routes the newcomer to the spare volunteer.
        assert attach_via_relay(transport, registry, "p3",
                                "127.0.0.1:5003")["relay"] == "vol-2"
    finally:
        transport.close()
        v1.stop()
        v2.stop()


# ---------------------------------------------------------------------------
# Relay death mid-generation -> normal failover/replay path
# ---------------------------------------------------------------------------

def test_relay_failover_when_relay_dies_mid_generation():
    """Kill the active volunteer between decode steps: the NAT'd server
    re-attaches to the standby (its heartbeat re-pick, compressed), the
    client's normal failover/replay path re-resolves the hop, tokens stay
    oracle-identical — and the breaker blames the dead VOLUNTEER, not the
    relayed peer."""
    events.get_recorder().enable()
    cfg = tiny_cfg()
    params = init_params(jax.random.PRNGKey(0), cfg)
    plan = StagePlan.from_splits(cfg.num_layers, parse_splits("4"))
    registry = PlacementRegistry(rng=random.Random(0))
    v1, _ = _volunteer("vol-1", 2, registry)
    v2, _ = _volunteer("vol-2", 1, registry)
    nsrv, nrec = _nat_stage(cfg, params, plan.stages[1], "nat-s1", registry)
    transport = TcpTransport(registry, wire_dtype="f32")
    try:
        assert attach_via_relay(transport, registry, "nat-s1",
                                nsrv.address)["relay"] == "vol-1"
        nrec.relay_via = "vol-1"
        registry.register(nrec)

        stage0 = StageExecutor(cfg, plan.stages[0],
                               slice_stage_params(cfg, params,
                                                  plan.stages[0]),
                               peer_id="client-local")
        client = PipelineClient(cfg, plan, stage0, transport, registry,
                                settle_seconds=0.0)
        sampling = SamplingParams(temperature=0.0)
        prompt = [5, 9, 23, 7]
        got = []
        steps = client.generate_stepwise(prompt, max_new_tokens=6,
                                         sampling=sampling)
        for i, step in enumerate(steps):
            got.extend(step.new_tokens)
            if i == 1:
                # Two steps in: the relay dies, the server re-picks.
                v1.stop()
                got2 = attach_via_relay(transport, registry, "nat-s1",
                                        nsrv.address, exclude=("vol-1",))
                assert got2 is not None and got2["relay"] == "vol-2"
                nrec.relay_via = "vol-2"
                registry.register(nrec)
        assert got == oracle_generate(cfg, params, prompt, 6, sampling)
        assert client.recoveries >= 1

        # Blame split: breaker failures landed on the dead volunteer; the
        # relayed peer's breaker never saw one (it did nothing wrong).
        assert client.breaker._peers.get("vol-1", {}).get("fails", 0) >= 1 \
            or client.breaker.state("vol-1") != "closed"
        assert client.breaker._peers.get("nat-s1", {}).get("fails", 0) == 0
        assert client.breaker.allow("nat-s1")

        # The flight recorder saw the relay loss (doctor's chain trigger).
        names = [e.name for e in events.get_recorder().events()]
        assert "relay_forward_error" in names
    finally:
        transport.close()
        for s in (v1, v2, nsrv):
            s.stop()


# ---------------------------------------------------------------------------
# relay_via replicates through gossip; re-discovery with every seed dead
# ---------------------------------------------------------------------------

def test_relay_record_rediscovered_through_gossip_after_seed_loss(tmp_path):
    """The relay_via record is ordinary gossip payload: after anti-entropy
    replicates it to a volunteer's mirror and BOTH seed registries die, a
    fresh client bootstraps through the peers cache, reads the relayed
    record from the mirror, and serves through the volunteer."""
    cfg = tiny_cfg()
    params = init_params(jax.random.PRNGKey(0), cfg)
    plan = StagePlan.from_splits(cfg.num_layers, parse_splits("4"))
    cache = str(tmp_path / "peers.json")

    seeds = [RegistryServer(), RegistryServer()]
    for s in seeds:
        s.start()
    pair = ",".join(s.address for s in seeds)
    reg1 = RemoteRegistry(pair, timeout=2.0, peers_cache=cache)

    # Volunteer with an embedded gossip mirror (a normal serve process).
    vnode = GossipNode("vol-1", ttl=60.0, rng=random.Random(0))
    vsrv = TcpStageServer(None, wire_dtype="f32", peer_id="vol-1",
                          gossip=vnode, relay_capacity=2)
    vsrv.start()
    vnode.self_address = vsrv.address
    vrec = ServerRecord(peer_id="vol-1", start_block=0, end_block=0,
                        address=vsrv.address, relay_capacity=2)
    vnode.publish(rec_to_dict(vrec))
    reg1.register(vrec)

    nsrv, nrec = _nat_stage(cfg, params, plan.stages[1], "nat-s1", reg1)
    transport = TcpTransport(reg1, wire_dtype="f32")
    tx2 = None
    try:
        assert attach_via_relay(transport, registry=reg1,
                                my_peer_id="nat-s1",
                                my_address=nsrv.address)["relay"] == "vol-1"
        nrec.relay_via = "vol-1"
        reg1.register(nrec)
        # Anti-entropy: the NAT'd server's gossip node replicates its
        # (relay_via-bearing) record into the volunteer's mirror.
        nnode = GossipNode("nat-s1", ttl=60.0, rng=random.Random(1))
        nnode.publish(rec_to_dict(nrec))
        gossip_exchange(nnode, vsrv.address)
        reg1.live_servers()              # persists the peers cache

        for s in seeds:
            s.stop()

        # Fresh client: dead seeds, only the cache file -> the volunteer's
        # mirror serves discovery, relay_via intact.
        reg2 = RemoteRegistry(pair, timeout=0.5, peers_cache=cache)
        recs = {r.peer_id: r for r in reg2.live_servers()}
        assert "nat-s1" in recs
        assert recs["nat-s1"].relay_via == "vol-1"

        tx2 = TcpTransport(reg2, wire_dtype="f32")
        stage0 = StageExecutor(cfg, plan.stages[0],
                               slice_stage_params(cfg, params,
                                                  plan.stages[0]),
                               peer_id="client-local")
        client = PipelineClient(cfg, plan, stage0, tx2, reg2,
                                settle_seconds=0.0)
        sampling = SamplingParams(temperature=0.0)
        prompt = [5, 9, 23, 7]
        res = client.generate(prompt, max_new_tokens=6, sampling=sampling)
        assert res.tokens == oracle_generate(cfg, params, prompt, 6, sampling)
    finally:
        transport.close()
        if tx2 is not None:
            tx2.close()
        vsrv.stop()
        nsrv.stop()
        for s in seeds:
            s.stop()


# ---------------------------------------------------------------------------
# Routing deprioritizes relayed peers (acceptance pin)
# ---------------------------------------------------------------------------

def test_routing_deprioritizes_relayed_peer():
    """Equal direct vs relayed replicas: the planner must take the direct
    one, and the cost gap must be exactly the extra DEFAULT_RTT relay leg."""
    direct = ServerRecord(peer_id="direct", start_block=4, end_block=8,
                          final_stage=True)
    relayed = ServerRecord(peer_id="relayed", start_block=4, end_block=8,
                           final_stage=True, relay_via="vol-1")
    route = plan_min_latency_route([relayed, direct], 4, 8)
    assert [h.record.peer_id for h in route] == ["direct"]

    gap = (route_cost([RouteHop(relayed, 4, 8)])
           - route_cost([RouteHop(direct, 4, 8)]))
    assert gap == pytest.approx(DEFAULT_RTT)


def test_relay_throughput_penalty_in_model():
    """use_relay folds RELAY_PENALTY into the network-bound estimate — the
    advertised-throughput half of the deprioritization."""
    direct = get_server_throughput(None, 64, num_blocks=4)
    relayed = get_server_throughput(None, 64, use_relay=True, num_blocks=4)
    assert relayed == pytest.approx((1.0 - RELAY_PENALTY) * direct)
    assert relayed < direct


# ---------------------------------------------------------------------------
# Blame attribution: which breaker opens for each failure site
# ---------------------------------------------------------------------------

def test_push_error_frame_carries_breaker_peer():
    """Wire-level contract: kind="push" error frames split routing blame
    (`peer`) from breaker blame (`breaker_peer`), and the transport maps
    both onto the raised PushChainError."""
    tx = TcpTransport(PlacementRegistry(), wire_dtype="f32")
    with pytest.raises(PushChainError) as ei:
        tx._parse_response("entry", {"verb": "error", "kind": "push",
                                     "peer": "tgt",
                                     "breaker_peer": "vol-1",
                                     "message": "relay died"}, b"")
    assert ei.value.peer_id == "tgt"
    assert ei.value.breaker_peer_id == "vol-1"
    # No breaker_peer -> the hop itself takes both blames (pre-relay shape).
    with pytest.raises(PushChainError) as ei:
        tx._parse_response("entry", {"verb": "error", "kind": "push",
                                     "peer": "tgt",
                                     "message": "push failed"}, b"")
    assert ei.value.peer_id == "tgt"
    assert ei.value.breaker_peer_id is None


def test_push_chain_blames_volunteer_when_relay_dead_and_target_when_not():
    """Real-wire regression for the push-chain error path: a pushing server
    that cannot DIAL the next hop's relay volunteer blames the volunteer
    (breaker_peer) while keeping routing blame on the hop; a live volunteer
    WITHOUT a circuit blames the target alone (it stopped heartbeating —
    the volunteer did its job)."""
    cfg = tiny_cfg()
    params = init_params(jax.random.PRNGKey(0), cfg)
    plan = StagePlan.from_splits(cfg.num_layers, parse_splits("2,4,6"))
    registry = PlacementRegistry(rng=random.Random(0))
    spec1 = plan.stages[1]
    ex1 = StageExecutor(cfg, spec1, slice_stage_params(cfg, params, spec1),
                        peer_id="entry-s1")
    s1 = TcpStageServer(ex1, wire_dtype="f32")
    s1.start()
    rec1 = make_server_record("entry-s1", spec1)
    rec1.address = s1.address
    registry.register(rec1)
    vsrv, _ = _volunteer("vol-1", 2, registry)
    transport = TcpTransport(registry, wire_dtype="f32")

    def _req(next_entry):
        return StageRequest(
            session_id=f"blame-{next_entry['relay_via']}-{next_entry['address']}",
            hidden=np.zeros((1, 3, cfg.hidden_size), np.float32),
            seq_len=3, cur_len=0, is_prefill=True, max_length=16,
            start_block=spec1.start, end_block=spec1.end,
            next_servers=(next_entry,))

    try:
        # Site (a): the relay volunteer is unreachable -> breaker blames it.
        with pytest.raises(PushChainError) as ei:
            transport.call("entry-s1", _req({
                "peer_id": "tgt", "relay_via": "vol-dead",
                "address": UNROUTABLE,
                "start_block": spec1.end, "end_block": cfg.num_layers}))
        assert ei.value.peer_id == "tgt"
        assert ei.value.breaker_peer_id == "vol-dead"

        # Site (b): volunteer alive but the target never attached (it is the
        # dead component) -> routing AND breaker blame stay on the target.
        with pytest.raises(PushChainError) as ei:
            transport.call("entry-s1", _req({
                "peer_id": "tgt", "relay_via": "vol-1",
                "address": vsrv.address,
                "start_block": spec1.end, "end_block": cfg.num_layers}))
        assert ei.value.peer_id == "tgt"
        assert ei.value.breaker_peer_id is None
    finally:
        transport.close()
        s1.stop()
        vsrv.stop()


def test_client_breaker_blames_breaker_peer_id_not_hop():
    """Recovery-path regression: a retryable failure carrying
    breaker_peer_id must feed the BREAKER for that peer while the hop keeps
    only routing blame; without it, the hop takes both (the pre-relay
    behavior, unchanged)."""
    cfg = tiny_cfg()
    client, transport, _, params, _ = build_cluster(cfg, splits="4")
    sampling = SamplingParams(temperature=0.0)
    prompt = [5, 9, 23]
    real_call = transport.call
    fired = {"relay": False, "plain": False}
    # record_success on the retry resets fail counters, so observe blame at
    # the moment it lands instead of inspecting counters afterwards.
    blamed = []
    real_record = client.breaker.record_failure

    def spy_record(peer_id):
        blamed.append(peer_id)
        return real_record(peer_id)

    client.breaker.record_failure = spy_record

    def fail_relay_once(peer_id, req, timeout=None):
        if not fired["relay"]:
            fired["relay"] = True
            exc = PeerUnavailable("volunteer vol-1 died")
            exc.breaker_peer_id = "vol-1"
            raise exc
        return real_call(peer_id, req, timeout=timeout)

    transport.call = fail_relay_once
    res = client.generate(prompt, max_new_tokens=4, sampling=sampling)
    assert res.tokens == oracle_generate(cfg, params, prompt, 4, sampling)
    hop_peer = "peer-s1-r0"
    assert blamed == ["vol-1"]          # the volunteer, never the hop

    def fail_plain_once(peer_id, req, timeout=None):
        if not fired["plain"]:
            fired["plain"] = True
            raise PeerUnavailable("the peer itself died")
        return real_call(peer_id, req, timeout=timeout)

    transport.call = fail_plain_once
    res = client.generate(prompt, max_new_tokens=4, sampling=sampling)
    assert res.tokens == oracle_generate(cfg, params, prompt, 4, sampling)
    assert blamed == ["vol-1", hop_peer]    # no breaker_peer_id -> the hop
