"""Decentralized control plane (round 5): the gossip-replicated registry.

Every ``--mode serve`` process embeds a GossipNode — a version-stamped
record store whose merge is a deterministic semilattice join (newest seq
wins, tombstone beats live on ties) — and answers the registry service's
verbs from its mirror, so ANY live stage server can bootstrap a client
after every seed registry dies. The reference build gets this property
from the Kademlia DHT (``src/dht_utils.py``); here it is explicit
anti-entropy over the existing framed-TCP plane.

The convergence property test and the in-process registry-loss soak are
the PR's acceptance bars; the rest pins the wire contract piece by piece.
"""

import os
import random
import subprocess
import sys
import time

import jax
import pytest

from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu import (
    main as main_mod,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu import (
    telemetry,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.models import (
    init_params,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.models.partition import (
    parse_splits,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime.faults import (
    FaultPlan,
    FaultRule,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime.net import (
    RegistryServer,
    RemoteRegistry,
    TcpStageServer,
    gossip_exchange,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.scheduling.gossip import (
    GossipNode,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.scheduling.registry import (
    ServerRecord,
    rec_to_dict,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.telemetry import (
    catalog,
    events,
)

from engines import tiny_cfg


def _rec(peer, stage=1, addr="127.0.0.1:1"):
    return ServerRecord(peer_id=peer, start_block=0, end_block=4,
                        stage_index=stage, address=addr)


def _wire(origin, seq, dead=False, ttl_s=30.0, window=45.0,
          addr="127.0.0.1:1"):
    """One gossip wire entry, as delta_for would encode it."""
    return {"origin": origin, "seq": seq, "dead": dead,
            "rec": None if dead else rec_to_dict(_rec(origin, addr=addr)),
            "window": window, "ttl_s": ttl_s}


def _mirror_server(peer_id, **kw):
    """An executor-less stage server with an embedded gossip mirror — the
    control-plane surface without the data plane."""
    node = GossipNode(peer_id, ttl=30.0, rng=random.Random(0))
    srv = TcpStageServer(None, wire_dtype="f32", peer_id=peer_id,
                         gossip=node, **kw)
    srv.start()
    node.self_address = srv.address
    return node, srv


# -- merge semantics (the semilattice join) -----------------------------------

def test_merge_newest_seq_wins_in_any_order():
    """Applying versions out of order converges to the same state as in
    order: seq is the total order, not arrival time."""
    new = _wire("pA", 2, addr="127.0.0.1:2")
    old = _wire("pA", 1, addr="127.0.0.1:1")

    fwd = GossipNode("n0", ttl=30.0)
    assert fwd.merge([old]) == 1
    assert fwd.merge([new]) == 1
    rev = GossipNode("n1", ttl=30.0)
    assert rev.merge([new]) == 1
    assert rev.merge([old]) == 0        # stale version changes nothing

    assert fwd.digest() == rev.digest() == {"pA": 2}
    for n in (fwd, rev):
        assert [r.address for r in n.live_servers()] == ["127.0.0.1:2"]


def test_tombstone_blocks_resurrection_until_newer_live_version():
    """A circulating tombstone beats any OLDER live version (and the
    equal-seq tie), so a slow replica can't resurrect an unregistered
    peer; a strictly newer live version (the peer actually came back)
    wins immediately."""
    n = GossipNode("n0", ttl=30.0)
    n.merge([_wire("pA", 3, dead=True, ttl_s=60.0, window=60.0)])
    assert n.live_count() == 0

    assert n.merge([_wire("pA", 2)]) == 0       # older live: rejected
    assert n.merge([_wire("pA", 3)]) == 0       # tie: tombstone wins
    assert n.live_count() == 0
    assert n.digest() == {"pA": 3}

    assert n.merge([_wire("pA", 4)]) == 1       # genuine rejoin
    assert [r.peer_id for r in n.live_servers()] == ["pA"]


def test_tombstone_expires_after_grace():
    """Tombstones are garbage-collected after their grace window — the
    deletion stops being re-announced instead of circulating forever."""
    n = GossipNode("n0", ttl=30.0)
    n.merge([_wire("pA", 5, dead=True, ttl_s=0.05, window=0.05)])
    assert n.digest() == {"pA": 5}
    time.sleep(0.1)
    assert n.digest() == {}
    # After the grace the origin may legitimately start over at seq 1.
    assert n.merge([_wire("pA", 1)]) == 1
    assert [r.peer_id for r in n.live_servers()] == ["pA"]


def test_convergence_property_randomized_delivery_orders():
    """The acceptance property: N replicas receiving the same version set
    in DIFFERENT (seeded) orders, with duplicates and arbitrary batch
    splits, end with identical digests and identical live sets —
    tombstones included."""
    master = random.Random(1234)
    origins = [f"p{i}" for i in range(6)]
    versions = []
    want_digest = {}
    want_live = []
    for i, origin in enumerate(origins):
        top = master.randint(1, 4)
        ends_dead = i < 2               # two origins end tombstoned
        for seq in range(1, top + 1):
            versions.append(_wire(origin, seq,
                                  dead=ends_dead and seq == top,
                                  ttl_s=60.0, window=90.0,
                                  addr=f"10.0.0.{i}:{seq}"))
        want_digest[origin] = top
        if not ends_dead:
            want_live.append(origin)

    nodes = [GossipNode(f"n{k}", ttl=60.0, tombstone_grace_s=120.0,
                        rng=random.Random(k)) for k in range(4)]
    for k, node in enumerate(nodes):
        rng = random.Random(9000 + k)
        feed = list(versions) + rng.sample(versions, len(versions) // 2)
        rng.shuffle(feed)
        while feed:
            batch = [feed.pop()
                     for _ in range(min(len(feed), rng.randint(1, 5)))]
            node.merge(batch)

    for node in nodes:
        assert node.digest() == want_digest
        assert sorted(r.peer_id for r in node.live_servers()) == \
            sorted(want_live)


# -- the wire: anti-entropy rounds and the mirror's registry verbs ------------

def test_gossip_exchange_converges_both_sides():
    """One digest-then-delta round leaves BOTH mirrors with the union:
    the response delta teaches the initiator, the push-back teaches the
    responder."""
    na, sa = _mirror_server("na")
    nb, sb = _mirror_server("nb")
    try:
        na.publish(rec_to_dict(_rec("pa", addr="127.0.0.1:21")))
        nb.publish(rec_to_dict(_rec("pb", addr="127.0.0.1:22")))
        sent, merged = gossip_exchange(na, sb.address)
        assert sent == 1 and merged == 1
        assert {r.peer_id for r in na.live_servers()} == {"pa", "pb"}
        assert {r.peer_id for r in nb.live_servers()} == {"pa", "pb"}
        assert na.digest() == nb.digest()
    finally:
        sa.stop()
        sb.stop()


def test_stage_server_answers_registry_verbs():
    """Any-peer bootstrap: a RemoteRegistry pointed at a STAGE SERVER
    speaks the registry service unmodified — register, the heartbeat
    known/unknown contract, list, unregister."""
    node, srv = _mirror_server("mirror")
    try:
        rr = RemoteRegistry(srv.address)
        rr.register(_rec("p1", addr="127.0.0.1:9"))
        assert rr.heartbeat("p1") is True
        assert rr.heartbeat("ghost") is False    # re-register trigger
        assert [r.peer_id for r in rr.live_servers()] == ["p1"]
        rr.unregister("p1")
        assert rr.live_servers() == []
        assert "p1" in node.digest()             # tombstone circulates
    finally:
        srv.stop()


def test_gossip_drop_fault_then_reconverge():
    """The chaos layer's gossip_drop kind swallows one anti-entropy frame
    (the initiator's round dies on read timeout); the NEXT round sails
    through and the mirror still converges."""
    node, srv = _mirror_server("flaky", allow_fault_injection=True)
    try:
        other = GossipNode("initiator", ttl=30.0)
        other.publish(rec_to_dict(_rec("pc", addr="127.0.0.1:31")))
        srv.fault_plan = FaultPlan(
            [FaultRule("gossip_drop", side="server", verb="gossip",
                       times=1)])
        with pytest.raises((TimeoutError, OSError)):
            gossip_exchange(other, srv.address, timeout=0.6)
        assert node.live_count() == 0            # the frame really died
        gossip_exchange(other, srv.address, timeout=5.0)
        assert {r.peer_id for r in node.live_servers()} == {"pc"}
    finally:
        srv.stop()


# -- total-outage survival (client side) --------------------------------------

def test_peers_cache_bootstraps_fresh_client_through_mirror(tmp_path):
    """A FRESH client with an empty snapshot and every seed dead finds the
    swarm through the --peers_cache file + a stage server's mirror, and
    the fallback is surfaced (event + counter)."""
    telemetry.enable()
    events.get_recorder().enable()
    cache = str(tmp_path / "peers.json")
    node, srv = _mirror_server("gs1")
    seed = RegistryServer()
    seed.start()
    try:
        rec = _rec("gs1", addr=srv.address)
        node.publish(rec_to_dict(rec))
        rr1 = RemoteRegistry(seed.address, peers_cache=cache)
        rr1.register(rec)
        assert [r.peer_id for r in rr1.live_servers()] == ["gs1"]
        assert os.path.exists(cache)             # snapshot persisted

        seed.stop()
        fallback = catalog.get("client_registry_fallback_reads_total")
        before = fallback.value
        rr2 = RemoteRegistry(seed.address, timeout=0.5, peers_cache=cache)
        recs = rr2.live_servers()                # dead seed, cache → mirror
        assert [r.peer_id for r in recs] == ["gs1"]
        assert fallback.value == before + 1
        names = [e.name for e in events.get_recorder().events()]
        assert "gossip_fallback" in names
        assert rr2.stale_info()["seeds_down"]
    finally:
        srv.stop()
        seed.stop()


def test_stale_serve_and_recovery_are_surfaced():
    """Satellite: serving from the stale snapshot is an OBSERVABLE
    degradation — registry_stale_serve + the stale-reads counter while the
    seeds are down, registry_recovered once a seed answers again."""
    telemetry.enable()
    recorder = events.get_recorder()
    recorder.enable()
    a = RegistryServer()
    a.start()
    host, port = a.address.rsplit(":", 1)
    rr = RemoteRegistry(a.address, timeout=0.5)
    rec = _rec("p1")                    # address 127.0.0.1:1 — no mirror
    rr.register(rec)
    assert [r.peer_id for r in rr.live_servers()] == ["p1"]

    stale = catalog.get("client_registry_stale_reads_total")
    before = stale.value
    a.stop()
    assert [r.peer_id for r in rr.live_servers()] == ["p1"]   # TTL grace
    assert stale.value == before + 1
    info = rr.stale_info()
    assert info["seeds_down"] and info["stale"]
    names = [e.name for e in recorder.events()]
    assert "registry_unreachable" in names
    assert "registry_stale_serve" in names

    a2 = RegistryServer(host=host, port=int(port))
    a2.start()
    try:
        rr.register(rec)                # what the serve heartbeat loop does
        assert [r.peer_id for r in rr.live_servers()] == ["p1"]
        info = rr.stale_info()
        assert not info["seeds_down"] and not info["stale"]
        recovered = [e for e in recorder.events()
                     if e.name == "registry_recovered"]
        assert recovered and recovered[-1].fields.get("source") == "seed"
    finally:
        a2.stop()


def test_registry_loss_soak_inprocess(tmp_path):
    """The tentpole's acceptance scenario, tier-1 edition: primary AND
    standby killed deterministically mid-generation — the in-flight
    generation and a fresh mirror-bootstrapped client both produce the
    clean run's exact tokens, a restarted seed is re-adopted, and the
    doctor reconstructs the outage as one failure chain."""
    cfg = tiny_cfg()
    params = init_params(jax.random.PRNGKey(0), cfg)
    res = main_mod.registry_loss_soak(
        cfg, params, prompt_ids=[5, 9, 23, 7, 81], max_new_tokens=5,
        seed=0, splits=parse_splits("3,6"),
        peers_cache=str(tmp_path / "peers.json"))
    assert res["ok"], res["problems"]
    assert res["tokens_chaos"] == res["tokens_clean"]
    assert res["tokens_bootstrap"] == res["tokens_clean"]
    assert res["chains"], "doctor found no registry-outage chain"


@pytest.mark.slow
def test_chaos_swarm_kill_registries_drill():
    """Multi-process twin: scripts/chaos_swarm.py --kill_registries
    SIGKILLs both seed registries under a live client; the in-flight
    client must finish and a second, freshly started client must
    bootstrap through a stage server's gossip mirror."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(repo, "scripts", "chaos_swarm.py"),
         "--kill_registries", "--splits", "4",
         "--max_new_tokens", "6", "--registry_port", "31377"],
        cwd=repo, env=env, timeout=900,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
    out = proc.stdout.decode(errors="replace")
    assert proc.returncode == 0, out
    assert "REGISTRY-LOSS DRILL PASS" in out
