"""Registry HA (VERDICT r3 item 6): the registry the build uses in place of
the reference's Kademlia DHT must not be a single point of failure the way
a lone process is. `RemoteRegistry` accepts a comma-separated address list:
writes broadcast to every registry (primary + standbys), reads fail over,
and a total outage serves the last snapshot under TTL grace. The DHT being
mirrored has no SPOF at all (reference ``src/dht_utils.py:34-242``).
"""

import threading
import time

import jax
import numpy as np

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
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime.net import (
    RegistryServer,
    RemoteRegistry,
    TcpStageServer,
    TcpTransport,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.scheduling.registry import (
    ServerRecord,
)

from engines import oracle_generate, tiny_cfg


def _rec(peer, stage=1, addr="127.0.0.1:1"):
    return ServerRecord(peer_id=peer, start_block=0, end_block=4,
                        stage_index=stage, address=addr)


def test_write_broadcast_and_read_failover():
    """A record registered through the pair lands on BOTH registries; with
    the primary dead, reads fail over and writes still succeed."""
    a, b = RegistryServer(), RegistryServer()
    a.start(), b.start()
    try:
        rr = RemoteRegistry(f"{a.address},{b.address}")
        rr.register(_rec("p1"))
        assert [r.peer_id for r in a.registry.live_servers()] == ["p1"]
        assert [r.peer_id for r in b.registry.live_servers()] == ["p1"]

        a.stop()
        # read fails over to the standby
        assert [r.peer_id for r in rr.live_servers()] == ["p1"]
        # a NEW server can still join (one dead registry tolerated)
        rr.register(_rec("p2"))
        assert {r.peer_id for r in rr.live_servers()} == {"p1", "p2"}
    finally:
        b.stop()


def test_stale_cache_ttl_grace():
    """Total registry outage: the last snapshot keeps serving, and its
    records age out through the normal TTL instead of erroring."""
    a = RegistryServer(ttl=0.8)
    a.start()
    rr = RemoteRegistry(a.address)
    rr.register(_rec("p1"))
    assert [r.peer_id for r in rr.live_servers()] == ["p1"]
    a.stop()
    # grace: cached snapshot still answers
    assert [r.peer_id for r in rr.live_servers()] == ["p1"]
    # ...and decays through the record TTL rather than living forever
    time.sleep(1.0)
    assert rr.live_servers() == []


def test_heartbeat_repopulates_restarted_registry():
    """A registry that restarts empty answers known=false; the server
    heartbeat loop's re-register contract refills it within one beat."""
    a = RegistryServer()
    a.start()
    host, port = a.address.rsplit(":", 1)
    rr = RemoteRegistry(a.address)
    rec = _rec("p1")
    rr.register(rec)
    assert rr.heartbeat("p1")
    a.stop()
    a2 = RegistryServer(host=host, port=int(port))   # restarted, EMPTY
    a2.start()
    try:
        known = rr.heartbeat("p1")
        assert not known                 # the loop's re-register trigger
        rr.register(rec)                 # what every heartbeat loop does
        assert rr.heartbeat("p1")
        assert [r.peer_id for r in a2.registry.live_servers()] == ["p1"]
    finally:
        a2.stop()


def test_generation_survives_primary_registry_death():
    """The VERDICT 'Done' bar: kill the primary registry mid-generation —
    the session completes — AND a new server joins via the standby and is
    discoverable for the next generation."""
    cfg = tiny_cfg()
    params = init_params(jax.random.PRNGKey(0), cfg)
    plan = StagePlan.from_splits(cfg.num_layers, parse_splits("4"))
    spec = plan.stages[1]

    prim, standby = RegistryServer(), RegistryServer()
    prim.start(), standby.start()
    pair = f"{prim.address},{standby.address}"

    servers = []

    def add_server(peer):
        ex = StageExecutor(cfg, spec, slice_stage_params(cfg, params, spec),
                           peer_id=peer)
        srv = TcpStageServer(ex, wire_dtype="f32")
        srv.start()
        rec = make_server_record(peer, spec)
        rec.address = srv.address
        RemoteRegistry(pair).register(rec)   # the serve path's broadcast
        servers.append(srv)
        return srv

    first = add_server("ha-s1")
    registry = RemoteRegistry(pair)
    transport = TcpTransport(registry, wire_dtype="f32")
    stage0 = StageExecutor(cfg, plan.stages[0],
                           slice_stage_params(cfg, params, plan.stages[0]),
                           peer_id="client-local")
    client = PipelineClient(cfg, plan, stage0, transport, registry,
                            settle_seconds=0.0)
    try:
        rng = np.random.default_rng(0)
        prompt = [int(t) for t in rng.integers(0, cfg.vocab_size, 12)]
        sampling = SamplingParams(temperature=0.0)

        # Kill the primary shortly after generation starts.
        killer = threading.Timer(0.2, prim.stop)
        killer.start()
        got = client.generate(prompt, max_new_tokens=8,
                              sampling=sampling).tokens
        killer.join()
        ref = oracle_generate(cfg, params, prompt, 8, sampling)
        assert got == ref, "generation across the registry kill diverged"

        # New server joins via the standby (primary is gone)...
        add_server("ha-s2")
        # ...and the ORIGINAL server dies, so the next generation can only
        # complete by DISCOVERING the new one through the standby.
        first.stop()
        got2 = client.generate(prompt, max_new_tokens=8,
                               sampling=sampling).tokens
        assert got2 == ref, "post-failover generation diverged"
    finally:
        transport.close()
        for s in servers:
            s.stop()
        standby.stop()
        # prim already stopped by the timer (stop() is idempotent there).


# -- failover internals (round 5 satellites) ----------------------------------

def test_up_order_rotates_and_demotes_backed_off_registries():
    """Read-path ordering: indices rotate from the preferred start, but
    registries inside their down-backoff window sink to the end — tried
    only as a last resort until the backoff expires."""
    rr = RemoteRegistry("127.0.0.1:1,127.0.0.1:2,127.0.0.1:3",
                        timeout=0.05)
    assert rr._up_order(0) == [0, 1, 2]
    assert rr._up_order(2) == [2, 0, 1]

    rr._down_until[1] = time.monotonic() + 60.0      # 1 is backing off
    assert rr._up_order(0) == [0, 2, 1]
    assert rr._up_order(1) == [2, 0, 1]

    rr._down_until[1] = time.monotonic() - 1.0       # backoff expired
    assert rr._up_order(1) == [1, 2, 0]


def test_stale_persistent_socket_retries_fresh_not_down():
    """A registry restart leaves the client's persistent socket half-open;
    the next RPC must retry ONCE on a fresh connection instead of marking
    the (live) registry down."""
    a = RegistryServer()
    a.start()
    host, port = a.address.rsplit(":", 1)
    rr = RemoteRegistry(a.address)
    rr.register(_rec("p1"))             # caches the persistent socket
    a.stop()
    a2 = RegistryServer(host=host, port=int(port))   # restarted, EMPTY
    a2.start()
    try:
        assert rr.live_servers() == []  # stale socket -> fresh retry wins
        assert rr._down_until[0] == 0.0, "live registry marked down"
    finally:
        a2.stop()


def test_register_buffered_during_outage_flushes_on_reconnect():
    """Satellite: a register issued while EVERY registry is down is
    buffered (last record per peer) and replayed on the first successful
    reconnect — it must not silently vanish."""
    a = RegistryServer()
    a.start()
    host, port = a.address.rsplit(":", 1)
    rr = RemoteRegistry(a.address, timeout=0.5)
    a.stop()

    rr.register(_rec("p1"))             # total outage: buffered, no raise
    assert "p1" in rr._pending_register

    a2 = RegistryServer(host=host, port=int(port))
    a2.start()
    try:
        rr.live_servers()               # first success triggers the flush
        assert not rr._pending_register
        assert [r.peer_id for r in a2.registry.live_servers()] == ["p1"]
        assert [r.peer_id for r in rr.live_servers()] == ["p1"]
    finally:
        a2.stop()
