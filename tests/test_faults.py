"""Deterministic chaos layer, circuit breakers, and deadline budgets.

Five concerns:

  * FaultPlan semantics — rule validation, seeded reproducibility, the
    to_dict/from_dict wire round trip;
  * every fault kind round-trips through its REAL hook: client dial/send,
    server send/dispatch, registry dispatch — over real TCP sockets;
  * the `fault` admin verb — install/report/clear over the wire, and the
    --allow_fault_injection consent gate refusing unconsented processes;
  * runtime hardening — the per-peer circuit breaker state machine (driven
    by an injected clock, no sleeps), the route-cache LRU affinity
    exemption, the LoRA capability gate, and deadline expiry as a TYPED
    non-retryable error on both the client and server side;
  * the acceptance e2e: the in-process chaos soak — clean run vs seeded
    FaultPlan run must emit IDENTICAL tokens while >= 5 fault kinds fire,
    and the doctor must reconstruct every injection from the event ring.
    (The full multi-process variant rides scripts/chaos_swarm.py and is
    marked slow.)
"""

import pathlib
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from engines import build_cluster, tiny_cfg

from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.main import (
    chaos_soak,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.models import (
    init_params,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.models.partition import (
    StagePlan,
    parse_splits,
    slice_stage_params,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime.client import (
    CircuitBreaker,
    make_server_record,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime.executor import (
    StageExecutionError,
)
from engines import stage_executor as StageExecutor
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime.faults import (
    FaultPlan,
    FaultRule,
    default_chaos_rules,
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
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime.transport import (
    DeadlineExceeded,
)

REPO = pathlib.Path(__file__).resolve().parents[1]


# -- FaultPlan semantics ------------------------------------------------------

def test_fault_rule_validation():
    with pytest.raises(ValueError):
        FaultRule("not_a_kind")
    with pytest.raises(ValueError):
        FaultRule("delay", side="martian")


def test_seeded_plans_reproducible_and_wire_roundtrip():
    rules = [FaultRule("delay", prob=0.3, times=1000, delay_s=0.0)]

    def firing_pattern(plan):
        return [plan.fire("send", ("delay",), side="client", peer="p",
                          verb="v") is not None for _ in range(64)]

    a = firing_pattern(FaultPlan(rules, seed=7))
    b = firing_pattern(FaultPlan(rules, seed=7))
    assert a == b and any(a) and not all(a)
    # A different seed draws a different probabilistic schedule.
    assert a != firing_pattern(FaultPlan(rules, seed=8))
    # from_dict(to_dict()) is behavior-preserving: the remote end of the
    # `fault` verb replays the exact schedule the operator declared.
    wired = FaultPlan.from_dict(FaultPlan(rules, seed=7).to_dict())
    assert firing_pattern(wired) == a


def test_default_chaos_rules_cover_every_side():
    rules = default_chaos_rules(["p0", "p1", "p2"], seed=0)
    assert {r.side for r in rules} == {"client", "server", "registry"}
    assert len({r.kind for r in rules}) == 7


# -- every fault kind through its real TCP hook -------------------------------

@pytest.fixture(scope="module")
def mini():
    """One registry + one stage server (both fault-consenting), real TCP."""
    cfg = tiny_cfg()
    params = init_params(jax.random.PRNGKey(0), cfg)
    plan = StagePlan.from_splits(cfg.num_layers, parse_splits("4"))
    reg_server = RegistryServer(allow_fault_injection=True)
    reg_server.start()
    spec = plan.stages[1]
    ex = StageExecutor(cfg, spec, slice_stage_params(cfg, params, spec),
                       peer_id="fault-s1")
    srv = TcpStageServer(ex, wire_dtype="f32", allow_fault_injection=True)
    srv.start()
    rec = make_server_record(ex.peer_id, spec)
    rec.address = srv.address
    reg_server.registry.register(rec)
    reg = RemoteRegistry(reg_server.address)
    yield {"cfg": cfg, "plan": plan, "reg": reg, "reg_server": reg_server,
           "srv": srv, "ex": ex, "peer": ex.peer_id, "rec": rec}
    srv.stop()
    reg_server.stop()


@pytest.mark.parametrize("kind,recovers_inline", [
    ("refuse_connect", False),
    ("reset_mid_frame", False),
    ("corrupt_payload", False),
    ("partial_write_stall", True),
    ("delay", True),
])
def test_client_side_kinds_fire_once_then_clear(mini, kind, recovers_inline):
    tx = TcpTransport(mini["reg"], wire_dtype="f32")
    plan = FaultPlan([FaultRule(kind, side="client", peer=mini["peer"],
                                nth=1, delay_s=0.01)])
    tx.set_fault_plan(plan)
    try:
        if recovers_inline:
            # Latency-only faults: the call still completes.
            assert tx.info(mini["peer"])["verb"] == "info"
        else:
            with pytest.raises((ConnectionError, OSError, TimeoutError)):
                tx.info(mini["peer"])
        assert plan.fired_count() == 1
        assert plan.report()[0]["kind"] == kind
        # One-shot (times=1): the next call sails through untouched.
        assert tx.info(mini["peer"])["verb"] == "info"
        assert plan.fired_count() == 1
    finally:
        tx.set_fault_plan(None)
        tx.close()


@pytest.mark.parametrize("kind", ["corrupt_payload", "accept_hang"])
def test_server_side_kinds_installed_over_the_wire(mini, kind):
    tx = TcpTransport(mini["reg"], wire_dtype="f32")
    try:
        tx.install_fault_plan(mini["peer"], FaultPlan(
            [FaultRule(kind, side="server", nth=1, delay_s=0.01)]))
        with pytest.raises((ConnectionError, OSError, TimeoutError)):
            tx.info(mini["peer"])
        assert tx.info(mini["peer"])["verb"] == "info"
        rep = tx.fault_report(mini["peer"])
        assert [f["kind"] for f in rep] == [kind]
        tx.install_fault_plan(mini["peer"], None)
        assert tx.fault_report(mini["peer"]) == []
    finally:
        tx.close()


def test_fault_verb_refused_without_consent(mini):
    # A second listener sharing the executor but WITHOUT the consent flag:
    # the verb must refuse, not install.
    gated = TcpStageServer(mini["ex"], wire_dtype="f32")
    gated.start()
    rec = make_server_record("gated-peer", mini["plan"].stages[1])
    rec.address = gated.address
    mini["reg_server"].registry.register(rec)
    tx = TcpTransport(mini["reg"], wire_dtype="f32")
    try:
        with pytest.raises(RuntimeError, match="fault injection disabled"):
            tx.install_fault_plan("gated-peer", FaultPlan(
                [FaultRule("delay", side="server", nth=1)]))
    finally:
        tx.close()
        gated.stop()
        mini["reg_server"].registry.unregister("gated-peer")


def test_registry_side_duplicate_and_stale(mini):
    reg = mini["reg"]
    reg._rpc({"verb": "fault", "plan": FaultPlan([
        FaultRule("duplicate", side="registry", verb="heartbeat", times=2),
        FaultRule("stale_registry", side="registry", verb="list", nth=1,
                  age_s=1000.0),
    ]).to_dict()})
    try:
        # duplicate: the verb is processed TWICE per frame — proving the
        # registry's verbs are idempotent under at-least-once delivery.
        assert reg.heartbeat(mini["peer"]) is True
        assert reg.heartbeat(mini["peer"]) is True
        # stale_registry: freshness rewound 1000 s >> ttl, the record
        # vanishes from the live view — a lagging/partitioned registry.
        assert reg.live_servers() == []
        firings = reg._rpc({"verb": "fault", "action": "report"})["firings"]
        assert sorted({f["kind"] for f in firings}) == [
            "duplicate", "stale_registry"]
        assert sum(f["kind"] == "duplicate" for f in firings) == 2
    finally:
        reg._rpc({"verb": "fault", "action": "clear"})
        mini["reg_server"].registry.register(mini["rec"])  # re-freshen
    assert [r.peer_id for r in reg.live_servers()] == [mini["peer"]]


# -- circuit breaker state machine (injected clock, no sleeps) ----------------

def test_breaker_opens_probes_and_readmits():
    t = [0.0]
    br = CircuitBreaker(threshold=3, base_backoff_s=1.0, jitter=0.0,
                        now=lambda: t[0])
    for _ in range(2):
        br.record_failure("p")
    assert br.state("p") == "closed" and br.allow("p")
    br.record_failure("p")
    assert br.state("p") == "open"
    assert not br.allow("p")                 # backoff pending: dial skipped
    t[0] = 1.01
    assert br.allow("p")                     # the half-open single probe
    assert br.state("p") == "half_open"
    assert not br.allow("p")                 # no probe stampede
    br.record_success("p")                   # probe succeeded
    assert br.state("p") == "closed"         # full readmission, no
    assert br.allow("p")                     # blacklist clear needed


def test_breaker_failed_probe_doubles_backoff():
    t = [0.0]
    br = CircuitBreaker(threshold=3, base_backoff_s=1.0, jitter=0.0,
                        now=lambda: t[0])
    for _ in range(3):
        br.record_failure("p")
    t[0] = 1.01
    assert br.allow("p")
    br.record_failure("p")                   # probe failed -> re-open
    assert br.state("p") == "open"
    t[0] = 1.01 + 1.5
    assert not br.allow("p")                 # 2nd backoff is 2.0 s
    t[0] = 1.01 + 2.01
    assert br.allow("p")


# -- route-cache LRU: affinity=None keys are exempt ---------------------------

def test_route_cache_evicts_only_affinity_keys():
    client, *_ = build_cluster(tiny_cfg(), splits="4")
    client.route()                           # (plain, None, None) fallback
    client.route(min_context=128)            # a second exempt fallback
    for i in range(80):                      # unbounded digest churn
        client.route(affinity=f"digest-{i}")
    assert len(client._routes) <= 64
    assert ("plain", None, None) in client._routes
    assert ("plain", 128, None) in client._routes
    # Only affinity-carrying keys paid eviction.
    assert sum(1 for k in client._routes if k[2] is None) == 2


# -- LoRA capability gate -----------------------------------------------------

def test_lora_train_call_rejected_before_shipping(mini):
    tx = TcpTransport(mini["reg"], wire_dtype="f32")
    try:
        # A successful info probe that LACKS the capability blocks the call
        # before any adapter bytes hit the wire.
        tx._peer_caps[mini["peer"]] = {"verb": "info", "version": 1,
                                       "lora": False}
        req = StageRequest(session_id="lora-gate",
                           hidden=jnp.zeros((1, 1, mini["cfg"].hidden_size)),
                           seq_len=1, cur_len=0, is_prefill=True,
                           max_length=8, train=True,
                           lora={"wq": {"a": None, "b": None}})
        with pytest.raises(StageExecutionError, match="does not advertise"):
            tx.call(mini["peer"], req)
        # The real server DOES advertise it: probe and confirm the flag.
        tx._peer_caps.pop(mini["peer"])
        caps = tx._capabilities(mini["peer"])
        assert caps and caps.get("lora") is True
    finally:
        tx.close()


# -- deadline budgets ---------------------------------------------------------

def test_deadline_expired_is_typed_and_non_retryable():
    client, *_ = build_cluster(tiny_cfg(), splits="4")
    with pytest.raises(DeadlineExceeded) as ei:
        client.generate([1, 2, 3], 4, deadline_s=1e-9)
    assert not isinstance(ei.value, (ConnectionError, TimeoutError))


def test_server_rejects_expired_budget(mini):
    tx = TcpTransport(mini["reg"], wire_dtype="f32")
    try:
        req = StageRequest(session_id="dead-on-arrival",
                           hidden=jnp.zeros((1, 1, mini["cfg"].hidden_size),
                                            jnp.float32),
                           seq_len=1, cur_len=0, is_prefill=True,
                           max_length=8, deadline_budget_s=-0.5)
        with pytest.raises(DeadlineExceeded):
            tx.call(mini["peer"], req)
    finally:
        tx.close()


# -- acceptance e2e: the chaos soak -------------------------------------------

def test_chaos_soak_tokens_identical_and_doctor_accounts(monkeypatch):
    cfg = tiny_cfg()
    params = init_params(jax.random.PRNGKey(0), cfg)
    res = chaos_soak(cfg, params, prompt_ids=[1, 2, 3, 4, 5],
                     max_new_tokens=10, seed=0, splits=(2, 4, 6),
                     wire_dtype="f32", request_timeout=5.0)
    assert res["ok"], res["problems"]
    assert res["tokens_clean"] == res["tokens_chaos"]
    assert len(res["kinds_fired"]) >= 5
    assert res["deadline_probe"] == "raised DeadlineExceeded"
    assert res["fault_chains"] >= 1


@pytest.mark.slow
def test_chaos_swarm_multiprocess():
    """Full-fidelity soak: one OS process per role, faults crossing real
    process boundaries, doctor merging scraped rings from every server."""
    rc = subprocess.call(
        [sys.executable, "scripts/chaos_swarm.py", "--splits", "4,8",
         "--max_new_tokens", "8", "--seed", "0"], cwd=REPO)
    assert rc == 0
