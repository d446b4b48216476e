"""Multi-tenant serving gateway (docs/SERVING.md): admission control,
weighted-fair scheduling, and SLO-aware load shedding.

Five concerns:

  * admission primitives — token-bucket refill under an injected clock,
    tenant-config validation, the --tenants JSON parser (nested + flat);
  * the three shed gates in order (queue_full / concurrency / rate), each
    a typed non-retryable Overloaded carrying an honest retry_after_s,
    and the invariant that a full queue never charges a tenant's bucket;
  * weighted fairness — DRR realizes exact weight ratios over rotations,
    idle tenants bank no credit, and the FairQueue orders within a tenant
    by earliest deadline first (FIFO ties, deadline-less last);
  * priority threading — the gateway's per-tenant priority rides
    StageRequest over real TCP into the server's task-pool prioritizer,
    replacing DummyTaskPrioritizer's inference constant; oversized work
    comes back as typed, permanent TaskRejected (not a retryable stage
    error), and the server_task_queue_depth gauge tracks the backlog;
  * the acceptance e2e: the in-process overload soak — 4:1 served-token
    fairness, baseline-identical tokens for every admitted request, all
    three shed reasons fired, and the doctor reconstructing the refusals.
    (The multi-process variant — real OS processes for registry, stages,
    gateway, and submitter — is marked slow.)
"""

import pathlib
import socket
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import pytest

from engines import tiny_cfg

from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu import (
    telemetry,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.main import (
    overload_soak,
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
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime.task_pool import (
    DummyTaskPrioritizer,
    PrioritizedTaskPool,
    StageRuntime,
    TaskPrioritizerBase,
    TaskRejected,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.serving import (
    AdmissionController,
    DeficitRoundRobin,
    FairQueue,
    Overloaded,
    TenantConfig,
    TokenBucket,
    parse_tenants_config,
)

REPO = pathlib.Path(__file__).resolve().parents[1]


class FakeClock:
    def __init__(self, t=0.0):
        self.t = t

    def __call__(self):
        return self.t


# -- token bucket -------------------------------------------------------------

def test_token_bucket_starts_full_and_refills():
    clk = FakeClock()
    b = TokenBucket(rate=2.0, burst=4.0, now=clk)
    assert b.tokens == 4.0                      # first burst is admitted
    for _ in range(4):
        assert b.try_take(1.0)
    assert not b.try_take(1.0)                  # drained
    assert b.time_until(1.0) == pytest.approx(0.5)   # 1 token at 2/s
    clk.t += 1.0
    assert b.tokens == pytest.approx(2.0)       # refilled rate*dt
    assert b.try_take(2.0)
    clk.t += 100.0
    assert b.tokens == 4.0                      # capped at burst


def test_tenant_config_validation():
    for bad in (dict(weight=0), dict(rate=0), dict(burst=-1),
                dict(max_concurrency=0)):
        with pytest.raises(ValueError):
            TenantConfig("t", **bad)


def test_parse_tenants_config_nested_and_flat():
    tenants, qd, ma = parse_tenants_config(
        {"tenants": {"gold": {"weight": 4, "rate": 20},
                     "bronze": {}},
         "max_queue_depth": 7, "max_active": 3})
    assert set(tenants) == {"gold", "bronze"}
    assert tenants["gold"].weight == 4 and tenants["gold"].rate == 20
    assert (qd, ma) == (7, 3)
    tenants, qd, ma = parse_tenants_config({"solo": {"weight": 2}})
    assert set(tenants) == {"solo"} and (qd, ma) == (64, 8)


# -- admission gates ----------------------------------------------------------

def test_admission_gate_order_and_retry_after():
    clk = FakeClock()
    ac = AdmissionController(
        {"t": TenantConfig("t", rate=1.0, burst=2.0, max_concurrency=1)},
        max_queue_depth=2, now=clk)

    # Gate 1: global watermark, checked FIRST — the refusal must not charge
    # the tenant's bucket (the later admits below still have their burst).
    with pytest.raises(Overloaded) as ei:
        ac.try_admit("t", queue_depth=2)
    assert ei.value.reason == "queue_full" and ei.value.retry_after_s > 0
    assert ac.inflight("t") == 0

    # Gate 2: per-tenant concurrency (queued + generating).
    ac.try_admit("t", queue_depth=0)
    with pytest.raises(Overloaded) as ei:
        ac.try_admit("t", queue_depth=0)
    assert ei.value.reason == "concurrency"
    ac.release("t")
    assert ac.inflight("t") == 0

    # Gate 3: the token bucket. One burst token is left (queue_full charged
    # nothing); after it, the refusal's retry_after_s is the honest refill
    # time at rate=1/s, and advancing the clock that far admits again.
    ac.try_admit("t", queue_depth=0)
    ac.release("t")
    with pytest.raises(Overloaded) as ei:
        ac.try_admit("t", queue_depth=0)
    assert ei.value.reason == "rate"
    assert ei.value.retry_after_s == pytest.approx(1.0)
    clk.t += 1.0
    ac.try_admit("t", queue_depth=0)


def test_admission_unknown_tenant_is_keyerror():
    ac = AdmissionController({"t": TenantConfig("t")})
    with pytest.raises(KeyError):
        ac.try_admit("nope", queue_depth=0)


def test_overloaded_outside_retryable_taxonomy():
    """Overloaded (like permanent TaskRejected) must never look like the
    connection/timeout errors the client failover path retries."""
    exc = Overloaded("full", 0.25, tenant="t", reason="queue_full")
    assert isinstance(exc, RuntimeError)
    assert not isinstance(exc, (ConnectionError, TimeoutError))
    assert exc.retry_after_s == 0.25 and exc.tenant == "t"


# -- weighted fairness --------------------------------------------------------

def test_drr_realizes_weight_ratios():
    drr = DeficitRoundRobin({"gold": 4.0, "bronze": 1.0})
    picks = [drr.pick({"gold", "bronze"}) for _ in range(50)]
    assert picks.count("gold") == 40 and picks.count("bronze") == 10
    drr3 = DeficitRoundRobin({"a": 3.0, "b": 2.0, "c": 1.0})
    picks = [drr3.pick({"a", "b", "c"}) for _ in range(60)]
    assert (picks.count("a"), picks.count("b"), picks.count("c")) \
        == (30, 20, 10)


def test_drr_idle_tenant_banks_no_credit():
    drr = DeficitRoundRobin({"gold": 4.0, "bronze": 1.0})
    for _ in range(40):                      # gold idle: bronze owns the pipe
        assert drr.pick({"bronze"}) == "bronze"
    # Reactivated gold gets its weighted share, NOT a 40-pick catch-up burst.
    picks = [drr.pick({"gold", "bronze"}) for _ in range(50)]
    assert picks.count("gold") == 40 and picks.count("bronze") == 10


def test_drr_validation_and_idle():
    with pytest.raises(ValueError):
        DeficitRoundRobin({})
    with pytest.raises(ValueError):
        DeficitRoundRobin({"t": 0.0})
    drr = DeficitRoundRobin({"t": 1.0})
    assert drr.pick(set()) is None
    assert drr.pick({"unknown"}) is None     # foreign tenants are ignored


def test_fair_queue_edf_within_tenant():
    q = FairQueue({"t": 1.0})
    assert q.push("t", "late", deadline_at=30.0) == 1
    assert q.push("t", "no-deadline-1") == 2
    assert q.push("t", "soon", deadline_at=10.0) == 3
    assert q.push("t", "no-deadline-2") == 4
    order = [q.try_pop()[1] for _ in range(4)]
    # Earliest deadline first; deadline-less last, FIFO among themselves.
    assert order == ["soon", "late", "no-deadline-1", "no-deadline-2"]
    assert q.try_pop() is None


def test_fair_queue_depths_unknown_tenant_and_drain():
    q = FairQueue({"a": 1.0, "b": 1.0})
    with pytest.raises(KeyError):
        q.push("nope", "x")
    q.push("a", 1)
    q.push("a", 2)
    q.push("b", 3)
    assert q.depth() == 3 and q.depths() == {"a": 2, "b": 1}
    drained = sorted(q.drain())
    assert drained == [("a", 1), ("a", 2), ("b", 3)] and q.depth() == 0


def test_fair_queue_pop_interleaves_by_weight():
    q = FairQueue({"gold": 4.0, "bronze": 1.0})
    for i in range(10):
        q.push("gold", f"g{i}")
        q.push("bronze", f"b{i}")
    first10 = [q.pop(timeout=1.0)[0] for _ in range(10)]
    assert first10.count("gold") == 8 and first10.count("bronze") == 2


# -- task-pool watermarks + priority threading --------------------------------

def test_pool_watermark_validation_and_cli_threading():
    with pytest.raises(ValueError):
        PrioritizedTaskPool("p", high_water=4, low_water=5)
    rt = StageRuntime(high_water=32, low_water=4)
    assert all(p.high_water == 32 and p.low_water == 4
               for p in rt.pools.values())


def test_queue_depth_gauge_tracks_backlog():
    telemetry.enable()
    try:
        rt = StageRuntime()
        for _ in range(3):
            rt.submit("inference", lambda: None)
        g = telemetry.catalog.get("server_task_queue_depth")
        assert g.labels(pool="inference").value == 3.0
        while rt.run_once():
            pass
        assert g.labels(pool="inference").value == 0.0
    finally:
        telemetry.disable()


def test_priority_kwarg_replaces_inference_constant():
    p = DummyTaskPrioritizer()
    assert p.prioritize("inference", 1) == 1.0          # reference constant
    assert p.prioritize("inference", 1, priority=0.25) == 0.25
    assert p.prioritize("forward", 1, priority=0.25) == 2.0  # only inference
    # And the runtime orders by it: a gold-tenant step (priority 1/4) must
    # run before an earlier-submitted default-priority step.
    rt = StageRuntime()
    order = []
    rt.submit("inference", lambda: order.append("default"))
    rt.submit("inference", lambda: order.append("gold"), priority=0.25)
    while rt.run_once():
        pass
    assert order == ["gold", "default"]


@pytest.fixture(scope="module")
def wire():
    """One registry + one runtime-backed stage server over real TCP, with a
    recording prioritizer (max_batch_size tiny so oversized work is easy)."""

    class Recorder(TaskPrioritizerBase):
        def __init__(self):
            self.calls = []
            self._inner = DummyTaskPrioritizer()

        def prioritize(self, kind, size, **kwargs):
            self.calls.append((kind, size, dict(kwargs)))
            return self._inner.prioritize(kind, size, **kwargs)

    cfg = tiny_cfg()
    params = init_params(jax.random.PRNGKey(0), cfg)
    plan = StagePlan.from_splits(cfg.num_layers, parse_splits("4"))
    rec_prio = Recorder()
    reg_server = RegistryServer()
    reg_server.start()
    spec = plan.stages[1]
    ex = StageExecutor(cfg, spec, slice_stage_params(cfg, params, spec),
                       peer_id="serving-s1")
    srv = TcpStageServer(ex, wire_dtype="f32",
                         runtime=StageRuntime(prioritizer=rec_prio,
                                              max_batch_size=4))
    srv.start()
    rec = make_server_record(ex.peer_id, spec)
    rec.address = srv.address
    reg_server.registry.register(rec)
    reg = RemoteRegistry(reg_server.address)
    yield {"cfg": cfg, "reg": reg, "peer": ex.peer_id, "prio": rec_prio}
    srv.stop()
    reg_server.stop()


def _prefill(cfg, session_id, seq_len, priority=None):
    return StageRequest(
        session_id=session_id,
        hidden=jnp.zeros((1, seq_len, cfg.hidden_size), jnp.float32),
        seq_len=seq_len, cur_len=0, is_prefill=True, max_length=16,
        priority=priority)


def test_oversized_task_is_typed_permanent_rejection(wire):
    """size > max_batch_size must surface as TaskRejected(permanent=True)
    on the CLIENT — not as a retryable stage error that burns the retry
    budget on work that can never succeed anywhere."""
    tx = TcpTransport(wire["reg"], wire_dtype="f32")
    try:
        with pytest.raises(TaskRejected) as ei:
            tx.call(wire["peer"], _prefill(wire["cfg"], "oversize", 5))
        assert ei.value.permanent
        assert not isinstance(ei.value, (ConnectionError, TimeoutError))
    finally:
        tx.close()


def test_gateway_priority_reaches_server_prioritizer(wire):
    """StageRequest.priority rides the wire into the task pool, replacing
    DummyTaskPrioritizer's inference constant (1.0) with 1/tenant_weight."""
    tx = TcpTransport(wire["reg"], wire_dtype="f32")
    try:
        wire["prio"].calls.clear()
        tx.call(wire["peer"], _prefill(wire["cfg"], "prio-gold", 2,
                                       priority=0.25))
        tx.call(wire["peer"], _prefill(wire["cfg"], "prio-default", 2))
        inf = [kw for kind, _, kw in wire["prio"].calls
               if kind == "inference"]
        assert inf[0].get("priority") == 0.25    # gateway-stamped
        assert inf[1].get("priority") is None    # no gateway: constant
    finally:
        tx.close()


# -- acceptance e2e: the overload soak ----------------------------------------

def test_overload_soak_fairness_tokens_and_shedding():
    cfg = tiny_cfg()
    params = init_params(jax.random.PRNGKey(0), cfg)
    res = overload_soak(cfg, params, prompt_ids=[1, 2, 3, 4, 5],
                        max_new_tokens=6, seed=0, splits=(3, 5),
                        wire_dtype="f32", request_timeout=30.0,
                        requests_per_tenant=2)
    assert res["ok"], res["problems"]
    assert res["gold_served"] > 0 and res["bronze_served"] > 0
    # All three admission gates fired, each with an honest retry hint.
    assert set(res["shed_reasons"]) == {"rate", "concurrency", "queue_full"}
    assert all(v > 0 for v in res["shed_reasons"].values())
    # The doctor reconstructed the refusals from the event ring.
    assert res["shed_chains"] >= 1


def test_overload_soak_burst_granularity():
    """The soak with --burst 4: gateway sessions decode in 4-tick jitted
    bursts against a full-span batched peer (the sequential no-gateway
    baseline stays per-step — it is the token oracle), sessions join/
    leave at burst boundaries, and the DRR is charged N tokens per pick,
    so the served-token fairness window still tracks the 4:1 weights at
    burst granularity."""
    cfg = tiny_cfg()
    params = init_params(jax.random.PRNGKey(0), cfg)
    res = overload_soak(cfg, params, prompt_ids=[1, 2, 3, 4, 5],
                        max_new_tokens=6, seed=0, splits=(3, 5),
                        wire_dtype="f32", request_timeout=30.0,
                        requests_per_tenant=2, burst=4)
    assert res["ok"], res["problems"]
    assert res["burst"] == 4
    assert res["gold_served"] > 0 and res["bronze_served"] > 0
    # Burst scheduling must not break the admission gates either.
    assert set(res["shed_reasons"]) == {"rate", "concurrency", "queue_full"}


@pytest.mark.slow
def test_gateway_multiprocess_drill():
    """Full-fidelity serving path: registry, stage servers, gateway, and a
    submitting tenant as separate OS processes over real sockets."""
    import os

    MAIN = ("global_capstone_design_distributed_inference_of_llms_over_the"
            "_internet_tpu.main")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    reg_port, gw_port = 31471, 31472
    reg_addr = f"127.0.0.1:{reg_port}"
    procs = []

    def spawn(role_args):
        proc = subprocess.Popen(
            [sys.executable, "-m", MAIN, "--model", "gpt2"] + role_args,
            cwd=REPO, env=env, stdout=subprocess.DEVNULL,
            stderr=subprocess.STDOUT)
        procs.append(proc)
        return proc

    def wait_port(port, deadline_s=120.0):
        deadline = time.time() + deadline_s
        while time.time() < deadline:
            for proc in procs:
                assert proc.poll() is None, \
                    f"a swarm process exited early (rc={proc.returncode})"
            try:
                socket.create_connection(("127.0.0.1", port),
                                         timeout=1.0).close()
                return
            except OSError:
                time.sleep(0.5)
        raise AssertionError(f"port {port} never came up")

    try:
        spawn(["--mode", "registry", "--registry_port", str(reg_port)])
        wait_port(reg_port)
        for stage in (1, 2):
            spawn(["--mode", "serve", "--splits", "4,8",
                   "--stage", str(stage), "--registry_addr", reg_addr])
        deadline = time.time() + 180
        while time.time() < deadline:
            try:
                if len(RemoteRegistry(reg_addr).live_servers()) >= 2:
                    break
            except OSError:
                pass
            time.sleep(1.0)
        else:
            raise AssertionError("stage servers never registered")
        spawn(["--mode", "gateway", "--splits", "4,8",
               "--registry_addr", reg_addr, "--rpc_port", str(gw_port),
               "--tenants", '{"gold": {"weight": 4}, "bronze": {}}'])
        wait_port(gw_port)
        rc = subprocess.call(
            [sys.executable, "-m", MAIN, "--model", "gpt2",
             "--mode", "submit", "--gateway_addr", f"127.0.0.1:{gw_port}",
             "--tenant", "gold", "--prompt", "hello", "--max_new_tokens",
             "8", "--submit_requests", "2", "--deadline_s", "120"],
            cwd=REPO, env=env, timeout=300)
        assert rc == 0
    finally:
        for proc in procs:
            proc.terminate()
        for proc in procs:
            try:
                proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                proc.kill()
