"""Hot-path phase profiler, critical-path doctor, swarm top, SLO burn rates.

Five concerns, matching ISSUE 9's test checklist:

  * phase attribution: bracketed phase totals sum to the simulated wall
    time, and the default-off profiler is a shared-noop zero-cost path;
  * device bubble fraction: a synthetic host stall between dispatches
    yields exactly the expected idle fraction, overlapped (double-
    buffered) dispatches yield zero;
  * the doctor's critical-path analysis over a REAL 2-stage in-process
    trace — the network/queue/compute/replay/client parts must SUM to
    each request's wall time (the acceptance-pinned property);
  * ``--mode top --once`` renders the swarm table from gossip-carried
    stats digests with every seed registry dead;
  * per-tenant SLO burn-rate math under an injected clock.
"""

import random
import re

import pytest

from engines import build_cluster, tiny_cfg

from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu import (
    main as main_mod,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu import (
    telemetry,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.ops.sampling import (
    SamplingParams,
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
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.serving.admission import (
    TenantConfig,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.serving.gateway import (
    SloTracker,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.telemetry import (
    MetricsRegistry,
    catalog,
    events,
    get_tracer,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.telemetry import (
    doctor as doc,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.telemetry.profiling import (
    DIGEST_FIELDS,
    PhaseProfiler,
    disable_phase_profiling,
    enable_phase_profiling,
    get_profiler,
    stats_digest,
)


# -- phase profiler -----------------------------------------------------------

def test_profiler_default_off_is_shared_noop():
    p = PhaseProfiler(enabled=False)
    b1, b2 = p.phase("dispatch"), p.phase("device")
    assert b1 is b2                        # ONE shared bracket, no alloc
    with b1:
        pass
    p.observe("dispatch", 1.0)
    p.device_interval(0.0, 1.0)
    assert p.snapshot() == {}
    assert p.bubble_fraction() == 0.0
    # The process-global profiler starts dark.
    assert get_profiler().enabled is False


def test_phase_attribution_sums_to_wall():
    reg = MetricsRegistry(enabled=True)
    p = PhaseProfiler(enabled=True, registry=reg)
    # One simulated request: the bracketed phases partition its wall time.
    wall = 0.0
    for name, dur in (("gateway_queue", 0.004), ("burst_build", 0.002),
                      ("dispatch", 0.001), ("device", 0.010),
                      ("readback", 0.003)):
        p.observe(name, dur)
        wall += dur
    snap = p.snapshot()
    assert sum(st["total_s"] for st in snap.values()) == pytest.approx(wall)
    assert snap["device"]["count"] == 1
    assert snap["device"]["mean_s"] == pytest.approx(0.010)
    # Mirrored into the catalog histogram (per-phase child).
    fam = reg.get("server_phase_seconds")
    by_phase = {dict(h.labels)["phase"]: h for h in fam.children()}
    assert by_phase["device"].count == 1
    assert by_phase["device"].sum == pytest.approx(0.010)


def test_bubble_fraction_synthetic_stall():
    p = PhaseProfiler(enabled=True, registry=MetricsRegistry(enabled=False))
    # Burst 1 runs [0, 1]; the host then stalls 0.5s before dispatching
    # burst 2, which runs [1.5, 2.5]: wall 2.5, busy 2.0 → bubble 0.2.
    p.device_interval(0.0, 1.0)
    p.device_interval(1.5, 2.5)
    assert p.bubble_fraction() == pytest.approx(0.2)

    # Overlapped (double-buffered) dispatch: burst 2 is enqueued at 0.8,
    # BEFORE burst 1 drains at 1.0 — no idle device time, zero bubble.
    p2 = PhaseProfiler(enabled=True, registry=MetricsRegistry(enabled=False))
    p2.device_interval(0.0, 1.0)
    p2.device_interval(0.8, 1.9)
    assert p2.bubble_fraction() == pytest.approx(0.0)


def test_profiled_pipeline_populates_socket_and_server_phases():
    """With the global profiler on, a REAL 2-stage generation populates the
    client-side socket phase and the serving-boundary server phase."""
    enable_phase_profiling()
    prof = get_profiler()
    prof.reset()
    try:
        cfg = tiny_cfg()
        client, _, _, _, _ = build_cluster(cfg, splits="3,6")
        client.generate([5, 9, 23, 7, 81], max_new_tokens=3,
                        sampling=SamplingParams(temperature=0.0))
        snap = prof.snapshot()
        assert snap["socket"]["count"] >= 1
        assert snap["server"]["count"] >= 2    # 2 remote stages per step
        assert snap["server"]["total_s"] > 0.0
    finally:
        disable_phase_profiling()
        prof.reset()


# -- stats digest -------------------------------------------------------------

def test_stats_digest_has_every_field():
    reg = MetricsRegistry(enabled=True)
    catalog.register_all(reg)
    d = stats_digest(registry=reg, profiler=PhaseProfiler(enabled=True))
    assert set(d) == set(DIGEST_FIELDS)
    for v in d.values():
        assert isinstance(v, (int, float))


# -- doctor critical path -----------------------------------------------------

def _trace_a_generation(tmp_path):
    """Run a real 2-remote-hop generation under tracing and return the
    dump-file stream the doctor would load."""
    telemetry.enable()
    tracer = get_tracer()
    tracer.clear()
    events.get_recorder().enable()
    events.get_recorder().clear()
    try:
        cfg = tiny_cfg()
        client, _, _, _, _ = build_cluster(cfg, splits="3,6")
        client.generate([5, 9, 23, 7, 81], max_new_tokens=3,
                        sampling=SamplingParams(temperature=0.0))
        path = str(tmp_path / "trace.jsonl")
        events.get_recorder().dump(path, registry=telemetry.get_registry())
        return events.load_dump(path), path
    finally:
        telemetry.disable()
        tracer.clear()


def test_critical_path_parts_sum_to_wall(tmp_path):
    stream, _ = _trace_a_generation(tmp_path)
    assert stream["spans"], "dump carried no _spans record"
    reports = doc.critical_path_reports([stream])
    assert reports, "no pipeline_step roots reconstructed"
    decode = [r for r in reports if r["phase"] == "decode"]
    assert decode, "no decode-step traces"
    for r in reports:
        parts = r["parts"]
        assert set(parts) == {"network", "queue", "compute", "replay",
                              "client"}
        # THE acceptance property: attribution sums to the request wall.
        assert sum(parts.values()) == pytest.approx(r["wall_s"],
                                                    rel=1e-9, abs=1e-12)
        for k in ("network", "queue", "compute", "replay"):
            assert parts[k] >= 0.0
        assert parts["client"] >= -1e-9    # residual; hops nest in root
    for r in decode:
        assert r["hops"] == 2              # stage1 + stage2
        assert r["parts"]["compute"] > 0.0
        # Critical path descends root → slowest hop → its server span.
        names = [n for n, _ in r["path"]]
        assert names[0] == "pipeline_step"
        assert names[1].startswith("hop:")
        assert names[2] == "server_forward"


def test_doctor_cli_renders_critical_path(tmp_path, capsys):
    _, path = _trace_a_generation(tmp_path)
    rc = main_mod.main(["--mode", "doctor", "--dumps", path,
                        "--critical_path"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "critical path" in out
    assert "compute" in out and "network" in out
    # Without the flag the section stays out of the report.
    rc = main_mod.main(["--mode", "doctor", "--dumps", path])
    out = capsys.readouterr().out
    assert rc == 0
    assert "critical path" not in out


# -- swarm top ----------------------------------------------------------------

def _mirror_server(peer_id):
    node = GossipNode(peer_id, ttl=30.0, rng=random.Random(0))
    srv = TcpStageServer(None, wire_dtype="f32", peer_id=peer_id,
                         gossip=node)
    srv.start()
    node.self_address = srv.address
    return node, srv


def _stats(tok_s):
    return {"tok_s": tok_s, "tokens_total": 100.0, "queue_depth": 1.0,
            "breaker_open": 0.0, "cache_hit_ratio": 0.5,
            "bubble_frac": 0.25, "uptime_s": 3.0}


def test_mode_top_once_survives_total_registry_loss(tmp_path, capsys):
    """--mode top --once keeps rendering the whole-swarm table after BOTH
    seed registries die: records come through the peers cache + a mirror,
    stats ride the gossip records verbatim."""
    cache = str(tmp_path / "peers.json")
    node1, srv1 = _mirror_server("top1")
    node2, srv2 = _mirror_server("top2")
    seeds = [RegistryServer(), RegistryServer()]
    for s in seeds:
        s.start()
    seed_addrs = ",".join(s.address for s in seeds)
    try:
        rec1 = ServerRecord(peer_id="top1", start_block=0, end_block=4,
                            stage_index=1, address=srv1.address)
        rec2 = ServerRecord(peer_id="top2", start_block=4, end_block=8,
                            stage_index=2, address=srv2.address)
        rr = RemoteRegistry(seed_addrs, peers_cache=cache)
        rr.register(rec1)
        rr.register(rec2)
        # One read while the seeds live persists the peers-cache snapshot
        # (the bootstrap file a fresh top process survives seed loss with).
        assert {r.peer_id for r in rr.live_servers()} == {"top1", "top2"}
        node1.publish(dict(rec_to_dict(rec1), stats=_stats(12.5)))
        node2.publish(dict(rec_to_dict(rec2), stats=_stats(7.25)))
        # One anti-entropy exchange each way: both mirrors hold the full
        # swarm (records + digests) before the control plane dies.
        gossip_exchange(node1, srv2.address)
        gossip_exchange(node2, srv1.address)
        for s in seeds:
            s.stop()                       # total seed-registry loss

        rc = main_mod.main(["--mode", "top", "--once",
                            "--registry_addr", seed_addrs,
                            "--peers_cache", cache,
                            "--gateway_addr", ""])
        out = capsys.readouterr().out
        assert rc == 0
        assert "top1" in out and "top2" in out
        assert "gossip via" in out         # stats came from a mirror
        # top2's digest arrived via gossip replication (the answering
        # peer top1 shows its own LIVE digest instead — fresher).
        assert "7.2" in out and "50.0" in out and "25.0" in out
        assert "[0,4)" in out and "[4,8)" in out
    finally:
        srv1.stop()
        srv2.stop()
        for s in seeds:
            s.stop()


# -- SLO burn rates -----------------------------------------------------------

def test_slo_burn_rate_math_with_injected_clock():
    t = [0.0]
    cfg = TenantConfig(name="gold", slo_ttft_s=0.1, slo_token_s=0.01,
                       slo_target=0.9)
    trk = SloTracker({"gold": cfg}, window_s=60.0, now=lambda: t[0])
    # 8 good + 2 bad TTFTs at a 90% target: bad fraction 0.2 over an error
    # budget of 0.1 → burning at 2x the sustainable rate.
    for _ in range(8):
        trk.observe("gold", "ttft", 0.05)
    for _ in range(2):
        trk.observe("gold", "ttft", 0.25)
    assert trk.burn_rate("gold", "ttft") == pytest.approx(2.0)
    # All per-token observations within objective: zero burn.
    for _ in range(5):
        trk.observe("gold", "token", 0.005)
    snap = trk.snapshot()
    assert snap["gold"]["ttft"] == pytest.approx(2.0)
    assert snap["gold"]["token"] == 0.0
    # The window forgets: 2 minutes later the bad epoch has aged out and
    # one good observation leaves burn at zero.
    t[0] = 120.0
    trk.observe("gold", "ttft", 0.05)
    assert trk.burn_rate("gold", "ttft") == 0.0


def test_slo_tracker_ignores_undeclared_objectives():
    cfg = TenantConfig(name="free")        # no objectives declared
    trk = SloTracker({"free": cfg}, window_s=60.0)
    trk.observe("free", "ttft", 99.0)
    trk.observe("unknown-tenant", "ttft", 99.0)
    assert trk.burn_rate("free", "ttft") == 0.0
    assert trk.snapshot() == {}


# -- the stage engine's own spans (ISSUE 25) ------------------------------------

STAGE_PROMPTS = {"a": [5, 9, 23], "b": [44, 2], "c": [100, 11, 12, 13]}


class _Spans:
    """Stands in for ``jax.profiler.TraceAnnotation``: records every span a
    live bracket opens."""

    def __init__(self):
        self.made = []

    def __call__(self, name, **meta):
        self.made.append((name, meta))
        return self

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return None


def _stage_adapter(monkeypatch, *, profiled, window_s=0.0):
    """A full-span batched adapter at the tiny preset whose profiler and
    registry are this test's own (nothing global is switched)."""
    import jax

    from test_batching import full_spec

    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.models import (
        init_params,
    )
    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime import (
        batching,
    )

    reg = MetricsRegistry(enabled=True)
    prof = PhaseProfiler(enabled=profiled, registry=reg)
    spans = _Spans()
    monkeypatch.setattr(batching, "_get_profiler", lambda: prof)
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", spans)
    cfg = tiny_cfg()
    inner = batching.BatchedStageExecutor(
        cfg, full_spec(cfg), init_params(jax.random.PRNGKey(21), cfg),
        slots=4, max_len=32)
    adapter = batching.BatchingStageAdapter(inner, window_s=window_s)
    adapter._m_held = catalog.get("server_batch_slots_held", reg)
    adapter._m_fill = catalog.get("server_batch_fill_sessions", reg)
    return adapter, prof, reg, spans


def _stage_request(sid, ids, *, cur_len, burst=0, prefill=False,
                   sampling=SamplingParams(temperature=0.0)):
    import jax.numpy as jnp

    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime.messages import (
        StageRequest,
    )

    return StageRequest(
        session_id=sid, hidden=jnp.asarray([ids], jnp.int32),
        seq_len=len(ids), cur_len=cur_len, is_prefill=prefill, max_length=32,
        sampling=sampling, generated_tokens=(7,),
        burst_len=burst, burst_budget=burst)


def _prefill_then_round(adapter, prof, burst):
    """Two sessions join with the profiler dark, then (profiler as the
    case set it) ONE prefill and ONE round of that session alone."""
    was = prof.enabled
    prof.set_enabled(False)
    for sid in ("a", "b"):
        adapter.forward(_stage_request(sid, STAGE_PROMPTS[sid], cur_len=0,
                                       prefill=True))
    prof.set_enabled(was)
    first = adapter.forward(_stage_request("c", STAGE_PROMPTS["c"],
                                           cur_len=0, prefill=True))
    assert first.token_id is not None
    resp = adapter.forward(_stage_request(
        "c", [first.token_id], cur_len=len(STAGE_PROMPTS["c"]), burst=burst))
    assert len(resp.burst_tokens) == burst if burst else resp.is_token


@pytest.mark.parametrize("burst", [4, 0], ids=["burst_round", "step_round"])
def test_stage_engine_phases_and_slots_held(monkeypatch, burst):
    adapter, prof, reg, spans = _stage_adapter(monkeypatch, profiled=True)
    _prefill_then_round(adapter, prof, burst)
    snap = prof.snapshot()
    for phase in ("prefill_wait", "prefill", "first_token", "prefill_ready"):
        assert snap[phase]["count"] == 1, phase
    round_phases = {"burst_build", "dispatch", "device", "device_queued",
                    "readback"}
    assert round_phases & set(snap) == (round_phases if burst else set())
    # Mirrored into server_phase_seconds{phase}, which the benchmark reads.
    mirrored = {dict(h.labels)["phase"]: h.count
                for h in reg.get("server_phase_seconds").children()}
    assert mirrored["first_token"] == 1 and mirrored["prefill_wait"] == 1
    # Three sessions HELD a slot when the round ran with one of them.
    held, fill = adapter._m_held, adapter._m_fill
    assert (held.count, held.sum) == (1, 3.0)
    assert (fill.count, fill.sum) == (1, 1.0)
    # Every live bracket is a span on the profiler's clock, with its session
    # (a round's brackets: how many sessions), and the window is a span
    # with no statistic.
    names = {n for n, _ in spans.made}
    want = {"stage.prefill_wait", "stage.prefill", "stage.first_token",
            "stage.prefill_ready", "stage.round_window"}
    if burst:
        want |= {"stage." + p for p in round_phases}
    assert names == want
    assert ("stage.first_token", {"session": "c"}) in spans.made
    if burst:
        assert ("stage.dispatch", {"sessions": 1}) in spans.made
    assert "round_window" not in snap


@pytest.mark.parametrize("burst", [4, 0], ids=["burst_round", "step_round"])
def test_stage_engine_dark_profiler_builds_nothing(monkeypatch, burst):
    adapter, prof, reg, spans = _stage_adapter(monkeypatch, profiled=False)
    for site in (prof.phase("prefill_wait", session="s"),
                 prof.phase("first_token", session="s"),
                 prof.device_phase(sessions=1),
                 prof.span("round_window", session="s"),
                 prof.span("request", session="s"),
                 prof.span("reply", session="s")):
        assert site is prof.phase("dispatch")        # the ONE shared no-op
    assert site.seconds == 0.0             # and it has measured nothing
    _prefill_then_round(adapter, prof, burst)
    assert spans.made == []                # no TraceAnnotation constructed
    assert prof.snapshot() == {}
    assert reg.get("server_phase_seconds") is None   # no series written
    assert adapter.inner.burst_parts is None         # no parts kept


def test_sampler_rounds_counted_by_stage(monkeypatch):
    """`server_sampler_rounds_total{stages}` says which path of the sampler
    a burst round's knobs switch on: the benchmark cells' knobs (0.8, 0.95,
    0, 1.0) run the nucleus filter; a greedy round, whose other knobs are
    the defaults (top-k 50, penalty 1.5), runs the argmax alone."""
    adapter, _, reg, _ = _stage_adapter(monkeypatch, profiled=False)
    adapter.inner._m_sampler = catalog.get("server_sampler_rounds_total", reg)
    cells = SamplingParams(temperature=0.8, top_p=0.95, top_k=0,
                           repetition_penalty=1.0)
    for sid, sp in (("a", cells), ("b", SamplingParams(temperature=0.0))):
        first = adapter.forward(_stage_request(
            sid, STAGE_PROMPTS[sid], cur_len=0, prefill=True, sampling=sp))
        adapter.forward(_stage_request(
            sid, [first.token_id], cur_len=len(STAGE_PROMPTS[sid]), burst=4,
            sampling=sp))
    counted = {dict(c.labels)["stages"]: c.value
               for c in reg.get("server_sampler_rounds_total").children()}
    assert counted == {"filter": 1.0, "greedy": 1.0}


def test_round_follower_wait_is_a_span(monkeypatch):
    """Two burst requests enter together, on ONE round: one leads
    (``round_window``: the leader's hold, whose statistic is
    ``server_round_hold_seconds``); the other waits for the leader's step
    under NO span of its own (``server_queue_wait_seconds`` is its series;
    a follower's span lay inside the leader's and took the label of the
    hold in a trace's idle gaps)."""
    import threading

    adapter, prof, _, spans = _stage_adapter(monkeypatch, profiled=True,
                                             window_s=1.0)
    firsts = {}
    for sid in ("a", "b"):
        firsts[sid] = adapter.forward(_stage_request(
            sid, STAGE_PROMPTS[sid], cur_len=0, prefill=True)).token_id
    adapter.inner.decode_burst(         # compile outside the 1 s window
        {"a": {"token": 1, "seed": 0, "budget": 4, "eos": None,
               "generated": (1,), "temperature": 0.0, "top_p": 1.0,
               "top_k": 0, "repetition_penalty": 1.0}}, 4)
    adapter.inner.rewind("a", len(STAGE_PROMPTS["a"]))
    spans.made.clear()
    barrier = threading.Barrier(2)
    out = {}

    def run(sid):
        barrier.wait()
        out[sid] = adapter.forward(_stage_request(
            sid, [firsts[sid]], cur_len=len(STAGE_PROMPTS[sid]), burst=4))

    threads = [threading.Thread(target=run, args=(s,)) for s in ("a", "b")]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=300)
    assert set(out) == {"a", "b"}
    names = [n for n, _ in spans.made]
    assert names.count("stage.round_window") == 1
    assert not [n for n in names if "wait" in n]
    # ``readback`` twice: the read, with the lock free, and the tables,
    # under it again
    assert sorted(names) == sorted(
        "stage." + p for p in ("round_window", "burst_build", "device",
                               "device_queued", "dispatch", "readback",
                               "readback"))
    assert ("stage.dispatch", {"sessions": 2}) in spans.made
    # what the engine keeps of the burst for a stall's record
    parts = adapter.inner.burst_parts
    assert sorted(parts) == ["build", "device", "dispatch", "queued",
                             "readback"]
    assert min(parts.values()) >= 0.0
    assert (adapter._m_fill.sum, adapter._m_held.sum) == (2.0, 2.0)


ENGINE_SCOPES = ("embed", "attention", "kv_update", "mlp")


@pytest.mark.parametrize("program, scopes", [
    ("burst_tick", ENGINE_SCOPES + ("head", "sampler", "stop_rules")),
    ("decode_step", ENGINE_SCOPES),
    ("prefill", ENGINE_SCOPES),
    ("prefill_suffix", ENGINE_SCOPES),
])
def test_engine_programs_carry_their_names(monkeypatch, program, scopes):
    """What the device trace shows: the module is ``jit_<program>`` and the
    operations carry the section's scope name."""
    import jax.numpy as jnp
    import numpy as np

    adapter, _, _, _ = _stage_adapter(monkeypatch, profiled=False)
    inner = adapter.inner
    inner.prefill("a", np.asarray([STAGE_PROMPTS["a"]], np.int32))
    ids = jnp.zeros((1, 8), jnp.int32)
    if program == "burst_tick":
        _, args = inner._burst_prep(
            {"a": {"token": 1, "seed": 0, "budget": 4, "eos": None,
                   "generated": (1,), "temperature": 0.8, "top_p": 0.95,
                   "top_k": 0, "repetition_penalty": 1.0}}, 4)
        lowered = inner._get_burst_jit(4).lower(
            inner.params, *args, inner.k, inner.v)
    elif program == "decode_step":
        lowered = inner._build_decode(1).lower(
            inner.params, jnp.zeros((4, 1), jnp.int32),
            jnp.asarray(inner.lengths), jnp.ones((4,), bool),
            inner.k, inner.v)
    elif program == "prefill":
        lowered = inner._build_prefill().lower(
            inner.params, ids, jnp.int32(1), inner.k, inner.v, jnp.int32(5))
    else:
        lowered = inner._build_prefill_suffix().lower(
            inner.params, ids, jnp.int32(1), inner.k, inner.v, jnp.int32(4),
            jnp.int32(5))
    text = lowered.as_text(debug_info=True)
    assert f"module @jit_{program} " in text.replace("attributes", " ")
    for scope in scopes:    # loc("jit(decode_step)/embed/…"), loc("mlp/…")
        assert re.search(rf'["/]{scope}/', text), scope
