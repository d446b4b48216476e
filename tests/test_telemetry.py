"""Telemetry subsystem: registry semantics, exposition format, tracing.

Four concerns, matching ISSUE 1's test checklist:

  * histogram bucket-edge placement (`le` is inclusive, Prometheus
    semantics) and interpolated quantiles;
  * counter/gauge/histogram thread-safety under concurrent mutation;
  * the text exposition's exact golden output (any drift here breaks real
    scrapers, so the assertion is byte-for-byte);
  * cross-stage trace propagation through a REAL in-process 2-remote-hop
    pipeline — one client span and one server span per stage hop, all on
    one trace_id, timestamps nested, reconstructable end-to-end.
"""

import threading

import jax

from engines import build_cluster, tiny_cfg

from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu import (
    telemetry,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.ops.sampling import (
    SamplingParams,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.telemetry import (
    MetricsRegistry,
    Tracer,
    catalog,
    exposition,
    get_tracer,
    reconstruct,
)


# -- histogram semantics ------------------------------------------------------

def test_histogram_bucket_edges():
    reg = MetricsRegistry(enabled=True)
    h = reg.histogram("lat", "", buckets=(1.0, 2.0, 5.0))
    # A value exactly equal to an upper bound belongs to that bucket
    # (le="1.0" INCLUDES 1.0 — Prometheus cumulative semantics).
    for v in (0.5, 1.0, 1.5, 2.0, 5.0, 7.0):
        h.observe(v)
    assert h.bucket_counts() == [2, 4, 5, 6]   # cumulative, +Inf last
    assert h.count == 6
    assert abs(h.sum - 17.0) < 1e-9


def test_histogram_quantiles():
    reg = MetricsRegistry(enabled=True)
    h = reg.histogram("lat", "", buckets=(1.0, 2.0, 4.0))
    assert h.quantile(0.5) is None             # empty histogram
    for _ in range(10):
        h.observe(1.5)                         # all mass in (1, 2]
    q = h.quantile(0.5)
    assert 1.0 < q <= 2.0                      # interpolated inside bucket
    assert h.quantile(1.0) == 2.0
    h.observe(100.0)                           # lands in +Inf: clamps
    assert h.quantile(1.0) == 4.0              # last finite bound


def test_disabled_registry_is_noop():
    reg = MetricsRegistry(enabled=False)
    c = reg.counter("c", "")
    g = reg.gauge("g", "")
    h = reg.histogram("h", "", buckets=(1.0,))
    c.inc(5)
    g.set(3)
    h.observe(0.5)
    assert c.value == 0.0 and g.value == 0.0 and h.count == 0
    reg.enable()
    c.inc(5)
    assert c.value == 5.0                      # same handle, flag flipped


# -- concurrency --------------------------------------------------------------

def test_counter_gauge_histogram_concurrency():
    reg = MetricsRegistry(enabled=True)
    c = reg.counter("reqs_total", "", labels=("k",)).labels(k="x")
    g = reg.gauge("occ", "")
    h = reg.histogram("lat", "", buckets=(0.5, 1.0))
    n_threads, n_iters = 8, 2000

    def work():
        for _ in range(n_iters):
            c.inc()
            g.inc(2.0)
            g.dec(1.0)
            h.observe(0.7)

    threads = [threading.Thread(target=work) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    total = n_threads * n_iters
    assert c.value == float(total)
    assert abs(g.value - total) < 1e-6
    assert h.count == total
    assert h.bucket_counts() == [0, total, total]


# -- exposition format --------------------------------------------------------

def test_exposition_golden_output():
    reg = MetricsRegistry(enabled=True)
    reg.counter("requests_total", "Requests.",
                labels=("outcome",)).labels(outcome="ok").inc(2)
    reg.gauge("occupancy", "Occupancy.").set(0.5)
    h = reg.histogram("lat_seconds", "Latency.", buckets=(0.1, 1.0))
    for v in (0.05, 0.5, 5.0):
        h.observe(v)
    assert exposition.render(reg) == (
        "# HELP lat_seconds Latency.\n"
        "# TYPE lat_seconds histogram\n"
        'lat_seconds_bucket{le="0.1"} 1\n'
        'lat_seconds_bucket{le="1"} 2\n'
        'lat_seconds_bucket{le="+Inf"} 3\n'
        "lat_seconds_sum 5.55\n"
        "lat_seconds_count 3\n"
        "# HELP occupancy Occupancy.\n"
        "# TYPE occupancy gauge\n"
        "occupancy 0.5\n"
        "# HELP requests_total Requests.\n"
        "# TYPE requests_total counter\n"
        'requests_total{outcome="ok"} 2\n'
    )


def test_register_all_exposes_required_families():
    """The acceptance scrape must show every catalogued family even with zero
    traffic (register_all materializes the schema on enable())."""
    reg = MetricsRegistry(enabled=True)
    catalog.register_all(reg)
    text = exposition.render(reg)
    for name in ("server_step_latency_seconds", "server_tokens_total",
                 "server_kv_occupancy_ratio", "server_prefix_cache_hits_total",
                 "client_retries_total"):
        assert f"# TYPE {name} " in text
    # Every catalogued name appears (the check_metrics_documented contract).
    for name in catalog.all_names():
        assert f"# HELP {name} " in text


def test_summary_aggregate():
    reg = MetricsRegistry(enabled=True)
    step = catalog.get("server_step_latency_seconds", reg)
    for _ in range(10):
        step.labels(phase="decode").observe(0.004)
    catalog.get("server_prefix_cache_hits_total", reg).inc(3)
    catalog.get("server_prefix_cache_misses_total", reg).inc(1)
    s = exposition.summary(reg)
    assert s["steps_total"] == 10
    assert s["steps_per_s"] > 0
    assert 1.0 <= s["step_p50_ms"] <= 10.0
    assert s["cache_hit_rate"] == 0.75


# -- tracing ------------------------------------------------------------------

def test_wire_context_roundtrip():
    tr = Tracer(enabled=True)
    root = tr.start_span("pipeline_step", kind="client")
    ctx = root.wire_context(hop=2)
    assert set(ctx) == {"trace_id", "parent", "hop"}
    assert ctx["trace_id"] == root.trace_id
    assert ctx["parent"] == root.span_id
    assert ctx["hop"] == 2
    srv = tr.span_from_wire(ctx, "server_forward", kind="server")
    assert srv.trace_id == root.trace_id
    assert srv.parent_id == root.span_id
    srv.end()
    root.end()
    wire = srv.to_wire()
    assert wire["trace_id"] == root.trace_id
    assert wire["start_s"] <= wire["end_s"]


def test_disabled_tracer_is_noop():
    tr = Tracer(enabled=False)
    s = tr.start_span("x")
    assert not s
    assert s.wire_context(0) is None and s.to_wire() is None
    assert tr.span_from_wire({"trace_id": "t", "parent": "p", "hop": 0},
                             "y") is not None
    assert tr.spans() == ()


def test_trace_propagation_two_stage_pipeline():
    """Decode steps through a REAL 2-remote-hop in-process pipeline must
    yield one reconstructable trace per step: a client root, one client span
    per hop, and one SERVER span per hop (recorded by LocalTransport at the
    serving boundary), all sharing the trace_id, with server timestamps
    nested inside the client hop's window."""
    telemetry.enable()
    tracer = get_tracer()
    tracer.clear()
    try:
        cfg = tiny_cfg()
        client, _, _, _, _ = build_cluster(cfg, splits="3,6")
        client.generate([5, 9, 23, 7, 81], max_new_tokens=3,
                        sampling=SamplingParams(temperature=0.0))
        traces = reconstruct(tracer.spans())
        decode_traces = []
        prefill_traces = []
        for tid, spans in traces.items():
            roots = [s for s in spans if s.name == "pipeline_step"]
            assert len(roots) == 1, "one root span per pipeline step"
            if roots[0].attrs.get("phase") == "decode":
                decode_traces.append((roots[0], spans))
            else:
                prefill_traces.append((roots[0], spans))
        assert len(prefill_traces) == 1
        assert len(decode_traces) >= 1      # >=1 decode step ran

        # Prefill covers the client-local stage0 hop too.
        _, pspans = prefill_traces[0]
        assert any(s.name == "hop:stage0" for s in pspans)

        for root, spans in decode_traces:
            hops = {s.name: s for s in spans
                    if s.kind == "client" and s.name.startswith("hop:")}
            servers = [s for s in spans if s.name == "server_forward"]
            assert set(hops) == {"hop:stage1", "hop:stage2"}
            assert len(servers) == 2, "one server span per stage hop"
            for s in spans:
                assert s.end_s is not None and s.end_s >= s.start_s
                if s is not root:
                    assert s.parent_id == root.span_id
            # Server-side work sits inside the client hop's wall window
            # (same process, same clock) and identifies its serving peer;
            # the client hop also carries the server's reported span.
            by_peer = {s.attrs.get("peer"): s for s in servers}
            for hop in hops.values():
                srv = by_peer[hop.attrs["peer"]]
                assert hop.start_s <= srv.start_s
                assert srv.end_s <= hop.end_s
                assert hop.attrs["server"]["span_id"] == srv.span_id
    finally:
        telemetry.disable()
        tracer.clear()


def test_tcp_metrics_verb_and_trace_over_wire():
    """The `metrics` wire verb returns a real exposition, `info` embeds the
    telemetry aggregate, and trace context/span summaries survive the framed
    TCP round trip (header keys, not just in-process object passing)."""
    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.models import (
        init_params,
    )
    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.models.partition import (
        StagePlan,
        parse_splits,
        slice_stage_params,
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

    telemetry.enable()
    tracer = get_tracer()
    tracer.clear()
    cfg = tiny_cfg()
    params = init_params(jax.random.PRNGKey(0), cfg)
    plan = StagePlan.from_splits(cfg.num_layers, parse_splits("4"))
    reg_server = RegistryServer()
    reg_server.start()
    servers = []
    try:
        spec = plan.stages[1]
        ex = StageExecutor(cfg, spec, slice_stage_params(cfg, params, spec),
                           peer_id="tcp-tele-s1")
        srv = TcpStageServer(ex, wire_dtype="f32")
        srv.start()
        rec = make_server_record("tcp-tele-s1", spec)
        rec.address = srv.address
        reg_server.registry.register(rec)
        servers.append(srv)

        registry = RemoteRegistry(reg_server.address)
        transport = TcpTransport(registry, wire_dtype="f32")
        stage0 = StageExecutor(cfg, plan.stages[0],
                               slice_stage_params(cfg, params, plan.stages[0]),
                               peer_id="client-local")
        client = PipelineClient(cfg, plan, stage0, transport, registry,
                                settle_seconds=0.0, seed=0)
        client.generate([5, 9, 23], max_new_tokens=2,
                        sampling=SamplingParams(temperature=0.0))

        # metrics verb: a real exposition with serving-boundary traffic.
        text = transport.metrics_text("tcp-tele-s1")
        assert "# TYPE server_step_latency_seconds histogram" in text
        assert 'server_requests_total{outcome="ok"}' in text

        # info verb: the compact aggregate rides the introspection frame.
        inf = transport.info("tcp-tele-s1")
        assert inf["telemetry"]["steps_total"] >= 1
        assert inf["telemetry"]["step_p50_ms"] is not None

        # Client hop spans carry the server's span summary decoded from the
        # TCP response frame's `span` header key.
        hop_spans = [s for s in tracer.spans()
                     if s.kind == "client" and s.name == "hop:stage1"]
        assert hop_spans
        wired = [s.attrs.get("server") for s in hop_spans
                 if isinstance(s.attrs.get("server"), dict)]
        assert wired, "no server span summary came back over the wire"
        for w in wired:
            assert w["name"] == "server_forward"
            assert w["start_s"] <= w["end_s"]
        transport.close()
    finally:
        telemetry.disable()
        tracer.clear()
        for s in servers:
            s.stop()
        reg_server.stop()
