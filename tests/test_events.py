"""Flight recorder + doctor: ISSUE 2's test checklist.

Five concerns:

  * ring semantics — bounded capacity, dropped counter, catalog-enforced
    event names, strict no-op when disabled;
  * the JSONL dump format round trip (meta line, optional metrics snapshot,
    truncated-tail tolerance);
  * crash dumps from REAL child processes — an uncaught exception and a
    SIGTERM both leave a parseable dump behind, and the signal path
    preserves the default termination exit code;
  * the ``dump-events`` wire verb over a real framed TCP round trip, plus
    the doctor's live-scrape ingestion of it;
  * the acceptance e2e: kill a stage mid-decode in a two-stage-replicated
    in-process swarm, dump, and assert ``--mode doctor`` reconstructs the
    timeout -> failover -> KV replay -> rebalance story with correct
    session/trace correlation.
"""

import json
import os
import pathlib
import signal
import subprocess
import sys
import textwrap

import pytest

from engines import build_cluster, tiny_cfg

from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu import (
    telemetry,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.ops.sampling import (
    SamplingParams,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.telemetry import (
    EventRecorder,
    MetricsRegistry,
    doctor,
    events,
    load_dump,
)

REPO = pathlib.Path(__file__).resolve().parents[1]
PKG = "global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu"


# -- ring semantics -----------------------------------------------------------

def test_catalog_rejects_unknown_event_names():
    rec = EventRecorder(enabled=True)
    with pytest.raises(KeyError):
        rec.emit("not_a_real_event")
    # Disabled fast path returns before the catalog lookup: a typo'd name
    # on a cold instrument site cannot crash a production process that
    # never turned the recorder on.
    off = EventRecorder(enabled=False)
    off.emit("not_a_real_event")
    assert len(off) == 0


def test_disabled_recorder_records_nothing():
    rec = EventRecorder(enabled=False)
    rec.emit("hop_retry", hop="stage1", attempt=1)
    assert len(rec) == 0
    rec.enable()
    rec.emit("hop_retry", hop="stage1", attempt=1)
    assert len(rec) == 1                       # same handle, flag flipped


def test_ring_overflow_keeps_newest_and_counts_drops():
    rec = EventRecorder(capacity=4, enabled=True)
    for i in range(6):
        rec.emit("hop_retry", hop="stage1", attempt=i)
    assert len(rec) == 4
    assert rec.dropped == 2
    assert [e.fields["attempt"] for e in rec.events()] == [2, 3, 4, 5]
    rec.clear()
    assert len(rec) == 0 and rec.dropped == 0


def test_severity_override_and_validation():
    rec = EventRecorder(enabled=True)
    rec.emit("hop_retry", hop="stage1", severity="error")
    assert rec.events()[0].severity == "error"
    with pytest.raises(ValueError):
        rec.emit("hop_retry", hop="stage1", severity="screaming")


# -- dump format --------------------------------------------------------------

def test_dump_roundtrip_and_truncated_tail(tmp_path):
    rec = EventRecorder(enabled=True)
    rec.emit("session_start", session_id="s1", trace_id="t1",
             kind="greedy", prompt_len=5)
    rec.emit("failover", session_id="s1", hop="stage1",
             old_peer="a", new_peer="b")
    path = tmp_path / "ev.jsonl"
    rec.dump(str(path))
    d = load_dump(str(path))
    assert d["meta"]["pid"] == os.getpid()
    assert d["meta"]["capacity"] == rec.capacity
    assert d["metrics"] is None                # global registry is off
    assert [e["event"] for e in d["events"]] == ["session_start", "failover"]
    first = d["events"][0]
    assert first["session"] == "s1" and first["trace"] == "t1"
    assert first["sub"] == "client" and first["sev"] == "info"
    assert first["fields"] == {"kind": "greedy", "prompt_len": 5}
    # A crash can cut the final write short: the loader must keep every
    # complete line and drop only the torn tail.
    path.write_text(path.read_text(encoding="utf-8") + '{"event": "hop_re',
                    encoding="utf-8")
    d2 = load_dump(str(path))
    assert [e["event"] for e in d2["events"]] == ["session_start", "failover"]


def test_dump_embeds_metrics_snapshot(tmp_path):
    reg = MetricsRegistry(enabled=True)
    reg.counter("client_retries_total", "Retries.").inc(2)
    rec = EventRecorder(enabled=True)
    rec.emit("hop_retry", hop="stage1", attempt=1)
    path = tmp_path / "ev.jsonl"
    rec.dump(str(path), registry=reg)
    d = load_dump(str(path))
    assert d["metrics"] is not None
    assert "client_retries_total 2" in d["metrics"]["exposition"]
    # ...and the doctor flags that counter as an anomaly.
    assert any("client_retries_total=2" in a for a in doctor.anomalies([d]))


# -- crash / signal dumps from real child processes ---------------------------

_CHILD_FATAL = textwrap.dedent(f"""
    import sys
    from {PKG}.telemetry import events
    events.get_recorder().enable()
    events.install_crash_hooks(sys.argv[1])
    events.emit("process_start", mode="serve", pid=0)
    events.emit("hop_retry", hop="stage1", attempt=1)
    raise ValueError("boom in the serving loop")
""")


def test_fatal_exception_leaves_parseable_dump(tmp_path):
    dump = tmp_path / "crash.jsonl"
    proc = subprocess.run(
        [sys.executable, "-c", _CHILD_FATAL, str(dump)],
        cwd=str(REPO), capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    # The wrapped excepthook must still delegate to the original: the
    # traceback reaches stderr exactly as without the black box.
    assert "boom in the serving loop" in proc.stderr
    d = load_dump(str(dump))
    names = [e["event"] for e in d["events"]]
    assert names[0] == "process_start"
    assert names[-1] == "fatal_exception"
    last = d["events"][-1]
    assert last["fields"]["type"] == "ValueError"
    assert "boom in the serving loop" in last["fields"]["message"]
    assert "ValueError" in last["fields"]["trace_tail"]


_CHILD_SIGNAL = textwrap.dedent(f"""
    import sys, time
    from {PKG}.telemetry import events
    events.get_recorder().enable()
    events.install_crash_hooks(sys.argv[1])
    events.emit("process_start", mode="serve", pid=0)
    print("ready", flush=True)
    while True:
        time.sleep(0.05)
""")


def test_sigterm_dumps_then_terminates_with_signal_exit(tmp_path):
    dump = tmp_path / "sig.jsonl"
    proc = subprocess.Popen(
        [sys.executable, "-c", _CHILD_SIGNAL, str(dump)],
        cwd=str(REPO), stdout=subprocess.PIPE, text=True)
    try:
        assert proc.stdout.readline().strip() == "ready"
        proc.send_signal(signal.SIGTERM)
        rc = proc.wait(timeout=30)
    finally:
        proc.kill()
        proc.stdout.close()
    # The handler re-delivers the signal under the default disposition, so
    # supervisors still see a signal death, not a clean exit.
    assert rc == -signal.SIGTERM
    d = load_dump(str(dump))
    names = [e["event"] for e in d["events"]]
    assert names[0] == "process_start"
    assert names[-1] == "signal_dump"
    assert d["events"][-1]["fields"]["signal"] == "SIGTERM"


def test_install_crash_hooks_uninstall_restores_hooks(tmp_path):
    prev = sys.excepthook
    uninstall = events.install_crash_hooks(str(tmp_path / "x.jsonl"))
    assert sys.excepthook is not prev
    uninstall()
    assert sys.excepthook is prev


# -- the dump-events wire verb ------------------------------------------------

def test_dump_events_wire_verb_and_live_scrape():
    import jax

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
    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime.net import (
        TcpStageServer,
        TcpTransport,
    )
    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.scheduling.registry import (
        PlacementRegistry,
    )

    rec = events.get_recorder()
    rec.enable()
    rec.clear()
    cfg = tiny_cfg()
    params = init_params(jax.random.PRNGKey(0), cfg)
    plan = StagePlan.from_splits(cfg.num_layers, parse_splits("4"))
    spec = plan.stages[1]
    ex = StageExecutor(cfg, spec, slice_stage_params(cfg, params, spec),
                       peer_id="ev-s1")
    srv = TcpStageServer(ex, wire_dtype="f32")
    srv.start()
    try:
        snap = PlacementRegistry()
        record = make_server_record("ev-s1", spec)
        record.address = srv.address
        snap.register(record)
        tx = TcpTransport(snap, wire_dtype="f32")
        events.emit("server_join", peer="ev-s1", start_block=4, end_block=8)
        text = tx.events_text("ev-s1")
        lines = [json.loads(ln) for ln in text.splitlines() if ln.strip()]
        assert lines[0]["record"] == "_meta"
        assert "server_join" in [ln.get("event") for ln in lines]

        # The doctor's live-scrape path parses the same text into a stream;
        # an unknown peer degrades to an error-annotated empty stream.
        streams = doctor.scrape_events(tx, ["ev-s1", "ghost"])
        tx.close()
        assert streams[0]["path"] == "live:ev-s1"
        assert "server_join" in [e["event"] for e in streams[0]["events"]]
        assert streams[1]["meta"]["error"]
        assert streams[1]["events"] == []
        report = doctor.diagnose_streams(streams)
        assert "live:ev-s1" in report
    finally:
        rec.disable()
        rec.clear()
        srv.stop()


# -- doctor unit behaviour ----------------------------------------------------

def _mk(name, wall, **kw):
    ev = {"event": name, "wall": wall, "ts": wall}
    for k in ("session", "trace", "fields"):
        if k in kw:
            ev[k] = kw.pop(k)
    assert not kw
    return ev


def test_merge_timeline_orders_across_processes():
    streams = [
        {"meta": {"pid": 1}, "metrics": None,
         "events": [_mk("failover", 10.0), _mk("session_start", 2.0)]},
        {"meta": {"pid": 2}, "metrics": None,
         "events": [_mk("hop_retry", 5.0)]},
    ]
    tl = doctor.merge_timeline(streams)
    assert [(e["event"], e["_src"]) for e in tl] == [
        ("session_start", "pid1"), ("hop_retry", "pid2"),
        ("failover", "pid1")]


def test_failure_chains_collapse_repeats_and_split_on_gaps():
    tl = [
        _mk("transport_timeout", 1.0, session="s", fields={"peer": "p1"}),
        _mk("hop_retry", 1.1, session="s",
            fields={"hop": "stage1", "attempt": 1}),
        _mk("hop_retry", 1.2, session="s",
            fields={"hop": "stage1", "attempt": 1}),
        _mk("failover", 1.3, session="s",
            fields={"hop": "stage1", "old_peer": "p1", "new_peer": "p2"}),
        # 100 s of silence on this session: a NEW chain, not the same story.
        _mk("transport_timeout", 101.0, session="s", fields={"peer": "p2"}),
    ]
    chains = doctor.failure_chains(tl)
    assert len(chains) == 2
    assert chains[0]["chain"] == (
        "p1 timeout -> retry stage1 attempt 1 (x2) "
        "-> failover stage1: p1 -> p2")
    assert chains[1]["chain"] == "p2 timeout"


def test_failure_chains_cover_faults_breaker_and_deadline():
    """The chaos-layer vocabulary: an injected fault triggers a chain, the
    breaker lifecycle rides it as links, and a deadline rejection opens its
    own story — all correlated by session."""
    tl = [
        _mk("fault_injected", 1.0, session="s",
            fields={"kind": "reset_mid_frame", "peer": "p1",
                    "site": "send"}),
        _mk("hop_retry", 1.1, session="s",
            fields={"hop": "stage1", "attempt": 1}),
        _mk("breaker_open", 1.2, session="s",
            fields={"peer": "p1", "backoff_s": 0.5}),
        _mk("breaker_half_open", 1.9, session="s", fields={"peer": "p1"}),
        _mk("breaker_close", 2.0, session="s", fields={"peer": "p1"}),
        # 100 s later, a different session's budget dies on arrival.
        _mk("deadline_rejected", 102.0, session="t",
            fields={"peer": "p2", "budget_s": -0.1}),
        _mk("deadline_expired", 102.1, session="t",
            fields={"over_s": 0.2}),
    ]
    chains = doctor.failure_chains(tl)
    assert len(chains) == 2
    assert chains[0]["sessions"] == {"s"}
    assert chains[0]["chain"] == (
        "injected reset_mid_frame at p1 -> retry stage1 attempt 1 "
        "-> breaker OPEN on p1 (backoff 0.5s) "
        "-> breaker half-open probe of p1 -> breaker closed on p1")
    assert chains[1]["sessions"] == {"t"}
    assert "rejected expired deadline" in chains[1]["chain"]
    assert "deadline expired client-side" in chains[1]["chain"]


def test_replay_costs_sum_per_session():
    tl = [
        _mk("replay_done", 1.0, session="a", fields={"tokens": 100}),
        _mk("replay_done", 2.0, session="a", fields={"tokens": 50}),
        _mk("replay_done", 3.0, session="b", fields={"tokens": 7}),
    ]
    assert doctor.replay_costs(tl) == {"a": 150, "b": 7}


# -- the acceptance e2e -------------------------------------------------------

def test_doctor_reconstructs_kill_failover_replay_rebalance(tmp_path):
    """Kill the pinned stage-2 peer mid-decode in a replicated in-process
    swarm; the flight-recorder dump (plus the replacement server's own
    stream) must let the doctor tell the whole story as ONE chain —
    error -> retry -> failover -> replay(N tokens) -> rebalance — keyed to
    the right session, with the retry's trace id matching a real recorded
    span."""
    telemetry.enable()
    rec = events.get_recorder()
    rec.clear()
    tracer = telemetry.get_tracer()
    tracer.clear()
    try:
        cfg = tiny_cfg()
        client, transport, _, _, _ = build_cluster(
            cfg, splits="2,4,6", replicas=2)
        seen_decode_steps = [0]

        def on_call(peer_id, req):
            if not req.is_prefill and not req.is_replay and "s2" in peer_id:
                seen_decode_steps[0] += 1
                if seen_decode_steps[0] == 3:
                    transport.kill(peer_id)

        transport.on_call = on_call
        client.generate([5, 9, 23, 7, 81], max_new_tokens=8,
                        sampling=SamplingParams(temperature=0.0))
        assert client.recoveries >= 1

        evs = rec.events()
        names = [e.name for e in evs]
        for must in ("session_start", "transport_error", "hop_retry",
                     "peer_failed", "failover", "replay_start",
                     "replay_done", "session_end"):
            assert must in names, f"missing {must} in {sorted(set(names))}"
        sid = next(e.session_id for e in evs if e.name == "session_start")
        retry = next(e for e in evs if e.name == "hop_retry")
        assert retry.session_id == sid
        # Trace correlation: the event stream and the tracer agree on ids.
        assert retry.trace_id
        assert retry.trace_id in {s.trace_id for s in tracer.spans()}
        fo = next(e for e in evs if e.name == "failover")
        replacement = fo.fields["new_peer"]

        p_client = tmp_path / "client.jsonl"
        rec.dump(str(p_client), registry=telemetry.get_registry())
        # In a real deployment the replacement server's process records its
        # own rebalance and dumps separately; model that second per-process
        # stream with a private recorder.
        srv_rec = EventRecorder(enabled=True)
        srv_rec.emit("rebalance_decision", peer=replacement,
                     from_start=4, from_end=6)
        srv_rec.emit("rebalance_done", peer=replacement,
                     start_block=4, end_block=6, seconds=0.01)
        p_server = tmp_path / "server.jsonl"
        srv_rec.dump(str(p_server))

        paths = [str(p_client), str(p_server)]
        streams = doctor.load_dumps(paths)
        chains = doctor.failure_chains(doctor.merge_timeline(streams))
        story = [c for c in chains if sid in c["sessions"]]
        assert story, f"no chain keyed to session {sid}: {chains}"
        chain = story[0]["chain"]
        assert "transport error" in chain or "timeout" in chain
        for step in ("retry", "failover", "replay of", "rebalance"):
            assert step in chain, f"{step!r} missing from chain: {chain}"
        assert retry.trace_id in story[0]["traces"]

        costs = doctor.replay_costs(doctor.merge_timeline(streams))
        assert costs.get(sid, 0) > 0           # the failover was not free

        report = doctor.diagnose(paths)
        assert "failure chains" in report
        assert sid in report
        assert f"{sid}: {costs[sid]} tokens" in report
        assert "rebalance" in report
    finally:
        telemetry.disable()
        rec.clear()
        tracer.clear()


# -- --mode doctor CLI --------------------------------------------------------

def test_doctor_cli_over_dump_files(tmp_path, capsys):
    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.main import (
        main,
    )

    rec = EventRecorder(enabled=True)
    rec.emit("transport_timeout", session_id="sX", peer="p1")
    rec.emit("failover", session_id="sX", hop="stage1",
             old_peer="p1", new_peer="p2")
    rec.emit("replay_done", session_id="sX", peer="p2",
             tokens=7, seconds=0.1)
    path = tmp_path / "d.jsonl"
    rec.dump(str(path))

    rc = main(["--mode", "doctor", "--dumps", str(path)])
    out = capsys.readouterr().out
    assert rc == 0
    assert "failure chains (1):" in out
    assert "p1 timeout" in out and "failover" in out
    assert "sX: 7 tokens" in out

    rc = main(["--mode", "doctor", "--dumps", str(tmp_path / "nope.jsonl")])
    captured = capsys.readouterr()
    assert rc == 1
    assert "not found" in captured.err
