"""The period of a round by its parts, as the benchmark reads it: seven
per-layer metrics of the stage engine over the series the program observes
where a round opens, starts, is answered and rejoined
(`server_round_period_seconds` = the last round's wall time +
`server_round_back_seconds` + `server_round_hold_seconds`;
`server_round_hold_prefill_seconds`; the serving boundary's
`server_reply_leg_seconds` and `server_request_leg_seconds`) and, for the
launch lag of a burst, over the phase profiler's `device` and `dispatch`
phases beside the tick program of the device trace. Five of them have a
`.open` twin for the open loop. Each finds nothing to read in a program
without its series (the parent of the PR that brought them)."""

import json
import os
import subprocess
import sys

import pytest

from perfbench.harness import readers
from perfbench.harness.manifest import Manifest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# metric -> the histogram whose window mean it is, in ms
MEANS = {"round_period_ms": "server_round_period_seconds",
         "round_back_ms": "server_round_back_seconds",
         "round_hold_ms": "server_round_hold_seconds",
         "reply_leg_ms": "server_reply_leg_seconds",
         "request_leg_ms": "server_request_leg_seconds"}
HOLD_PREFILL, LAG = "hold_prefill_ms_per_round", "burst_launch_lag_ms"
SEVEN = sorted(MEANS) + [HOLD_PREFILL, LAG]
CLOSED = ["gpt2xl-chat-sat8", "qwen2-7b-int8-decode16",
          "ouro-2.6b-reason-sat8"]
# what the parent's scrape holds: series of PR 41 and before, none of these
PARENT = {"server_burst_tokens_total": 5.0,
          "server_decode_round_seconds_sum": 9.0,
          "server_decode_round_seconds_count": 90.0,
          "server_round_rejoin_seconds_sum": 0.9,
          "server_round_rejoin_seconds_count": 300.0}


@pytest.fixture(scope="module")
def man():
    return Manifest(ROOT)


def hist(family, total, count):
    return {family + "_sum": float(total), family + "_count": float(count)}


def phase(name, total, count):
    return {f'server_phase_seconds_sum{{phase="{name}"}}': float(total),
            f'server_phase_seconds_count{{phase="{name}"}}': float(count)}


def ctx_of(before, after, **more):
    return {"counters_before": {"p": before}, "counters_after": {"p": after},
            **more}


def trace_of(**mean_s):
    return {"programs": {name: {"mean_s": v} for name, v in mean_s.items()}}


@pytest.mark.parametrize("name", SEVEN)
def test_the_metric_files_say_what_the_benchmark_asks(man, name):
    desc = man.layer_metric(name)
    assert (desc["layer"], desc["moves"], desc["better"], desc["unit"]) == (
        "stage engine", "gap_p75_ms", "lower", "ms")
    # the lag takes the phases' spans (and the tick program off the trace)
    assert desc["source"] == ("program_span" if name == LAG
                              else "program_counter")
    # a stock reader, or a reader file of its own beside the metric
    assert (desc.get("reader") in readers.STOCK) != bool(
        man.layer_reader_file(name))
    (entry,) = [m for m in man.data["per_layer"] if m["name"] == name]
    assert set(entry) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    for key in ("unit", "better", "source", "layer", "moves"):
        assert entry[key] == desc[key]
    assert entry["workloads"] == CLOSED


def test_every_cell_is_asked_for_its_own(man):
    """The closed loops' traced lines are asked for the seven, the open
    loop's for the five twins (the hold's prefill share and the lag have
    none: 17 requests a window; the traced stretch and the window hold
    different rounds). They are the LAST entries: appended, nothing moved."""
    man.validate()
    twins = [n + ".open" for n in sorted(MEANS)]
    for w in man.data["workloads"]:
        asked = {m["name"] for m in man.metrics_for(w["name"], "per_layer")}
        closed = man.traffic(w["traffic"])["kind"] == "closed"
        assert (w["name"] in CLOSED) == closed
        assert set(SEVEN) & asked == (set(SEVEN) if closed else set())
        assert set(twins) & asked == (set() if closed else set(twins))
    tail = [m["name"] for m in man.data["per_layer"]][-12:]
    assert sorted(tail[:7]) == sorted(SEVEN) and sorted(tail[7:]) == twins


@pytest.mark.parametrize("name", sorted(MEANS))
def test_a_mean_is_the_window_s_in_ms_and_its_twin_reads_the_same(man, name):
    fam = MEANS[name]
    ctx = ctx_of({**PARENT, **hist(fam, 1.0, 100)},
                 {**PARENT, **hist(fam, 23.0, 300)})
    assert readers.read_metric(man, name, ctx) == pytest.approx(110.0)
    twin = man.layer_metric(name + ".open")
    assert twin["as"] == name and twin["moves"] == "gap_mean_ms"
    assert readers.read_metric(man, name + ".open", ctx) == \
        readers.read_metric(man, name, ctx)
    # a window without an observation: nothing to read
    same = {**PARENT, **hist(fam, 1.0, 100)}
    assert readers.read_metric(man, name, ctx_of(same, same)) is None


@pytest.mark.parametrize("name", SEVEN + [n + ".open" for n in sorted(MEANS)])
def test_the_parent_s_scrapes_give_nothing_to_read(man, name):
    after = {k: 2 * v for k, v in PARENT.items()}
    for ctx in ({}, ctx_of({}, {}), ctx_of(PARENT, after),
                ctx_of(PARENT, after, trace=trace_of(jit_burst_tick=0.08))):
        assert readers.read_metric(man, name, ctx) is None


@pytest.mark.parametrize("held, rounds, want", [
    ((0.0, 0), (1.0, 10), 0.0),          # rounds, and no prefill in a hold
    ((0.5, 70), (9.0, 100), 5.0),        # 70 prefills held the lock 0.5 s
    ((0.5, 70), (0.0, 0), None),         # no round in the window
], ids=["none-held", "some", "no-round"])
def test_the_hold_s_prefill_share_is_per_round(man, held, rounds, want):
    fam, per = ("server_round_hold_prefill_seconds",
                "server_decode_round_seconds")
    ctx = ctx_of({**hist(fam, 0.25, 30), **hist(per, 2.0, 20)},
                 {**hist(fam, 0.25 + held[0], 30 + held[1]),
                  **hist(per, 2.0 + rounds[0], 20 + rounds[1])})
    got = readers.read_metric(man, HOLD_PREFILL, ctx)
    assert got is None if want is None else got == pytest.approx(want)


def test_the_launch_lag_is_device_less_dispatch_less_the_tick_program(man):
    """100 rounds: 9.5 s from dispatch to ready, 0.2 s of it the enqueue,
    and a burst program of 84 ms on the device: 9 ms a round in which the
    results were awaited and the chip was not running the burst."""
    before = {**phase("device", 1.0, 10), **phase("dispatch", 0.1, 10)}
    after = {**phase("device", 10.5, 110), **phase("dispatch", 0.3, 110)}
    trace = trace_of(jit_prefill=0.007, jit_burst_tick=0.084)
    assert readers.read_metric(man, LAG, ctx_of(before, after, trace=trace)) \
        == pytest.approx(9.0)
    # an untraced run has no phases; a CPU's trace no device program
    assert readers.read_metric(man, LAG, ctx_of({}, {}, trace=trace)) is None
    assert readers.read_metric(man, LAG, ctx_of(before, after)) is None
    assert readers.read_metric(
        man, LAG, ctx_of(before, after, trace=trace_of())) is None
    only = {**phase("device", 10.5, 110)}
    assert readers.read_metric(
        man, LAG, ctx_of({}, only, trace=trace)) is None


def test_the_traced_rehearsal_prints_the_program_s_counters(tmp_path):
    """The whole harness at the CPU rehearsal's size, traced: the six
    metrics that read the program's own series are on the line (the lag
    needs a device program: none on a CPU), and
    `scripts/round_close_report.py` prints the identities from the run's
    two scrapes, period = exec + back + hold within what two means over
    nearly the same rounds can differ by."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    env.pop("XLA_FLAGS", None)
    out = str(tmp_path / "out")
    res = subprocess.run(
        [sys.executable, os.path.join(ROOT, "perfbench", "run.py"),
         "--workload", "qwen2-7b-int8-decode16", "--seed", str(2 ** 31 + 44),
         "--seconds", "4", "--trace", "1", "--dry-run-cpu", "--out", out],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert res.returncode == 0, res.stdout[-3000:] + res.stderr[-2000:]
    last = json.loads(res.stdout.strip().splitlines()[-1])
    assert last["cpu_dry_run"] is True and last["correct"] is True
    got = {k[len("cpu_dry_run."):]: v["value"]
           for k, v in last["metrics"].items()}
    assert set(SEVEN) - set(got) == {LAG}
    assert all(got[n] >= 0.0 for n in SEVEN if n != LAG)
    assert got["reply_leg_ms"] + got["request_leg_ms"] < \
        got["round_rejoin_ms"]
    rep = subprocess.run(
        [sys.executable, os.path.join(ROOT, "scripts",
                                      "round_close_report.py"), out],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert rep.returncode == 0, rep.stderr[-2000:]
    ids = json.loads(rep.stdout.strip().splitlines()[-1])["identities"]
    whole, parts, rest = ids["period = exec + back + hold"]
    assert whole == pytest.approx(got["round_period_ms"])
    assert abs(rest) < 0.05 * whole and len(parts) == 3
    whole, parts, rest = ids["rejoin = reply + away + request"]
    assert rest == pytest.approx(0.0, abs=1e-9) and parts[1] > 0.0
    assert ids["exec = host + lag + ticks + rest"] is None
