"""The per-layer metrics that read the stage engine's own spans and counts
(PR 25): each metric file validates, the stock-reader ones read the right
value from hand-made counters, the two program-name readers from a
hand-made ``programs`` summary, and on the CPU rehearsal the counter and
phase metrics print while the two device ones stay out."""

import json
import os
import subprocess
import sys

import pytest

from perfbench.harness import readers
from perfbench.harness.manifest import Manifest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

PHASE_METRICS = {"prefill_lock_wait_ms": "prefill_wait",
                 "prefill_host_ms": "prefill",
                 "first_token_ms": "first_token"}
HISTOGRAM_METRICS = {"round_wait_ms": ("server_queue_wait_seconds", 1e3),
                     "round_exec_ms": ("server_decode_round_seconds", 1e3),
                     "round_slots_held": ("server_batch_slots_held", 1.0)}
TRACE_METRICS = ("prefill_device_ms", "eager_programs_per_prefill")
NEW = (*PHASE_METRICS, *HISTOGRAM_METRICS, *TRACE_METRICS)


def test_new_metrics_are_in_a_valid_manifest_for_both_cells():
    man = Manifest(ROOT)
    man.validate()
    for cell in ("gpt2xl-chat-sat8", "qwen2-7b-int8-decode16"):
        names = [m["name"] for m in man.metrics_for(cell, "per_layer")]
        assert set(NEW) <= set(names)
    by_name = {m["name"]: m for m in man.data["per_layer"]}
    for name in NEW:
        assert by_name[name]["moves"] == "gap_p75_ms"
        # a stock reader, or a reader file of its own beside the metric
        desc = man.layer_metric(name)
        assert (desc.get("reader") in readers.STOCK) != bool(
            man.layer_reader_file(name))


def _counters(series):
    """``{key: (before, after)}`` -> a ctx with one server's two scrapes."""
    return {"counters_before": {"s0": {k: v[0] for k, v in series.items()}},
            "counters_after": {"s0": {k: v[1] for k, v in series.items()}}}


@pytest.mark.parametrize("name", sorted(PHASE_METRICS))
def test_phase_metrics_read_mean_ms_per_occurrence(name):
    phase = PHASE_METRICS[name]
    ctx = _counters({
        f'server_phase_seconds_sum{{phase="{phase}"}}': (10.0, 13.0),
        f'server_phase_seconds_count{{phase="{phase}"}}': (20.0, 24.0),
        # another phase's series never leaks in
        'server_phase_seconds_sum{phase="device"}': (0.0, 99.0),
        'server_phase_seconds_count{phase="device"}': (0.0, 9.0)})
    assert readers.read_metric(Manifest(ROOT), name, ctx) == pytest.approx(
        750.0)
    # a server without --profile_phases has no such series: left out
    assert readers.read_metric(Manifest(ROOT), name, _counters({})) is None


@pytest.mark.parametrize("name", sorted(HISTOGRAM_METRICS))
def test_histogram_metrics_read_the_window_mean(name):
    family, scale = HISTOGRAM_METRICS[name]
    ctx = _counters({family + "_sum": (4.0, 10.0),
                     family + "_count": (2.0, 10.0)})
    assert readers.read_metric(Manifest(ROOT), name, ctx) == pytest.approx(
        0.75 * scale)
    # the parent program has no server_batch_slots_held: nothing to read
    assert readers.read_metric(Manifest(ROOT), name, _counters({})) is None


def _prog(count, seconds):
    return {"count": count, "seconds": seconds, "whole": count,
            "whole_seconds": seconds, "mean_s": seconds / count}


NAMED = {"jit_burst_tick(111)": _prog(5, 4.0),
         "jit_prefill(222)": _prog(2, 0.004),
         "jit_prefill(333)": _prog(1, 0.007),          # another bucket
         "jit_prefill_suffix(444)": _prog(1, 0.001),
         "jit_dot_general(555)": _prog(4, 0.002),
         "jit__pad(666)": _prog(4, 0.001),
         "jit_fn(777)": _prog(12, 0.001)}              # someone's eager fn
NO_PREFILL = {k: v for k, v in NAMED.items() if "prefill" not in k}
UNNAMED = {"jit_fn(111)": _prog(5, 4.0), "jit_fn(222)": _prog(3, 0.01)}


def _traced(programs, prefills=25.0, stretch=4.0):
    """A traced run's ctx: a 50 s window with ``prefills`` prefills (the
    phase profiler's count), ``stretch`` seconds of it traced."""
    ctx = _counters({'server_phase_seconds_count{phase="prefill"}':
                     (5.0, 5.0 + prefills)} if prefills else {})
    ctx.update(w0=100.0, w1=150.0,
               trace={"window_s": stretch, "programs": programs})
    return ctx


@pytest.mark.parametrize("name, ctx, want", [
    # 25 prefills in 50 s, 4 s traced: 2 prefills expected in the stretch
    ("prefill_device_ms", _traced(NAMED), 1e3 * 0.012 / 2),
    ("eager_programs_per_prefill", _traced(NAMED), 20 / 2),
    # a stretch no prefill fell into still reads (one run in ten, chip)
    ("prefill_device_ms", _traced(NO_PREFILL), 0.0),
    ("eager_programs_per_prefill", _traced(NO_PREFILL), 20 / 2),
    # the parent: programs without names, no prefill phase either
    ("prefill_device_ms", _traced(UNNAMED, prefills=0), None),
    ("eager_programs_per_prefill", _traced(UNNAMED, prefills=0), None),
    ("prefill_device_ms", _traced(UNNAMED), None),
    ("eager_programs_per_prefill", _traced(NAMED, prefills=0), None),
])
def test_program_name_readers(name, ctx, want):
    got = readers.read_metric(Manifest(ROOT), name, ctx)
    assert got == (pytest.approx(want) if want is not None else None)


@pytest.mark.parametrize("name", TRACE_METRICS)
@pytest.mark.parametrize("trace", [None, {}, {"window_s": 4.0,
                                              "programs": {}}])
def test_program_name_readers_without_a_device_trace(name, trace):
    ctx = _traced({})
    ctx["trace"] = trace
    assert readers.read_metric(Manifest(ROOT), name, ctx) is None


def test_dry_run_prints_the_span_metrics_and_no_device_ones(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    env.pop("XLA_FLAGS", None)
    res = subprocess.run(
        [sys.executable, os.path.join(ROOT, "perfbench", "run.py"),
         "--workload", "gpt2xl-chat-sat8", "--seed", str(2 ** 31 + 2525),
         "--seconds", "5", "--trace", "1", "--dry-run-cpu",
         "--out", str(tmp_path / "out")],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert res.returncode == 0, res.stdout[-3000:] + res.stderr[-2000:]
    last = json.loads(res.stdout.strip().splitlines()[-1])
    assert last["cpu_dry_run"] is True and last["correct"] is True
    metrics = last["metrics"]
    for name in (*PHASE_METRICS, *HISTOGRAM_METRICS):
        assert metrics["cpu_dry_run." + name]["value"] >= 0.0, name
    for name in TRACE_METRICS:
        assert "cpu_dry_run." + name not in metrics
    held = metrics["cpu_dry_run.round_slots_held"]["value"]
    fill = metrics["cpu_dry_run.round_fill_sessions"]["value"]
    assert 1.0 <= fill <= held <= 4.0     # the rehearsal runs 4 sessions
