"""BENCHMARK.json and its data files: the contract's rules a file can
break, and that a new cell, configuration, mix or metric is new files
only — shown by loading a throw-away example of each from a temporary
directory."""

import copy
import json
import os
import shutil
import subprocess
import sys

import pytest

from perfbench.harness import readers
from perfbench.harness.manifest import Manifest, ManifestError

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def test_the_committed_manifest_is_valid():
    man = Manifest(ROOT)
    man.validate()
    d = man.data
    assert d["command"][:2] == ["python3", "perfbench/run.py"]
    assert sum(1 for w in d["workloads"] if w["chips"] == 4) <= 1
    for w in d["workloads"]:
        e2e = {m["name"] for m in man.metrics_for(w["name"], "end_to_end")}
        # the closed loops are decided by the 75th percentile: one full
        # round a token; the open loop's window holds ~130 gaps on a
        # lattice of block counts, and is decided by their mean
        gap = ("gap_p75_ms" if man.traffic(w["traffic"])["kind"] == "closed"
               else "gap_mean_ms")
        assert {gap, "setup_s"} <= e2e
        assert not {"gap_p75_ms", "gap_mean_ms"} - {gap} & e2e
        for m in man.metrics_for(w["name"], "per_layer"):
            assert m["moves"] in e2e
        cfg = man.config(w["config"])
        assert cfg["source"] == man.config_entry(w["config"])["source"]
        for key in ("reduced", "assumed", "deployment", "hf_config",
                    "check"):
            assert key in cfg
        tr = man.traffic(w["traffic"])
        assert tr["users"] and tr["sessions"] <= int(
            cfg["deployment"]["servers"][0]["args"][
                cfg["deployment"]["servers"][0]["args"].index("--slots") + 1])
    assert len(json.dumps(d)) < 64 * 1024


def split_names():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return sorted(m["name"] for m in json.load(f)["per_layer"]
                      if m["name"].endswith(".open"))


@pytest.mark.parametrize("name", split_names())
def test_a_split_reads_as_the_metric_it_splits(name):
    """One quantity, two entries: `<metric>` moves `gap_p75_ms` in the
    closed loops, `<metric>.open` moves `gap_mean_ms` in the open loop. The
    split's file names the other (`as`) and brings no reader or params of
    its own, so both read the same number from the same run."""
    man = Manifest(ROOT)
    by_name = {m["name"]: m for m in man.data["per_layer"]}
    split, base = by_name[name], by_name[name[:-len(".open")]]
    desc = man.layer_metric(name)
    assert desc["as"] == base["name"]
    assert not {"reader", "params"} & set(desc)
    assert man.layer_reader_file(name) is None
    for key in ("unit", "better", "source", "layer"):
        assert split[key] == base[key] == desc[key]
    assert (split["moves"], base["moves"]) == ("gap_mean_ms", "gap_p75_ms")
    assert split["workloads"] == ["gpt2xl-chat-open"]
    assert "gpt2xl-chat-open" not in base.get("workloads", ())
    # a keyless metric is every cell's that reports what it moves
    for w in man.data["workloads"]:
        asked = {m["name"] for m in man.metrics_for(w["name"], "per_layer")}
        assert (name in asked) == (w["name"] == "gpt2xl-chat-open")
        assert not (base["name"] in asked and name in asked)


def test_a_split_and_its_metric_read_one_number():
    man = Manifest(ROOT)
    fam = "server_round_rejoin_seconds"
    ctx = {"counters_before": {"p": {fam + "_sum": 1.0, fam + "_count": 100.0}},
           "counters_after": {"p": {fam + "_sum": 1.9, fam + "_count": 300.0}}}
    assert readers.read_metric(man, "round_rejoin_ms.open", ctx) == \
        readers.read_metric(man, "round_rejoin_ms", ctx) == pytest.approx(4.5)
    rows = {"counters_before": {"p": {}}, "counters_after": {"p": {
        "server_attn_rows_read_total": 640.0,
        "server_attn_rows_span_total": 1024.0}}}
    assert readers.read_metric(man, "attn_rows_read_share.open", rows) == \
        readers.read_metric(man, "attn_rows_read_share", rows) == \
        pytest.approx(62.5)
    assert readers.read_metric(man, "attn_rows_read_share.open", {}) is None


def test_no_ttft_statistic_decides():
    # PERF.md section 2: TTFT failed the promotion test in every cell
    names = [m["name"] for m in Manifest(ROOT).data["end_to_end"]]
    assert not [n for n in names if "ttft" in n]


@pytest.fixture
def sandbox(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "perfbench"), tmp_path / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    return tmp_path


def rewrite(root, fn):
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        d = json.load(f)
    fn(d)
    with open(path, "w") as f:
        json.dump(d, f)


def test_new_cell_config_mix_and_metric_are_new_files_only(sandbox):
    before = {}
    for base, _, files in os.walk(sandbox / "perfbench"):
        for name in files:
            p = os.path.join(base, name)
            before[p] = open(p, "rb").read()
    man = Manifest(sandbox)
    cfg = man.config("gpt2-xl")
    cfg["name"] = "gpt2-xl-b"
    cfg["source"] = "https://example.org/another"
    (sandbox / "perfbench/configs/gpt2-xl-b.json").write_text(json.dumps(cfg))
    mix = dict(man.traffic("chat-sat8"), kind="open", rate_rps=2.5,
               arrival="poisson")
    (sandbox / "perfbench/traffic/chat-open.json").write_text(json.dumps(mix))
    (sandbox / "perfbench/layer_metrics/first_gap_ms.json").write_text(
        json.dumps({"layer": "client", "unit": "ms", "better": "lower",
                    "source": "host_clock", "moves": "gap_mean_ms",
                    "params": {"scale": 1000.0}}))
    (sandbox / "perfbench/layer_metrics/first_gap_ms.py").write_text(
        "def read(ctx, params):\n"
        "    r = ctx['records'][0]['deliveries']\n"
        "    return (r[1][0] - r[0][0]) * params['scale']\n")

    def add(d):
        d["configs"].append({"name": "gpt2-xl-b", "source": cfg["source"],
                             "file": "perfbench/configs/gpt2-xl-b.json",
                             "reduced": [], "why": "throw-away"})
        d["workloads"].append({"name": "b-open", "config": "gpt2-xl-b",
                               "traffic": "chat-open", "chips": 1,
                               "why": "throw-away open-loop cell"})
        next(m for m in d["end_to_end"] if m["name"] == "gap_mean_ms")[
            "workloads"].append("b-open")
        d["per_layer"].append({"name": "first_gap_ms", "unit": "ms",
                               "better": "lower", "source": "host_clock",
                               "layer": "client", "moves": "gap_mean_ms",
                               "workloads": ["b-open"]})

    rewrite(sandbox, add)
    man = Manifest(sandbox)
    man.validate()
    assert man.traffic(man.workload("b-open")["traffic"])["kind"] == "open"
    names = [m["name"] for m in man.metrics_for("b-open", "per_layer")]
    assert "first_gap_ms" in names and "int8_kernel_roofline_share" not in names
    assert "first_gap_ms" not in [
        m["name"] for m in man.metrics_for("gpt2xl-chat-sat8", "per_layer")]
    ctx = {"records": [{"deliveries": [[1.0, 1], [1.25, 16]]}],
           "config": {"name": "gpt2-xl-b"}}
    assert readers.read_metric(man, "first_gap_ms", ctx) == 250.0
    for p, body in before.items():         # nothing that existed changed
        assert open(p, "rb").read() == body


BREAKS = {
    "unit with a space": lambda d: d["end_to_end"][0].update(
        unit="tokens per s"),
    "name with a slash": lambda d: d["per_layer"][0].update(name="a/b"),
    "moves names no end-to-end metric": lambda d: d["per_layer"][0].update(
        moves="ttft_p95_ms"),
    "bound over 0.1": lambda d: d["end_to_end"][1].update(bound=0.2),
    "moves a metric the cell does not report": lambda d: next(
        m for m in d["per_layer"] if m["name"] == "client_tokens_per_s"
    ).update(moves="tokens_per_s"),
    "two four-chip cells": lambda d: [w.update(chips=4)
                                      for w in d["workloads"]],
    "unknown key on a metric": lambda d: d["per_layer"][0].update(why="x"),
    "reduced names a width": lambda d: d["configs"][1].update(
        reduced=["hidden_size"]),
    "no setup_s": lambda d: d["end_to_end"].pop(),
    "the same pair twice": lambda d: d["workloads"].append(
        dict(d["workloads"][0], name="again")),
    "run_seconds too long": lambda d: d.update(run_seconds=52),
    "config outside paths": lambda d: d["configs"][0].update(
        file="tests/x.json"),
    "unused config": lambda d: d["workloads"].pop(),
    "metric file disagrees": lambda d: d["per_layer"][0].update(unit="s"),
}


@pytest.mark.parametrize("what", sorted(BREAKS))
def test_a_broken_manifest_is_refused(sandbox, what):
    rewrite(sandbox, BREAKS[what])
    with pytest.raises(ManifestError):
        Manifest(sandbox).validate()


def test_stock_readers_on_hand_made_counters():
    text_a = ('server_batch_fill_sessions_sum 10\n'
              'server_batch_fill_sessions_count 2\n'
              'server_phase_seconds_sum{phase="burst_build"} 1.0\n'
              'server_phase_seconds_count{phase="burst_build"} 2\n'
              'server_phase_seconds_sum{phase="dispatch"} 0.5\n'
              'server_phase_seconds_count{phase="dispatch"} 2\n'
              'server_phase_seconds_sum{phase="readback"} 0.5\n'
              'server_phase_seconds_count{phase="readback"} 2\n# c\n')
    text_b = text_a.replace(" 10\n", " 80\n").replace(" 2\n", " 12\n") \
        .replace(" 1.0\n", " 1.5\n").replace(" 0.5\n", " 0.75\n")
    ctx = {"counters_before": {"p": readers.parse_prometheus(text_a)},
           "counters_after": {"p": readers.parse_prometheus(text_b)},
           "traffic": {"route": {"burst": 16}},
           "trace": {"busy_s": 8.0, "window_s": 12.5, "extent_s": 10.0,
                     "programs": {
               "jit_fn(1)": {"count": 5, "whole": 3, "mean_s": 0.8},
               "jit_fn(2)": {"count": 9, "whole": 9, "mean_s": 0.02},
               "jit_fn(3)": {"count": 1, "whole": 0, "mean_s": None}}}}
    assert readers.histogram_mean(
        ctx, {"family": "server_batch_fill_sessions"}) == 7.0
    assert readers.phase_sum_per_round(ctx, {
        "phases": ["burst_build", "dispatch", "readback"],
        "scale": 1000.0}) == pytest.approx(100.0)
    # the burst program is the longest-running one: 0.8 s over 16 ticks
    assert readers.trace_ms_per_tick(ctx, {}) == pytest.approx(50.0)
    # a route without bursts has no burst program: nothing to read
    per_step = dict(ctx, traffic={"route": {"burst": 0}})
    assert readers.trace_ms_per_tick(per_step, {}) is None
    assert readers.trace_ms_per_tick({"trace": None, "traffic": {
        "route": {}}}, {}) is None
    # over the stretch the trace recorded, not the profiler's 12.5 s
    assert readers.trace_idle_share(ctx, {}) == pytest.approx(20.0)
    assert readers.trace_idle_share({"trace": None}, {}) is None
    recs = [{"sent": 1.0, "due": None, "error": None, "prompt_len": 8,
             "deliveries": [[2.0, 1], [3.0, 16], [5.0, 16], [6.0, 4]]}]
    rctx = {"records": recs, "w0": 0.0, "w1": 10.0, "traffic": {}}
    assert readers.records_stat(rctx, {"stat": "tokens_per_s"}) == 3.7
    assert readers.records_stat(rctx, {"stat": "gap_p95"}) == 250.0
    assert readers.records_stat(rctx, {"stat": "ttft_mean"}) == 1000.0
    with pytest.raises(ValueError):
        readers.records_stat(rctx, {"stat": "median"})
    assert readers.histogram_mean({}, {"family": "x"}) is None


@pytest.mark.parametrize("config,nbytes", [("gpt2-xl", 2),
                                           ("qwen2-7b-int8", 1)])
def test_step_roofline_takes_the_weight_width_from_the_configuration(
        config, nbytes):
    """A later quantised configuration states its own width in its own
    file; the shared metric file names no configuration."""
    from perfbench.harness import roofline

    man = Manifest(ROOT)
    cfg = man.config(config)
    assert cfg["weight_bytes"] == nbytes
    assert "params_by_config" not in man.layer_metric("step_roofline_share")
    text = ("server_batch_fill_sessions_sum 40\n"
            "server_batch_fill_sessions_count 10\n")
    recs = [{"sent": 1.0, "due": None, "error": None, "prompt_len": 100,
             "deliveries": [[2.0, 1], [3.0, 16]]}]
    ctx = {"counters_before": {}, "counters_after": {
               "p": readers.parse_prometheus(text)},
           "records": recs, "w0": 0.0, "w1": 10.0,
           "traffic": {"route": {"burst": 16}}, "config": cfg,
           "hf": cfg["hf_config"], "device": {"kind": "TPU v5 lite"},
           "trace": {"programs": {"jit_fn(1)": {"whole": 3, "mean_s": 0.8}}}}
    share = readers.read_metric(man, "step_roofline_share", ctx)
    hf = cfg["hf_config"]
    rows = readers.stats.ctx_rows_in_use(recs, 0.0, 10.0)
    cost = roofline.tick_cost(hf, layers=roofline.shape_of(hf)["layers"],
                              sessions=4.0, kv_rows=rows, weight_bytes=nbytes)
    least, _ = roofline.roofline_s(cost, "TPU v5 lite")
    assert share == pytest.approx(100.0 * least / 0.05)
    assert 0 < share < 100
    with pytest.raises(KeyError):       # no width stated: no silent default
        readers.read_metric(man, "step_roofline_share", dict(
            ctx, config={k: v for k, v in cfg.items()
                         if k != "weight_bytes"}))


def run_py(cwd, *extra, env=None):
    e = dict(os.environ, JAX_PLATFORMS="cpu")
    e.update(env or {})
    return subprocess.run(
        [sys.executable, os.path.join(cwd, "perfbench", "run.py"),
         "--workload", "gpt2xl-chat-sat8", "--seed", "3", "--seconds", "1",
         "--trace", "0", "--out", os.path.join(cwd, "out"), *extra],
        cwd=cwd, env=e, capture_output=True, text=True, timeout=120)


def test_a_run_without_an_accelerator_prints_no_result():
    res = run_py(ROOT)
    assert res.returncode != 0
    assert '"correct"' not in res.stdout and "FAIL" in res.stdout


def test_a_directory_with_only_the_benchmark_fails(sandbox):
    # BENCHMARK.json and the files under paths alone: no program to run.
    res = run_py(str(sandbox), env={"JAX_PLATFORMS": ""})
    assert res.returncode != 0
    assert '"correct"' not in res.stdout


def test_no_topology_or_backend_at_import():
    code = ("import sys; sys.path.insert(0, %r)\n"
            "import perfbench.run, perfbench.harness.loadgen\n"
            "import perfbench.harness.check, perfbench.harness.trace\n"
            "import perfbench.harness.serve_shim\n"
            "import perfbench.harness.reference\n"
            "import jax._src.xla_bridge as xb\n"
            "assert not xb._backends, xb._backends\n" % ROOT)
    res = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=120,
                         env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert res.returncode == 0, res.stderr[-2000:]
