#!/usr/bin/env python3
"""The benchmark's one command. From the root of a checkout:

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs ONE cell of BENCHMARK.json: starts the configuration's deployment
through the program's normal entry point (registry + servers, each server
inside harness/serve_shim.py so that its chip can be read), drives it with
the cell's traffic from one load-generator child, measures for --seconds
after warm-up and ramp, stops every process, then checks the program's
outputs against the plain reference (harness/check.py) and prints one JSON
object as the last line. ``--trace 0`` prints the cell's end-to-end
metrics, ``--trace 1`` its per-layer metrics from a run of the same
length whose last stretch (the traffic file's ``trace_seconds``) is traced
and whose servers run with --telemetry --profile_phases.

This parent never imports JAX: one process per chip. Without an accelerator
it exits non-zero and prints no result. ``--dry-run-cpu`` rehearses the
whole path at a tiny preset on the CPU and prints under names that say so;
it is never the driver's command."""

from __future__ import annotations

T_PROCESS_START = __import__("time").time()

import argparse
import json
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

from perfbench.harness import procs, readers  # noqa: E402
from perfbench.harness.manifest import Manifest  # noqa: E402
from perfbench.harness.procs import BenchFailure  # noqa: E402
from perfbench.harness.traffic import least_slot  # noqa: E402

# The program's own launch convention (JAX-free): host-side roles on the
# CPU, chip owners on JAX_PLATFORMS=tpu so that JAX raises where there is
# no chip, chip i of a host pinned with the libtpu variables. In a
# directory without the program this import fails, and so does the run.
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.utils.platform import (  # noqa: E402,E501
    chip_env,
    host_env,
)

PKG = "global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu"
# JAX's persistent compile cache: a fixed place inside the checkout (the
# path is part of the cache's key), unless the machine already names one.
CACHE_DIR = os.path.join(ROOT, ".jax_cache")


def say(*parts) -> None:
    print(*parts, flush=True)


def base_env(dry: bool = False) -> dict:
    """What every child inherits (``dry``: every role on the CPU): the compile cache at a fixed place, with
    JAX told to keep even the programs that compile in under a second (its
    default skips them, and the program builds dozens of small ones per
    prompt length: a warm run would build them all again)."""
    env = dict(os.environ)
    env.pop("BENCH_RUN", None)
    env.setdefault("JAX_COMPILATION_CACHE_DIR", CACHE_DIR)
    env["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
    env["PYTHONUNBUFFERED"] = "1"
    if dry:
        env["JAX_PLATFORMS"] = "cpu"
    return env


def dry_traffic(traffic: dict) -> dict:
    """The same mix at an eighth of the lengths, for the CPU rehearsal."""
    t = dict(traffic)
    t["prompt_lens"] = [max(4, n // 8) for n in traffic["prompt_lens"]]
    t["token_budgets"] = [max(3, n // 8) for n in traffic["token_budgets"]]
    t["sessions"] = min(4, traffic["sessions"])
    if t["route"].get("burst"):
        t["route"] = dict(t["route"], burst=4)
    return t


def server_argv(server: dict, model_args, reg_addr: str, seed: int,
                trace: bool, dry: bool, traffic: dict) -> list:
    """``traffic`` is what the servers will be offered: under ``dry`` the
    rehearsal's mix, and the slot is the shortest that holds IT."""
    args = list(server["args"])
    if dry:
        for flag, val in (("--max_session_len", str(least_slot(traffic))),
                          ("--slots", str(traffic["sessions"])),
                          ("--burst", str(traffic["route"].get("burst", 0)))):
            if flag in args:
                args[args.index(flag) + 1] = val
    args += ["--registry_addr", reg_addr, "--seed", str(seed), *model_args]
    if trace:
        args += ["--telemetry", "--profile_phases"]
    return procs.python_argv("perfbench.harness.serve_shim", *args)


def load_counters(path: str) -> dict:
    out = {}
    if os.path.exists(path):
        with open(path) as f:
            for line in f:
                row = json.loads(line)
                out[row["peer"]] = readers.parse_prometheus(row["text"])
    return out


def run_cell(args, man: Manifest, out_dir: str) -> dict:
    cell = man.workload(args.workload)
    config = man.config(cell["config"])
    traffic = man.traffic(cell["traffic"])
    dry, trace = args.dry_run_cpu, bool(args.trace)
    if dry:
        traffic = dry_traffic(traffic)
    seconds = float(args.seconds)
    dep = config["deployment"]
    model_args = config["dry_run_model_args"] if dry else dep["model_args"]
    # The program takes its weights from --seed as a 31-bit PRNG seed.
    weights_seed = args.seed % (2 ** 31 - 1)

    base = base_env(dry)
    ps = procs.Procs(out_dir, ROOT)
    setup, result = {}, {}
    try:
        port = procs.free_port()
        reg_addr = f"127.0.0.1:{port}"
        t = time.time()
        reg = ps.spawn("registry", procs.python_argv(
            PKG + ".main", "--mode", "registry", "--registry_port",
            str(port)), host_env(base))
        reg.wait_line("REGISTRY_ADDR=", 120)
        setup["registry_s"] = time.time() - t
        servers = []
        for i, srv in enumerate(dep["servers"]):
            env = chip_env(base, srv.get("chip"))
            env.update(srv.get("env", {}))
            servers.append(ps.spawn(
                srv["name"], server_argv(srv, model_args, reg_addr,
                                         weights_seed, trace, dry, traffic),
                env))
        spec = {"registry_addr": reg_addr, "model_args": model_args,
                "weights_seed": weights_seed, "traffic": traffic,
                "seed": args.seed, "seconds": seconds, "out_dir": out_dir,
                "scrape": trace, "serve_timeout_s": 1100,
                "trace_seconds": float(traffic.get("trace_seconds", 4))
                if trace else 0}
        spec_path = os.path.join(out_dir, "load_spec.json")
        with open(spec_path, "w") as f:
            json.dump(spec, f)
        load = ps.spawn("load", procs.python_argv(
            "perfbench.harness.loadgen", spec_path), host_env(base))
        handshakes = []
        for srv in servers:
            line = srv.wait_line("SERVING ", 1100)
            handshakes.append(procs.handshake_fields(line))
            say("  " + line)
        setup["serve_ready_s"] = max(time.time() - s.t_spawn
                                     for s in servers)
        for hs in handshakes:
            if not dry and hs.get("platform") != "tpu":
                raise BenchFailure(f"a server runs on "
                                   f"{hs.get('platform')!r}, not a TPU")
            if hs.get("codec") != "native":
                raise BenchFailure(f"wire codec {hs.get('codec')!r}")
        warm = json.loads(load.wait_line("LOAD warm ", 600)[10:])
        setup["warm_s"] = warm["warm_s"]
        start = json.loads(load.wait_line("LOAD window_start ", 600)[18:])
        setup["ramp_s"] = start["ramp_s"]
        setup["setup_s"] = start["wall"] - T_PROCESS_START
        setup["warm_per_shape_s"] = warm["per_shape_s"]
        trace_dir = os.path.join(out_dir, "trace")
        if trace:
            load.wait_line("LOAD trace_start ", seconds + 120)
            for srv in servers:
                srv.ask(f"trace_start {trace_dir}/{srv.name}")
        end = json.loads(load.wait_line("LOAD window_end ", seconds + 120)[16:])
        traced = [srv.ask("trace_stop", 300) for srv in servers] if trace \
            else []
        done = json.loads(load.wait_line("LOAD done ", 300)[10:])
        rc = ps.wait_exit(load, 120)
        if rc != 0:
            raise BenchFailure(f"load generator exit {rc}:\n"
                               f"{procs.tail(load.log)}")
        shim = [srv.ask("stats") for srv in servers]
        compiles = sum(1 for st in shim for t in st["compile_times"]
                       if start["wall"] <= t < end["wall"])
        for srv in servers:
            ps.stop(srv)
        ps.stop(reg)
        result.update(load=done, setup=setup, compiles_in_window=compiles,
                      shim=shim, traced=traced, trace_dir=trace_dir,
                      config=config, traffic=traffic, cell=cell,
                      handshakes=handshakes, seconds=seconds)
    finally:
        ps.stop_all()
    return result


def run_check(args, man: Manifest, cell: dict, out_dir: str) -> dict:
    """After every server has exited (a chip has one owner)."""
    base = base_env(args.dry_run_cpu)
    ps = procs.Procs(out_dir, ROOT)
    argv = procs.python_argv(
        "perfbench.harness.check",
        "--config", os.path.join(ROOT, man.config_entry(
            cell["config"])["file"]),
        "--traffic", os.path.join(man.dir, "traffic",
                                  cell["traffic"] + ".json"),
        "--seeds", str(args.seed))
    if args.dry_run_cpu:
        argv.append("--dry-run-cpu")
    try:
        chk = ps.spawn("check", argv, chip_env(base))
        ps.wait_exit(chk, 900)
        lines = chk.lines("CHECK ")
        if not lines:
            raise BenchFailure(f"check printed no result:\n"
                               f"{procs.tail(chk.log)}")
        return json.loads(lines[-1][6:])
    finally:
        ps.stop_all()


def reduce_trace(res: dict, out_dir: str) -> dict:
    """The xplane files -> a summary, in a child (reading them needs jax)."""
    base = host_env(base_env())
    ps = procs.Procs(out_dir, ROOT)
    window = min(t["t_stop"] - t["t_start"] for t in res["traced"])
    out = os.path.join(out_dir, "trace_summary.json")
    try:
        child = ps.spawn("trace_reduce", procs.python_argv(
            "perfbench.harness.trace", res["trace_dir"], out, str(window)),
            base)
        if ps.wait_exit(child, 600) != 0:
            raise BenchFailure(f"trace reduction failed:\n"
                               f"{procs.tail(child.log)}")
    finally:
        ps.stop_all()
    shutil.rmtree(res["trace_dir"], ignore_errors=True)   # ~100 MB a run
    with open(out) as f:
        return json.load(f)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--dry-run-cpu", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    if (not args.dry_run_cpu
            and os.environ.get("JAX_PLATFORMS", "").split(",")[0] == "cpu"):
        # The caller hid the accelerator: a measurement never falls back.
        say("FAIL: JAX_PLATFORMS=cpu hides the accelerator; the benchmark "
            "measures on a TPU only (--dry-run-cpu rehearses)")
        return 1
    man = Manifest(ROOT)
    out_dir = os.path.abspath(args.out or os.path.join(
        ROOT, "chiprun_out", "perfbench",
        f"{args.workload}.s{args.seed}.t{args.trace}"))
    os.makedirs(out_dir, exist_ok=True)
    try:
        man.validate()
        res = run_cell(args, man, out_dir)
        check = run_check(args, man, res["cell"], out_dir)
        trace = reduce_trace(res, out_dir) if args.trace else None
    except BenchFailure as exc:
        say(f"FAIL: {exc}")
        return 1
    dry = args.dry_run_cpu
    load, setup, cell = res["load"], res["setup"], res["cell"]
    device = dict(check["device"])
    if not dry and device["platform"] != "tpu":
        say(f"FAIL: JAX found no accelerator ({device})")
        return 1
    if device["count"] < cell["chips"] and not dry:
        say(f"FAIL: the cell asks for {cell['chips']} chips, JAX found "
            f"{device['count']}")
        return 1
    device["memory_peak_bytes"] = max(
        s["device"]["memory_peak_bytes"] for s in res["shim"])
    early_share = (load["stopped_early"] / load["finished"]
                   if load["finished"] else 0.0)
    say("RUN " + json.dumps({
        "workload": args.workload, "seed": args.seed, "trace": args.trace,
        # how much of the profiler's start-to-stop the device trace holds
        **({"trace_recorded_share": trace["extent_s"] / trace["window_s"]}
           if trace else {}),
        "window_s": load["window_s"], "setup_parts_s": setup,
        # the work the run was given: mean rows (prompt + tokens so far) a
        # session held at its deliveries inside the window
        "ctx_rows_in_use": load["ctx_rows_in_use"],
        "gap_samples": load["gap_samples"],
        "gap_p50_ms": load["gap_p50_ms"], "gap_p75_ms": load["gap_p75_ms"],
        "gap_p95_ms": load["gap_p95_ms"], "gap_mean_ms": load["gap_mean_ms"],
        "tokens_per_s": load["tokens_per_s"],
        "ttft_samples": load["ttft_samples"],
        "ttft_mean_ms": load["ttft_mean_ms"],
        "ttft_p95_ms": load["ttft_p95_ms"],
        "requests_finished": load["finished"],
        "stopped_early": load["stopped_early"],
        "failed_by_cause": load["causes"],
        "generator_lateness_p95_ms": load["lateness_p95_ms"],
        "generator_threads_alive": load["threads_alive"],
        "compiles_in_window": res["compiles_in_window"],
        "memory_peak_bytes": device["memory_peak_bytes"],
        "device": {k: device[k] for k in ("platform", "kind", "count")}}))
    check_line = "CHECK " + json.dumps({k: check[k] for k in (
        "logit_rel_rms", "logit_rel_rms_limit", "burst_gap",
        "burst_gap_limit", "burst_gap_max", "logit_rows", "burst_rounds",
        "burst_tokens", "finite", "layers", "sizes", "quant", "episodes",
        "drive", "pass")})
    say(check_line)
    correct = bool(check["pass"] and load["failed"] == 0
                   and early_share <= 0.01
                   and res["compiles_in_window"] == 0
                   and load["threads_alive"] == 0)
    metrics = {}
    if not args.trace:
        values = {"tokens_per_s": load["tokens_per_s"],
                  "gap_p75_ms": load["gap_p75_ms"],
                  "gap_mean_ms": load["gap_mean_ms"],
                  "ttft_mean_ms": load["ttft_mean_ms"],
                  "setup_s": setup["setup_s"]}
        for m in man.metrics_for(args.workload, "end_to_end"):
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    else:
        with open(os.path.join(out_dir, "records.jsonl")) as f:
            records = [json.loads(l) for l in f]
        ctx = {"records": records, "w0": load["w0"], "w1": load["w1"],
               "counters_before": load_counters(
                   os.path.join(out_dir, "metrics_before.jsonl")),
               "counters_after": load_counters(
                   os.path.join(out_dir, "metrics_after.jsonl")),
               "trace": trace, "setup": setup, "hf": res["config"]["hf_config"],
               "config": res["config"],
               "reference_file": man.reference_file(res["config"]),
               "traffic": res["traffic"], "device": device}
        for m in man.metrics_for(args.workload, "per_layer"):
            try:
                v = readers.read_metric(man, m["name"], ctx)
            except KeyError as exc:
                if dry:
                    v = None          # e.g. no peaks for a CPU
                else:
                    raise
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        # busy and idle are shares of the stretch the trace recorded
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = trace["extent_s"]
        say("TRACE " + json.dumps({
            "notes": ctx.get("notes", {}), "devices": trace["devices"],
            "busy_s": trace["busy_s"], "window_s": trace["window_s"],
            "extent_s": trace["extent_s"], "recorded": trace["recorded"],
            "profiler": [{k: t[k] for k in ("t_start", "t_stop", "write_s")}
                         for t in res["traced"]]}))
    if dry:
        metrics = {"cpu_dry_run." + k: v for k, v in metrics.items()}
    line = {"correct": correct, "attempted": load["attempted"],
            "failed": load["failed"], "metrics": metrics, "device": device}
    if args.trace and trace:
        line["breakdown"] = trace["breakdown"]
    if dry:
        line["cpu_dry_run"] = True
    # each number compared beside its limit, last in the line
    line["compared"] = {k: [check[k], check[k + "_limit"]]
                        for k in ("logit_rel_rms", "burst_gap")}
    print(json.dumps(line), flush=True)
    # what was compared beside its limits, as the last of standard error too
    print(check_line, file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
