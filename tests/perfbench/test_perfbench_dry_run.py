"""The whole harness end to end at the CPU rehearsal's size: registry,
server inside the shim, load generator, traced window, check, reduction.
Every name it prints says it is a dry run; no device metric comes out."""

import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def test_traced_dry_run_of_the_first_cell(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    env.pop("XLA_FLAGS", None)
    res = subprocess.run(
        [sys.executable, os.path.join(ROOT, "perfbench", "run.py"),
         "--workload", "gpt2xl-chat-sat8", "--seed", str(2 ** 31 + 77),
         "--seconds", "4", "--trace", "1", "--dry-run-cpu",
         "--out", str(tmp_path / "out")],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert res.returncode == 0, res.stdout[-3000:] + res.stderr[-2000:]
    lines = res.stdout.strip().splitlines()
    last = json.loads(lines[-1])
    assert last["cpu_dry_run"] is True and last["correct"] is True
    assert last["failed"] == 0 and last["attempted"] > 0
    assert last["device"]["platform"] == "cpu"
    assert all(k.startswith("cpu_dry_run.") for k in last["metrics"])
    assert "cpu_dry_run.round_fill_sessions" in last["metrics"]
    # device metrics need a device trace: none is printed from a CPU
    assert "cpu_dry_run.device_idle_share" not in last["metrics"]
    assert "cpu_dry_run.step_roofline_share" not in last["metrics"]
    run = json.loads(next(l for l in lines if l.startswith("RUN "))[4:])
    assert run["compiles_in_window"] == 0 and run["stopped_early"] == 0
    assert run["generator_threads_alive"] == 0
    with open(tmp_path / "out" / "records.jsonl") as f:
        recs = [json.loads(l) for l in f]
    assert recs and all(r["error"] is None for r in recs)
    done = [r for r in recs if r["stop"] is not None]
    assert all(sum(n for _, n in r["deliveries"]) == r["budget"]
               for r in done)
