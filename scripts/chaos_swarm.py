#!/usr/bin/env python
"""Chaos soak against a REAL multi-process swarm: registry + stage servers
launched as separate OS processes (every role started with
--allow_fault_injection --telemetry), then ``--mode chaos --chaos_attach``
drives the soak over the wire — clean run, seeded FaultPlan installation on
every side, faulty run, token-equality check, and the doctor cross-check
against the servers' scraped event rings.

This is the full-fidelity variant of the in-process soak that runs in
tier-1 (tests/test_faults.py): here a mid-frame reset really crosses a
process boundary and the doctor really merges rings from N processes.

Usage (tiny random-weight gpt2 by default)::

    python scripts/chaos_swarm.py --model gpt2 --splits 4,8 \
        --prompt "hello" --max_new_tokens 10 --seed 0

``--kill_registries`` runs the total-registry-loss drill instead: a
primary + standby registry and a gossiping stage swarm come up as real OS
processes, a client starts generating, and BOTH registries get SIGKILLed
mid-run. The in-flight client must finish (rc=0), and a SECOND, freshly
started client — seeds still dead, armed only with the shared
``--peers_cache`` file — must bootstrap through a stage server's gossip
mirror and generate too. This is the multi-process twin of the in-process
``--mode chaos --chaos_scenario registry_loss`` soak.
"""

import argparse
import os
import signal
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
MAIN = "global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.main"


from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.utils.platform import (  # noqa: E402
    chip_env,
    host_env,
)


def registry_list(addr):
    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime.net import (
        RemoteRegistry,
    )

    return RemoteRegistry(addr).live_servers()


def _teardown(procs):
    for proc, log in procs:
        if proc.poll() is None:
            proc.send_signal(signal.SIGINT)
    for proc, log in procs:
        try:
            proc.wait(timeout=10)
        except subprocess.TimeoutExpired:
            proc.kill()
        log.close()


def kill_registries_drill(args, env, spawn, procs, common, log_dir):
    """Total-registry-loss drill, multi-process edition: SIGKILL every seed
    under a live client, then bootstrap a brand-new client through a stage
    server's gossip mirror using only the shared --peers_cache file."""
    num_stages = len(args.splits.split(","))
    seeds = (f"127.0.0.1:{args.registry_port},"
             f"127.0.0.1:{args.registry_port + 1}")
    # Shared by every role: the serve processes' registry reads keep it
    # fresh, so a client started AFTER the massacre still finds live
    # stage-server addresses in it (writes are atomic os.replace).
    peers_cache = os.path.join(log_dir, "peers_cache.json")
    reg_procs = []
    try:
        for k, port in enumerate((args.registry_port,
                                  args.registry_port + 1)):
            reg_procs.append(spawn(
                ["--mode", "registry", "--registry_port", str(port)],
                f"rl_registry{k}", chip=None))
        deadline = time.time() + 30
        while time.time() < deadline:
            try:
                registry_list(seeds)
                break
            except OSError:
                time.sleep(0.3)
        else:
            raise SystemExit("registries did not come up")
        print(f"registries up at {seeds}")

        for i in range(1, num_stages + 1):
            spawn(common + ["--mode", "serve", "--splits", args.splits,
                            "--registry_addr", seeds, "--stage", str(i),
                            "--peers_cache", peers_cache],
                  f"rl_stage{i}", chip=i)
        deadline = time.time() + args.startup_timeout
        while time.time() < deadline:
            try:
                recs = [r for r in registry_list(seeds)
                        if str(r.state) == "online"]
            except OSError:
                recs = []
            if len(recs) >= num_stages:
                break
            for proc, _ in procs:
                if proc.poll() is not None:
                    raise SystemExit(
                        f"a swarm process exited early (rc={proc.returncode})"
                        " — see logs in " + log_dir)
            time.sleep(1.0)
        else:
            raise SystemExit("servers did not register in time — "
                             "see logs in " + log_dir)
        print(f"{num_stages} stage servers registered; waiting for the "
              "peers cache")
        # The serve processes' first gossip tick does a registry list read,
        # which persists the cache — the fresh client's only map once the
        # seeds are gone. Don't pull the trigger before it exists.
        deadline = time.time() + 30
        while time.time() < deadline and not os.path.exists(peers_cache):
            time.sleep(0.3)
        if not os.path.exists(peers_cache):
            raise SystemExit("peers cache never written — see logs in "
                             + log_dir)
        print("peers cache written; starting client #1")

        client_cmd = (
            [sys.executable, "-m", MAIN] + common
            + ["--mode", "client", "--splits", args.splits,
               "--registry_addr", seeds, "--peers_cache", peers_cache,
               "--prompt", args.prompt,
               "--max_new_tokens", str(args.max_new_tokens),
               "--seed", str(args.seed)])
        log1 = open(os.path.join(log_dir, "rl_client1.log"), "w")
        c1 = subprocess.Popen(client_cmd, cwd=REPO, env=env,
                              stdout=log1, stderr=subprocess.STDOUT)
        procs.append((c1, log1))
        time.sleep(args.kill_after)
        for rp in reg_procs:
            if rp.poll() is None:
                rp.kill()       # SIGKILL: no goodbye frame, no state flush
        print("SIGKILLed the primary AND the standby registry")
        rc1 = c1.wait(timeout=args.startup_timeout)
        if rc1 != 0:
            print(f"FAIL: in-flight client exited rc={rc1} — "
                  f"logs in {log_dir}")
            return 1
        print("in-flight client finished across total seed loss (rc=0)")

        # Fresh client: empty snapshot, every seed dead — only the cache
        # file and the gossip mirrors stand between it and "no live servers".
        rc2 = subprocess.call(client_cmd, cwd=REPO, env=env)
        if rc2 != 0:
            print(f"FAIL: fresh bootstrap client exited rc={rc2} — "
                  f"logs in {log_dir}")
            return 1
        print("REGISTRY-LOSS DRILL PASS: fresh client bootstrapped through "
              "a stage server's gossip mirror")
        return 0
    finally:
        _teardown(procs)


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--model", default="gpt2")
    p.add_argument("--checkpoint", default=None)
    p.add_argument("--splits", default="4,8")
    p.add_argument("--prompt", default="hello world")
    p.add_argument("--max_new_tokens", type=int, default=10)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--registry_port", type=int, default=31345)
    p.add_argument("--startup_timeout", type=float, default=600.0)
    p.add_argument("--kill_registries", action="store_true",
                   help="registry-loss drill: primary+standby seeds, "
                        "SIGKILL both mid-generation, in-flight client "
                        "must finish and a fresh client must bootstrap "
                        "off a stage server's gossip mirror")
    p.add_argument("--kill_after", type=float, default=2.0,
                   help="--kill_registries: seconds after the first "
                        "client starts before the seeds are killed")
    args = p.parse_args()

    num_stages = len(args.splits.split(","))  # stages 1..N (0 = client)
    reg_addr = f"127.0.0.1:{args.registry_port}"
    # One process per chip: the registry is host-side, stage server i owns
    # chip i, and the client (it runs stage 0) owns chip 0. The caller
    # chooses the platform — JAX_PLATFORMS=cpu keeps every role on the CPU.
    env = chip_env(os.environ, 0)
    procs = []

    log_dir = tempfile.mkdtemp(prefix="chaos_swarm_")

    def spawn(role_args, log_name, chip):
        child = (host_env(os.environ) if chip is None
                 else chip_env(os.environ, chip))
        log = open(os.path.join(log_dir, f"{log_name}.log"), "w")
        proc = subprocess.Popen(
            [sys.executable, "-m", MAIN] + role_args,
            cwd=REPO, env=child, stdout=log, stderr=subprocess.STDOUT,
        )
        procs.append((proc, log))
        return proc

    common = ["--model", args.model]
    if args.checkpoint:
        common += ["--checkpoint", args.checkpoint]

    if args.kill_registries:
        return kill_registries_drill(args, env, spawn, procs, common, log_dir)

    try:
        # Every role consents to chaos: the `fault` admin verb is refused
        # unless the process opts in, and --telemetry arms the event rings
        # the doctor scrapes afterwards.
        spawn(["--mode", "registry",
               "--registry_port", str(args.registry_port),
               "--allow_fault_injection", "--telemetry"], "chaos_registry",
              chip=None)
        deadline = time.time() + 30
        while time.time() < deadline:
            try:
                registry_list(reg_addr)
                break
            except OSError:
                time.sleep(0.3)
        else:
            raise SystemExit("registry did not come up")
        print(f"registry up at {reg_addr}")

        for i in range(1, num_stages + 1):
            spawn(common + ["--mode", "serve", "--splits", args.splits,
                            "--registry_addr", reg_addr, "--stage", str(i),
                            "--allow_fault_injection", "--telemetry"],
                  f"chaos_stage{i}", chip=i)

        deadline = time.time() + args.startup_timeout
        while time.time() < deadline:
            try:
                recs = [r for r in registry_list(reg_addr)
                        if str(r.state) == "online"]
            except OSError:
                recs = []
            if len(recs) >= num_stages:
                break
            for proc, _ in procs:
                if proc.poll() is not None:
                    raise SystemExit(
                        f"a swarm process exited early (rc={proc.returncode})"
                        " — see logs in " + log_dir)
            time.sleep(1.0)
        else:
            raise SystemExit("servers did not register in time — "
                             "see logs in " + log_dir)
        print(f"{num_stages} stage servers registered; starting chaos soak")

        rc = subprocess.call(
            [sys.executable, "-m", MAIN] + common
            + ["--mode", "chaos", "--chaos_attach", "--splits", args.splits,
               "--registry_addr", reg_addr, "--prompt", args.prompt,
               "--max_new_tokens", str(args.max_new_tokens),
               "--seed", str(args.seed), "--telemetry"],
            cwd=REPO, env=env)
        return rc
    finally:
        _teardown(procs)


if __name__ == "__main__":
    sys.exit(main())
