#!/usr/bin/env python
"""Launch a REAL multi-process swarm on one host: registry + stage servers +
client, each its own OS process talking framed TCP.

One process per chip, and the launcher itself never touches JAX: the
registry is a host-side role (``JAX_PLATFORMS=cpu``), stage server i owns
chip i and the client — it runs stage 0 — owns chip 0
(``utils.platform.chip_env``: ``JAX_PLATFORMS=tpu`` plus the libtpu pin).
The caller chooses the platform: with ``JAX_PLATFORMS=cpu`` in the
environment every role stays on the CPU. Each spawn prints the variables
it was given; logs go to ``--log_dir``.

The reference's ``scripts/run_all.py`` (component 17) did this with log
scraping as the readiness signal ("handlers registered" regexes,
run_all.py:33-72) and a human as the assertion engine. Here readiness is a
registry poll — each server's record must be live before the client starts —
and the generation result prints at the end.

Usage (tiny random-weight gpt2 by default)::

    python scripts/run_swarm.py --model gpt2 --splits 4,8 \
        --prompt "hello" --max_new_tokens 8
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

# What a spawn line shows of the child's environment.
_SHOWN = ("JAX_PLATFORMS", "TPU_VISIBLE_CHIPS", "TPU_CHIPS_PER_PROCESS_BOUNDS",
          "TPU_PROCESS_BOUNDS", "XLA_FLAGS")


def registry_list(addr):
    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime.net import (
        RemoteRegistry,
    )

    return RemoteRegistry(addr).live_servers()


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--model", default="gpt2")
    p.add_argument("--checkpoint", default=None)
    p.add_argument("--splits", default="4,8")
    p.add_argument("--prompt", default="hello world")
    p.add_argument("--max_new_tokens", type=int, default=8)
    p.add_argument("--temperature", type=float, default=0.0)
    p.add_argument("--registry_port", type=int, default=31335)
    p.add_argument("--startup_timeout", type=float, default=600.0)
    p.add_argument("--lb", action="store_true",
                   help="elastic load-balancing servers (spans chosen from "
                        "swarm coverage) instead of fixed --splits spans")
    p.add_argument("--num_servers", type=int, default=2,
                   help="--lb: how many elastic servers to spawn")
    p.add_argument("--num_blocks", type=int, default=None,
                   help="--lb: blocks per elastic server")
    p.add_argument("--batched", action="store_true",
                   help="fixed-split servers use the continuous-batching "
                        "engine (--mode serve --batched)")
    p.add_argument("--slots", type=int, default=8,
                   help="--batched: concurrent sessions per server")
    p.add_argument("--quant", choices=["none", "int8", "nf4"],
                   default="none",
                   help="server-side weight-only quantization (forwarded "
                        "to --mode serve)")
    p.add_argument("--prefix_cache_mb", type=int, default=0,
                   help="enable each server's prompt-prefix KV store "
                        "(forwarded to --mode serve)")
    p.add_argument("--tp", type=int, default=1,
                   help="fixed-split servers shard their stage over a "
                        "local ('tp',) mesh of N devices")
    p.add_argument("--sp", type=int, default=1,
                   help="fixed-split servers run sequence-parallel "
                        "long-context serving over N devices")
    p.add_argument("--device_count", type=int, default=None,
                   help="force N virtual CPU devices per process "
                        "(xla_force_host_platform_device_count)")
    p.add_argument("--dtype", default=None,
                   help="forwarded to every role (--dtype)")
    p.add_argument("--wire_dtype", default=None,
                   help="forwarded to every role (--wire_dtype)")
    p.add_argument("--log_dir", default=None,
                   help="where each role's log goes (default: a fresh "
                        "temp directory, printed at start)")
    args = p.parse_args()

    num_stages = len(args.splits.split(","))  # stages 1..N (0 = client)
    reg_addr = f"127.0.0.1:{args.registry_port}"
    on_cpu = os.environ.get("JAX_PLATFORMS") == "cpu"
    if max(args.tp, args.sp) > 1 and not on_cpu:
        raise SystemExit(
            "--tp/--sp servers take several chips each; this launcher "
            "gives every process ONE (run them by hand, or on the CPU "
            "with JAX_PLATFORMS=cpu)")
    base = dict(os.environ)
    device_count = args.device_count
    if device_count is None and on_cpu and max(args.tp, args.sp) > 1:
        # --tp/--sp servers need that many devices; a CPU swarm has one
        # unless we force virtual devices — without this every server exits
        # at startup and readiness never arrives.
        device_count = max(args.tp, args.sp)
    if device_count:
        base["XLA_FLAGS"] = (base.get("XLA_FLAGS", "") +
                             f" --xla_force_host_platform_device_count="
                             f"{device_count}").strip()
    log_dir = args.log_dir or tempfile.mkdtemp(prefix="run_swarm_")
    os.makedirs(log_dir, exist_ok=True)
    print(f"logs in {log_dir}")
    procs = []

    def child_env(name, chip):
        """chip=None: a host-side role; chip=i: the role owns chip i."""
        env = host_env(base) if chip is None else chip_env(base, chip)
        shown = " ".join(f"{k}={env[k]}" for k in _SHOWN if env.get(k))
        print(f"spawn {name}: {shown}", flush=True)
        return env

    def spawn(role_args, log_name, chip):
        log = open(os.path.join(log_dir, f"{log_name}.log"), "w")
        proc = subprocess.Popen(
            [sys.executable, "-m", MAIN] + role_args,
            cwd=REPO, env=child_env(log_name, chip), stdout=log,
            stderr=subprocess.STDOUT,
        )
        procs.append((proc, log))
        return proc

    if args.quant != "none" and args.tp > 1:
        raise SystemExit(
            "--quant does not compose with --tp (the TP shard specs have "
            "no layout for quantized leaves) — drop one of the flags")
    if args.prefix_cache_mb and args.sp > 1:
        # Fail HERE with the real reason — forwarding the flag would make
        # every server exit at startup and the readiness loop would only
        # report "a swarm process exited early".
        raise SystemExit(
            "--prefix_cache_mb does not compose with --sp — drop the flag "
            "or serve session/batched replicas")

    common = ["--model", args.model]
    if args.checkpoint:
        common += ["--checkpoint", args.checkpoint]
    if args.dtype:
        common += ["--dtype", args.dtype]
    if args.wire_dtype:
        common += ["--wire_dtype", args.wire_dtype]

    try:
        spawn(["--mode", "registry",
               "--registry_port", str(args.registry_port)], "registry",
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

        num_servers = args.num_servers if args.lb else num_stages
        for i in range(1, num_servers + 1):
            role = ["--mode", "serve", "--splits", args.splits,
                    "--registry_addr", reg_addr]
            if args.lb:
                role += ["--use_load_balancing", "--peer_id", f"lb{i}"]
                if args.num_blocks:
                    role += ["--num_blocks", str(args.num_blocks)]
            else:
                role += ["--stage", str(i)]
                if args.batched:
                    role += ["--batched", "--slots", str(args.slots)]
                if args.tp > 1:
                    role += ["--tp", str(args.tp)]
                if args.sp > 1:
                    role += ["--sp", str(args.sp)]
            if args.prefix_cache_mb:
                role += ["--prefix_cache_mb", str(args.prefix_cache_mb)]
            if args.quant != "none":
                role += ["--quant", args.quant]
            spawn(common + role, f"stage{i}", chip=i)

        # Readiness = every server's record is live AND ONLINE in the
        # registry (elastic servers register JOINING first while they
        # compile — replaces the reference's log-pattern scraping).
        deadline = time.time() + args.startup_timeout
        while time.time() < deadline:
            try:
                recs = [r for r in registry_list(reg_addr)
                        if str(r.state) == "online"]
            except OSError:
                recs = []
            if len(recs) >= num_servers:
                break
            for proc, _ in procs:
                if proc.poll() not in (None,):
                    raise SystemExit(
                        f"a swarm process exited early (rc={proc.returncode})"
                        f" — see logs in {log_dir}")
            time.sleep(1.0)
        else:
            raise SystemExit("servers did not register in time — see logs "
                             f"in {log_dir}")
        print(f"{num_servers} stage servers registered; starting client")
        for _, log in procs:
            # Each server's handshake line: which device it opened.
            with open(log.name) as f:
                for line in f:
                    if line.startswith("SERVING "):
                        print(line.rstrip(), flush=True)

        client_args = ["--mode", "client", "--splits", args.splits,
                       "--registry_addr", reg_addr,
                       "--prompt", args.prompt,
                       "--max_new_tokens", str(args.max_new_tokens),
                       "--temperature", str(args.temperature)]
        if args.lb:
            client_args += ["--use_load_balancing"]
        rc = subprocess.call([sys.executable, "-m", MAIN] + common
                             + client_args, cwd=REPO,
                             env=child_env("client", chip=0))
        return rc
    finally:
        for proc, log in procs:
            if proc.poll() is None:
                proc.send_signal(signal.SIGINT)
        for proc, log in procs:
            try:
                proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                proc.kill()
            log.close()


if __name__ == "__main__":
    sys.exit(main())
