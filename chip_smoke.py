#!/usr/bin/env python
"""chip_smoke.py — the quickest proof that the served path still starts on
the chip. Run from the checkout root on a machine with a TPU:

    python chip_smoke.py              # one chip, < 1200 s cold
    python chip_smoke.py --chips 4    # one four-chip host (run by builders)
    python chip_smoke.py --cpu-dry-run [--chips 4]   # before chip time

It drives the system through the entry points a user calls
(``python -m …main``), at the full width of models the repo supports, with
seeded random weights, and fails (non-zero exit, no result line) if any
phase fails — no phase is wrapped in a catch that lets the run go on:

  probe    a child asks JAX for its device; anything but a TPU fails
  serve    gpt2-xl, full width and depth, bf16: ``registry`` +
           ``serve --stage 0 --batched --burst N`` (the one process that
           owns the chip) + four concurrent ``client`` sessions, greedy and
           sampled, prompts on both sides of a prefill bucket edge. Burst
           ids == repeat ids == per-step ids from the same server; no
           ``burst_fallback``; handshake says platform=tpu, codec=native
  numeric  after the server has exited, in a process of its own: the
           batched engine's prefill + decode logits against
           ``models.full_forward`` in float32 at highest matmul precision,
           gpt2-xl widths (depth cut)
  kernels  qwen2-7b widths (depth cut): ``serve … --quant int8`` and
           ``--quant nf4`` (NF4_KERNEL=1) must report compiled — not
           interpreted — Pallas kernels with launches > 0, and the engine
           must agree with the dequantised float32 reference at decode
           M = 8 and 16 and one prefill bucket
  --chips 4: fused 4-stage pipeline ids == oracle ids (float32, highest),
           and the four-process TCP swarm (one chip per process) ids == the
           one-chip served run's

One process per chip: this parent never imports JAX, host-side roles get
``JAX_PLATFORMS=cpu``, chip owners get ``JAX_PLATFORMS=tpu``, and a
chip-owning child has EXITED before the next one starts. The last stdout
line is ``{"ok": true, "device": {...}}`` with the device as JAX reports it.

``--cpu-dry-run`` runs every phase's code path at a tiny preset on the CPU
(Pallas kernels in interpret mode where the process is ours); it prints
platform=cpu and is never the driver's command.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import re
import signal
import socket
import subprocess
import sys
import time
from typing import Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
PKG = "global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu"
MAIN = PKG + ".main"
sys.path.insert(0, HERE)

# JAX-free imports: the parent must never open the device. In a directory
# that holds only this file they fail, and so does the run.
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.utils.platform import (  # noqa: E402
    DEFAULT_COMPILE_CACHE,
    chip_env,
    host_env,
)

# Relative RMS error allowed between the bf16 engine's logits and the
# float32/highest reference on the SAME weights. bf16 keeps 8 significand
# bits (unit roundoff 2^-9 = 2.0e-3); the engine rounds the residual
# stream, the attention/MLP outputs and the KV cache to bf16 in every
# layer, which random-walks to about 1e-2 of the logits' RMS over the 2-4
# layers compared here. Measured on the v5e (chip run, PR 21): gpt2-xl
# widths, 4 layers, 7.3e-3; qwen2-7b widths, 2 layers, int8 1.42e-2 and
# NF4 1.62e-2 (its K = 18944 reductions and gate*up product carry more
# rounded terms; the unquantised engine is printed beside them). 3e-2
# passes those with room and fails a path that computes in anything
# coarser than bf16 (fp8-e4m3, unit roundoff 2^-4, lands above 1e-1) or
# drops a term of the layer.
LOGIT_REL_RMS_TOL = 3e-2


@dataclasses.dataclass(frozen=True)
class Preset:
    model: str                 # served at full width AND depth
    max_session_len: int
    slots: int
    burst: int
    new_tokens: int
    prompt_lens: tuple         # one on each side of a prefill bucket edge
    numeric_layers: int        # depth of the float32 reference comparison
    kernel_model: str          # widths the Pallas kernels qualify at
    kernel_layers: int
    kernel_prompt: int
    decode_steps: int
    four_chip_model: str
    four_chip_splits: str
    four_chip_tokens: int
    four_chip_prompt: int      # inside the stage servers' warm-up shapes


# gpt2-xl: the reference's second configuration (BASELINE.json), 48 layers,
# hidden 1600, 1.56 B parameters, ~3.1 GB bf16 — full depth fits one chip.
# qwen2-7b widths (hidden 3584, FFN 18944, 28/4 heads) at depth 2: every
# fused site (wqkv 3584x4608, wo 3584x3584, wgu 3584x37888, wd 18944x3584)
# is present once per layer; depth is cut to 2 by TIME, not memory — NF4
# quantisation is host-side numpy (~13 s/layer) and the run has 1200 s.
REAL = Preset(model="gpt2-xl", max_session_len=1024, slots=8, burst=16,
              new_tokens=64, prompt_lens=(120, 136), numeric_layers=4,
              kernel_model="qwen2-7b", kernel_layers=2, kernel_prompt=120,
              decode_steps=4, four_chip_model="gpt2-xl",
              four_chip_splits="12,24,36", four_chip_tokens=32,
              four_chip_prompt=14)
DRY = Preset(model="gpt2", max_session_len=128, slots=4, burst=4,
             new_tokens=12, prompt_lens=(14, 18), numeric_layers=2,
             kernel_model="gpt2", kernel_layers=2, kernel_prompt=14,
             decode_steps=2, four_chip_model="gpt2",
             four_chip_splits="3,6,9", four_chip_tokens=8,
             four_chip_prompt=14)


class SmokeFailure(Exception):
    pass


def say(*parts) -> None:
    print(*parts, flush=True)


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


# ---------------------------------------------------------------------------
# Children that use JAX (each in a process of its own)
# ---------------------------------------------------------------------------

def child_probe() -> int:
    import jax
    import jaxlib

    dev = jax.devices()[0]
    stats = dev.memory_stats() or {}
    print("PROBE " + json.dumps({
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices()), "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "bytes_limit": stats.get("bytes_limit")}), flush=True)
    return 0


def child_numeric(args) -> int:
    """Engine logits vs the float32 reference, one quantisation at a time;
    prints one NUMERIC json line per (quant, slots). Exit 1 on a miss."""
    t_start = time.time()
    import jax
    import jax.numpy as jnp
    import numpy as np

    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.models import (
        full_forward, get_config, init_kv_cache, init_params)
    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.models.partition import (
        StagePlan)
    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.models.quant import (
        NF4Tensor, QuantizedTensor, quantize_params)
    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.ops import (
        int8_kernel, nf4_kernel, quant_kernel_report)
    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime.batching import (
        BatchedStageExecutor)
    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.utils.platform import (
        compile_cache_dir)

    compile_cache_dir()
    if args.interpret:
        int8_kernel._INTERPRET = nf4_kernel._INTERPRET = True
    cfg = dataclasses.replace(get_config(args.model), num_layers=args.layers)
    spec = StagePlan.even(cfg.num_layers, 1).stages[0]
    params = init_params(jax.random.PRNGKey(0), cfg, dtype=jnp.bfloat16)
    rng = np.random.default_rng(0)
    steps = args.decode_steps
    seqs = [rng.integers(0, cfg.vocab_size, (n + steps,)).astype(np.int32)
            for n in args.prompt_lens]
    ok = True

    def f32_leaf(x):
        if isinstance(x, QuantizedTensor):
            return QuantizedTensor(x.q, x.s, "float32").dequant()
        if isinstance(x, NF4Tensor):
            return NF4Tensor(x.packed, x.scales, x.in_dim,
                             "float32").dequant()
        return x.astype(jnp.float32) if jnp.issubdtype(
            x.dtype, jnp.floating) else x

    for quant in args.quants:
        qparams = params if quant == "none" else quantize_params(params,
                                                                 quant)
        # The reference: the SAME (de)quantised weights in float32, whole
        # sequence in one uncached causal pass, every matmul at highest
        # precision (a float32 matmul on the MXU is otherwise bf16 passes).
        # (jitted: eager, the NF4 select tree's whole-stack temporaries
        # ran a 16 GB chip out of memory.)
        ref_params = jax.jit(lambda tree: jax.tree.map(
            f32_leaf, tree,
            is_leaf=lambda x: isinstance(x, (QuantizedTensor, NF4Tensor))
        ))(qparams)
        refs = []
        with jax.default_matmul_precision("highest"):
            fwd = jax.jit(lambda p, ids, k, v: full_forward(
                cfg, p, ids, k, v, jnp.int32(0))[0])
            for seq in seqs:
                k, v = init_kv_cache(cfg, cfg.num_layers, 1, len(seq))
                refs.append(np.asarray(fwd(ref_params, seq[None, :], k, v))[0])
        del ref_params
        for slots in args.slots:
            t0 = time.time()
            eng = BatchedStageExecutor(
                cfg, spec, qparams, slots=slots,
                max_len=max(args.prompt_lens) + steps + 8,
                dtype=jnp.bfloat16)
            worst = 0.0
            finite = True

            def compare(got, want):
                nonlocal worst, finite
                got = np.asarray(got, np.float32).reshape(-1)
                finite = finite and bool(np.isfinite(got).all())
                worst = max(worst, float(
                    np.linalg.norm(got - want) / np.linalg.norm(want)))

            for i, (seq, n) in enumerate(zip(seqs, args.prompt_lens)):
                h = eng.prefill(f"s{i}", seq[None, :n])
                compare(eng.logits(h[:, -1:]), refs[i][n - 1])
            warm_s = time.time() - t0
            for j in range(steps):
                out = eng.decode_batch(
                    {f"s{i}": seq[None, n + j:n + j + 1]
                     for i, (seq, n) in enumerate(zip(seqs,
                                                      args.prompt_lens))})
                for i, n in enumerate(args.prompt_lens):
                    compare(eng.logits(out[f"s{i}"]), refs[i][n + j])
            passed = finite and worst <= LOGIT_REL_RMS_TOL
            ok = ok and passed
            print("NUMERIC " + json.dumps({
                "model": args.model, "layers": args.layers, "quant": quant,
                "slots": slots, "prompt_lens": list(args.prompt_lens),
                "decode_steps": steps, "rel_rms_worst": round(worst, 5),
                "tol": LOGIT_REL_RMS_TOL, "finite": finite, "pass": passed,
                "warmup_s": round(warm_s, 1)}), flush=True)
    print("KERNELS " + json.dumps(quant_kernel_report()), flush=True)
    print(f"NUMERIC_DONE total_s={time.time() - t_start:.1f}", flush=True)
    return 0 if ok else 1


# ---------------------------------------------------------------------------
# Parent-side process plumbing (no JAX)
# ---------------------------------------------------------------------------

class Run:
    """Everything one smoke run started, so that it can stop all of it."""

    def __init__(self, out_dir: str, base_env: dict, dry: bool):
        self.out = out_dir
        self.base = base_env
        self.dry = dry
        self.procs: List[subprocess.Popen] = []
        self.t0 = time.time()

    def spawn(self, name: str, argv: List[str],
              env: dict) -> subprocess.Popen:
        log = open(os.path.join(self.out, name + ".log"), "w")
        say(f"  spawn {name}: JAX_PLATFORMS={env.get('JAX_PLATFORMS')}"
            + (f" TPU_VISIBLE_CHIPS={env['TPU_VISIBLE_CHIPS']}"
               if env.get("TPU_VISIBLE_CHIPS") else ""))
        proc = subprocess.Popen(argv, cwd=HERE, env=env, stdout=log,
                                stderr=subprocess.STDOUT, text=True)
        self.procs.append(proc)
        return proc

    def stop(self, proc: subprocess.Popen, timeout: float = 60.0) -> None:
        """SIGINT, then WAIT for the exit: the next chip owner may not
        start while this one still holds the chip."""
        if proc.poll() is None:
            proc.send_signal(signal.SIGINT)
            try:
                proc.wait(timeout=timeout)
            except subprocess.TimeoutExpired:
                proc.kill()
                proc.wait(timeout=30)

    def stop_all(self) -> None:
        for proc in self.procs:
            if proc.poll() is None:
                proc.kill()
        for proc in self.procs:
            try:
                proc.wait(timeout=30)
            except subprocess.TimeoutExpired:
                pass

    def wait_line(self, path: str, prefix: str, proc: subprocess.Popen,
                  timeout: float) -> str:
        deadline = time.time() + timeout
        while time.time() < deadline:
            with open(path) as f:
                for line in f:
                    if line.startswith(prefix):
                        return line.rstrip("\n")
            check(proc.poll() is None,
                  f"{path}: process exited rc={proc.returncode} before "
                  f"printing {prefix!r}:\n{tail(path)}")
            time.sleep(0.5)
        raise SmokeFailure(f"{path}: no {prefix!r} within {timeout:.0f}s:\n"
                           f"{tail(path)}")

    def run_child(self, name: str, argv: List[str], env: dict,
                  timeout: float) -> str:
        """Run a child to its END and return its output; non-zero fails."""
        path = os.path.join(self.out, name + ".log")
        proc = self.spawn(name, argv, env)
        try:
            rc = proc.wait(timeout=timeout)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait(timeout=30)
            raise SmokeFailure(f"{name}: no exit within {timeout:.0f}s:\n"
                               f"{tail(path)}")
        check(rc == 0, f"{name}: exit code {rc}:\n{tail(path)}")
        with open(path) as f:
            return f.read()


def tail(path: str, n: int = 25) -> str:
    try:
        with open(path) as f:
            return "".join(f.readlines()[-n:])
    except OSError as exc:
        return f"<{exc}>"


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def handshake_fields(line: str) -> Dict[str, str]:
    return {k: v.strip('"') for k, v in
            re.findall(r'(\w+)=("[^"]*"|\S+)', line)}


def main_argv(*args: str) -> List[str]:
    return [sys.executable, "-m", MAIN, *args]


def ids_of(text: str, who: str) -> List[int]:
    """The token ids a generation's closing report printed."""
    m = re.search(r"^IDS (\[.*\])$", text, re.M)
    check(m is not None, f"{who}: no IDS line:\n{text[-1500:]}")
    return json.loads(m.group(1))


def prompt_of(length: int, salt: int) -> str:
    """`length` ASCII bytes (the byte tokenizer: one token each), distinct
    per salt so that sessions never share a prompt by accident."""
    words = ("swarm", "stage", "token", "cache", "burst", "chip", "relay")
    text = " ".join(words[(salt + i) % len(words)]
                    for i in range(length))
    return text[:length]


# ---------------------------------------------------------------------------
# Served phases
# ---------------------------------------------------------------------------

class Served:
    """registry + one full-span batched server; yields client runs."""

    def __init__(self, run: Run, tag: str, model: str, *, dtype: str,
                 slots: int, max_len: int, burst: int,
                 num_layers: Optional[int] = None, quant: str = "none",
                 env_extra: Optional[dict] = None,
                 chip: Optional[int] = None, timeout: float = 600.0):
        self.run, self.tag, self.model = run, tag, model
        self.num_layers, self.burst = num_layers, burst
        port = free_port()
        self.reg_addr = f"127.0.0.1:{port}"
        self.model_args = ["--model", model] + (
            ["--num_layers", str(num_layers)] if num_layers else [])
        self.registry = run.spawn(
            f"{tag}_registry",
            main_argv("--mode", "registry", "--registry_port", str(port)),
            host_env(run.base))
        run.wait_line(os.path.join(run.out, f"{tag}_registry.log"),
                      "REGISTRY_ADDR=", self.registry, 60)
        env = chip_env(run.base, chip)
        env.update(env_extra or {})
        t0 = time.time()
        self.log = os.path.join(run.out, f"{tag}_server.log")
        self.server = run.spawn(
            f"{tag}_server",
            main_argv("--mode", "serve", "--stage", "0", "--batched",
                      "--burst", str(burst), "--slots", str(slots),
                      "--max_session_len", str(max_len), "--dtype", dtype,
                      "--quant", quant, "--registry_addr", self.reg_addr,
                      *self.model_args), env)
        line = run.wait_line(self.log, "SERVING ", self.server, timeout)
        self.warmup_s = time.time() - t0
        self.hs = handshake_fields(line)
        say(f"  {line}")
        say(f"  {tag}: warm-up (spawn -> SERVING) {self.warmup_s:.1f}s")
        check(self.hs.get("codec") == "native",
              f"{tag}: wire codec is {self.hs.get('codec')!r}, not the "
              "native one (build of native/codec.cpp failed?)")
        if not run.dry:
            check(self.hs.get("platform") == "tpu",
                  f"{tag}: server runs on {self.hs.get('platform')!r}")

    def kernels(self) -> dict:
        line = self.run.wait_line(self.log, "KERNELS ", self.server, 10)
        return json.loads(line[len("KERNELS "):])

    def clients(self, label: str, sessions: List[dict], *, burst: int,
                new_tokens: int, timeout: float = 600.0) -> List[List[int]]:
        """Run `sessions` CONCURRENTLY (one client process each, host-side
        roles: JAX_PLATFORMS=cpu); return each one's token ids."""
        procs = []
        for i, s in enumerate(sessions):
            name = f"{self.tag}_{label}_client{i}"
            argv = main_argv(
                "--mode", "client", "--registry_addr", self.reg_addr,
                "--prompt", s["prompt"], "--max_new_tokens",
                str(new_tokens), "--temperature", str(s["temperature"]),
                "--seed", str(s["seed"]), "--request_timeout", "300",
                "--events-dump",
                os.path.join(self.run.out, name + ".events.jsonl"),
                *self.model_args)
            if burst:
                argv += ["--burst", str(burst)]
            procs.append((name, self.run.spawn(name, argv,
                                               host_env(self.run.base))))
        ids = []
        deadline = time.time() + timeout
        for name, proc in procs:
            path = os.path.join(self.run.out, name + ".log")
            try:
                rc = proc.wait(timeout=max(1.0, deadline - time.time()))
            except subprocess.TimeoutExpired:
                raise SmokeFailure(f"{name}: request did not complete:\n"
                                   f"{tail(path)}")
            check(rc == 0, f"{name}: exit code {rc}:\n{tail(path)}")
            with open(path) as f:
                text = f.read()
            got = ids_of(text, name)
            check(len(got) >= 1, f"{name}: no tokens")
            stop = re.search(r"stopped by (\w+)", text)
            say(f"    {name}: {len(got)} tokens, stopped by "
                f"{stop.group(1) if stop else '?'}")
            with open(os.path.join(self.run.out,
                                   name + ".events.jsonl")) as f:
                events = f.read()
            check("burst_fallback" not in events,
                  f"{name}: burst_fallback event — the session did not run "
                  "on the full-span server")
            check("STAGE0 " not in text,
                  f"{name}: the client built a stage-0 executor; a "
                  "full-span session must compute nothing locally")
            ids.append(got)
        return ids

    def close(self) -> None:
        self.run.stop(self.server)
        self.run.stop(self.registry)
        check(self.server.returncode is not None, "server did not exit")


def phase_serve(run: Run, p: Preset) -> dict:
    say(f"PHASE serve: {p.model} full depth bf16, slots={p.slots} "
        f"max_session_len={p.max_session_len} burst={p.burst}")
    srv = Served(run, "serve", p.model, dtype="bfloat16", slots=p.slots,
                 max_len=p.max_session_len, burst=p.burst)
    try:
        lo, hi = p.prompt_lens
        sessions = [
            {"prompt": prompt_of(lo, 0), "temperature": 0.0, "seed": 0},
            {"prompt": prompt_of(hi, 1), "temperature": 0.0, "seed": 0},
            {"prompt": prompt_of(lo, 2), "temperature": 0.8, "seed": 11},
            {"prompt": prompt_of(hi, 3), "temperature": 0.8, "seed": 12},
        ]
        say(f"  {len(sessions)} concurrent sessions, prompts of {lo} and "
            f"{hi} tokens, {p.new_tokens} new tokens each")
        first = srv.clients("burst", sessions, burst=p.burst,
                            new_tokens=p.new_tokens)
        again = srv.clients("repeat", sessions, burst=p.burst,
                            new_tokens=p.new_tokens)
        check(first == again, "same prompt + seed gave different ids on "
              f"the second run:\n{first}\n{again}")
        say("  repeat ids == first ids")
        step = srv.clients("perstep", sessions, burst=0,
                           new_tokens=p.new_tokens)
        check(first == step, "burst ids differ from per-step ids from the "
              f"same server:\n{first}\n{step}")
        say("  burst ids == per-step ids")
        check(any(len(t) == p.new_tokens for t in first),
              "no session decoded its full token budget")
    finally:
        srv.close()
    say(f"PHASE serve: PASS warmup_s={srv.warmup_s:.1f}")
    return {"warmup_s": round(srv.warmup_s, 1),
            "tokens": [len(t) for t in first]}


def run_numeric(run: Run, name: str, model: str, layers: int,
                quants: List[str], slots: List[int], prompt_lens,
                decode_steps: int, timeout: float) -> dict:
    env = chip_env(run.base)
    env["NF4_KERNEL"] = "1"
    argv = [sys.executable, os.path.abspath(__file__), "--child", "numeric",
            "--model", model, "--layers", str(layers),
            "--quants", ",".join(quants),
            "--slots", ",".join(map(str, slots)),
            "--prompt_lens", ",".join(map(str, prompt_lens)),
            "--decode_steps", str(decode_steps)]
    if run.dry:
        argv.append("--interpret")
    out = run.run_child(name, argv, env, timeout)
    rows = [json.loads(l[len("NUMERIC "):]) for l in out.splitlines()
            if l.startswith("NUMERIC {")]
    check(len(rows) == len(quants) * len(slots),
          f"{name}: {len(rows)} results for {len(quants)}x{len(slots)}")
    for r in rows:
        say(f"    {r}")
    kern = json.loads(next(l for l in out.splitlines()
                           if l.startswith("KERNELS "))[len("KERNELS "):])
    return {"kernels": kern,
            "rel_rms_worst": max(r["rel_rms_worst"] for r in rows),
            "warmup_s": max(r["warmup_s"] for r in rows)}


def phase_numeric(run: Run, p: Preset) -> dict:
    say(f"PHASE numeric: {p.model} widths, {p.numeric_layers} layers, "
        f"batched engine (bf16) vs full_forward (float32, highest), "
        f"rel-RMS tol {LOGIT_REL_RMS_TOL}")
    res = run_numeric(run, "numeric", p.model, p.numeric_layers, ["none"],
                      [p.slots], p.prompt_lens, p.decode_steps, 600)
    say(f"PHASE numeric: PASS warmup_s={res['warmup_s']}")
    return {k: res[k] for k in ("rel_rms_worst", "warmup_s")}


def check_kernel_report(rep: dict, kind: str, where: str, *,
                        compiled: bool, interpreted: bool = False) -> None:
    """Print where each site ran; with `compiled` (or `interpreted`, the
    dry run's own process) require the Pallas path wherever the shape
    qualifies. A CPU server has no Mosaic: its sites are all XLA."""
    say(f"    {where} {kind}: interpret={rep['interpret']} "
        f"launches={rep['launches']}")
    for site, path in rep["sites"].items():
        say(f"      site {site}: {path}")
    check(rep["sites"], f"{where}: no {kind} matmul site was traced")
    if not (compiled or interpreted):
        return
    check(rep["interpret"] is interpreted,
          f"{where}: {kind} kernel interpret={rep['interpret']}")
    check(rep["launches"] > 0, f"{where}: {kind} kernel never launched "
          "(the backend dispatch turned it off?)")
    for site, path in rep["sites"].items():
        m, k, n = map(int, site.split("x"))
        if k % 128 == 0 and n % 128 == 0:
            check(path.startswith("pallas"),
                  f"{where}: {kind} site {site} qualifies by shape but "
                  f"ran on {path}")


def phase_kernels(run: Run, p: Preset) -> dict:
    say(f"PHASE kernels: {p.kernel_model} widths, {p.kernel_layers} layers")
    result: dict = {}
    # Served through the same entry: int8 at decode M = slots = 8, NF4 at
    # M = 16; each prompt also runs one prefill bucket.
    for quant, slots in (("int8", 8), ("nf4", 16)):
        srv = Served(run, f"serve_{quant}", p.kernel_model,
                     num_layers=p.kernel_layers, dtype="bfloat16",
                     slots=slots, max_len=max(256, p.kernel_prompt + 72),
                     burst=8, quant=quant, env_extra={"NF4_KERNEL": "1"})
        try:
            check_kernel_report(srv.kernels()[quant], quant,
                                f"serve --quant {quant} --slots {slots}",
                                compiled=not run.dry)
            sessions = [
                {"prompt": prompt_of(p.kernel_prompt, 4),
                 "temperature": 0.8, "seed": 21},
                {"prompt": prompt_of(p.kernel_prompt + 4, 5),
                 "temperature": 0.0, "seed": 0},
            ]
            srv.clients("burst", sessions, burst=8,
                        new_tokens=min(p.new_tokens, 24))
        finally:
            srv.close()
        result[f"serve_{quant}_warmup_s"] = round(srv.warmup_s, 1)
    # Against the dequantised float32 reference, in a process of its own:
    # both kernels at decode M = 8 and 16 and the prefill bucket, with the
    # unquantised engine beside them (what bf16 alone costs at these widths).
    res = run_numeric(run, "numeric_kernels", p.kernel_model,
                      p.kernel_layers, ["none", "int8", "nf4"], [8, 16],
                      (p.kernel_prompt,), p.decode_steps, 900)
    for quant in ("int8", "nf4"):
        check_kernel_report(res["kernels"][quant], quant, "numeric",
                            compiled=not run.dry, interpreted=run.dry)
    result["numeric_warmup_s"] = res["warmup_s"]
    result["rel_rms_worst"] = res["rel_rms_worst"]
    say(f"PHASE kernels: PASS {json.dumps(result)}")
    return result


# ---------------------------------------------------------------------------
# Four chips
# ---------------------------------------------------------------------------

def phase_four_chips(run: Run, p: Preset, device_count: int) -> dict:
    say(f"PHASE four-chips: {p.four_chip_model}, float32 at highest matmul "
        f"precision, {p.four_chip_tokens} tokens, sampled seed 0")
    check(device_count >= 4, f"--chips 4 but JAX found {device_count} device(s)")
    base = run.base = dict(run.base,
                           JAX_DEFAULT_MATMUL_PRECISION="highest")
    if run.dry:
        base["XLA_FLAGS"] = (base.get("XLA_FLAGS", "") +
                             " --xla_force_host_platform_device_count=4"
                             ).strip()
    prompt = prompt_of(p.four_chip_prompt, 6)
    gen = ["--model", p.four_chip_model, "--dtype", "float32", "--prompt",
           prompt, "--max_new_tokens", str(p.four_chip_tokens),
           "--temperature", "0.8"]
    # (a) the collective-permute pipeline on a real four-chip stage mesh
    t0 = time.time()
    oracle = ids_of(run.run_child("oracle", main_argv("--mode", "oracle",
                                                      *gen),
                                  chip_env(base, 0), 900), "oracle")
    t_oracle = time.time() - t0
    t0 = time.time()
    fused_out = run.run_child("fused", main_argv(
        "--mode", "fused", "--num_stages", "4", *gen), chip_env(base), 900)
    t_fused = time.time() - t0
    check("fused pipeline: 4 stages" in fused_out,
          "fused: the pipeline did not build 4 stages")
    fused = ids_of(fused_out, "fused")
    check(fused == oracle, f"fused 4-stage ids != oracle ids:\n{fused}\n"
                           f"{oracle}")
    say(f"  fused 4-stage ids == oracle ids ({len(fused)} tokens; oracle "
        f"{t_oracle:.0f}s, fused {t_fused:.0f}s wall)")
    # (b) the TCP swarm: four processes, one chip each
    srv = Served(run, "serve_f32", p.four_chip_model, dtype="float32",
                 slots=4, max_len=256, burst=8, chip=0)
    try:
        served, = srv.clients(
            "burst", [{"prompt": prompt, "temperature": 0.8, "seed": 0}],
            burst=8, new_tokens=p.four_chip_tokens)
    finally:
        srv.close()
    log_dir = os.path.join(run.out, "swarm")
    swarm_out = run.run_child("run_swarm", [
        sys.executable, os.path.join(HERE, "scripts", "run_swarm.py"),
        "--model", p.four_chip_model, "--splits", p.four_chip_splits,
        "--dtype", "float32", "--wire_dtype", "f32", "--prompt", prompt,
        "--max_new_tokens", str(p.four_chip_tokens), "--temperature", "0.8",
        "--registry_port", str(free_port()), "--log_dir", log_dir],
        base, 1200)
    lines = [l for l in swarm_out.splitlines()
             if l.startswith(("SERVING ", "STAGE0 "))]
    for l in lines:
        say(f"  {l}")
    hs = [handshake_fields(l) for l in lines]
    check(len(hs) == 4, f"swarm: {len(hs)} handshake lines, want 3 servers "
                        f"+ the client's stage 0:\n{swarm_out[-2000:]}")
    if not run.dry:
        check(all(h.get("platform") == "tpu" and h.get("device_count") == "1"
                  for h in hs), "swarm: a process is not on exactly one TPU")
        chips = sorted(h.get("visible_chips", "?") for h in hs)
        check(chips == ["0", "1", "2", "3"],
              f"swarm: processes hold chips {chips}, want one each of 0-3")
    swarm = ids_of(swarm_out, "swarm client")
    check(swarm == served, f"swarm ids != one-chip served ids:\n{swarm}\n"
                           f"{served}")
    say(f"  four-process swarm ids == one-chip served ids "
        f"({len(swarm)} tokens); served == oracle: {served == oracle}")
    say("PHASE four-chips: PASS")
    return {"tokens": len(swarm), "served_equals_oracle": served == oracle}


# ---------------------------------------------------------------------------

def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--cpu-dry-run", action="store_true")
    ap.add_argument("--out", default=None,
                    help="logs directory (default chiprun_out/chip_smoke)")
    ap.add_argument("--child", choices=("probe", "numeric"))
    ap.add_argument("--model")
    ap.add_argument("--layers", type=int)
    ap.add_argument("--quants", type=lambda s: s.split(","))
    ap.add_argument("--slots", type=lambda s: [int(x) for x in s.split(",")])
    ap.add_argument("--prompt_lens",
                    type=lambda s: tuple(int(x) for x in s.split(",")))
    ap.add_argument("--decode_steps", type=int, default=4)
    ap.add_argument("--interpret", action="store_true")
    args = ap.parse_args()
    if args.child == "probe":
        return child_probe()
    if args.child == "numeric":
        return child_numeric(args)

    dry = args.cpu_dry_run
    preset = DRY if dry else REAL
    out_dir = os.path.abspath(args.out or os.path.join(
        HERE, "chiprun_out", "chip_smoke" + ("_dry" if dry else "")
        + ("_4" if args.chips == 4 else "")))
    os.makedirs(out_dir, exist_ok=True)
    base = dict(os.environ)
    if dry:
        base["JAX_PLATFORMS"] = "cpu"
    run = Run(out_dir, base, dry)
    say(f"chip_smoke: chips={args.chips} dry_run={dry} logs in {out_dir}")
    say("compile cache: "
        + (os.environ.get("JAX_COMPILATION_CACHE_DIR")
           or DEFAULT_COMPILE_CACHE))
    summary: dict = {}
    try:
        # The probe INHERITS the environment: with the chip hidden
        # (JAX_PLATFORMS=cpu) it reports cpu and the run fails here.
        probe_out = run.run_child(
            "probe", [sys.executable, os.path.abspath(__file__), "--child",
                      "probe"], base, 300)
        dev = json.loads(next(l for l in probe_out.splitlines()
                              if l.startswith("PROBE "))[len("PROBE "):])
        say(f"device: platform={dev['platform']} device_kind="
            f"{dev['kind']!r} device_count={dev['count']} "
            f"jax={dev['jax']} jaxlib={dev['jaxlib']} "
            f"bytes_limit={dev['bytes_limit']}")
        check(dry or dev["platform"] == "tpu",
              f"JAX found no accelerator (platform={dev['platform']})")
        if args.chips == 4:
            summary["four_chips"] = phase_four_chips(run, preset,
                                                     4 if dry else dev["count"])
        else:
            summary["serve"] = phase_serve(run, preset)
            summary["numeric"] = phase_numeric(run, preset)
            summary["kernels"] = phase_kernels(run, preset)
    except SmokeFailure as exc:
        say(f"FAIL: {exc}")
        return 1
    finally:
        run.stop_all()
    say("SUMMARY " + json.dumps(summary))
    say(f"total {time.time() - run.t0:.0f}s")
    result = {"ok": True, "device": {"platform": dev["platform"],
                                     "kind": dev["kind"],
                                     "count": dev["count"]}}
    if dry:
        result["dry_run"] = True
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
