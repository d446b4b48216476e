"""The load generator: ONE process, one thread per session, each thread a
``PipelineClient`` built the way ``main.py``'s client role builds its own
(no process per request: Python's start-up would land in the time to
first token). Every route is served whole by a full-span peer, so this
process computes nothing and never opens a device.

It writes one record per request to ``<out>/records.jsonl`` and talks to
the parent on stdout with ``LOAD <event> <json>`` lines.

Usage: python -m perfbench.harness.loadgen <spec.json>"""

from __future__ import annotations

import dataclasses
import json
import os
import sys
import threading
import time

from . import stats
from .traffic import Request, Schedule

PKG = "global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu"


def say(event: str, **fields) -> None:
    sys.stdout.write(f"LOAD {event} {json.dumps(fields)}\n")
    sys.stdout.flush()


def no_stage0():
    raise RuntimeError("the load generator holds no chip: a session that a "
                       "full-span peer serves whole builds no stage 0")


class Clients:
    """Client objects for every session thread, from the program's own
    parts: registry, transport, PipelineClient."""

    def __init__(self, spec: dict):
        import importlib

        main = importlib.import_module(PKG + ".main")
        net = importlib.import_module(PKG + ".runtime.net")
        client = importlib.import_module(PKG + ".runtime.client")
        partition = importlib.import_module(PKG + ".models.partition")
        sampling = importlib.import_module(PKG + ".ops.sampling")
        self._net, self._client = net, client
        argv = ["--mode", "client", "--registry_addr", spec["registry_addr"],
                "--seed", str(spec["weights_seed"]), *spec["model_args"]]
        self.args = main.build_parser().parse_args(argv)
        self.cfg = main.load_config(self.args)
        self.plan = partition.StagePlan.even(self.cfg.num_layers, 4)
        self.model = self.args.model_name or self.args.model
        self.registry = net.RemoteRegistry(spec["registry_addr"])
        tr = spec["traffic"]
        if tr["route"]["kind"] != "full_span":
            raise ValueError(f"route kind {tr['route']['kind']!r}: only "
                             "full_span is built")
        self.timeout = float(tr.get("request_timeout_s", 120.0))
        self.sampling = sampling.SamplingParams(**tr["sampling"])
        self.burst = int(tr["route"].get("burst", 0))
        self.transports = []

    def make(self):
        tx = self._net.TcpTransport(self.registry,
                                    wire_dtype=self.args.wire_dtype,
                                    model=self.model)
        self.transports.append(tx)
        return self._client.PipelineClient(
            self.cfg, self.plan, no_stage0, tx, self.registry,
            total_blocks=self.cfg.num_layers, request_timeout=self.timeout,
            seed=0, model=self.model)

    def wait_route(self, timeout: float) -> None:
        """Until the registry lists servers for every block of the model."""
        deadline = time.time() + timeout
        need = self.cfg.num_layers
        while time.time() < deadline:
            try:
                recs = [r for r in self.registry.live_servers()
                        if getattr(r, "state", "online") == "online"]
            except (ConnectionError, OSError):
                recs = []
            covered = set()
            for r in recs:
                covered.update(range(r.start_block, r.end_block))
            if covered >= set(range(need)) and any(
                    r.final_stage for r in recs):
                return
            time.sleep(0.1)
        raise RuntimeError("no route: the registry never listed servers "
                           "for every block")

    def scrape(self, path: str) -> None:
        """Every live server's ``metrics`` verb, one JSON line per peer."""
        tx = self._net.TcpTransport(self.registry)
        try:
            with open(path, "w") as f:
                for r in self.registry.live_servers():
                    if r.address:
                        f.write(json.dumps({
                            "peer": r.peer_id,
                            "text": tx.metrics_text(r.peer_id)}) + "\n")
        finally:
            tx.close()

    def close(self) -> None:
        for tx in self.transports:
            tx.close()


class Load:
    def __init__(self, spec: dict, clients: Clients):
        self.spec = spec
        self.clients = clients
        self.schedule = Schedule(spec["traffic"], spec["seed"])
        self.vocab = clients.cfg.vocab_size
        self.t0 = time.monotonic()
        self.lock = threading.Lock()
        self.records: list = []
        self.next_index = 0
        self.stop = threading.Event()
        self.first_token = [threading.Event()
                            for _ in range(self.schedule.sessions)]

    def now(self) -> float:
        return time.monotonic() - self.t0

    def run_request(self, client, req, session: int, prompt_ids) -> dict:
        rec = {"index": req.index, "session": session, "due": req.due_s,
               "sent": None, "deliveries": [], "done": None, "stop": None,
               "error": None, "budget": req.budget,
               "prompt_len": req.prompt_len}
        with self.lock:
            self.records.append(rec)
        client.seed = req.sampling_seed
        rec["sent"] = self.now()
        gen = client.generate_stepwise(
            prompt_ids, req.budget, sampling=self.clients.sampling,
            eos_token_id=None, burst=self.clients.burst)
        try:
            for step in gen:
                if step.done:
                    rec["stop"] = step.result.stopped_by
                    break
                rec["deliveries"].append([self.now(), len(step.new_tokens)])
                if session >= 0:
                    self.first_token[session].set()
                if self.stop.is_set():
                    break                 # the window is over: abandon
        except Exception as exc:  # a failed request is a counted outcome
            rec["error"] = f"{type(exc).__name__}: {exc}"[:300]
        finally:
            gen.close()                   # releases the session's slot
            rec["done"] = self.now()
        return rec

    def warm(self) -> dict:
        """One request per distinct prompt length, sequentially: the first
        with a budget that runs a whole decode round, the rest prefill
        only. Builds every program the window can meet."""
        client = self.clients.make()
        t = time.monotonic()
        errors, per_shape = [], []
        burst = max(1, self.clients.burst)
        for j, n in enumerate(self.schedule.warm_lengths()):
            req = Request(index=-1 - j, prompt_len=n,
                          budget=burst + 2 if j == 0 else 1,
                          sampling_seed=j, due_s=None)
            ids = [(7 * i + j) % self.vocab for i in range(n)]
            rec = self.run_request(client, req, -1, ids)
            per_shape.append(round(rec["done"] - rec["sent"], 3))
            if rec["error"]:
                errors.append(rec["error"])
        with self.lock:
            self.records.clear()
        return {"warm_s": time.monotonic() - t,
                "shapes": self.schedule.warm_lengths(),
                "per_shape_s": per_shape, "errors": errors}

    def take(self):
        with self.lock:
            k = self.next_index
            self.next_index += 1
        return self.schedule.request(k)

    def session_loop(self, i: int) -> None:
        client = self.clients.make()
        think = float(self.spec["traffic"].get("think_s", 0.0))
        while not self.stop.is_set():
            req = self.take()
            if req.due_s is not None:
                # open loop: wait until the request falls due (never
                # early; lateness is sent - due, reported per run)
                delay = self.window_origin + req.due_s - self.now()
                if delay > 0 and self.stop.wait(delay):
                    break
                req = dataclasses.replace(
                    req, due_s=self.window_origin + req.due_s)
            self.run_request(client, req, i,
                             self.schedule.prompt_ids(req.index, self.vocab))
            if think and self.stop.wait(think):
                break

    def run(self) -> dict:
        spec = self.spec
        out = spec["out_dir"]
        self.window_origin = self.now()
        threads = [threading.Thread(target=self.session_loop, args=(i,),
                                    name=f"session-{i}", daemon=True)
                   for i in range(self.schedule.sessions)]
        t_ramp = time.monotonic()
        for th in threads:
            th.start()
        if self.schedule.kind == "closed":
            for ev in self.first_token:       # ramp: every session served
                if not ev.wait(self.clients.timeout):
                    raise RuntimeError("ramp: a session got no first token")
            # ... and, where the mix asks for it, until that many requests
            # have ended: sessions that start together run in step at
            # first, and a window should open on the steady mix.
            want = int(spec["traffic"].get("ramp_finished_requests", 0))
            deadline = time.monotonic() + self.clients.timeout
            while want and time.monotonic() < deadline:
                with self.lock:
                    if sum(r["stop"] is not None
                           for r in self.records) >= want:
                        break
                time.sleep(0.05)
        ramp_s = time.monotonic() - t_ramp
        if spec["scrape"]:
            self.clients.scrape(os.path.join(out, "metrics_before.jsonl"))
        w0 = self.now()
        say("window_start", wall=time.time(), ramp_s=ramp_s)
        traced = min(float(spec.get("trace_seconds", 0)), spec["seconds"])
        time.sleep(spec["seconds"] - traced)
        if traced:            # the parent traces the window's last stretch
            say("trace_start", wall=time.time())
            time.sleep(traced)
        w1 = self.now()
        self.stop.set()
        say("window_end", wall=time.time())
        if spec["scrape"]:
            self.clients.scrape(os.path.join(out, "metrics_after.jsonl"))
        for th in threads:
            th.join(timeout=self.clients.timeout)
        alive = sum(th.is_alive() for th in threads)
        with self.lock:
            records = [dict(r) for r in self.records]
        with open(os.path.join(out, "records.jsonl"), "w") as f:
            for r in records:
                f.write(json.dumps(r) + "\n")
        summary = stats.summarize(records, w0, w1, self.clients.timeout)
        summary.update({"w0": w0, "w1": w1, "ramp_s": ramp_s,
                        "threads_alive": alive,
                        "ctx_rows_in_use": stats.ctx_rows_in_use(
                            records, w0, w1)})
        return summary


def main() -> int:
    with open(sys.argv[1]) as f:
        spec = json.load(f)
    t_start = time.time()
    clients = Clients(spec)
    say("imported", s=time.time() - t_start)
    try:
        clients.wait_route(spec["serve_timeout_s"])
        say("route", s=time.time() - t_start)
        load = Load(spec, clients)
        warm = load.warm()
        say("warm", **warm)
        if warm["errors"]:
            return 1
        summary = load.run()
        say("done", **summary)
    finally:
        clients.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
