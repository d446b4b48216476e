#!/usr/bin/env python3
"""Hand reduction of one profiler trace by the NAMES the program gives it
(PERF.md section 5; not part of the benchmark, which reads none of this yet).

    python3 scripts/trace_sections.py reduce <dir-with-xplane> <out.json>
    python3 scripts/trace_sections.py run --workload W --seed N --seconds S

``reduce`` reads every ``*.xplane.pb`` under the directory and reports

  * device seconds per engine program (``XLA Modules`` names:
    ``jit_burst_tick``, ``jit_prefill``, ...) and per ``jax.named_scope``
    section inside the burst program (``embed`` ... ``stop_rules``; ``none``
    is the time of operations that carry no scope), self times, so a
    ``while`` does not count its body twice;
  * which stat carried the scope name (it is on the event's METADATA in the
    raw proto, so this reads ``*.xplane.pb`` with TensorFlow's
    ``xplane_pb2``; ``jax.profiler.ProfileData`` does not show it);
  * the program's ``stage.*`` host spans (telemetry/profiling.py): count,
    seconds and how many carry ``session=`` per name (``stage.round_window``,
    ``stage.request``, ``stage.reply`` and a request's three phases do; a
    round's phases carry ``sessions=``), and ONE request's spans from
    ``stage.prefill_wait`` to
    the end of ``stage.first_token`` with the rounds and the device programs
    that ran meanwhile.

``run`` is ``perfbench/run.py --trace 1`` with this reduction made on the
trace before the harness deletes it (the harness is called, not edited)."""

from __future__ import annotations

import bisect
import collections
import json
import os
import re
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

SCOPES = ("embed", "attention", "kv_update", "mlp", "loop_norm",
          "exit_gate", "head", "sampler", "stop_rules",
          # opened INSIDE ``attention`` / ``mlp`` by a latent family under a
          # learned selection with expert layers: the innermost name counts
          "indexer", "topk_select", "latent_read", "router", "experts",
          "shared_expert",
          # a sliding latent layer's read of its ring, and the headwise gate
          "window_read", "attn_gate")
SCOPE_RE = re.compile(r"(?:^|/)(%s)(?=/|$)" % "|".join(SCOPES))


def _scope_in(op_name: str):
    """The innermost of `SCOPES` in an operation's ``op_name`` path."""
    found = SCOPE_RE.findall(op_name)
    return found[-1] if found else None
TICK_RE = re.compile(r"^jit_burst_tick")
# Host events at least this long are kept, to show where a request's
# thread stood still inside stage.first_token.
LONG_HOST_EVENT_S = 0.05


def _load(path: str):
    """The raw XSpace. ``jax.profiler.ProfileData`` shows an event's own
    stats only; the scope name is on the ``tf_op`` stat of the event's
    METADATA (XLA's ``op_name``), which the proto keeps."""
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    space = xplane_pb2.XSpace()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    return space


def _stats(plane, stats) -> dict:
    """{stat name: value} of a metadata's or an event's stats (a
    ``ref_value`` names another stat-metadata entry: its name IS the
    string)."""
    names = plane.stat_metadata
    out = {}
    for st in stats:
        kind = st.WhichOneof("value")
        val = getattr(st, kind)
        if kind == "ref_value":
            val = names[val].name
        elif kind == "bytes_value":
            continue
        out[names[st.metadata_id].name] = val
    return out


def _events(plane, line):
    """(metadata id, start_s, duration_s, event) per event of a line, on
    the trace's clock."""
    t0 = line.timestamp_ns * 1e-9
    for ev in line.events:
        yield (ev.metadata_id, t0 + ev.offset_ps * 1e-12,
               ev.duration_ps * 1e-12, ev)


def _short(name: str) -> str:
    return re.sub(r"\(.*", "", name)


def _control_flow_scopes(order, scope_of) -> dict:
    """Scopes for the operations that hold others and carry no scope name
    of their own: a ``conditional`` or a ``while`` whose nested operations
    ALL sit in one scope belongs to it (the loop over blocks of
    `runtime.batching._block_stats` is ``attention``; the layer scan's
    ``while`` holds every scope and stays unscoped). ``order`` is sorted by
    start, longest first."""
    held = collections.defaultdict(set)         # metadata id -> scopes
    stack = []                                  # (end, metadata id)
    for mid, s, d in order:
        while stack and stack[-1][0] <= s:
            stack.pop()
        for _, outer in stack:
            if scope_of[outer][0] is None:
                held[outer].add(scope_of[mid][0])
        stack.append((s + d, mid))
    return {mid: (next(iter(scopes)), "nested operations")
            for mid, scopes in held.items()
            if len(scopes) == 1 and None not in scopes}


def reduce_file(path: str) -> dict:
    from perfbench.harness.trace import self_times

    space = _load(path)
    out = {"file": os.path.basename(path), "devices": {}, "host": {}}
    stage_events, host_threads = [], {}
    for plane in space.planes:
        meta = plane.event_metadata
        if plane.name.startswith("/device:"):
            mods, ops = [], []
            for line in plane.lines:
                if line.name == "XLA Modules":
                    mods += [(meta[m].name, s, d)
                             for m, s, d, _ in _events(plane, line)]
                elif line.name == "XLA Ops":
                    ops += [(m, s, d) for m, s, d, _ in _events(plane, line)]
            if not ops:
                continue
            # One lookup per distinct operation: its scope and which stat
            # of its metadata carried it.
            scope_of, stat_names, example = {}, collections.Counter(), None
            unscoped_label = {}
            for mid in {m for m, _, _ in ops}:
                st = _stats(plane, meta[mid].stats)
                stat_names.update(st.keys())
                scope_of[mid] = (None, None)
                # An operation the compiler made (a copy, a slice of a
                # stacked weight) has no op_name; some keep a source line.
                unscoped_label[mid] = "%s @ %s" % (
                    re.sub(r"[.\d]+$", "", meta[mid].display_name
                           or meta[mid].name[:40]),
                    os.path.basename(str(st.get("source", "-"))))
                for key, val in st.items():
                    m = isinstance(val, str) and _scope_in(val)
                    if m:
                        scope_of[mid] = (m, key)
                        example = example or {
                            "name": meta[mid].name[:160],
                            "stats": {k: str(v)[:160] for k, v in st.items()}}
                        break
            mods.sort(key=lambda m: m[1])
            starts = [m[1] for m in mods]

            def module_at(t):
                i = bisect.bisect_right(starts, t) - 1
                if i >= 0 and t <= mods[i][1] + mods[i][2]:
                    return mods[i][0]
                return "(no module)"

            # self_times keeps the order of its sorted input
            order = sorted(ops, key=lambda ev: (ev[1], -ev[2]))
            selfs = self_times([(str(m), s, d) for m, s, d in order])
            scope_of.update(_control_flow_scopes(order, scope_of))
            by_prog = collections.defaultdict(float)
            tick_scopes = collections.defaultdict(float)
            via_count = collections.Counter()
            # (scope, operation @ source line) -> [runs, self seconds]
            tick_ops = collections.defaultdict(lambda: [0, 0.0])
            for (mid, s, d), (_, self_s) in zip(order, selfs):
                prog = module_at(s + d / 2)
                by_prog[_short(prog)] += self_s
                if TICK_RE.match(prog):
                    scope, via = scope_of[mid]
                    tick_scopes[scope or "none"] += self_s
                    op = tick_ops[scope, unscoped_label[mid]]
                    op[0] += 1
                    op[1] += self_s
                    if scope:
                        via_count[via] += 1
            tick_total = sum(tick_scopes.values())
            longest = sorted(tick_ops.items(), key=lambda kv: -kv[1][1])
            out["devices"][plane.name] = {
                "module_runs": dict(collections.Counter(
                    _short(m[0]) for m in mods)),
                "self_seconds_by_program": dict(sorted(
                    by_prog.items(), key=lambda kv: -kv[1])[:25]),
                "burst_tick_seconds_by_scope": dict(tick_scopes),
                "burst_tick_share_by_scope": {
                    k: v / tick_total for k, v in tick_scopes.items()}
                if tick_total else {},
                "burst_tick_unscoped_top": dict(
                    [(label, secs) for (scope, label), (_, secs) in longest
                     if scope is None][:8]),
                # "<scope>: <operation> @ <source line>" -> [runs, self
                # seconds]: a kernel (a custom call) keeps its own name.
                "burst_tick_top_ops": {
                    f"{scope or 'none'}: {label}": runs
                    for (scope, label), runs in longest[:16]},
                "scope_carried_by_stat": dict(via_count),
                "op_metadata_stat_names": dict(stat_names),
                "example_scoped_op": example,
                "modules": mods,
            }
        elif plane.name.startswith("/host:CPU"):
            for i, line in enumerate(plane.lines):
                thread = f"{line.name}#{i}"
                for mid, s, d, ev in _events(plane, line):
                    name = meta[mid].name
                    if name.startswith("stage."):
                        stage_events.append({
                            "name": name, "thread": thread, "start_s": s,
                            "dur_s": d, "args": _stats(plane, ev.stats)})
                    elif d >= LONG_HOST_EVENT_S:
                        host_threads.setdefault(thread, []).append(
                            (name, s, d))
    agg = collections.defaultdict(lambda: [0, 0.0, 0])
    for ev in stage_events:
        agg[ev["name"]][0] += 1
        agg[ev["name"]][1] += ev["dur_s"]
        agg[ev["name"]][2] += "session" in ev["args"]
    out["host"]["stage_spans"] = {
        k: {"count": n, "seconds": s, "with_session": k_sid}
        for k, (n, s, k_sid) in sorted(agg.items())}
    out["host"]["one_request"] = _one_request(stage_events, host_threads,
                                              out["devices"])
    for dev in out["devices"].values():
        del dev["modules"]
    return out


def _one_request(stage_events, host_threads, devices):
    """The first request whose prefill_wait AND first_token both lie in the
    trace: its spans, the rounds that ran meanwhile, the runtime's long
    host events on its thread (where it stood still), the device programs
    between the start of its wait and the end of its first token."""
    by_sid = collections.defaultdict(list)
    for ev in stage_events:
        sid = ev["args"].get("session")
        if sid is not None:
            by_sid[sid].append(ev)
    for sid, evs in sorted(by_sid.items(),
                           key=lambda kv: min(e["start_s"] for e in kv[1])):
        names = {e["name"] for e in evs}
        if {"stage.prefill_wait", "stage.first_token"} <= names:
            t0 = min(e["start_s"] for e in evs)
            t1 = max(e["start_s"] + e["dur_s"] for e in evs
                     if e["name"] == "stage.first_token")
            mine = [e for e in evs if t0 <= e["start_s"] <= t1]
            rounds = [e for e in stage_events
                      if e["name"] == "stage.device"
                      and e["start_s"] < t1
                      and e["start_s"] + e["dur_s"] > t0]
            thread = mine[0]["thread"]
            stalls = [{"name": n, "thread": thread, "start_s": s,
                       "dur_s": d, "args": {}}
                      for n, s, d in host_threads.get(thread, ())
                      if t0 <= s <= t1]
            spans = [{"name": e["name"], "thread": e["thread"],
                      "at_ms": 1e3 * (e["start_s"] - t0),
                      "ms": 1e3 * e["dur_s"], "args": e["args"]}
                     for e in sorted(mine + rounds + stalls,
                                     key=lambda e: e["start_s"])]
            progs = collections.Counter()
            for dev in devices.values():
                for name, s, d in dev["modules"]:
                    if t0 <= s <= t1:
                        progs[_short(name)] += 1
                break       # host spans are on the first device's process
            return {"session": sid, "ms": 1e3 * (t1 - t0), "spans": spans,
                    "device_programs_meanwhile": dict(progs)}
    return None


def cmd_reduce(trace_dir: str, out_path: str) -> int:
    from perfbench.harness.trace import find_xplanes

    files = find_xplanes(trace_dir)
    if not files:
        print(f"no *.xplane.pb under {trace_dir}", file=sys.stderr)
        return 1
    result = [reduce_file(p) for p in files]
    with open(out_path, "w") as f:
        json.dump(result, f, indent=1)
    for r in result:
        for dev, d in r["devices"].items():
            print("SECTIONS", json.dumps({
                "device": dev, "module_runs": d["module_runs"],
                "burst_tick_seconds_by_scope":
                    d["burst_tick_seconds_by_scope"],
                "burst_tick_share_by_scope": d["burst_tick_share_by_scope"],
                "scope_carried_by_stat": d["scope_carried_by_stat"],
                "unscoped_top": d["burst_tick_unscoped_top"],
                "top_ops": d["burst_tick_top_ops"]}))
        print("SPANS", json.dumps(r["host"]["stage_spans"]))
        print("REQUEST", json.dumps(r["host"]["one_request"]))
    return 0


def cmd_run(argv) -> int:
    """perfbench/run.py's traced run, with ``reduce`` made on the trace
    directory before the harness's own reduction deletes it."""
    from perfbench import run as bench

    harness_reduce = bench.reduce_trace

    def reduce_both(res, out_dir):
        out = os.path.join(out_dir, "sections.json")
        rc = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "reduce",
             res["trace_dir"], out],
            env=bench.host_env(bench.base_env()), cwd=ROOT).returncode
        if rc:
            print(f"trace_sections: reduce exit {rc}", flush=True)
        return harness_reduce(res, out_dir)

    bench.reduce_trace = reduce_both
    sys.argv = ["perfbench/run.py", *argv, "--trace", "1"]
    return bench.main()


def main() -> int:
    if len(sys.argv) >= 4 and sys.argv[1] == "reduce":
        return cmd_reduce(sys.argv[2], sys.argv[3])
    if len(sys.argv) >= 2 and sys.argv[1] == "run":
        return cmd_run(sys.argv[2:])
    print(__doc__, file=sys.stderr)
    return 2


if __name__ == "__main__":
    sys.exit(main())
