"""Reduction of one profiler trace (``*.xplane.pb``) to what the per-layer
metrics read: device busy seconds (the union of the intervals in which an
operation ran), time per operation name, and the longest idle gaps with
what the host was doing in them. Checked against a small recorded summary
in the tests; the same code runs on a chip's trace.

Usage: python -m perfbench.harness.trace <dir-with-trace> <out.json>"""

from __future__ import annotations

import bisect
import glob
import json
import os
import re
import sys
from typing import Dict, Iterable, List, Optional, Tuple

Event = Tuple[str, float, float]          # name, start_s, duration_s


def union_seconds(intervals: Iterable[Tuple[float, float]]) -> float:
    """Total length covered by (start, end) intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def idle_gaps(intervals: Iterable[Tuple[float, float]], t0: float,
              t1: float) -> List[Tuple[float, float]]:
    """(start, end) of every stretch of [t0, t1] no interval covers."""
    gaps, edge = [], t0
    for s, e in sorted(intervals):
        if s > edge:
            gaps.append((edge, min(s, t1)))
        edge = max(edge, e)
    if edge < t1:
        gaps.append((edge, t1))
    return [(s, e) for s, e in gaps if e > s]


def self_times(events: List[Event]) -> List[Tuple[str, float]]:
    """(name, self seconds) per event: its duration less the part its
    nested events cover (a ``while`` holds its body's operations), so that
    summing over names never counts an instant twice."""
    out: List[List] = []
    stack: List[Tuple[float, int]] = []          # (end, index into out)
    for name, s, d in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        while stack and stack[-1][0] <= s:
            stack.pop()
        if stack:
            out[stack[-1][1]][1] -= min(d, stack[-1][0] - s)
        out.append([name, d])
        stack.append((s + d, len(out) - 1))
    return [(n, max(0.0, v)) for n, v in out]


def _clean(name: str) -> str:
    return re.sub(r"[^A-Za-z0-9_.\-]+", "_", name)[:80]


def label_gaps(gaps: List[Tuple[float, float]], host: List[Event],
               top: int = 10, consider: int = 400) -> List[List]:
    """Attribute the longest gaps to host spans: the innermost host span
    covering the gap's middle (``inside_<name>``), else the next one to
    start (``before_<name>``); seconds summed per label."""
    host = sorted(host, key=lambda ev: ev[1])
    starts = [ev[1] for ev in host]
    sums: Dict[str, float] = {}
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:consider]:
        mid = (s + e) / 2
        i = bisect.bisect_right(starts, mid)
        best = None
        for name, hs, hd in host[max(0, i - 64):i]:
            if hs <= mid <= hs + hd and (best is None or hd < best[1]):
                best = (name, hd)
        if best is not None:
            label = "inside_" + _clean(best[0])
        elif i < len(host):
            label = "before_" + _clean(host[i][0])
        else:
            label = "no_host_span"
        sums[label] = sums.get(label, 0.0) + (e - s)
    return [[k, v] for k, v in
            sorted(sums.items(), key=lambda kv: -kv[1])[:top]]


def program_stats(modules: Dict[str, List[Event]]) -> Dict[str, dict]:
    """Per device program (an ``XLA Modules`` event name): how often it
    ran and its mean and total device seconds. A run the trace cut at
    either edge is recorded short or not at all, so the mean is taken
    over the runs that are not the first or last event of their device."""
    out: Dict[str, dict] = {}
    for evs in modules.values():
        evs = sorted(evs, key=lambda ev: ev[1])
        for i, (name, _, d) in enumerate(evs):
            st = out.setdefault(name, {"count": 0, "seconds": 0.0,
                                       "whole": 0, "whole_seconds": 0.0})
            st["count"] += 1
            st["seconds"] += d
            if 0 < i < len(evs) - 1:
                st["whole"] += 1
                st["whole_seconds"] += d
    for st in out.values():
        st["mean_s"] = (st["whole_seconds"] / st["whole"] if st["whole"]
                        else None)
    return out


def summarize(device_ops: Dict[str, List[Event]], host: List[Event],
              window_s: Optional[float] = None,
              modules: Optional[Dict[str, List[Event]]] = None) -> dict:
    """device_ops: per device plane, its operation events. Returns busy
    seconds (mean over devices), per-name totals and labelled gaps."""
    per_dev_busy, per_name, counts = [], {}, {}
    extent, gaps, recorded = 0.0, [], {}
    for i, (dev, evs) in enumerate(sorted(device_ops.items())):
        iv = [(s, s + d) for _, s, d in evs]
        per_dev_busy.append(union_seconds(iv))
        for name, d in self_times(evs):
            per_name[name] = per_name.get(name, 0.0) + d
            counts[name] = counts.get(name, 0) + 1
        t_first = min(s for s, _ in iv)
        t_last = max(e for _, e in iv)
        extent = max(extent, t_last - t_first)
        if i == 0:          # gaps are labelled on the first device's clock
            gaps = idle_gaps(iv, t_first, t_last)
            recorded = {"device_events": len(evs), "first_op_s": t_first,
                        "last_op_s": t_last}
    if host:                # the same clock: the host tracer's own stretch
        recorded["host_events"] = len(host)
        recorded["first_host_s"] = min(s for _, s, _ in host)
        recorded["last_host_s"] = max(s + d for _, s, d in host)
    n = max(1, len(device_ops))
    window = window_s or extent
    top_ops = sorted(per_name.items(), key=lambda kv: -kv[1])
    return {
        "devices": len(device_ops),
        "busy_s": sum(per_dev_busy) / n,
        "op_seconds_total": sum(per_name.values()) / n,
        "window_s": window,
        "extent_s": extent,
        "recorded": recorded,
        "programs": program_stats(modules or {}),
        "ops": {k: {"seconds": v / n, "count": counts[k] / n}
                for k, v in top_ops[:400]},
        "breakdown": {
            "device_ops": [[_clean(k), v / n] for k, v in top_ops[:10]],
            "idle_gaps": label_gaps(gaps, host),
        },
    }


def read_xplane(path: str):
    """Device operation events and program runs per device plane, and host
    spans, in seconds on the trace's own clock."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    device_ops: Dict[str, List[Event]] = {}
    modules: Dict[str, List[Event]] = {}
    host: List[Event] = []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            lines = list(plane.lines)
            ops = [l for l in lines if l.name == "XLA Ops"]
            evs = []
            for line in ops:
                for ev in line.events:      # the name is the HLO text
                    evs.append((ev.name[:200], ev.start_ns * 1e-9,
                                ev.duration_ns * 1e-9))
            if evs:
                device_ops[plane.name] = evs
            mods = [(ev.name, ev.start_ns * 1e-9, ev.duration_ns * 1e-9)
                    for l in lines if l.name == "XLA Modules"
                    for ev in l.events]
            if mods:
                modules[plane.name] = mods
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for ev in line.events:
                    host.append((ev.name, ev.start_ns * 1e-9,
                                 ev.duration_ns * 1e-9))
    return device_ops, modules, host


def find_xplanes(trace_dir: str) -> List[str]:
    return sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                            recursive=True))


def main() -> int:
    trace_dir, out = sys.argv[1], sys.argv[2]
    window_s = float(sys.argv[3]) if len(sys.argv) > 3 else None
    files = find_xplanes(trace_dir)
    if not files:
        print(f"no *.xplane.pb under {trace_dir}", file=sys.stderr)
        return 1
    device_ops: Dict[str, List[Event]] = {}
    modules: Dict[str, List[Event]] = {}
    host: List[Event] = []
    for i, path in enumerate(files):       # one file per traced process
        ops, mods, h = read_xplane(path)
        for dev, evs in ops.items():
            device_ops[f"{i}:{dev}"] = evs
        for dev, evs in mods.items():
            modules[f"{i}:{dev}"] = evs
        if i == 0:
            host = h
    summary = summarize(device_ops, host, window_s, modules)
    summary["files"] = len(files)
    with open(out, "w") as f:
        json.dump(summary, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
