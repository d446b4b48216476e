#!/usr/bin/env python3
"""What a benchmark run's files say about how its rounds closed.

    python3 scripts/round_close_report.py <run directory> [...]

A run directory is what `perfbench/run.py` leaves (``--out``, by default
``chiprun_out/perfbench/<cell>.s<seed>.t<trace>``). From ``records.jsonl``,
every run: the per-token gaps of the window, where the 75th percentile
sits among them (the ranks the one-round atom spans: gaps within 25% of
the median of the lower half), and the stalls (a request's deliveries over
10 x the median apart: a round ten times as long as the cell's).
From the two scrapes of a traced run (``metrics_before/after.jsonl``):
rounds by what closed them, `round_bound_share` and `round_rejoin_ms` by
the benchmark's own readers, the rejoin histogram, fill beside slots held,
and the rounds longer than each bucket of `server_decode_round_seconds`.
One JSON line a run; reads files only."""

import json
import os
import re
import statistics
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from perfbench.harness import readers, stats  # noqa: E402
from perfbench.harness.manifest import Manifest  # noqa: E402
from perfbench.run import load_counters  # noqa: E402


def window_of(run_dir: str):
    with open(os.path.join(run_dir, "load.log")) as f:
        m = re.findall(r'"w0": ([0-9.]+), "w1": ([0-9.]+)', f.read())
    return float(m[-1][0]), float(m[-1][1])


def gaps_report(run_dir: str) -> dict:
    with open(os.path.join(run_dir, "records.jsonl")) as f:
        records = [json.loads(line) for line in f]
    w0, w1 = window_of(run_dir)
    gaps = sorted(stats.gap_samples_ms(records, w0, w1))
    if not gaps:
        return {"gap_samples": 0}
    # seconds between one request's deliveries: a round, or two
    apart = [b[0] - a[0] for r in records if not r.get("error")
             for a, b in zip(r["deliveries"], r["deliveries"][1:])
             if w0 <= b[0] < w1]
    round_s = statistics.median(apart)
    one = statistics.median(gaps[:len(gaps) // 2 + 1])   # a one-round gap
    atom = [i for i, g in enumerate(gaps) if abs(g - one) <= 0.25 * one]
    return {"gap_samples": len(gaps),
            "gap_p50_ms": stats.percentile(gaps, 50),
            "gap_p75_ms": stats.percentile(gaps, 75),
            "gap_p95_ms": stats.percentile(gaps, 95),
            "one_round_gap_ms": one,
            "one_round_ranks": [atom[0] / len(gaps),
                                (atom[-1] + 1) / len(gaps)],
            "one_round_share": len(atom) / len(gaps),
            "deliveries_apart_median_s": round_s,
            "deliveries_apart_max_s": max(apart),
            "deliveries_over_10x_median_apart": sum(
                d > 10 * round_s for d in apart),
            "tokens_per_s": stats.tokens_per_s(records, w0, w1)}


def buckets(ctx: dict, family: str) -> dict:
    """A histogram's window deltas by upper bound (not cumulative)."""
    out, below = {}, 0.0
    keys = [k for peer in ctx["counters_after"].values() for k in peer
            if k.startswith(family + "_bucket{")]
    for key in sorted(set(keys), key=lambda k: float(
            re.search(r'le="([^"]+)"', k).group(1).replace("+Inf", "inf"))):
        total = readers.counter_delta(ctx, key) or 0.0
        if total - below:
            out[re.search(r'le="([^"]+)"', key).group(1)] = total - below
        below = total
    return out


def rounds_report(run_dir: str, man: Manifest) -> dict:
    ctx = {"counters_before": load_counters(
               os.path.join(run_dir, "metrics_before.jsonl")),
           "counters_after": load_counters(
               os.path.join(run_dir, "metrics_after.jsonl"))}
    if not ctx["counters_after"]:
        return {}
    out = {"closed_by": {
        by: readers.counter_delta(
            ctx, f'server_round_closed_total{{by="{by}"}}')
        for by in ("joined", "bound", "window")}}
    for name in ("round_bound_share", "round_rejoin_ms",
                 "round_fill_sessions", "round_slots_held", "round_wait_ms",
                 "round_exec_ms"):
        out[name] = readers.read_metric(man, name, ctx)
    out["rejoin_s"] = buckets(ctx, "server_round_rejoin_seconds")
    out["round_s"] = buckets(ctx, "server_decode_round_seconds")
    return out


def main() -> int:
    man = Manifest(ROOT)
    for run_dir in sys.argv[1:]:
        line = {"run": os.path.basename(os.path.normpath(run_dir))}
        line.update(gaps_report(run_dir))
        line.update(rounds_report(run_dir, man))
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
