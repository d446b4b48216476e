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
and the rounds longer than each bucket of `server_decode_round_seconds`
(`period_s`, `hold_s`: the same for the periods and the holds);
the period of a round by its parts, as three identities whose two sides
are measured apart (``identities``: period = exec + back + hold; rejoin =
reply + away + request, away being the client's turnaround and the wire;
exec = host phases + launch lag + ticks + rest; the last needs
``trace_summary.json``, which a traced run leaves) and the rounds split by
whether their program was enqueued behind a prompt's (exec = share x behind
+ (1 - share) x clear, exact; queued = share x (behind - clear); clear =
host phases + ticks + rest, the rest being the launch; a prompt's device
time over the whole window against the traced stretch's); what the program
recorded of its stalls (rounds over 4 x their predecessor, those behind a
prompt apart, seconds by part); and the host-device transfers a burst
round issued, up and down (``transfers_per_round``); and the prompts that
took the engine's lock for a program of their own, by whether a round's
step was in flight then (``prefill_enqueued``: ``burst``, behind the running
step; ``gap``, between two; ``burst_share`` is the share of prompts for
which the lock that is free under a running step engages). One JSON line a
run; reads files only."""

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


PERIOD_METRICS = ("round_period_ms", "round_exec_ms", "round_back_ms",
                  "round_hold_ms", "hold_prefill_ms_per_round",
                  "round_rejoin_ms", "reply_leg_ms", "request_leg_ms",
                  "engine_host_ms_per_round", "burst_launch_lag_ms",
                  "round_behind_prefill_share", "round_exec_clear_ms",
                  "round_exec_behind_prefill_ms",
                  "burst_queued_ms_per_round", "prefill_ready_ms",
                  "prefill_device_ms")


def identities(ctx: dict, man: Manifest, gap_p50_ms=None) -> dict:
    """The period of a round by its parts, each identity's two sides read
    apart: ``[whole, [parts...], whole - sum(parts)]`` in ms, None where a
    series is missing (an untraced run, a program without it)."""
    v = {n: readers.read_metric(man, n, ctx) for n in PERIOD_METRICS}
    prog = readers.tick_program(ctx)
    if prog is not None:
        v["ticks_ms"] = prog["mean_s"] * 1e3         # a burst's whole run
    if None not in (v["round_rejoin_ms"], v["reply_leg_ms"],
                    v["request_leg_ms"]):
        # the client's turnaround and the wire: what the legs leave
        v["away_ms"] = (v["round_rejoin_ms"] - v["reply_leg_ms"]
                        - v["request_leg_ms"])
    if None not in (v["round_behind_prefill_share"],
                    v["round_exec_behind_prefill_ms"],
                    v["round_exec_clear_ms"]):
        # a mean round's two kinds, and the wait that tells them apart
        share = v["round_behind_prefill_share"] / 100.0
        v["behind_part_ms"] = share * v["round_exec_behind_prefill_ms"]
        v["clear_part_ms"] = (1.0 - share) * v["round_exec_clear_ms"]
        v["queue_ms"] = share * (v["round_exec_behind_prefill_ms"]
                                 - v["round_exec_clear_ms"])

    def line(whole, *parts):
        vals = [v.get(n) for n in (whole,) + parts]
        if any(x is None for x in vals):
            return None
        return [vals[0], vals[1:], vals[0] - sum(vals[1:])]

    prefill_s = readers.counter_delta(
        ctx, 'server_phase_seconds_sum{phase="prefill"}')
    rounds = readers.counter_delta(ctx, "server_decode_round_seconds_count")
    out = {"period = exec + back + hold": line(
               "round_period_ms", "round_exec_ms", "round_back_ms",
               "round_hold_ms"),
           "rejoin = reply + away + request": line(
               "round_rejoin_ms", "reply_leg_ms", "away_ms",
               "request_leg_ms"),
           "exec = host + lag + ticks + rest": line(
               "round_exec_ms", "engine_host_ms_per_round",
               "burst_launch_lag_ms", "ticks_ms"),
           "exec = share x behind + (1 - share) x clear": line(
               "round_exec_ms", "behind_part_ms", "clear_part_ms"),
           "queued = share x (behind - clear)": line(
               "burst_queued_ms_per_round", "queue_ms"),
           # the rest is the launch: 2-4 ms where nothing is in its way
           "clear = host + ticks + rest": line(
               "round_exec_clear_ms", "engine_host_ms_per_round",
               "ticks_ms"),
           # one quantity, the whole window against the traced stretch
           "prefill_ready = prefill_device + rest": line(
               "prefill_ready_ms", "prefill_device_ms"),
           # every prefill program's time under the lock a round (traced
           # runs: the phase), and the part of it that fell into a hold;
           # the rest fell between a round's results and the next's opening
           "prefill_ms_per_round": (
               None if prefill_s is None or not rounds
               else prefill_s / rounds * 1e3),
           "hold_prefill_ms_per_round": v["hold_prefill_ms_per_round"]}
    ticks = readers.histogram_mean(ctx, {"family": "server_burst_ticks"})
    if v["round_period_ms"] is not None and ticks and gap_p50_ms:
        # the program's clock against the client's: what a token costs
        out["period / ticks over gap_p50_ms"] = (
            v["round_period_ms"] / ticks / gap_p50_ms)
    return out


def stalls_report(ctx: dict) -> dict:
    """What the program recorded of its stalls in the window."""
    fam = "server_round_stall_seconds_total"
    parts = sorted({re.search(r'part="([^"]+)"', k).group(1)
                    for peer in ctx["counters_after"].values() for k in peer
                    if k.startswith(fam + "{")})
    if readers.counter_delta(
            ctx, "server_round_behind_prefill_seconds_count") is None:
        # a program before the label: every stall is one to look into
        faults = readers.counter_delta(ctx, "server_round_stalls_total")
        queues = None
    else:                     # a child is there once it has counted
        faults, queues = (readers.counter_delta(
            ctx, f'server_round_stalls_total{{behind_prefill="{b}"}}') or 0.0
            for b in ("false", "true"))
    return {"rounds": faults,
            # a long prompt ahead of a short round: a queue, no fault
            "rounds_behind_prefill": queues,
            "seconds_by_part": {
                p: readers.counter_delta(ctx, f'{fam}{{part="{p}"}}')
                for p in parts}}


def transfers_report(ctx: dict) -> dict:
    """Host-device transfers the burst rounds of the window issued, per
    direction, over the burst programs dispatched: 2 up (3 on an engine
    with a rider lane) and 1 down a round; a request whose ids arrived on
    the device adds a read (`server_burst_transfers_total`)."""
    rounds = readers.counter_delta(ctx, "server_burst_dispatches_total")
    moved = {d: readers.counter_delta(
                 ctx, f'server_burst_transfers_total{{dir="{d}"}}')
             for d in ("up", "down")}
    return {d: None if n is None or not rounds else n / rounds
            for d, n in moved.items()}


def prefills_report(ctx: dict) -> dict:
    """The window's prompts that enqueued a program of their own, by what
    the device was doing when they took the engine's lock
    (`server_prefill_enqueued_total`); None where a run's scrapes lack the
    series (a program whose leader held the lock through its step), a share
    only where some prompt was counted."""
    by = {d: readers.counter_delta(
              ctx, f'server_prefill_enqueued_total{{during="{d}"}}')
          for d in ("burst", "gap")}
    if all(n is None for n in by.values()):
        return {"burst": None, "gap": None, "burst_share": None}
    by = {d: n or 0.0 for d, n in by.items()}     # a child: once counted
    total = sum(by.values())
    return {**by, "burst_share": by["burst"] / total if total else None}


def rounds_report(run_dir: str, man: Manifest, gap_p50_ms=None) -> dict:
    w0, w1 = window_of(run_dir)
    ctx = {"w0": w0, "w1": w1,
           "counters_before": load_counters(
               os.path.join(run_dir, "metrics_before.jsonl")),
           "counters_after": load_counters(
               os.path.join(run_dir, "metrics_after.jsonl"))}
    if not ctx["counters_after"]:
        return {}
    summary = os.path.join(run_dir, "trace_summary.json")
    if os.path.exists(summary):
        with open(summary) as f:
            ctx["trace"] = json.load(f)
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
    # a stall outside a round's step (the process stood still while a
    # leader held its round) is no long round: it shows here
    out["period_s"] = buckets(ctx, "server_round_period_seconds")
    out["hold_s"] = buckets(ctx, "server_round_hold_seconds")
    out["identities"] = identities(ctx, man, gap_p50_ms)
    out["stalls_recorded"] = stalls_report(ctx)
    out["transfers_per_round"] = transfers_report(ctx)
    out["prefill_enqueued"] = prefills_report(ctx)
    return out


def main() -> int:
    man = Manifest(ROOT)
    for run_dir in sys.argv[1:]:
        line = {"run": os.path.basename(os.path.normpath(run_dir))}
        line.update(gaps_report(run_dir))
        line.update(rounds_report(run_dir, man, line.get("gap_p50_ms")))
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
