"""Metric arithmetic on the load generator's per-request records.

A record: ``{"index", "session", "due", "sent", "deliveries": [[t, n],
...], "done", "stop", "error", "budget", "prompt_len"}``; times are seconds
on the load generator's monotonic clock. The window is ``[w0, w1)``."""

from __future__ import annotations

import math
import statistics
from typing import Dict, Iterable, List, Optional, Sequence


def percentile(values: Sequence[float], q: float) -> float:
    """Nearest-rank percentile (q in 0..100): the smallest value with at
    least q% of the samples at or below it. No interpolation, so a tail is
    always a value that was observed."""
    if not values:
        raise ValueError("percentile of no samples")
    s = sorted(values)
    rank = max(1, math.ceil(q / 100.0 * len(s)))
    return s[rank - 1]


def delivered_tokens(records: Iterable[dict], w0: float, w1: float) -> int:
    """Output tokens delivered inside the window. A session in flight at
    either edge counts its in-window deliveries; a failed request none."""
    total = 0
    for r in records:
        if r.get("error"):
            continue
        total += sum(n for t, n in r["deliveries"] if w0 <= t < w1)
    return total


def tokens_per_s(records: Iterable[dict], w0: float, w1: float) -> float:
    return delivered_tokens(records, w0, w1) / (w1 - w0)


def _gaps(records: Iterable[dict], w0: float, w1: float):
    """(seconds since the request's previous delivery, tokens in this one)
    for every delivery after a request's first that lands in the window."""
    for r in records:
        if r.get("error"):
            continue
        prev = None
        for t, n in r["deliveries"]:
            if prev is not None and n > 0 and w0 <= t < w1:
                yield t - prev, n
            prev = t


def gap_samples_ms(records: Iterable[dict], w0: float,
                   w1: float) -> List[float]:
    """For every delivery after a request's first, inside the window: the
    time since that request's previous delivery over the tokens in this
    one (a burst reply carries up to N, a per-step reply 1), in ms/token."""
    return [dt * 1e3 / n for dt, n in _gaps(records, w0, w1)]


def gap_mean_ms(records: Iterable[dict], w0: float,
                w1: float) -> Optional[float]:
    """The token-weighted mean of the same gaps: the time the window's
    deliveries (after a request's first) waited for, over the tokens they
    carried. Every gap counts by its tokens, so no rank sits on an edge
    between two round lengths."""
    waited = tokens = 0.0
    for dt, n in _gaps(records, w0, w1):
        waited += dt
        tokens += n
    return waited * 1e3 / tokens if tokens else None


def ttft_samples_ms(records: Iterable[dict], w0: float, w1: float,
                    timeout_s: float) -> List[float]:
    """Send (open loop: due) -> first token, over requests SENT in the
    window. A failed request, or one with no first token, counts as the
    request timeout."""
    out = []
    for r in records:
        start = r["due"] if r.get("due") is not None else r["sent"]
        if r["sent"] is None or not w0 <= r["sent"] < w1:
            continue
        if r.get("error") or not r["deliveries"]:
            out.append(timeout_s * 1e3)
        else:
            out.append((r["deliveries"][0][0] - start) * 1e3)
    return out


def request_counts(records: Iterable[dict], w0: float, w1: float) -> dict:
    """attempted: requests in flight at any instant of the window; failed:
    those that raised, by cause; stopped_early: ended before their budget
    for another reason than the window closing."""
    attempted = failed = early = finished = 0
    causes: Dict[str, int] = {}
    for r in records:
        if r["sent"] is None or r["sent"] >= w1:
            continue
        if r["done"] is not None and r["done"] < w0:
            continue
        attempted += 1
        if r.get("error"):
            failed += 1
            cause = r["error"].split(":")[0]
            causes[cause] = causes.get(cause, 0) + 1
            continue
        if r.get("stop") is None:
            continue                      # cut by the end of the window
        finished += 1
        got = sum(n for _, n in r["deliveries"])
        if got < r["budget"]:
            early += 1
    return {"attempted": attempted, "failed": failed, "causes": causes,
            "finished": finished, "stopped_early": early}


def lateness_ms(records: Iterable[dict]) -> List[float]:
    """Open loop: how late each request was sent after it fell due."""
    return [(r["sent"] - r["due"]) * 1e3 for r in records
            if r.get("due") is not None and r["sent"] is not None]


def spread(values: Sequence[float]) -> float:
    """Interquartile distance over the median, as the builder's contract
    measures it (``statistics.quantiles(values, n=4)``)."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def spread_dropping_farthest(values: Sequence[float]) -> float:
    """The driver's rule for tightness: leave out the run farthest from
    the median where that narrows the spread."""
    med = statistics.median(values)
    far = max(range(len(values)), key=lambda i: abs(values[i] - med))
    rest = [v for i, v in enumerate(values) if i != far]
    if len(rest) < 2:
        return spread(values)
    return min(spread(values), spread(rest))


def summarize(records: List[dict], w0: float, w1: float,
              timeout_s: float) -> dict:
    gaps = gap_samples_ms(records, w0, w1)
    ttft = ttft_samples_ms(records, w0, w1, timeout_s)
    late = lateness_ms(records)
    out = dict(request_counts(records, w0, w1))
    out.update({
        "window_s": w1 - w0,
        "delivered_tokens": delivered_tokens(records, w0, w1),
        "tokens_per_s": tokens_per_s(records, w0, w1),
        "gap_samples": len(gaps),
        "gap_p50_ms": percentile(gaps, 50) if gaps else None,
        "gap_p75_ms": percentile(gaps, 75) if gaps else None,
        "gap_p95_ms": percentile(gaps, 95) if gaps else None,
        "gap_mean_ms": gap_mean_ms(records, w0, w1),
        "ttft_samples": len(ttft),
        "ttft_mean_ms": statistics.fmean(ttft) if ttft else None,
        "ttft_p50_ms": percentile(ttft, 50) if ttft else None,
        "ttft_p95_ms": percentile(ttft, 95) if ttft else None,
        "lateness_p95_ms": percentile(late, 95) if late else None,
        "lateness_samples": len(late),
    })
    return out


def ctx_rows_in_use(records: Iterable[dict], w0: float, w1: float
                    ) -> Optional[float]:
    """Mean KV rows a session holds when one of its deliveries lands in
    the window: prompt + tokens delivered so far."""
    rows = []
    for r in records:
        if r.get("error"):
            continue
        sofar = 0
        for t, n in r["deliveries"]:
            sofar += n
            if w0 <= t < w1:
                rows.append(r["prompt_len"] + sofar)
    return statistics.fmean(rows) if rows else None
