"""Per-layer metric readers. A metric is ``layer_metrics/<name>.json``: its
``reader`` names one of the stock readers below (with ``params``), or a
``<name>.py`` beside it gives ``read(ctx, params)``. A reader that finds
nothing to read returns None and the metric is left out of the line.

``ctx`` is what one traced run gathered: ``records`` with the window
``w0``/``w1``, ``counters_before``/``counters_after`` (each server's
``metrics`` verb, parsed), ``trace`` (harness/trace.py's summary), ``setup``
(the parts of set-up, seconds), ``hf`` (the published config), ``config``,
``reference_file`` (the configuration's own module, where its file names
one), ``traffic`` and ``device``."""

from __future__ import annotations

import re
import statistics
from typing import Callable, Dict, Optional

from . import roofline, stats
from .manifest import defined_names, load_module


def parse_prometheus(text: str) -> Dict[str, float]:
    out = {}
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        key, _, val = line.rpartition(" ")
        try:
            out[key] = float(val)
        except ValueError:
            continue
    return out


def counter_delta(ctx: dict, key: str) -> Optional[float]:
    """Summed over the servers: how far a series moved in the window."""
    total, seen = 0.0, False
    for peer, after in ctx.get("counters_after", {}).items():
        before = ctx.get("counters_before", {}).get(peer, {})
        if key in after:
            total += after[key] - before.get(key, 0.0)
            seen = True
    return total if seen else None


def tick_program(ctx: dict) -> Optional[dict]:
    """The device program that runs the decode ticks of a burst cell: the
    one whose whole runs last longest. A round is N ticks and each reads
    every weight once, so it cannot take less than N x (bytes of the
    weights / HBM rate): 61 ms for gpt2-xl and 47 ms for qwen2-7b-int8 at
    N = 16 on a v5e, where the longest prefill these cells send (488
    tokens) takes 7 ms (my chip runs, PR 24). A cell without bursts needs
    a reader of its own."""
    progs = [p for p in (ctx.get("trace") or {}).get("programs", {}).values()
             if p.get("mean_s")]
    return max(progs, key=lambda p: p["mean_s"]) if progs else None


def device_s_per_tick(ctx: dict) -> Optional[float]:
    """Mean device seconds of one whole run of the tick program, over the
    ticks a run holds (the route's burst length)."""
    prog = tick_program(ctx)
    burst = int(ctx["traffic"]["route"].get("burst", 0))
    if prog is None or burst < 1:
        return None
    return prog["mean_s"] / burst


# -- stock readers ----------------------------------------------------------

def records_stat(ctx, params):
    """A statistic of the per-request records over the window: ttft_mean |
    ttft_p95 | gap_p95 | tokens_per_s."""
    recs, w0, w1 = ctx["records"], ctx["w0"], ctx["w1"]
    stat = params["stat"]
    if stat == "tokens_per_s":
        return stats.tokens_per_s(recs, w0, w1)
    if stat == "gap_p95":
        vals = stats.gap_samples_ms(recs, w0, w1)
        return stats.percentile(vals, 95) if vals else None
    timeout = float(ctx["traffic"].get("request_timeout_s", 120.0))
    vals = stats.ttft_samples_ms(recs, w0, w1, timeout)
    if not vals:
        return None
    if stat == "ttft_mean":
        return statistics.fmean(vals)
    if stat == "ttft_p95":
        return stats.percentile(vals, 95)
    raise ValueError(f"records_stat: unknown stat {stat!r}")


def histogram_mean(ctx, params):
    """Mean of a server histogram over the window: delta sum / delta
    count, times ``scale``."""
    fam = params["family"]
    n = counter_delta(ctx, fam + "_count")
    s = counter_delta(ctx, fam + "_sum")
    if not n or s is None:
        return None
    return s / n * params.get("scale", 1.0)


def phase_sum_per_round(ctx, params):
    """Phase profiler: the listed phases' seconds over the window, per
    occurrence of the first one, times ``scale``."""
    total, first_n = 0.0, None
    for i, ph in enumerate(params["phases"]):
        s = counter_delta(ctx, f'server_phase_seconds_sum{{phase="{ph}"}}')
        n = counter_delta(ctx, f'server_phase_seconds_count{{phase="{ph}"}}')
        if s is None or not n:
            return None
        total += s
        if i == 0:
            first_n = n
    return total / first_n * params.get("scale", 1.0)


def trace_ms_per_tick(ctx, params):
    v = device_s_per_tick(ctx)
    return None if v is None else v * 1e3


def trace_idle_share(ctx, params):
    """1 - busy over the stretch the trace RECORDED (first to last device
    operation), not over the profiler's start-to-stop wall time: a trace
    that starts late or ends early would read as idle (PERF.md, PR 27)."""
    tr = ctx.get("trace")
    if not tr or not tr.get("extent_s") or not tr.get("busy_s"):
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["extent_s"])


def tick_cost_of(ctx: dict) -> Callable:
    """The count of a tick's bytes and operations: the configuration's own
    (``tick_cost`` in the module its file names, which also gets ``ctx``
    for what only a run can count), else the stock one. The module is
    only run where it defines the function: it imports JAX, the parent
    of a run otherwise does not."""
    own = ctx.get("reference_file")
    if own and "tick_cost" in defined_names(own):
        fn = load_module(own).tick_cost
        return lambda hf, **kw: fn(hf, ctx=ctx, **kw)
    return roofline.tick_cost


def served_layers(hf: dict) -> int:
    """The depth the cell serves: the published depth key, as cut."""
    for key in ("num_hidden_layers", "n_layer", "n_layers", "num_layers"):
        if key in hf:
            return int(hf[key])
    raise KeyError(f"no depth key among {sorted(hf)}")


def step_roofline(ctx, params):
    """The least time one tick needs on this chip (weights once at the
    width the configuration's file states, ``weight_bytes``; KV rows in
    use; head; the larger of the byte and the operation bound) over the
    device time a tick took."""
    tick = device_s_per_tick(ctx)
    fill = histogram_mean(ctx, {"family": "server_batch_fill_sessions"})
    rows = stats.ctx_rows_in_use(ctx["records"], ctx["w0"], ctx["w1"])
    if tick is None or fill is None or rows is None:
        return None
    hf = ctx["hf"]
    cost = tick_cost_of(ctx)(
        hf, layers=served_layers(hf), sessions=fill,
        kv_rows=rows, weight_bytes=ctx["config"]["weight_bytes"])
    least, bound = roofline.roofline_s(cost, ctx["device"]["kind"])
    ctx.setdefault("notes", {})["step_roofline_bound"] = bound
    return 100.0 * least / tick


def kernel_roofline(ctx, params):
    """A kernel's call sites against their roofline. Time: the kernel's
    own events (``call_pattern`` on the operation's HLO text) PLUS the
    operations that stage its weights (``staging_pattern``) — XLA copies
    each layer's int8 weight out of the layer stack before the call, and
    where it parks the copy in fast memory the kernel alone would read
    above the HBM roofline. Least time: each site's bytes and operations
    once per group of calls (one call per site)."""
    tr = ctx.get("trace")
    if not tr:
        return None
    call = re.compile(params["call_pattern"])
    stage = re.compile(params["staging_pattern"])
    calls = sum(v["count"] for k, v in tr["ops"].items() if call.search(k))
    secs = sum(v["seconds"] for k, v in tr["ops"].items()
               if call.search(k) or stage.search(k))
    if not calls or not secs:
        return None
    m = int(params["m"])
    sites = [s for s in roofline.matmul_sites(ctx["hf"])
             if s[0] in params["sites"]]
    per_group = [roofline.int8_site_cost(m, k, n) for _, k, n in sites]
    groups = calls / len(sites)
    p = roofline.peaks(ctx["device"]["kind"])
    least = groups * max(
        sum(c["bytes"] for c in per_group) / p["hbm_bytes_per_s"],
        sum(c["flops"] for c in per_group) / p["bf16_flops"])
    return 100.0 * least / secs


def setup_part(ctx, params):
    return ctx["setup"].get(params["part"])


STOCK: Dict[str, Callable] = {
    "records_stat": records_stat,
    "histogram_mean": histogram_mean,
    "phase_sum_per_round": phase_sum_per_round,
    "trace_ms_per_tick": trace_ms_per_tick,
    "trace_idle_share": trace_idle_share,
    "step_roofline": step_roofline,
    "kernel_roofline": kernel_roofline,
    "setup_part": setup_part,
}


def read_metric(manifest, name: str, ctx: dict) -> Optional[float]:
    desc = manifest.layer_metric(name)
    if "as" in desc:
        # one quantity, split by the end-to-end metric its cells report:
        # read it as the other metric's file says
        name = desc["as"]
        desc = manifest.layer_metric(name)
    own = manifest.layer_reader_file(name)
    if own:
        fn = load_module(own).read
    else:
        fn = STOCK[desc["reader"]]
    value = fn(ctx, desc.get("params", {}))
    return None if value is None else float(value)
