"""`attn_rows_read_share`: the share of a cache layer's rows the window's
decode ticks read, from the two counters the batched engine keeps on the
host (`server_attn_rows_read_total` / `server_attn_rows_span_total`). The
reader gives the ratio of the two deltas, summed over the servers, and
finds nothing in a program without the series (the parent of the PR that
brought them) or in a window without a tick; the benchmark lists it in
every cell, and the CPU rehearsal of a cell prints it."""

import json
import os
import subprocess
import sys

import pytest

from perfbench.harness import readers
from perfbench.harness.manifest import Manifest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NAME = "attn_rows_read_share"


@pytest.fixture(scope="module")
def man():
    return Manifest(ROOT)


def series(read=None, span=None):
    text = "server_burst_tokens_total 5\n"
    if read is not None:
        text += f"server_attn_rows_read_total {read}\n"
    if span is not None:
        text += f"server_attn_rows_span_total {span}\n"
    return readers.parse_prometheus(text)


def test_the_metric_is_listed_in_every_cell(man):
    man.validate()
    (entry,) = [m for m in man.data["per_layer"] if m["name"] == NAME]
    assert (entry["unit"], entry["better"], entry["source"],
            entry["layer"], entry["moves"]) == (
        "%", "lower", "program_counter", "model step", "gap_p75_ms")
    for cell in (w["name"] for w in man.data["workloads"]):
        # under its own name where the cell reports gap_p75_ms, as
        # `<name>.open` where the cell is decided by gap_mean_ms
        (asked,) = [m for m in man.metrics_for(cell, "per_layer")
                    if m["name"] in (NAME, NAME + ".open")]
        assert (asked is entry) == (cell in entry["workloads"])
        assert asked["moves"] in {                # a gap metric
            m["name"] for m in man.metrics_for(cell, "end_to_end")}


@pytest.mark.parametrize("before,after,want", [
    # 5 of 8 blocks a tick over 100 ticks of 8 slots x 1024 rows
    ((0, 0), (100 * 5 * 128 * 8, 100 * 8 * 1024), 62.5),
    # a window that starts after the server has served a while
    ((4096, 8192), (4096 + 3 * 1024, 8192 + 4 * 1024), 75.0),
    # every tick read every row
    ((0, 0), (2048, 2048), 100.0),
    # ticks that found nobody active read nothing
    ((10, 20), (10, 20 + 4096), 0.0),
], ids=["five-of-eight", "mid-life", "full", "nobody-active"])
def test_the_share_is_the_ratio_of_the_two_deltas(man, before, after, want):
    ctx = {"counters_before": {"p": series(*before)},
           "counters_after": {"p": series(*after)}}
    assert readers.read_metric(man, NAME, ctx) == pytest.approx(want)


def test_the_share_sums_over_the_servers(man):
    ctx = {"counters_before": {"p": series(0, 0), "q": series(100, 100)},
           "counters_after": {"p": series(256, 1024),
                              "q": series(100 + 768, 100 + 1024)}}
    assert readers.read_metric(man, NAME, ctx) == pytest.approx(50.0)


@pytest.mark.parametrize("case", ["no-series", "read-only", "no-tick",
                                  "no-counters"])
def test_the_reader_finds_nothing_without_the_series(man, case):
    """The parent commit has neither series: the metric is left out of the
    line and nothing raises. Nor does a window in which no tick ran."""
    ctx = {
        "no-series": {"counters_before": {"p": series()},
                      "counters_after": {"p": series()}},
        "read-only": {"counters_before": {"p": series(0)},
                      "counters_after": {"p": series(64)}},
        "no-tick": {"counters_before": {"p": series(640, 1024)},
                    "counters_after": {"p": series(640, 1024)}},
        "no-counters": {},
    }[case]
    assert readers.read_metric(man, NAME, ctx) is None


def test_traced_dry_run_prints_the_share(tmp_path):
    """The harness on the CPU in the lighter gpt2-xl cell: the share is on
    the line and the server's two counters moved. The rehearsal's slots
    are ONE 128-row block long, so a tick reads all of a layer or (nobody
    active) nothing: the share can only say "at most 100" here."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    env.pop("XLA_FLAGS", None)
    res = subprocess.run(
        [sys.executable, os.path.join(ROOT, "perfbench", "run.py"),
         "--workload", "gpt2xl-chat-open", "--seed", str(2 ** 31 + 135),
         "--seconds", "4", "--trace", "1", "--dry-run-cpu",
         "--out", str(tmp_path / "out")],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert res.returncode == 0, res.stdout[-3000:] + res.stderr[-2000:]
    last = json.loads(res.stdout.strip().splitlines()[-1])
    assert last["cpu_dry_run"] is True and last["correct"] is True
    # the open loop reports the share as its split, read as NAME's file says
    share = last["metrics"]["cpu_dry_run." + NAME + ".open"]
    assert share["unit"] == "%" and 0.0 < share["value"] <= 100.0
    with open(tmp_path / "out" / "metrics_after.jsonl") as f:
        total = readers.parse_prometheus(json.loads(f.readline())["text"])
    assert (0 < total["server_attn_rows_read_total"]
            <= total["server_attn_rows_span_total"])
    assert total["server_attn_rows_span_total"] % 128 == 0
