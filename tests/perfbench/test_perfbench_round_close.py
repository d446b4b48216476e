"""`round_bound_share` and `round_rejoin_ms`: what the stage engine counts
where a round closes (`server_round_closed_total{by}`, counted by the round's
leader, and `server_round_rejoin_seconds`). The first has a reader of its
own (a ratio of labelled counters), the second the stock `histogram_mean`;
both find nothing in a program without the series (the parent of the PR
that brought them), and the share finds nothing in a window whose rounds
all closed by the window (one session in flight)."""

import os

import pytest

from perfbench.harness import readers
from perfbench.harness.manifest import Manifest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
SHARE, REJOIN = "round_bound_share", "round_rejoin_ms"


@pytest.fixture(scope="module")
def man():
    return Manifest(ROOT)


def closes(**by):
    return {f'server_round_closed_total{{by="{k}"}}': float(v)
            for k, v in by.items()}


def ctx_of(before, after):
    return {"counters_before": before, "counters_after": after}


@pytest.mark.parametrize("name", [SHARE, REJOIN])
def test_the_metric_files_say_what_the_benchmark_asks(man, name):
    desc = man.layer_metric(name)
    assert (desc["layer"], desc["moves"], desc["better"], desc["source"]) == (
        "stage engine", "gap_p75_ms", "lower", "program_counter")
    assert desc["unit"] == {SHARE: "%", REJOIN: "ms"}[name]
    # a stock reader, or a reader file of its own beside the metric
    assert (desc.get("reader") in readers.STOCK) != bool(
        man.layer_reader_file(name))


@pytest.mark.parametrize("name", [SHARE, REJOIN])
def test_the_benchmark_lists_the_metric_where_it_finds_something(man, name):
    """The entry says what the metric's file says; every closed loop's
    traced line is asked for it, and the open loop's for the rejoin alone
    (all its rounds close by the window: the share finds nothing there)."""
    man.validate()
    (entry,) = [m for m in man.data["per_layer"] if m["name"] == name]
    desc = man.layer_metric(name)
    for key in ("unit", "better", "source", "layer", "moves"):
        assert entry[key] == desc[key]
    for w in man.data["workloads"]:
        asked = {m["name"] for m in man.metrics_for(w["name"], "per_layer")}
        if man.traffic(w["traffic"])["kind"] == "closed":
            assert w["name"] in entry["workloads"] and name in asked
        else:
            assert w["name"] not in entry["workloads"]
            assert (name + ".open" in asked) == (name == REJOIN)


@pytest.mark.parametrize("before, after, want", [
    # 2 of 40 held rounds ran out of time; the 3 window closes do not count
    (closes(), closes(joined=38, bound=2, window=3), 5.0),
    # a window that starts after the server has served a while
    (closes(joined=100, bound=10, window=7),
     closes(joined=190, bound=20, window=7), 10.0),
    # every held round was joined: the label `bound` never appeared
    (closes(window=1), closes(window=1, joined=12), 0.0),
    # nobody ever came: every held round ran out
    (closes(), closes(bound=4), 100.0),
], ids=["few", "mid-life", "all-joined", "all-bound"])
def test_the_share_is_bound_over_held_rounds(man, before, after, want):
    ctx = ctx_of({"p": before}, {"p": after})
    assert readers.read_metric(man, SHARE, ctx) == pytest.approx(want)


def test_the_share_sums_over_the_servers(man):
    ctx = ctx_of({"p": closes(), "q": closes(joined=5, bound=5)},
                 {"p": closes(joined=9, bound=1),
                  "q": closes(joined=14, bound=6)})
    assert readers.read_metric(man, SHARE, ctx) == pytest.approx(10.0)


@pytest.mark.parametrize("before, after", [
    ({}, {}),                                         # no counters at all
    ({"p": {}}, {"p": {"server_burst_tokens_total": 5.0}}),   # the parent
    ({"p": closes(window=2)}, {"p": closes(window=30)}),      # window only
    ({"p": closes(joined=4, bound=1, window=2)},              # no held round
     {"p": closes(joined=4, bound=1, window=9)}),             # in the window
], ids=["no-counters", "no-series", "window-only", "none-held-in-window"])
def test_the_share_finds_nothing_to_read(man, before, after):
    assert readers.read_metric(man, SHARE, ctx_of(before, after)) is None


def test_the_rejoin_is_the_window_s_mean_in_ms(man):
    fam = "server_round_rejoin_seconds"
    ctx = ctx_of({"p": {fam + "_sum": 1.0, fam + "_count": 100.0}},
                 {"p": {fam + "_sum": 1.9, fam + "_count": 300.0}})
    assert readers.read_metric(man, REJOIN, ctx) == pytest.approx(4.5)
    # the parent program has no such histogram; an idle window no sample
    assert readers.read_metric(man, REJOIN, ctx_of({"p": {}}, {"p": {}})) \
        is None
    same = {"p": {fam + "_sum": 1.0, fam + "_count": 100.0}}
    assert readers.read_metric(man, REJOIN, ctx_of(same, same)) is None
