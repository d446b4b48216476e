"""What a decode round waited for on the device, as the benchmark reads it:
five per-layer metrics of the stage engine over the series the program keeps
where a round's program is enqueued behind a prompt's
(`server_round_behind_prefill_seconds` beside `server_decode_round_seconds`;
the phase profiler's `device_queued` inside `device` and `prefill_ready`
inside `first_token`). The six closed cells are asked for them, the open
loop for none. Each finds nothing to read in a program without its series
(the parent of the PR that brought them) and reads 0 where the series is
there and did not move."""

import pytest

from test_perfbench_round_period import PARENT, ROOT, ctx_of, hist, phase

from perfbench.harness import readers
from perfbench.harness.manifest import Manifest

BEHIND, ROUNDS = ("server_round_behind_prefill_seconds",
                  "server_decode_round_seconds")
SHARE, CLEAR, BEHIND_MS, QUEUED, READY = FIVE = (
    "round_behind_prefill_share", "round_exec_clear_ms",
    "round_exec_behind_prefill_ms", "burst_queued_ms_per_round",
    "prefill_ready_ms")
SPANS = (QUEUED, READY)
CLOSED = ["gpt2xl-chat-sat8", "qwen2-7b-int8-decode16",
          "ouro-2.6b-reason-sat8", "evabyte-doc-sat8", "glm5-doc-sat8",
          "dots3-doc-sat8"]


@pytest.fixture(scope="module")
def man():
    return Manifest(ROOT)


def window(behind, rounds, queued=None, ready=None):
    """A window's two scrapes: ``behind`` and ``rounds`` are the (seconds,
    count) the two families moved by; ``queued`` and ``ready`` the two
    phases' likewise, where the run was profiled."""
    before = {**PARENT, **hist(BEHIND, 0.5, 2), **hist(ROUNDS, 9.0, 90)}
    after = {**{k: 2 * v for k, v in PARENT.items()},
             **hist(BEHIND, 0.5 + behind[0], 2 + behind[1]),
             **hist(ROUNDS, 9.0 + rounds[0], 90 + rounds[1])}
    for name, moved in (("device_queued", queued), ("prefill_ready", ready)):
        if moved is not None:
            before.update(phase(name, 1.0, 4))
            after.update(phase(name, 1.0 + moved[0], 4 + moved[1]))
    return ctx_of(before, after)


@pytest.mark.parametrize("name", FIVE)
def test_the_metric_files_say_what_the_benchmark_asks(man, name):
    desc = man.layer_metric(name)
    assert (desc["layer"], desc["moves"], desc["better"]) == (
        "stage engine", "gap_p75_ms", "lower")
    assert desc["unit"] == ("%" if name == SHARE else "ms")
    assert desc["source"] == ("program_span" if name in SPANS
                              else "program_counter")
    assert (desc.get("reader") in readers.STOCK) != bool(
        man.layer_reader_file(name))
    (entry,) = [m for m in man.data["per_layer"] if m["name"] == name]
    for key in ("unit", "better", "source", "layer", "moves"):
        assert entry[key] == desc[key]
    assert entry["workloads"] == CLOSED


def test_the_six_closed_cells_are_asked_and_the_open_loop_is_not(man):
    """Appended: the five are the LAST entries, and nothing before them
    moved (`BENCHMARK.json` validates with them)."""
    man.validate()
    assert [m["name"] for m in man.data["per_layer"]][-5:] == list(FIVE)
    for w in man.data["workloads"]:
        asked = {m["name"] for m in man.metrics_for(w["name"], "per_layer")}
        closed = man.traffic(w["traffic"])["kind"] == "closed"
        assert (w["name"] in CLOSED) == closed
        assert set(FIVE) & asked == (set(FIVE) if closed else set())


@pytest.mark.parametrize("name", FIVE)
def test_the_parent_s_scrapes_give_nothing_to_read(man, name):
    after = {k: 2 * v for k, v in PARENT.items()}
    profiled = {**after, **phase("device", 9.0, 90),
                **phase("first_token", 3.0, 20)}
    for ctx in ({}, ctx_of({}, {}), ctx_of(PARENT, after),
                ctx_of(PARENT, profiled)):
        assert readers.read_metric(man, name, ctx) is None


@pytest.mark.parametrize("name, want", [
    (SHARE, 0.0), (BEHIND_MS, 0.0), (CLEAR, 250.0), (QUEUED, 0.0),
    (READY, None)], ids=FIVE)
def test_a_series_that_is_there_and_did_not_move_reads_zero(man, name, want):
    """A hundred rounds of 0.25 s, none behind a prompt, nothing queued (a
    rider's cell); no prefill: a mean of nothing is nothing to read."""
    ctx = window((0.0, 0), (25.0, 100), queued=(0.0, 100), ready=(0.0, 0))
    got = readers.read_metric(man, name, ctx)
    assert got is None if want is None else got == pytest.approx(want)


@pytest.mark.parametrize("name, want", [
    (SHARE, 16.0), (BEHIND_MS, 1300.0), (CLEAR, 225.0), (QUEUED, 172.0),
    (READY, 600.0)], ids=FIVE)
def test_a_window_of_both_kinds_of_round(man, name, want):
    """A hundred rounds, 16 of them 1.3 s behind a prompt and 84 clear ones
    of 0.225 s; the bursts waited 17.2 s for what was ahead; 20 prompts
    were 0.6 s on the device after the lock's release."""
    ctx = window((16 * 1.3, 16), (16 * 1.3 + 84 * 0.225, 100),
                 queued=(17.2, 100), ready=(12.0, 20))
    assert readers.read_metric(man, name, ctx) == pytest.approx(want)


def test_the_split_identity_and_the_queue_it_explains(man):
    """`round_exec_ms` = share x behind + (1 - share) x clear, exactly; and
    the queued time a round is share x (behind - clear) where the wait is
    all that tells the two kinds apart."""
    ctx = window((16 * 1.3, 16), (16 * 1.3 + 84 * 0.225, 100),
                 queued=(17.2, 100), ready=(12.0, 20))
    v = {n: readers.read_metric(man, n, ctx)
         for n in FIVE + ("round_exec_ms",)}
    share = v[SHARE] / 100.0
    assert v["round_exec_ms"] == pytest.approx(
        share * v[BEHIND_MS] + (1 - share) * v[CLEAR], rel=1e-12)
    assert v[QUEUED] == pytest.approx(share * (v[BEHIND_MS] - v[CLEAR]))


def test_an_unfenced_run_has_the_split_and_not_the_queued_time(man):
    """Telemetry without the profiler: the count and the split are there,
    the two phases are not; and a window whose every round was behind a
    prompt has no clear round to take a mean of."""
    ctx = window((16 * 1.3, 16), (16 * 1.3 + 84 * 0.225, 100))
    got = {n: readers.read_metric(man, n, ctx) for n in FIVE}
    assert got[QUEUED] is None and got[READY] is None
    assert None not in (got[SHARE], got[BEHIND_MS], got[CLEAR])
    ctx = window((4 * 1.3, 4), (4 * 1.3, 4))
    assert readers.read_metric(man, SHARE, ctx) == pytest.approx(100.0)
    assert readers.read_metric(man, CLEAR, ctx) is None
    assert readers.read_metric(man, SHARE, window((0.0, 0), (0.0, 0))) is None


def test_the_report_prints_the_split_and_tells_a_queue_from_a_stall(man):
    """`scripts/round_close_report.py` on the same window: the three
    identities of the split beside the ones it had, and the stalls the
    program recorded with those behind a prompt apart (a scrape without the
    label, the parent's, has every stall as one to look into)."""
    import os

    from perfbench.harness.manifest import load_module

    report = load_module(os.path.join(ROOT, "scripts",
                                      "round_close_report.py"))
    ctx = window((16 * 1.3, 16), (16 * 1.3 + 84 * 0.225, 100),
                 queued=(17.2, 100), ready=(12.0, 20))
    ids = report.identities(ctx, man)
    whole, parts, rest = ids["exec = share x behind + (1 - share) x clear"]
    assert whole == pytest.approx(397.0) and rest == pytest.approx(0.0)
    assert parts == pytest.approx([0.16 * 1300.0, 0.84 * 225.0])
    whole, parts, rest = ids["queued = share x (behind - clear)"]
    assert (whole, parts[0]) == pytest.approx((172.0, 172.0))
    # no phases of the host, no trace: nothing to set the clear rounds against
    assert ids["clear = host + ticks + rest"] is None
    assert ids["prefill_ready = prefill_device + rest"] is None
    fam = "server_round_stalls_total"
    for peer in ctx["counters_after"].values():
        peer[fam + '{behind_prefill="true"}'] = 9.0
    assert report.stalls_report(ctx)["rounds"] == 0.0
    assert report.stalls_report(ctx)["rounds_behind_prefill"] == 9.0
    old = ctx_of(PARENT, {**PARENT, fam: 3.0})
    assert report.stalls_report(old) == {
        "rounds": 3.0, "rounds_behind_prefill": None, "seconds_by_part": {}}
