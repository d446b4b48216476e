"""A round's step in flight: the leader holds the adapter's lock to build
and enqueue its step and to collect the results, and lets go of it while
the device runs the step (`BatchingStageAdapter._lead`, ``_flying``).

What the held lock used to guard is held here: (a) a prompt of ANOTHER
session goes up while a burst is in flight, behind it, and both come out as
in the serial order; (b) a round opened meanwhile does not run before the
collect, so N sessions' K bursts stay K rounds, with a rider too; (c) a
drop and a new prompt of a session IN the flight wait for the collect; (d) a
failure in either half releases every waiter and the rider's slot.

A burst is held in flight by a `Gate` in place of the engine's
`burst_fetch`, the one step that blocks: the test says when it lands. The
token cases run the real engine at the tiny preset (one compiled engine for
the module, `tests/engines.py`), the counting cases
`test_round_close.SlotsOnly`, the failures the tiny looped engine with its
rider lane."""

import threading
import time

import pytest

from engines import ids_of, looped, tiny_engine
from test_looped_rider import stage_request
from test_profiling import STAGE_PROMPTS, _stage_request
from test_round_close import (
    MEET_S,
    TICKS,
    Client,
    prompt,
    report_module,
    seat,
)
from test_round_close import make as slots_only

from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime import (
    batching,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime.executor import (
    StageExecutionError,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.telemetry import (
    catalog,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.telemetry.metrics import (
    MetricsRegistry,
)

BURST = 4
KEY = ("burst", BURST)
# How long a case looks for something that must NOT happen while a burst is
# held in flight (a sound engine passes however the machine is loaded).
QUIET_S = 0.2


class Gate:
    """In place of an engine's `burst_fetch`: says a burst is up
    (``up``, once a burst) and lets it land when the test says (``land``)."""

    def __init__(self, eng):
        self.fetch = eng.burst_fetch
        self.up = threading.Semaphore(0)
        self.land = threading.Semaphore(0)
        eng.burst_fetch = self

    def __call__(self, flight):
        self.up.release()
        assert self.land.acquire(timeout=60.0)
        return self.fetch(flight)

    def is_up(self, timeout=60.0):
        return self.up.acquire(timeout=timeout)


def until(what, timeout=60.0):
    """Returns once ``what()`` holds: a state another thread brings about."""
    end = time.monotonic() + timeout
    while not what():
        assert time.monotonic() < end, "never came about"
        time.sleep(0.001)


def thread(fn, *args):
    out = {}

    def run():
        try:
            out["got"] = fn(*args)
        except Exception as exc:
            out["error"] = exc

    th = threading.Thread(target=run, daemon=True)
    th.out = out
    th.start()
    return th


def joined(*threads):
    for th in threads:
        th.join(60.0)
        assert not th.is_alive()


def staged(gated=True):
    """An adapter over the real tiny engine, sessions ``a`` and ``b`` in
    their slots; its prompts' counter in a registry of its own."""
    _, _, eng = tiny_engine(21, slots=4, max_len=64)
    ad = batching.BatchingStageAdapter(eng, window_s=0.0, step_timeout=60.0)
    ad._m_enqueued = catalog.get("server_prefill_enqueued_total",
                                 MetricsRegistry(enabled=True))
    ad.first = {sid: first_token(ad, sid) for sid in ("a", "b")}
    return ad, eng, Gate(eng) if gated else None


def first_token(ad, sid):
    return ad.forward(_stage_request(sid, STAGE_PROMPTS[sid], cur_len=0,
                                     prefill=True)).token_id


def burst(ad, sid):
    cur = int(ad.inner.lengths[ad.inner.slot(sid)])
    resp = ad.forward(_stage_request(sid, [ad.first[sid]], cur_len=cur,
                                     burst=BURST))
    return list(resp.burst_tokens)


def tables(eng):
    return (dict(eng._slot_of), list(eng._free), eng.lengths.tolist())


def enqueued(ad):
    return {dict(c.labels)["during"]: int(c.value)
            for c in ad._m_enqueued.children()}


# -- (a) a prompt behind the running burst ------------------------------------


def test_a_prompt_of_another_session_goes_up_behind_the_burst_in_flight():
    ad, eng, gate = staged()
    a = thread(burst, ad, "a")
    assert gate.is_up()
    assert ad._flying == (KEY, frozenset("a"))
    was = int(eng.lengths[eng.slot("a")])
    # the whole prefill, first token and all, while the burst is up: the
    # lock is free, the prompt's rows are written behind the burst's, and
    # the burst's own lengths are still the collect's to write
    ad.first["c"] = first_token(ad, "c")
    assert ad._flying == (KEY, frozenset("a")) and a.is_alive()
    assert int(eng.lengths[eng.slot("c")]) == len(STAGE_PROMPTS["c"])
    assert int(eng.lengths[eng.slot("a")]) == was
    assert enqueued(ad) == {"gap": 2, "burst": 1}
    gate.land.release()
    joined(a)
    assert ad._flying is None
    assert int(eng.lengths[eng.slot("a")]) == was + BURST
    gate.land.release()
    got = {"a": a.out["got"], "c": burst(ad, "c"), "first": ad.first["c"]}
    # the serial order: a's burst, then the prompt, then its burst
    twin, teng, _ = staged(gated=False)
    want = {"a": burst(twin, "a")}
    twin.first["c"] = want["first"] = first_token(twin, "c")
    want["c"] = burst(twin, "c")
    assert got == want
    assert tables(eng) == tables(teng)
    assert enqueued(twin) == {"gap": 3}


def test_the_report_prints_the_prompts_by_what_the_device_was_doing():
    """`scripts/round_close_report.py` ``prefill_enqueued``: what moved of
    `server_prefill_enqueued_total` in the window by its label, summed over
    the servers, and the share that went up behind a running step; a label
    that never counted has no child and reads 0 beside one that did; None
    where a run's scrapes lack the series (the parent of this change)."""
    report = report_module()
    assert "server_prefill_enqueued_total" in catalog.SPEC
    burst_, gap = ('server_prefill_enqueued_total{during="burst"}',
                   'server_prefill_enqueued_total{during="gap"}')
    ctx = {"counters_before": {"p": {burst_: 10.0, gap: 30.0},
                               "q": {gap: 8.0}},
           "counters_after": {"p": {burst_: 40.0, gap: 36.0},
                              "q": {gap: 12.0}}}
    assert report.prefills_report(ctx) == {"burst": 30.0, "gap": 10.0,
                                           "burst_share": 0.75}
    only = {"counters_before": {"p": {burst_: 1.0}},
            "counters_after": {"p": {burst_: 5.0}}}
    assert report.prefills_report(only) == {"burst": 4.0, "gap": 0.0,
                                            "burst_share": 1.0}
    old = {"counters_before": {"p": {"server_burst_dispatches_total": 1.0}},
           "counters_after": {"p": {"server_burst_dispatches_total": 9.0}}}
    assert report.prefills_report(old) == {"burst": None, "gap": None,
                                           "burst_share": None}
    idle = {"counters_before": ctx["counters_after"],
            "counters_after": ctx["counters_after"]}
    assert report.prefills_report(idle) == {"burst": 0.0, "gap": 0.0,
                                            "burst_share": None}


# -- (b) a round opened under a burst in flight -------------------------------


def test_a_round_opened_under_a_burst_in_flight_waits_for_the_collect():
    ad, eng, gate = staged()
    a = thread(burst, ad, "a")
    assert gate.is_up()
    b = thread(burst, ad, "b")             # opens the next round, and leads
    until(lambda: KEY in ad._rounds)
    assert not gate.is_up(QUIET_S)         # nothing more is enqueued
    assert eng.burst_dispatches == 1 and b.is_alive()
    assert ad._flying == (KEY, frozenset("a"))
    gate.land.release()
    assert gate.is_up()                    # now it is: a's has been collected
    assert ad._flying == (KEY, frozenset("b")) and not a.out.get("error")
    gate.land.release()
    joined(a, b)
    twin, teng, _ = staged(gated=False)
    assert (a.out["got"], b.out["got"]) == (burst(twin, "a"),
                                            burst(twin, "b"))
    assert tables(eng) == tables(teng)


@pytest.mark.parametrize("rider", [False, True], ids=["bursts", "a_rider_too"])
def test_n_sessions_k_bursts_are_k_rounds(rider):
    """Three sessions in a closed loop of four bursts, a turnaround each:
    four rounds of three. A prompt that arrives while the second is in
    flight rides the third on an engine with a lane (it opens that round,
    under the running one, and leads it: the three are waited for, no round
    runs with the rider alone); without a lane its program goes up behind
    the second, and the rounds are the same four."""
    ad, eng = slots_only("burst", window_s=0.02, round_s=0.4,
                         rider_rows=16 if rider else 0)
    ad.burst_ticks = TICKS if rider else 0
    seat(ad, "a", "b", "c")
    ad.window_s = MEET_S               # the first round: all three meet
    clients = [Client(ad, s, "burst", [0.0, 0.02, 0.02, 0.02]) for s in "abc"]
    for c in clients:
        c.start()
    eng.running(0)
    ad.window_s = 0.02
    eng.running(1)
    first = ad.forward(prompt("r"))        # the lock is free: no wait for it
    assert first.token_id == 7
    for c in clients:
        c.join(60.0)
        assert not c.is_alive() and c.error is None
    want = [(list("abc"), None)] * 4
    if rider:
        want[2] = (list("abc"), "r")
    assert [r[1:] for r in eng.rounds] == want
    assert ad._flying is None and int(eng.lengths[eng.slot("r")]) == 3


# -- (c) a session in the flight is left alone until the collect --------------


def drop(ad, sid):
    ad.drop_session(sid)


def prompt_again(ad, sid):
    return first_token(ad, sid)


def retry(ad, sid):
    """The burst request again, as a client whose reply was lost sends it:
    the same ``cur_len``."""
    cur = len(STAGE_PROMPTS[sid])
    return ad.forward(_stage_request(sid, [ad.first[sid]], cur_len=cur,
                                     burst=BURST))


@pytest.mark.parametrize("what", [drop, prompt_again, retry])
def test_a_session_in_the_flight_is_touched_after_the_collect(what):
    ad, eng, gate = staged()
    a = thread(burst, ad, "a")
    assert gate.is_up()
    before = tables(eng)
    op = thread(what, ad, "a")
    op.join(QUIET_S)
    assert op.is_alive() and tables(eng) == before
    assert ad._flying == (KEY, frozenset("a"))
    gate.land.release()
    gate.land.release()                    # a retry's own round, if it runs
    joined(a, op)
    twin, teng, _ = staged(gated=False)
    assert a.out["got"] == burst(twin, "a")
    if what is retry:
        # refused as it always was: the first request's burst has advanced
        # the slot, and the lengths it is held against are the collect's
        assert isinstance(op.out["error"], StageExecutionError)
        assert "stale retry" in str(op.out["error"])
        assert eng.burst_dispatches == teng.burst_dispatches
    else:
        assert op.out["got"] == what(twin, "a")
    assert tables(eng) == tables(teng) and ad._flying is None


# -- (d) a failure in either half ---------------------------------------------


class Lost:
    """A device result that cannot be read."""

    def __array__(self, *args, **kw):
        raise RuntimeError("device lost")


def fails_at_enqueue(monkeypatch, eng):
    def lost(*args):
        raise RuntimeError("device lost")

    monkeypatch.setitem(eng._burst_jits, 2, lost)     # shared: undone


def fails_at_fetch(monkeypatch, eng):
    fetch = eng.burst_fetch

    def lost(flight):
        flight.packed = Lost()
        fetch(flight)

    eng.burst_fetch = lost


def fails_at_collect(monkeypatch, eng):
    def lost(*args):
        raise RuntimeError("device lost")

    eng._burst_collect = lost


@pytest.mark.parametrize("fails", [fails_at_enqueue, fails_at_fetch,
                                   fails_at_collect])
def test_a_failed_half_releases_every_waiter_and_the_rider_s_slot(
        monkeypatch, fails):
    """A round of a leader, a follower and a rider whose burst fails: all
    three are told (a retryable stage error), the rider's slot is free
    again, no flight is left on record, and the next round runs."""
    _, _, eng = looped()
    ad = batching.BatchingStageAdapter(eng, window_s=MEET_S,
                                       step_timeout=60.0)
    ad.warmup(burst=2)
    first = {}
    for sid, n in (("a", 11), ("b", 19)):      # b rides a round of its own
        first[sid] = ad.forward(stage_request(
            sid, ids_of(n, 1), prefill=True)).token_id
    before = tables(eng)
    real = (eng.burst_fetch, eng._burst_collect)
    fails(monkeypatch, eng)
    r = thread(ad.forward, stage_request("r", ids_of(5, 3), prefill=True))
    until(lambda: getattr(ad._rounds.get(("burst", 2)), "rider", None))
    asks = [thread(ad.forward, stage_request(
        sid, [first[sid]], cur_len=n, burst=2))
        for sid, n in (("a", 11), ("b", 19))]
    joined(r, *asks)
    for th in (r, *asks):
        assert isinstance(th.out["error"], StageExecutionError)
        assert "device lost" in str(th.out["error"])
    assert tables(eng) == before and eng.slot("r") is None
    assert ad._flying is None and ("burst", 2) not in ad._rounds
    # the engine serves on: the same requests, and the prompt rides
    monkeypatch.undo()
    eng.burst_fetch, eng._burst_collect = real
    ad.window_s = 0.0
    for sid, n in (("a", 11), ("b", 19)):
        again = ad.forward(stage_request(sid, [first[sid]], cur_len=n,
                                         burst=2))
        assert len(again.burst_tokens) == 2
    assert ad.forward(stage_request(
        "r", ids_of(5, 3), prefill=True)).cache_len == 5


def test_stacks_rebuilt_under_a_burst_in_flight_fail_its_round_alone():
    """A prompt whose program fails ON the device takes the donated stacks
    with it: `_recover_slot` rebuilds them and evicts every session, now
    possibly under a burst in flight. That burst's collect writes no length
    into a slot its session no longer holds: its waiters are told (a
    retryable error) and the tables stay as the recovery left them."""
    ad, eng, gate = staged()
    a = thread(burst, ad, "a")
    assert gate.is_up()
    with ad._lock:                  # what `_recover_slot` does to the tables
        eng._slot_of.clear()
        eng.lengths[:] = 0
        eng._free = list(range(eng.slots))
    gate.land.release()
    joined(a)
    assert isinstance(a.out["error"], StageExecutionError)
    assert "lost their slots" in str(a.out["error"])
    assert tables(eng) == ({}, list(range(eng.slots)), [0] * eng.slots)
    assert ad._flying is None
    assert first_token(ad, "c") is not None        # and serves on
