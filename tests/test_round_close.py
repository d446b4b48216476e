"""When a batched round closes (`BatchingStageAdapter._close_round`).

A round's leader holds the round open for the sessions that the last round
of that width has just answered, and only for those: it closes at the last
expected join, after at most `REJOIN_SHARE` of that round's wall time (never
under ``window_s``), and sleeps ``window_s`` where nobody is on the way back.
Every case runs for burst rounds and for per-step rounds.

The timing cases drive the adapter over the engine's own slot tables with
the device taken out (`SlotsOnly`: a round takes ``round_s`` of sleep), so
that a round's length, and with it the bound, is the test's to set; the
token case runs the real engine at a tiny size."""

import threading
import time
import types

import jax
import numpy as np
import pytest

from engines import engine, tiny_cfg

from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.ops.sampling import (
    SamplingParams,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.models import (
    init_params,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.models.partition import (
    ROLE_FULL,
    StageSpec,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime import (
    batching,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime.executor import (
    StageExecutionError,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime.messages import (
    StageRequest,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.telemetry import (
    catalog,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.telemetry.metrics import (
    MetricsRegistry,
)

KINDS = ["burst", "step"]
TICKS, HID, VOCAB = 4, 8, 32
# A round of 0.8 s: its leader waits up to 0.2 s for a session on its way
# back. A client turns round in 0.04 s, longer than the 0.02 s window, so
# without the wait it would miss every round it is not the leader of.
ROUND_S, WINDOW_S, TURN_S = 0.8, 0.02, 0.04
BOUND_S = batching.REJOIN_SHARE * ROUND_S
SLACK_S = 0.06           # a loaded machine's scheduling, either way
# A window no thread's start outlasts, for the rounds whose MEMBERS a case
# asserts: whoever is to share a round is in it however late it was
# scheduled, and a round that waits for sessions on their way back still
# closes at the last join.
MEET_S = 1.0


class SlotsOnly(batching.BatchedStageExecutor):
    """The batched engine's slot tables with no device behind them: a
    prefill takes a slot, a round sleeps ``round_s`` and grows its
    sessions' rows, a burst reports the stop the test set for a session."""

    def __init__(self, *, burst, slots=8, round_s=ROUND_S, rider_rows=0):
        self.spec = types.SimpleNamespace(is_first=True, is_last=burst,
                                          start=0, end=1)
        self.cfg = types.SimpleNamespace(hidden_size=HID, loop_steps=1,
                                         eva_window=0)
        self.slots, self.max_len = slots, 1 << 16
        self._slot_of, self._free = {}, list(range(slots))
        self.lengths = np.zeros((slots,), np.int64)
        self.rider_rows, self.prefix_store = rider_rows, None
        self.k = None
        self.round_s = round_s
        self.rounds = []            # (start instant, sessions, rider or None)
        self._ran = threading.Condition()
        self.stops = {}             # session -> the stop its burst reports

    def _begins(self, sessions, rider=None):
        with self._ran:
            self.rounds.append((time.monotonic(), sorted(sessions), rider))
            self._ran.notify_all()

    def running(self, i, timeout=30.0):
        """Returns once round ``i`` (from 0) has begun: the adapter's lock
        is its leader's until ``round_s`` later."""
        with self._ran:
            assert self._ran.wait_for(lambda: len(self.rounds) > i, timeout)

    def prefill(self, sid, x, prefix_len=0):
        s = self._alloc(sid)
        self.lengths[s] = np.shape(x)[1]
        return np.zeros((1, np.shape(x)[1], HID), np.float32)

    def logits(self, hidden):
        out = np.zeros((1, np.shape(hidden)[1], VOCAB), np.float32)
        out[..., 7] = 1.0
        return out

    def decode_batch(self, hidden):
        self._begins(hidden)
        time.sleep(self.round_s)
        for sid, h in hidden.items():
            self.lengths[self._slot_of[sid]] += np.shape(h)[1]
        return dict(hidden)

    def burst_enqueue(self, entries, n_ticks, rider=None):
        self._begins(entries, rider and rider["session_id"])
        return entries, n_ticks, rider

    def burst_fetch(self, flight):
        time.sleep(self.round_s)       # the device: the lock is free

    def burst_collect(self, flight):
        entries, n_ticks, rider = flight
        out = {}
        for sid, e in entries.items():
            s = self._slot_of[sid]
            self.lengths[s] += min(e["budget"], n_ticks)
            out[sid] = {"tokens": [1] * min(e["budget"], n_ticks),
                        "stop": self.stops.get(sid),
                        "cache_len": int(self.lengths[s])}
        if rider is not None:
            s = self._alloc(rider["session_id"])
            self.lengths[s] = len(rider["ids"])
            out[rider["session_id"]] = {"token": 7,
                                        "cache_len": len(rider["ids"])}
        return out


def make(kind, *, window_s=WINDOW_S, **engine):
    eng = SlotsOnly(burst=kind == "burst", **engine)
    ad = batching.BatchingStageAdapter(eng, window_s=window_s,
                                       step_timeout=30.0)
    reg = MetricsRegistry(enabled=True)
    ad._m_fill = catalog.get("server_batch_fill_sessions", reg)
    ad._m_closed = catalog.get("server_round_closed_total", reg)
    ad._m_rejoin = catalog.get("server_round_rejoin_seconds", reg)
    return ad, eng


def closed(ad):
    return {dict(c.labels)["by"]: int(c.value)
            for c in ad._m_closed.children() if c.value}


def prompt(sid, rows=3):
    return StageRequest(
        session_id=sid, hidden=np.ones((rows,), np.int32)[None],
        seq_len=rows, cur_len=0, is_prefill=True, max_length=1 << 16,
        sampling=SamplingParams(temperature=0.0))


def seat(ad, *sids):
    """Sessions with a slot and some rows, none of them on its way to a
    round (no reply is on record)."""
    for sid in sids:
        ad.inner.prefill(sid, np.zeros((1, 3), np.int32))


def ask(ad, sid, kind, budget=TICKS):
    cur = int(ad.inner.lengths[ad.inner.slot(sid)])
    if kind == "burst":
        return ad.forward(StageRequest(
            session_id=sid, hidden=np.asarray([[1]], np.int32), seq_len=1,
            cur_len=cur, is_prefill=False, max_length=1 << 16,
            burst_len=TICKS, burst_budget=budget))
    return ad.forward(StageRequest(
        session_id=sid, hidden=np.zeros((1, 1, HID), np.float32), seq_len=1,
        cur_len=cur, is_prefill=False, max_length=1 << 16))


class Client(threading.Thread):
    """One session's requests: before its i-th it waits ``delays[i]`` (its
    turnaround, counted from the reply before) and then for ``gates[i]()``
    where there is one (`SlotsOnly.running`: a round, not an instant), and
    it notes when it sent and when the reply came back."""

    def __init__(self, ad, sid, kind, delays, budgets=None, gates=None):
        super().__init__(daemon=True)
        self.ad, self.sid, self.kind = ad, sid, kind
        self.delays, self.budgets = delays, budgets or {}
        self.gates = gates or {}
        self.sent, self.back, self.error = [], [], None

    def run(self):
        for i, delay in enumerate(self.delays):
            time.sleep(delay)
            if i in self.gates:
                self.gates[i]()
            self.sent.append(time.monotonic())
            try:
                ask(self.ad, self.sid, self.kind,
                    self.budgets.get(i, TICKS))
            except Exception as exc:
                self.error = exc
                return
            self.back.append(time.monotonic())


def run_all(*clients, timeout=60.0):
    for c in clients:
        c.start()
    for c in clients:
        c.join(timeout)
        assert not c.is_alive(), f"session {c.sid} hangs"


@pytest.mark.parametrize("kind", KINDS)
def test_two_cohorts_merge_into_one_round(kind):
    """(1) Two sessions run a round; two more ask during it (once the
    engine says it runs) and wait for the lock. Whoever leads the next
    round holds it for the two that round answered: every round from the
    second on runs all four. No instant decides who is in a round: the
    window and the bound are `MEET_S`."""
    ad, eng = make(kind, window_s=MEET_S)
    seat(ad, "a", "b", "c", "d")
    early = [Client(ad, s, kind, [0.0, TURN_S, TURN_S]) for s in "ab"]
    late = [Client(ad, s, kind, [0.0, TURN_S],
                   gates={0: lambda: eng.running(0)}) for s in "cd"]
    run_all(*early, *late)
    assert [r[1] for r in eng.rounds] == [
        ["a", "b"], ["a", "b", "c", "d"], ["a", "b", "c", "d"]]
    fill = ad._m_fill
    assert (fill.count, fill.sum) == (3, 2.0 + 4.0 + 4.0)
    assert closed(ad) == {"window": 1, "joined": 2}
    # a, b twice and c, d once came back from the round before: a way
    # back is a turnaround, counted from the reply (from the round's start
    # it would be `ROUND_S` more)
    assert ad._m_rejoin.count == 6
    assert TURN_S <= ad._m_rejoin.sum / 6 < TURN_S + ROUND_S / 2


@pytest.mark.parametrize("kind", KINDS)
def test_the_round_closes_at_the_last_expected_join(kind):
    """(2) Three sessions come back 0.01, 0.05 and 0.1 s after their
    replies, under a window (and so a bound) of 0.5 s: the next round
    starts when the third is in, not a window later."""
    ad, eng = make(kind, window_s=0.5)
    seat(ad, "a", "b", "c")
    clients = [Client(ad, s, kind, [0.0, d])
               for s, d in zip("abc", (0.01, 0.05, 0.1))]
    run_all(*clients)
    assert [r[1] for r in eng.rounds] == [["a", "b", "c"]] * 2
    last_join = max(c.sent[1] for c in clients)
    assert 0.0 <= eng.rounds[1][0] - last_join < SLACK_S
    assert closed(ad) == {"window": 1, "joined": 1}


NOT_BACK = {"eos": ["burst"], "repeat": ["burst"], "short_budget": ["burst"],
            "old_reply": KINDS, "dropped_meanwhile": KINDS}


@pytest.mark.parametrize("kind, why", [
    (k, w) for w, kinds in NOT_BACK.items() for k in kinds])
def test_who_is_not_waited_for(kind, why):
    """(3) ``b`` shared the first round and does not ask again. A burst
    that stopped, or one whose budget was short of a whole burst (a
    client's last), says so in the reply: nobody waits. A reply older than
    the bound: nobody waits. A session dropped while the leader waits for
    it: the drop wakes the leader, and the round goes at once."""
    ad, eng = make(kind, window_s=WINDOW_S if why != "dropped_meanwhile"
                   else 4 * BOUND_S)
    seat(ad, "a", "b")
    if why in ("eos", "repeat"):
        eng.stops["b"] = why
    a_waits = 2 * BOUND_S if why == "old_reply" else TURN_S
    asks_again, dropped = threading.Event(), []
    a = Client(ad, "a", kind, [0.0, a_waits], gates={1: asks_again.set})
    b = Client(ad, "b", kind, [0.0],
               budgets={0: TICKS - 1} if why == "short_budget" else None)
    if why == "dropped_meanwhile":

        def drop():             # 0.05 s into the leader's wait for ``b``
            asks_again.wait(30.0)
            time.sleep(0.05)
            dropped.append(time.monotonic())
            ad.drop_session("b")

        threading.Thread(target=drop, daemon=True).start()
    run_all(a, b)
    assert [r[1] for r in eng.rounds] == [["a", "b"], ["a"]]
    held = eng.rounds[1][0] - a.sent[1]
    if why == "dropped_meanwhile":
        # waited for (0.8 s allowed), let go by the drop and at once: by the
        # dropper's own stamp, not by how long the machine let it sleep
        assert closed(ad) == {"window": 1, "joined": 1}
        assert 0.0 <= eng.rounds[1][0] - dropped[0] < SLACK_S
        assert "b" not in ad._replied and ad.inner.slot("b") is None
    else:
        assert closed(ad) == {"window": 2}
        assert WINDOW_S <= held < WINDOW_S + SLACK_S


@pytest.mark.parametrize("kind", KINDS)
def test_a_session_that_never_returns_costs_one_bound_once(kind):
    """(4) ``b`` is answered and never asks again. The next round waits
    for it until its reply is a bound old, no longer, and is counted as
    closed by the bound; the round after that does not wait at all."""
    ad, eng = make(kind)
    seat(ad, "a", "b")
    a = Client(ad, "a", kind, [0.0, TURN_S, TURN_S])
    b = Client(ad, "b", kind, [0.0])
    run_all(a, b)
    assert [r[1] for r in eng.rounds] == [["a", "b"], ["a"], ["a"]]
    # the first round's replies left at a.back[0], give or take the release
    late = eng.rounds[1][0] - a.back[0]
    assert BOUND_S - 0.02 < late < BOUND_S + SLACK_S
    assert WINDOW_S <= eng.rounds[2][0] - a.sent[2] < WINDOW_S + SLACK_S
    assert closed(ad) == {"window": 2, "bound": 1}


@pytest.mark.parametrize("kind", KINDS)
def test_nobody_on_the_way_is_the_window_as_it_was(kind):
    """(5) One session in flight: every round is open for ``window_s``, no
    more and no less, and counts as closed by the window."""
    ad, eng = make(kind, window_s=0.1, round_s=0.05)
    seat(ad, "a")
    a = Client(ad, "a", kind, [0.0, TURN_S, TURN_S])
    run_all(a)
    assert [r[1] for r in eng.rounds] == [["a"]] * 3
    for i in range(3):
        assert 0.1 <= eng.rounds[i][0] - a.sent[i] < 0.1 + SLACK_S
    assert closed(ad) == {"window": 3}
    assert ad._replied.keys() == {"a"}


@pytest.mark.parametrize("kind", KINDS)
def test_a_prefill_runs_while_a_leader_waits(kind):
    """(6) ``a`` leads the second round and waits for ``b`` (0.25 s on its
    way, 0.5 s allowed). A prompt that arrives meanwhile gets the lock and
    its first token at once, and since that session asks next, the round
    takes it too."""
    ad, eng = make(kind, window_s=0.5)
    ad.forward(prompt("warm"))                    # the sampler's compile
    ad.drop_session("warm")
    seat(ad, "a", "b")
    done = {}

    def new_session():
        time.sleep(0.5 + ROUND_S + 0.1)           # a is waiting for b
        done["first"] = ad.forward(prompt("c"))
        done["c"] = time.monotonic()
        ask(ad, "c", kind)

    c = threading.Thread(target=new_session, daemon=True)
    clients = [Client(ad, "a", kind, [0.0, 0.01]),
               Client(ad, "b", kind, [0.0, 0.25])]
    c.start()
    run_all(*clients)
    c.join(30)
    assert [r[1] for r in eng.rounds] == [["a", "b"], ["a", "b", "c"]]
    assert done["c"] < clients[1].sent[1] < eng.rounds[1][0]
    assert (done["first"].token_id == 7 if kind == "burst"
            else done["first"].hidden is not None)
    assert closed(ad) == {"window": 1, "joined": 1}


@pytest.mark.parametrize("leads", ["a_session", "the_rider"])
def test_a_rider_and_a_waiting_leader(leads):
    """(7) Burst rounds of an engine with a rider lane. A prompt that
    arrives while ``a`` waits for ``b`` rides that round; one that arrives
    first leads the round, and waits for both sessions as any leader
    does."""
    ad, eng = make("burst", window_s=0.5, rider_rows=16)
    ad.burst_ticks = TICKS
    seat(ad, "a", "b")
    delays = {"a_session": (0.01, 0.25), "the_rider": (0.2, 0.25)}[leads]
    got = {}

    def rider():
        time.sleep(0.5 + ROUND_S + 0.1)
        got["r"] = ad.forward(prompt("r"))

    riding = threading.Thread(target=rider, daemon=True)
    riding.start()
    run_all(*(Client(ad, s, "burst", [0.0, d])
              for s, d in zip("ab", delays)))
    riding.join(30)
    assert [r[1:] for r in eng.rounds] == [(["a", "b"], None),
                                           (["a", "b"], "r")]
    assert (got["r"].token_id, got["r"].cache_len) == (7, 3)
    assert closed(ad) == {"window": 1, "joined": 1}
    # the rider's first token went out with that round: it asks next
    assert ad._replied.keys() == {"a", "b", "r"}


@pytest.mark.parametrize("kind", KINDS)
def test_a_leader_that_fails_while_waiting_releases_its_followers(kind):
    """(8) The leader waits for ``c``; ``b`` has joined and follows. The
    wait raises: both get the error at once, not after `step_timeout`, and
    the engine serves the next round."""
    ad, eng = make(kind, window_s=0.5)
    seat(ad, "a", "b", "c")
    real, woken = ad._cond.wait, []

    def wait(timeout=None):
        if len(eng.rounds) == 1:          # the second round's leader
            woken.append(timeout)
            if len(woken) == 2:           # b's join woke it; c is not in
                raise RuntimeError("the leader broke")
        return real(timeout)

    clients = [Client(ad, "a", kind, [0.0, 0.01]),
               Client(ad, "b", kind, [0.0, 0.05]),
               Client(ad, "c", kind, [0.0])]
    ad._cond.wait = wait
    t0 = time.monotonic()
    run_all(*clients)
    assert time.monotonic() - t0 < 0.5 + ROUND_S + 0.5
    for c in clients[:2]:
        assert isinstance(c.error, StageExecutionError)
        assert "the leader broke" in str(c.error)
    assert [r[1] for r in eng.rounds] == [["a", "b", "c"]]
    ad._cond.wait = real
    ask(ad, "c", kind)
    assert eng.rounds[1][1] == ["c"]


# -- the real engine: who shares a round does not change a session's tokens --

@pytest.fixture(scope="module")
def tiny():
    cfg = tiny_cfg()
    spec = StageSpec(index=0, role=ROLE_FULL, start=0, end=cfg.num_layers)
    return cfg, spec, init_params(jax.random.PRNGKey(5), cfg)


SAMPLED = SamplingParams(temperature=0.8, top_p=0.95, top_k=0,
                         repetition_penalty=1.0)
PROMPTS = {"a": [5, 9, 23], "b": [44, 2], "c": [100, 11, 12, 13], "d": [8]}


@pytest.mark.parametrize("kind", KINDS)
def test_a_session_s_sampled_tokens_do_not_depend_on_the_round(tiny, kind):
    """(9) Session ``a`` with a fixed seed: alone, in a round of two and in
    a full round of four it draws the same tokens, two rounds running."""
    cfg, spec, params = tiny

    def run(sids):
        eng = engine(cfg, spec, params, slots=4, max_len=32)
        ad = batching.BatchingStageAdapter(eng, window_s=0.5)
        reg = MetricsRegistry(enabled=True)
        ad._m_fill = catalog.get("server_batch_fill_sessions", reg)
        ad.warmup(burst=TICKS if kind == "burst" else 0)
        out = {}

        def session(sid, seed):
            ids = PROMPTS[sid]
            toks = [ad.forward(StageRequest(
                session_id=sid, hidden=np.asarray([ids], np.int32),
                seq_len=len(ids), cur_len=0, is_prefill=True, max_length=32,
                sampling=SAMPLED, step_seed=seed)).token_id]
            ready.wait(60)
            for _ in range(2):
                resp = ad.forward(StageRequest(
                    session_id=sid,
                    hidden=np.asarray([[toks[-1]]], np.int32), seq_len=1,
                    cur_len=len(ids) + len(toks) - 1, is_prefill=False,
                    max_length=32, sampling=SAMPLED,
                    generated_tokens=tuple(toks),
                    step_seed=seed + len(toks),
                    burst_len=TICKS if kind == "burst" else 0,
                    burst_budget=TICKS if kind == "burst" else 0))
                toks += (list(resp.burst_tokens) if kind == "burst"
                         else [resp.token_id])
            out[sid] = toks

        ready = threading.Barrier(len(sids))
        threads = [threading.Thread(target=session, args=(s, 100 * i + 7),
                                    daemon=True)
                   for i, s in enumerate(sids)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(300)
        assert set(out) == set(sids)
        # every round ran all of them
        assert (ad._m_fill.count, ad._m_fill.sum) == (2, 2.0 * len(sids))
        return out["a"]

    alone = run("a")
    assert len(alone) == 1 + 2 * (TICKS if kind == "burst" else 1)
    assert run("ab") == alone
    assert run("abcd") == alone


@pytest.mark.parametrize("kind", KINDS)
def test_the_reply_record_goes_with_the_slot(kind):
    """(10) A record exists only for a session that holds a slot: it goes
    when the session is dropped, when it sends a new prompt, when its slot
    is evicted, and twenty sessions through four slots leave four."""
    ad, eng = make(kind, slots=4, round_s=0.01)
    for i in range(20):
        sid = f"s{i}"
        ad.forward(prompt(sid))
        assert ad._replied[sid][0] is None          # a first token's
        ask(ad, sid, kind)
        assert ad._replied[sid][0] in (1, ("burst", TICKS))
        assert len(ad._replied) <= 4
        if i >= 3:
            ad.drop_session(f"s{i - 3}")
            assert f"s{i - 3}" not in ad._replied
    assert ad._replied.keys() == set(eng._slot_of) == {"s17", "s18", "s19"}
    ad.forward(prompt("s19"))                       # again: a new request
    assert ad._replied["s19"][0] is None
    eng._recover_slot("s18", eng.slot("s18"))       # a failed dispatch
    ask(ad, "s19", kind)
    assert ad._replied.keys() == {"s17", "s19"}
    eng._slot_of.clear()                            # ... that took the stacks
    assert ad._returning(1, time.monotonic(), 1.0) == {}
    assert ad._replied == {}


def report_module():
    """`scripts/round_close_report.py`, loaded from its file."""
    import importlib.util
    import os

    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "scripts", "round_close_report.py")
    spec = importlib.util.spec_from_file_location("round_close_report", path)
    report = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(report)
    return report


def test_the_report_prints_a_round_s_transfers_over_its_dispatches():
    """`scripts/round_close_report.py` ``transfers_per_round``: what moved
    of `server_burst_transfers_total` in the window, per direction, over
    the burst programs dispatched in it, summed over the servers; None
    where a run's scrapes lack the series (a program before PR 49) or no
    burst ran."""
    report = report_module()
    up, down = ('server_burst_transfers_total{dir="up"}',
                'server_burst_transfers_total{dir="down"}')
    rounds = "server_burst_dispatches_total"
    ctx = {"counters_before": {"p": {rounds: 10.0, up: 20.0, down: 10.0},
                               "q": {rounds: 0.0, up: 0.0, down: 0.0}},
           "counters_after": {"p": {rounds: 60.0, up: 120.0, down: 75.0},
                              "q": {rounds: 50.0, up: 150.0, down: 50.0}}}
    assert report.transfers_report(ctx) == {"up": 2.5, "down": 1.15}
    old = {"counters_before": {"p": {rounds: 10.0}},
           "counters_after": {"p": {rounds: 60.0}}}
    assert report.transfers_report(old) == {"up": None, "down": None}
    idle = {"counters_before": ctx["counters_after"],
            "counters_after": ctx["counters_after"]}
    assert report.transfers_report(idle) == {"up": None, "down": None}
