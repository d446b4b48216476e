"""The period of a round, accounted inside the program (`BatchingStageAdapter`
`_step_starts`, `_join`, `_answered`, `_stalled`, `_prefill`; the serving
boundary's two legs in `runtime/net.py`).

A closed loop's token costs one PERIOD of the round machine: the last
round's wall time + the way back of the first session + the leader's hold.
The adapter observes the three and their sum round by round, counts the
prefills that fall into a hold, and says what a stalled round was made of;
the serving boundary times a reply's way out and a request's way in from
instants that travel on the message objects and never reach the wire.

The timing cases drive the adapter over `test_round_close.SlotsOnly` (the
engine's slot tables with a sleep for a device), so that a round's length
is the test's to set."""

import threading
import time

import numpy as np
import pytest

from test_round_close import (
    KINDS,
    MEET_S,
    TICKS,
    Client,
    SlotsOnly,
    ask,
    prompt,
    run_all,
    seat,
)

from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime import (
    batching,
    net,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime.messages import (
    StageRequest,
    StageResponse,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.telemetry import (
    catalog,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.telemetry.events import (
    EventRecorder,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.telemetry.metrics import (
    MetricsRegistry,
)

ROUND_S, WINDOW_S, TURN_S = 0.12, 0.01, 0.02
SERIES = {"_m_round": "server_decode_round_seconds",
          "_m_rejoin": "server_round_rejoin_seconds",
          "_m_period": "server_round_period_seconds",
          "_m_back": "server_round_back_seconds",
          "_m_hold": "server_round_hold_seconds",
          "_m_hold_prefill": "server_round_hold_prefill_seconds",
          "_m_request_leg": "server_request_leg_seconds",
          "_m_stalls": "server_round_stalls_total",
          "_m_stall_s": "server_round_stall_seconds_total"}


class Seen:
    """A histogram that keeps every observation, in order."""

    def __init__(self):
        self.values = []

    def observe(self, v):
        self.values.append(v)


def stalls(ad):
    """The stalls counted so far, by whether the round was behind a
    prompt's programs."""
    return {dict(c.labels)["behind_prefill"]: int(c.value)
            for c in ad._m_stalls.children() if c.value}


def make(kind, *, on=True, window_s=WINDOW_S, engine=SlotsOnly, **kw):
    """An adapter over the slot tables whose series live in a registry of
    the test's own (``on`` False: one that is switched off) and whose
    events go to a recorder of its own."""
    eng = engine(burst=kind == "burst", **{"round_s": ROUND_S, **kw})
    ad = batching.BatchingStageAdapter(eng, window_s=window_s,
                                       step_timeout=30.0)
    reg = MetricsRegistry(enabled=on)
    for attr, name in SERIES.items():
        setattr(ad, attr, catalog.get(name, reg))
    ad._events = EventRecorder(enabled=on)
    return ad, eng, reg


@pytest.mark.parametrize("kind", KINDS)
def test_period_is_exec_plus_back_plus_hold_round_by_round(kind):
    """Three sessions in a closed loop: every round after the first holds
    sessions the last one answered, and its period is that round's wall
    time + the way back of the first session in + the leader's hold."""
    ad, eng, _ = make(kind, window_s=0.1, round_s=0.4)   # a bound of 0.1 s
    for attr in ("_m_round", "_m_period", "_m_back", "_m_hold", "_m_rejoin"):
        setattr(ad, attr, Seen())
    seat(ad, "a", "b", "c")
    run_all(*[Client(ad, s, kind, [0.0] + [TURN_S + 0.01 * i] * 3)
              for i, s in enumerate("abc")])
    assert [r[1] for r in eng.rounds] == [["a", "b", "c"]] * 4
    walls, periods = ad._m_round.values, ad._m_period.values
    backs, holds = ad._m_back.values, ad._m_hold.values
    # the hold is every round's; a period needs a round before it
    assert (len(walls), len(holds), len(periods), len(backs)) == (4, 4, 3, 3)
    # By the adapter's own stamps, never by how long a loaded machine lets a
    # thread sleep: ``came`` is when the three came back after round i, as
    # `_join` stamped them (in the order they joined).
    assert len(ad._m_rejoin.values) == 9
    for i in range(3):
        came = ad._m_rejoin.values[3 * i:3 * i + 3]
        assert periods[i] == pytest.approx(
            walls[i] + backs[i] + holds[i + 1], abs=1e-6)
        # the round opens with the first session back, after its turnaround
        # at the least (a sleep is never short) ...
        assert TURN_S <= backs[i] <= came[0] < backs[i] + 0.05
        # ... and its leader holds it until the last one is in, no longer
        assert 0 < came[2] - came[0] <= holds[i + 1] < (
            came[2] - came[0] + 0.06)
        assert periods[i] == pytest.approx(
            eng.rounds[i + 1][0] - eng.rounds[i][0], abs=0.01)
    # ... and a token's gap is the period over the tokens of a round
    assert 0.4 + TURN_S <= min(periods)


@pytest.mark.parametrize("kind", KINDS)
def test_no_period_without_a_session_that_came_back(kind):
    """``a`` runs a round and does not ask again; ``b``, whom no round has
    answered, runs the next: the stretch between the two is an idle stretch
    of an open loop, never a period. The hold is every round's."""
    ad, eng, _ = make(kind)
    seat(ad, "a", "b")
    ask(ad, "a", kind)
    time.sleep(0.05)
    ask(ad, "b", kind)
    assert [r[1] for r in eng.rounds] == [["a"], ["b"]]
    assert (ad._m_period.count, ad._m_back.count) == (0, 0)
    assert (ad._m_hold.count, ad._m_round.count) == (2, 2)
    assert ad._m_rejoin.count == 0
    # a comes back now: the third round is one period after the second
    ask(ad, "a", kind)
    assert ad._m_period.count == 0      # answered by the round BEFORE last
    ask(ad, "a", kind)
    assert (ad._m_period.count, ad._m_back.count) == (1, 1)
    assert ad._m_period.sum == pytest.approx(
        eng.rounds[3][0] - eng.rounds[2][0], abs=0.01)


class SlowPrefill(SlotsOnly):
    def prefill(self, sid, x, prefix_len=0):
        time.sleep(0.03)
        return super().prefill(sid, x, prefix_len)


@pytest.mark.parametrize("kind", KINDS)
def test_a_prefill_inside_a_hold_is_counted_and_one_outside_is_not(kind):
    """``a`` leads the second round and holds it for ``b`` (0.2 s on its
    way, 0.4 s allowed). A prompt that takes the lock meanwhile lands in
    ``server_round_hold_prefill_seconds`` with the time it held the lock;
    the prompts before any round was open do not."""
    ad, eng, _ = make(kind, window_s=0.4, engine=SlowPrefill)
    ad.forward(prompt("warm"))                   # no round is open
    ad.drop_session("warm")
    seat(ad, "a", "b")
    assert ad._m_hold_prefill.count == 0

    def new_session():
        time.sleep(0.4 + ROUND_S + 0.08)         # a is waiting for b
        ad.forward(prompt("c"))

    c = threading.Thread(target=new_session, daemon=True)
    c.start()
    run_all(Client(ad, "a", kind, [0.0, 0.01]),
            Client(ad, "b", kind, [0.0, 0.2]))
    c.join(30)
    assert not c.is_alive()
    assert [r[1] for r in eng.rounds] == [["a", "b"], ["a", "b"]]
    held = ad._m_hold_prefill
    assert held.count == 1 and 0.03 <= held.sum < 0.03 + 0.06
    # the hold it fell into is at least as long
    assert ad._m_hold.sum >= 0.4 + 0.2 - 0.01


def test_a_rider_runs_no_program_and_is_not_a_prefill_in_the_hold():
    """On an engine with a rider lane a prompt that finds another session
    in a slot rides the next burst round: it never takes the lock for a
    program of its own, whatever round is open."""
    ad, eng, _ = make("burst", window_s=0.3, rider_rows=16)
    ad.burst_ticks = TICKS
    seat(ad, "a")
    out = {}

    def rider():
        time.sleep(0.05)                          # a's round is open
        out["first"] = ad.forward(prompt("c"))

    c = threading.Thread(target=rider, daemon=True)
    c.start()
    ask(ad, "a", "burst")
    c.join(30)
    assert [r[1:] for r in eng.rounds] == [(["a"], "c")]
    assert out["first"].token_id == 7
    assert ad._m_hold_prefill.count == 0
    # the rider asks for the next round: its reply carries the instant
    assert out["first"].t_done > 0.0


PARTS = {"build": 0.004, "dispatch": 0.002, "queued": 0.0, "device": 0.25,
         "readback": 0.01}


class SkewedClock:
    """``time`` as `batching` reads it, with a clock the test moves: an
    engine's round ADDS its length to ``monotonic()`` instead of sleeping
    it, so a round's wall time is the length the test set plus the
    microseconds of Python around it, whatever else the machine runs
    (under six test workers a 0.6 s sleep overslept its 0.1 s of room)."""

    def __init__(self):
        self.skew = 0.0

    def monotonic(self):
        return time.monotonic() + self.skew

    sleep = staticmethod(time.sleep)


class Skips(SlotsOnly):
    """`SlotsOnly` whose rounds move the test's clock and do not sleep."""

    clock = None

    def _round(self, run, *args, **kw):
        length, self.round_s = self.round_s, 0.0
        try:
            return run(*args, **kw)
        finally:
            self.round_s = length
            self.clock.skew += length

    def decode_batch(self, hidden):
        return self._round(super().decode_batch, hidden)

    def burst_fetch(self, flight):
        return self._round(super().burst_fetch, flight)


@pytest.mark.parametrize("kind, profiled", [
    ("burst", True), ("burst", False), ("step", False)])
def test_a_stalled_round_says_what_it_was_made_of(monkeypatch, kind,
                                                  profiled):
    """A round over 4 x the last of its width: one count, its seconds by
    part (the burst's four phases where the profiler measured them, the
    rest ``other``; everything ``other`` without them), ONE event. The
    rounds' lengths are on a clock of the test's own (`SkewedClock`)."""
    clock = SkewedClock()
    monkeypatch.setattr(batching, "time", clock)
    ad, eng, reg = make(kind, engine=Skips)
    eng.clock = clock
    seat(ad, "a", "b")
    for _ in range(2):
        ask(ad, "a", kind)                        # rounds of 0.12 s
    assert stalls(ad) == {} and len(ad._events) == 0
    eng.round_s = 5 * ROUND_S
    if profiled:
        eng.burst_parts = dict(PARTS)
    # The round the two are to share is open until the later of them is in
    # (``a`` is on its way back: the round closes at its join; where ``a``
    # leads, for a window no thread's start outlasts), not for 0.01 s.
    ad.window_s = MEET_S
    run_all(Client(ad, "a", kind, [0.0]), Client(ad, "b", kind, [0.0]))
    ad.window_s = WINDOW_S
    assert eng.rounds[2][1] == ["a", "b"]
    eng.round_s = ROUND_S
    ask(ad, "a", kind)                 # 1/5 of the last: no stall either
    assert stalls(ad) == {"false": 1}
    by_part = {dict(c.labels)["part"]: c.value
               for c in reg.get("server_round_stall_seconds_total").children()}
    assert set(by_part) == set(batching.STALL_PARTS) | {"other"}
    wall = sum(by_part.values())
    assert 5 * ROUND_S <= wall < 5 * ROUND_S + 0.1
    for part in batching.STALL_PARTS:
        assert by_part[part] == (PARTS[part] if profiled else 0.0)
    (ev,) = [e for e in ad._events.events() if e.name == "round_stall"]
    f = ev.fields
    assert {p + "_s" for p in by_part} | {
        "wall_s", "last_wall_s", "sessions", "ticks", "rider",
        "behind_prefill", "gc_collections"} == set(f)
    assert f["wall_s"] == pytest.approx(wall, abs=1e-5)
    assert f["last_wall_s"] == pytest.approx(ROUND_S, abs=0.05)
    assert (f["sessions"], f["rider"], f["behind_prefill"]) == (
        2, False, False)
    assert f["ticks"] == (TICKS if kind == "burst" else 1)
    assert f["device_s"] == (PARTS["device"] if profiled else 0.0)
    assert len(f["gc_collections"]) == 3 and min(f["gc_collections"]) >= 0


@pytest.mark.parametrize("kind", KINDS)
def test_with_the_registry_and_the_recorder_off_nothing_is_kept(kind):
    """The dark path: every series stays empty, no event is kept, the
    engine keeps no parts, no collector count is read, and a round builds
    no event; a stall, once in many rounds, is the one thing handed on."""
    ad, eng, reg = make(kind, on=False)
    built, emit = [], ad._events.emit
    ad._events.emit = lambda name, **kw: (built.append(name),
                                          emit(name, **kw))
    seat(ad, "a", "b")
    run_all(Client(ad, "a", kind, [0.0, TURN_S]),
            Client(ad, "b", kind, [0.0, TURN_S]))
    eng.round_s = 5 * ROUND_S
    ask(ad, "a", kind)                            # a stall, unseen
    for attr in SERIES:
        m = getattr(ad, attr)
        if hasattr(m, "count"):
            assert (m.count, m.sum) == (0, 0.0), attr
    assert stalls(ad) == {}
    assert not any(c.value for c in reg.get(
        "server_round_stall_seconds_total").children())
    assert built == ["round_stall"] and len(ad._events) == 0
    assert ad._gc_seen is None and eng.burst_parts is None


# -- the serving boundary's two legs -----------------------------------------


class _Peers:
    """The registry a `TcpTransport` asks for a peer's address."""

    def __init__(self, **addresses):
        self._by_id = addresses

    def get(self, peer_id):
        from types import SimpleNamespace
        return SimpleNamespace(address=self._by_id[peer_id], relay_via=None)


@pytest.fixture
def served(monkeypatch):
    """A batched adapter over the slot tables behind a real TCP stage
    server, every series of the process in a registry of the test's own,
    and every frame either side writes kept."""
    reg = MetricsRegistry(enabled=True)
    real_get = catalog.get
    monkeypatch.setattr(
        catalog, "get", lambda name, registry=None: real_get(name, reg))
    frames = []
    real_send = net._send_frame

    def send(sock, header, payload=b""):
        frames.append(dict(header))
        return real_send(sock, header, payload)

    monkeypatch.setattr(net, "_send_frame", send)
    eng = SlotsOnly(burst=True, round_s=ROUND_S)
    ad = batching.BatchingStageAdapter(eng, window_s=WINDOW_S,
                                       step_timeout=30.0, peer_id="p")
    srv = net.TcpStageServer(ad, wire_dtype="f32", peer_id="p")
    srv.start()
    tx = net.TcpTransport(_Peers(p=srv.address), wire_dtype="f32")
    yield ad, eng, reg, tx, frames
    tx.close()
    srv.stop()


def burst_request(ad, sid, budget=TICKS):
    return StageRequest(
        session_id=sid, hidden=np.asarray([[1]], np.int32), seq_len=1,
        cur_len=int(ad.inner.lengths[ad.inner.slot(sid)]), is_prefill=False,
        max_length=1 << 16, burst_len=TICKS, burst_budget=budget)


def test_the_two_legs_are_observed_and_lie_inside_the_rejoin(served):
    """Three burst requests of one session over TCP. Each reply that says
    the session asks again is timed from the round's results to its frame
    written; each request that rejoins from its frame read to its join.
    The client's turnaround lies between the two, so both legs together
    are under the rejoin."""
    ad, eng, reg, tx, frames = served
    seat(ad, "a")
    for i in range(3):
        time.sleep(TURN_S)
        resp = tx.call("p", burst_request(ad, "a", TICKS if i < 2
                                          else TICKS - 1))
        assert len(resp.burst_tokens) == (TICKS if i < 2 else TICKS - 1)
        assert resp.t_done == 0.0          # the client's object has none
    reply = reg.get("server_reply_leg_seconds")
    request = reg.get("server_request_leg_seconds")
    rejoin = reg.get("server_round_rejoin_seconds")
    # the last burst was short of a whole one: its reply ends the request
    assert (reply.count, request.count, rejoin.count) == (2, 2, 2)
    assert 0.0 < reply.sum and 0.0 < request.sum
    assert reply.sum + request.sum + 2 * TURN_S <= rejoin.sum + 0.005
    assert reg.get("server_round_period_seconds").count == 2


def test_the_instants_never_reach_the_wire(served):
    """``t_recv`` and ``t_done`` are host-only: the request's header is the
    same dict with the instant set, and no frame either side wrote carries
    either (nor anything else the parent's frames did not)."""
    ad, eng, reg, tx, frames = served
    seat(ad, "a")
    req = burst_request(ad, "a")
    meta, _ = net._encode_tensor(np.asarray(req.hidden), "f32")
    plain = net._request_header(req, meta)
    req.t_recv = 123.456
    assert net._request_header(req, meta) == plain
    tx.call("p", req)
    tx.call("p", burst_request(ad, "a"))
    asked = [f for f in frames if f.get("verb") == "forward"]
    told = [f for f in frames if f.get("verb") == "burst"]
    assert len(asked) == len(told) == 2
    # the transport adds what it negotiates (the reply's precision)
    assert all(set(plain) <= set(f) <= set(plain) | {"wire_dtype", "model"}
               for f in asked)
    assert not any({"t_recv", "t_done"} & set(f) for f in frames)
    assert all(set(f) == {"verb", "session_id", "tokens", "stop",
                          "cache_len"} for f in told)
    # what arrived is what was sent: the server stamped its own instant
    assert ad._m_request_leg.count == 1
    back = net._header_to_request(asked[0], net._encode_tensor(
        np.asarray(req.hidden), "f32")[1])
    assert back.t_recv == 0.0 and StageResponse(session_id="a").t_done == 0.0


def test_an_in_process_request_carries_no_instant():
    """A path with no serving boundary (the in-process transport) has no
    frame and so no leg: the rejoin is observed, the request leg is not."""
    ad, eng, _ = make("burst")
    seat(ad, "a")
    for _ in range(3):
        resp = ask(ad, "a", "burst")
    assert ad._m_rejoin.count == 2 and ad._m_request_leg.count == 0
    assert resp.t_done > 0.0               # the reply says: asks again
    assert np.shape(resp.burst_tokens) == (TICKS,)
