"""What a decode round waited for on the device: the burst behind a
prompt's programs, counted and timed in the engine.

With telemetry on, `BatchedStageExecutor.prefill` keeps the last device
result of the programs it enqueued (`_ahead`); the next round's dispatch
takes it, clears it and asks it whether it is finished (`_dispatched`). An
unfinished one means the round's program sits behind the prompt's on the one
in-order device queue: `BatchingStageAdapter._answered` observes such a
round's wall time a second time, in `server_round_behind_prefill_seconds`,
and a stall of such a round says so. Profiled, the wait itself is phase
``device_queued`` inside ``device``, and a prompt's programs finishing is
phase ``prefill_ready`` inside ``first_token``.

The real engine at the tiny preset runs the prefill and the burst; what the
test sets is the RESULT the engine asks (`Result`: ``is_ready()`` answers
what the test says, ``block_until_ready()`` takes as long as it says). The
stall and the rider run over `test_round_period.Skips` (slot tables on a
clock the test moves) through the engine's own `prefill` and
`_dispatched`."""

import contextlib
import os
import re
import threading
import time

import jax
import numpy as np
import pytest

from engines import ROOT, tiny_engine
from test_profiling import STAGE_PROMPTS, _stage_request
from test_round_close import HID, TICKS, ask, prompt, seat
from test_round_period import (
    ROUND_S,
    Seen,
    Skips,
    SkewedClock,
    make,
    stalls,
)

from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime import (
    batching,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.telemetry import (
    catalog,
    events,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.telemetry.events import (
    EventRecorder,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.telemetry.metrics import (
    MetricsRegistry,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.telemetry.profiling import (
    PHASES,
    PhaseProfiler,
)

BURST = 4
PKG = "global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu"


class Result:
    """A prompt's last device result, as the engine asks it: finished or not
    by the test's word, and ``takes`` seconds to wait for."""

    def __init__(self, ready, takes=0.0):
        self.ready, self.takes, self.waited = ready, takes, 0

    def is_ready(self):
        return self.ready

    def block_until_ready(self):
        time.sleep(self.takes)
        self.ready, self.waited = True, self.waited + 1
        return self

    def __getitem__(self, rows):
        return np.zeros((1, 1, HID), np.float32)     # the head's one row


class Spans:
    """Stands in for ``jax.profiler.TraceAnnotation``: the order in which
    the live brackets' spans open (``+name``) and close (``-name``)."""

    def __init__(self):
        self.order = []

    @contextlib.contextmanager
    def __call__(self, name, **meta):
        self.order.append("+" + name)
        try:
            yield
        finally:
            self.order.append("-" + name)

    def inside(self, inner, outer):
        """Every ``inner`` span opened and closed inside an ``outer`` one."""
        depth, seen = 0, 0
        for mark in self.order:
            if mark[1:] == outer:
                depth += 1 if mark[0] == "+" else -1
            elif mark == "+" + inner:
                assert depth == 1, self.order
                seen += 1
            elif mark == "-" + inner:
                assert depth == 1, self.order
        return seen


@pytest.fixture
def stage(monkeypatch):
    """``stage(on, profiled)``: an adapter over the real engine at the tiny
    preset, its series in a registry of the test's own (``on``: telemetry's
    switch, which the engine reads too), its phases in a profiler of its
    own. Sessions ``a`` and ``b`` hold a slot."""

    def build(on=True, profiled=False):
        reg = MetricsRegistry(enabled=on)
        prof = PhaseProfiler(enabled=profiled, registry=reg)
        spans = Spans()
        monkeypatch.setattr(batching, "_get_profiler", lambda: prof)
        monkeypatch.setattr(jax.profiler, "TraceAnnotation", spans)
        _, _, eng = tiny_engine(21, slots=4, max_len=64)
        eng._registry = reg
        ad = batching.BatchingStageAdapter(eng, window_s=0.0)
        ad._m_round = catalog.get("server_decode_round_seconds", reg)
        ad._m_behind = catalog.get("server_round_behind_prefill_seconds",
                                   reg)
        ad.first = {
            sid: ad.forward(_stage_request(
                sid, STAGE_PROMPTS[sid], cur_len=0, prefill=True)).token_id
            for sid in ("a", "b")}
        return ad, eng, prof, spans

    return build


def burst(ad, sid="a"):
    """One burst round of ``sid`` alone."""
    cur = int(ad.inner.lengths[ad.inner.slot(sid)])
    resp = ad.forward(_stage_request(sid, [ad.first[sid]], cur_len=cur,
                                     burst=BURST))
    ad.first[sid] = resp.burst_tokens[-1]
    return resp


def test_prefill_keeps_what_it_returned_and_the_round_takes_it(stage):
    """The ONE place: what `prefill` hands its caller is what the engine
    keeps, whichever form ran; the round's dispatch takes and clears it. On
    this backend a result is finished when the call returns, so the round
    is a clear one."""
    ad, eng, _, _ = stage()
    h = eng.prefill("c", np.asarray([STAGE_PROMPTS["c"]], np.int32))
    assert eng._ahead is h and h.is_ready()
    burst(ad)
    assert eng._ahead is None and eng.behind_prefill is False
    assert (ad._m_round.count, ad._m_behind.count) == (1, 0)


def test_a_round_is_behind_only_while_the_prompt_s_result_is_unfinished(
        stage):
    ad, eng, _, _ = stage()
    eng._ahead = still = Result(ready=False)
    burst(ad)
    assert eng._ahead is None and eng.behind_prefill is True
    assert (ad._m_round.count, ad._m_behind.count) == (1, 1)
    assert ad._m_behind.sum == ad._m_round.sum       # the same wall time
    assert still.waited == 0             # unfenced: asked, never waited for
    # a finished one (its programs ran while the sessions were on their way
    # back) does not count, and neither does a round with nothing ahead
    eng._ahead = Result(ready=True)
    burst(ad)
    assert eng._ahead is None and eng.behind_prefill is False
    burst(ad)
    assert (ad._m_round.count, ad._m_behind.count) == (3, 1)


def test_a_step_round_takes_what_is_ahead_too(stage):
    """`decode_batch` (a per-tick round, a speculative verify) dispatches
    as a burst does."""
    ad, eng, _, _ = stage()
    eng._ahead = Result(ready=False)
    cur = int(eng.lengths[eng.slot("a")])
    ad.forward(_stage_request("a", [ad.first["a"]], cur_len=cur))
    assert eng._ahead is None and eng.behind_prefill is True
    assert (ad._m_round.count, ad._m_behind.count) == (1, 1)


def test_the_two_families_split_a_mixed_run_of_rounds(stage):
    """Twelve rounds, every third behind a prompt and every third with a
    finished one ahead: the behind rounds' observations are those rounds'
    own of `server_decode_round_seconds`, so count and sum of the clear
    rounds are the differences, and mean = share x behind + (1 - share) x
    clear to the digit."""
    ad, eng, _, _ = stage()
    ad._m_round, ad._m_behind = Seen(), Seen()
    kinds = [None, True, False] * 4
    for ready in kinds:
        eng._ahead = None if ready is None else Result(ready)
        burst(ad)
    walls, behind = ad._m_round.values, ad._m_behind.values
    assert (len(walls), len(behind)) == (12, 4)
    assert behind == [w for w, ready in zip(walls, kinds) if ready is False]
    clear = [w for w, ready in zip(walls, kinds) if ready is not False]
    share = len(behind) / len(walls)
    assert sum(walls) - sum(behind) == pytest.approx(sum(clear), abs=1e-12)
    assert sum(walls) / 12 == pytest.approx(
        share * sum(behind) / 4 + (1 - share) * sum(clear) / 8, abs=1e-12)


def test_device_queued_nests_inside_device_and_is_zero_with_nothing_ahead(
        stage):
    ad, eng, prof, spans = stage(profiled=True)
    burst(ad)                                        # nothing ahead
    snap = prof.snapshot()
    prof.reset()
    assert snap["device_queued"]["count"] == snap["device"]["count"] == 1
    assert snap["device_queued"]["total_s"] < 1e-3
    assert eng.burst_parts["queued"] < 1e-3
    # a prompt's programs ahead, 0.05 s of them left: the wait is the
    # phase's, and comes out of the part ``device``
    eng._ahead = ahead = Result(ready=False, takes=0.05)
    burst(ad)
    snap = prof.snapshot()
    assert ahead.waited == 1 and eng.behind_prefill is True
    assert snap["device_queued"]["count"] == snap["device"]["count"] == 1
    assert 0.05 <= snap["device_queued"]["total_s"] < 0.05 + 0.05
    parts = eng.burst_parts
    assert tuple(parts) == batching.STALL_PARTS
    assert parts["queued"] == pytest.approx(
        snap["device_queued"]["max_s"], abs=1e-9)
    assert snap["device"]["max_s"] == pytest.approx(
        parts["dispatch"] + parts["queued"] + parts["device"], abs=1e-9)
    assert spans.inside("stage.device_queued", "stage.device") == 2
    assert spans.inside("stage.dispatch", "stage.device") == 2
    # mirrored into the series the benchmark reads
    mirrored = {dict(h.labels)["phase"]: h.count for h in
                eng._registry.get("server_phase_seconds").children()}
    assert mirrored["device_queued"] == 2


def test_prefill_ready_nests_inside_first_token(stage):
    ad, eng, prof, spans = stage(profiled=True)
    spans.order.clear()
    prof.reset()
    first = ad.forward(_stage_request("c", STAGE_PROMPTS["c"], cur_len=0,
                                      prefill=True))
    assert first.token_id is not None
    snap = prof.snapshot()
    assert snap["prefill_ready"]["count"] == snap["first_token"]["count"] == 1
    assert snap["prefill_ready"]["total_s"] <= snap["first_token"]["total_s"]
    assert spans.inside("stage.prefill_ready", "stage.first_token") == 1
    # after the lock's release: the phase ``prefill`` is closed by then
    assert spans.order.index("-stage.prefill") < spans.order.index(
        "+stage.first_token")
    assert {"prefill_ready", "device_queued"} <= set(PHASES)


def test_with_telemetry_off_nothing_is_kept_and_no_family_is_touched(stage):
    ad, eng, prof, spans = stage(on=False)
    h = eng.prefill("c", np.asarray([STAGE_PROMPTS["c"]], np.int32))
    assert h is not None and eng._ahead is None
    first = ad.forward(_stage_request("c", STAGE_PROMPTS["c"], cur_len=0,
                                      prefill=True))
    assert first.token_id is not None and eng._ahead is None
    burst(ad)
    assert eng._ahead is None and eng.behind_prefill is False
    assert (ad._m_round.count, ad._m_behind.count) == (0, 0)
    assert eng.burst_parts is None and spans.order == []
    assert prof.snapshot() == {}


# -- on a clock of the test's own ---------------------------------------------


class Queued(Skips):
    """`Skips` whose prompts go through the engine's own `prefill` (the
    program is the slot tables'; its result is ``result``, the test's) and
    whose rounds ask what is ahead at their dispatch, as the engine's do."""

    result = None
    prefill = batching.BatchedStageExecutor.prefill

    def _prefill_full(self, sid, x):
        Skips.prefill(self, sid, x)
        return self.result

    def decode_batch(self, hidden):
        self._dispatched()
        return super().decode_batch(hidden)

    def burst_enqueue(self, entries, n_ticks, rider=None):
        self._dispatched()
        return super().burst_enqueue(entries, n_ticks, rider)


def queued(monkeypatch, kind="burst", **kw):
    clock = SkewedClock()
    monkeypatch.setattr(batching, "time", clock)
    ad, eng, reg = make(kind, engine=Queued, **kw)
    eng.clock, eng._registry = clock, reg
    ad._m_behind = catalog.get("server_round_behind_prefill_seconds", reg)
    return ad, eng, reg


@pytest.mark.parametrize("profiled", [True, False],
                         ids=["profiled", "unfenced"])
def test_a_stall_behind_a_prompt_says_it_is_a_queue(monkeypatch, profiled):
    """A round of 0.12 s, then a prompt of many rows and the next round
    behind it, 1.2 s: over 4 x, a ``round_stall``; its event says
    ``behind_prefill``, its count carries the label, and where the profiler
    measured the burst its seconds have the part ``queued``, taken out of
    ``device``."""
    ad, eng, reg = queued(monkeypatch)
    eng.result = Result(ready=True)
    seat(ad, "a")
    for _ in range(2):
        ask(ad, "a", "burst")
    assert stalls(ad) == {} and ad._m_behind.count == 0
    eng.result = Result(ready=False)
    ad.forward(prompt("c", rows=200))
    assert eng._ahead is eng.result
    eng.round_s = 10 * ROUND_S
    if profiled:
        eng.burst_parts = {"build": 0.004, "dispatch": 0.002, "queued": 1.0,
                           "device": 0.18, "readback": 0.01}
    ask(ad, "a", "burst")
    assert stalls(ad) == {"true": 1} and ad._m_behind.count == 1
    by_part = {dict(c.labels)["part"]: c.value for c in reg.get(
        "server_round_stall_seconds_total").children()}
    assert set(by_part) == set(batching.STALL_PARTS) | {"other"}
    assert by_part["queued"] == (1.0 if profiled else 0.0)
    assert by_part["device"] == (0.18 if profiled else 0.0)
    assert sum(by_part.values()) == pytest.approx(ad._m_behind.sum)
    (ev,) = [e for e in ad._events.events() if e.name == "round_stall"]
    assert ev.fields["behind_prefill"] is True
    assert ev.fields["queued_s"] == (1.0 if profiled else 0.0)
    # the next round is a fifth of that one and clear: no stall, no count
    eng.round_s = 2 * ROUND_S
    ask(ad, "a", "burst")
    assert stalls(ad) == {"true": 1} and ad._m_behind.count == 1
    assert ad._m_round.count == 4


def test_a_rider_never_counts(monkeypatch):
    """On an engine with a rider lane a prompt that finds another session
    in a slot enqueues no program: nothing is ahead of the round it rides,
    nor of the next."""
    ad, eng, _ = queued(monkeypatch, window_s=0.3, rider_rows=16)
    ad.burst_ticks = TICKS
    eng.result = Result(ready=False)
    seat(ad, "a")                      # a program prefill: nobody to ride
    assert eng._ahead is eng.result
    ask(ad, "a", "burst")
    assert (ad._m_round.count, ad._m_behind.count) == (1, 1)

    def rider():
        time.sleep(0.05)                          # a's round is open
        ad.forward(prompt("c"))

    c = threading.Thread(target=rider, daemon=True)
    c.start()
    ask(ad, "a", "burst")
    c.join(30)
    assert not c.is_alive() and eng.rounds[-1][1:] == (["a"], "c")
    assert eng._ahead is None
    ask(ad, "c", "burst")
    assert (ad._m_round.count, ad._m_behind.count) == (3, 1)


# -- the event that went ------------------------------------------------------


def test_five_thousand_rounds_leave_the_engine_s_kv_layout_on_record(
        monkeypatch):
    """A round leaves no event of its own, so an engine's ``kv_layout``
    (one event, at its start) and a ``round_stall`` are still in the
    4096-entry ring, and in its dump, when an operator asks."""
    ad, eng, _ = queued(monkeypatch, window_s=0.0, round_s=0.01)
    rec = ad._events = EventRecorder(enabled=True)
    rec.emit("kv_layout", shape=[8, 8, 64, 2, 16], dtype="float32")
    seat(ad, "a")
    for _ in range(5000):
        ask(ad, "a", "burst", budget=1)
    assert ad._m_round.count == 5000 > rec.capacity
    assert [e.name for e in rec.events()] == ["kv_layout"]
    assert rec.dropped == 0
    assert '"event": "kv_layout"' in rec.render_jsonl(
        registry=MetricsRegistry(enabled=False))


def test_burst_round_is_emitted_nowhere_and_named_in_no_document():
    assert "burst_round" not in events.EVENTS
    word = re.compile(r"[`'\"]burst_round[`'\"]")
    named = []
    for top in (PKG, "scripts", "docs", "perfbench"):
        for folder, _, files in os.walk(os.path.join(ROOT, top)):
            for name in files:
                if name.endswith((".py", ".md", ".json")):
                    path = os.path.join(folder, name)
                    with open(path, encoding="utf-8") as f:
                        if word.search(f.read()):
                            named.append(os.path.relpath(path, ROOT))
    assert named == []
