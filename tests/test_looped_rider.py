"""The rider lane of a looped stack's burst (`engines.looped`, the tiny
model of ``test_looped_stack.py``): a joining request's prompt rows ride the
ticks in which the other sessions decode."""

import threading
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime.batching import (
    BatchingStageAdapter,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime.executor import (
    _sample_last,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime.messages import (
    SamplingParams,
    StageRequest,
)

from engines import (
    TOLERANCE,
    greedy_entry,
    ids_of,
    looped,
    looped_logits,
    rel_rms,
    rider_of,
    transfer_counts,
    two_decoding,
)

@pytest.mark.parametrize("kind", ["float32", "bfloat16", "int8"])
@pytest.mark.parametrize("t", [1, 16, 17, 40])
def test_a_rider_s_rows_are_the_prefill_program_s(t, kind):
    """A prompt of t rows (less than a chunk, one chunk exactly, one row
    into the second, three chunks with the last part full) riding a 4-tick
    burst in which two other sessions decode, against the prefill program
    on a twin engine after the same burst: the decoding sessions' tokens
    are the twin's, the rider's first token is the twin's greedy one, its K
    and V rows of every (pass, layer) are the program's, its length is t,
    and its NEXT row through the cache is the reference's."""
    _, weights, a = looped(kind)
    _, _, b = looped(kind)
    ids = ids_of(t + 1, 3)
    ent_a, ent_b = two_decoding(a), two_decoding(b)
    got = a.decode_burst(ent_a, 4, rider=rider_of("r", ids[:t]))
    want = b.decode_burst(ent_b, 4)
    h = b.prefill("r", ids[None, :t])
    for sid in ent_a:
        assert got[sid] == want[sid]
    assert got["r"] == {
        "token": int(np.argmax(np.asarray(b.logits(h))[0, -1])),
        "cache_len": t}
    assert a.lengths[a.slot("r")] == t
    loose = {"float32": 1e-5, "bfloat16": 2e-2, "int8": 2e-2}[kind]
    for mine, theirs in ((a.k, b.k), (a.v, b.v)):
        assert rel_rms(mine[:, a.slot("r"), :t],
                       theirs[:, b.slot("r"), :t]) <= loose
    out = a.decode_batch({"r": ids[None, t:t + 1]})
    row = np.asarray(a.logits(out["r"]))[0, 0]
    assert rel_rms(row, looped_logits(weights, ids)[t]) <= (
        TOLERANCE[kind])


def test_a_rider_s_sampled_token_is_the_host_s():
    """Sampled (temperature, top-p, a penalty over the tokens sent so far):
    the device draws the rider's first token with the key and the knobs
    `executor._sample_rows` gives a prefill's on the host."""

    _, _, a = looped()
    _, _, b = looped()
    ids = ids_of(21, 4)
    knobs = {"temperature": 0.9, "top_p": 0.9, "top_k": 0,
             "repetition_penalty": 1.3}
    hit = 0
    for seed in range(6):
        got = a.decode_burst(two_decoding(a), 4, rider=rider_of(
            "r", ids, seed=seed, generated=(5, 9), **knobs))
        req = types.SimpleNamespace(
            sampling=SamplingParams(**knobs), generated_tokens=(5, 9),
            step_seed=seed)
        want = _sample_last(b.logits(b.prefill("r", ids[None])), 21, req)
        assert got["r"]["token"] == want, seed
        hit += want != int(np.argmax(np.asarray(
            b.logits(b.prefill("r", ids[None])))[0, -1]))
    assert hit          # the draw is not the argmax on every seed


def test_a_lane_without_a_rider_writes_nothing():
    """A burst with no rider leaves every slot that does not decode as it
    was, bit for bit: the lane's rows point at slot 0 and write back what
    they read."""
    _, _, eng = looped()
    eng.prefill("idle", ids_of(30, 5)[None])         # slot 0? whichever
    entries = two_decoding(eng)
    s = eng.slot("idle")
    before = (np.asarray(eng.k[:, s]), np.asarray(eng.v[:, s]))
    eng.decode_burst(entries, 4)
    np.testing.assert_array_equal(np.asarray(eng.k[:, s]), before[0])
    np.testing.assert_array_equal(np.asarray(eng.v[:, s]), before[1])
    free = [f for f in range(eng.slots) if f not in eng._slot_of.values()]
    assert not free or not np.asarray(eng.k[:, free[0]]).any()


def test_what_does_not_fit_the_lane_does_not_ride():
    _, _, eng = looped()                           # 64-row slots
    assert eng.can_ride(64, 4) and not eng.can_ride(65, 4)
    assert not eng.can_ride(0, 4) and not eng.can_ride(17, 1)
    with pytest.raises(ValueError, match="does not ride"):
        eng.decode_burst({}, 1, rider=rider_of("r", ids_of(17)))
    assert eng.slot("r") is None and len(eng._free) == eng.slots


def stage_request(sid, ids, *, cur_len=0, burst=0, prefill=False, seed=0):

    return StageRequest(
        session_id=sid, hidden=jnp.asarray([ids], jnp.int32),
        seq_len=len(ids), cur_len=cur_len, is_prefill=prefill,
        max_length=64, sampling=SamplingParams(temperature=0.0),
        step_seed=seed, burst_len=burst, burst_budget=burst)


def test_a_prefill_rides_when_another_session_holds_a_slot():
    """Through the adapter. The first prefill finds the engine empty and
    runs the prefill program; with that session in its slot, two more
    prefills that arrive together each ride a burst round of their own (a
    round carries one rider), beside the first session's burst; the tokens
    are those of a twin engine that ran the program for all three; a
    prefill with a stored prefix to copy, or one too long for the lane,
    runs the program."""
    _, _, eng = looped()
    _, _, twin = looped()
    ad = BatchingStageAdapter(eng, window_s=0.05)
    ad.warmup(burst=2)
    assert ad.burst_ticks == 2
    prompts = {"a": ids_of(11, 1), "b": ids_of(19, 2), "c": ids_of(5, 3)}
    want = {sid: int(np.argmax(np.asarray(
        twin.logits(twin.prefill(sid, ids[None])))[0, -1]))
        for sid, ids in prompts.items()}
    first = ad.forward(stage_request("a", prompts["a"], prefill=True))
    assert first.token_id == want["a"] and eng.burst_dispatches == 1
    got = {}

    def send(sid, req):
        got[sid] = ad.forward(req)

    threads = [threading.Thread(target=send, args=(sid, stage_request(
        sid, prompts[sid], prefill=True))) for sid in "bc"]
    threads.append(threading.Thread(target=send, args=("a", stage_request(
        "a", [first.token_id], cur_len=11, burst=2))))
    for th in threads:
        th.start()
    for th in threads:
        th.join(120)
    assert {sid: got[sid].token_id for sid in "bc"} == {
        "b": want["b"], "c": want["c"]}
    assert (got["b"].cache_len, got["c"].cache_len) == (19, 5)
    assert eng.burst_dispatches == 3        # warm-up's + one a rider
    assert len(got["a"].burst_tokens) == 2
    res = twin.decode_burst({"a": greedy_entry(want["a"], 2)}, 2)
    assert list(got["a"].burst_tokens) == res["a"]["tokens"]
    ad.drop_session("c")
    long = ad.forward(stage_request("d", ids_of(40, 4), prefill=True))
    assert eng.burst_dispatches == 3 and long.cache_len == 40


@pytest.mark.parametrize("sessions", [1, 2])
def test_a_round_with_a_lane_crosses_the_boundary_four_times(monkeypatch,
                                                             sessions):
    """A burst round of an engine with a rider lane, with and without a
    rider, for one session and for every slot but the rider's: three
    arrays up (the slots' int32 and float32, the lane's int32 vector) and
    ONE read, by `server_burst_transfers_total` over
    `server_burst_dispatches_total`; every argument but the parameters and
    the stacks is a HOST array when the program is called (so nothing ran
    on the device to make it)."""
    _, _, eng = looped()
    read = transfer_counts(eng)
    for i in range(sessions):
        eng.prefill(f"s{i}", ids_of(6 + i, i)[None])
    real, seen = eng._get_burst_jit(2), []

    def recording(params, *args):
        seen.append([type(a) for a in args if not isinstance(a, tuple)])
        return real(params, *args)

    monkeypatch.setitem(eng._burst_jits, 2, recording)   # shared: undone
    entries = {f"s{i}": greedy_entry(3 + i, 2) for i in range(sessions)}
    eng.decode_burst(entries, 2)
    assert read() == (3, 1, 1)
    res = eng.decode_burst(
        {sid: greedy_entry(4, 2) for sid in entries}, 2,
        rider=rider_of("r", ids_of(9, 7)))
    assert read() == (6, 2, 2) and res["r"]["cache_len"] == 9
    for types_ in seen:
        host = [t for t in types_ if t is np.ndarray]
        assert len(host) == 3 and len(types_) == 3 + len(
            jax.tree.leaves((eng.k, eng.v))), types_


def test_no_eager_program_runs_between_two_bursts(caplog):
    """A looped engine with a rider, every compiled program forgotten
    after its first burst: the second burst, rider and all, compiles the
    burst program and NOTHING else (a scalar handed to ``jnp.asarray`` on
    its own, as the rider's eight once were, would compile a convert
    program here; so would any eager slice, pad or cast of a result)."""
    import logging

    _, _, eng = looped()
    eng.prefill("s", ids_of(6)[None])
    first = eng.decode_burst({"s": greedy_entry(3, 2)}, 2)["s"]["tokens"]
    jax.clear_caches()
    with jax.log_compiles(), caplog.at_level(logging.WARNING):
        caplog.clear()
        res = eng.decode_burst(
            {"s": greedy_entry(first[-1], 2, generated=first)}, 2,
            rider=rider_of("r", ids_of(9, 7), temperature=0.8, top_p=0.9,
                           top_k=5, repetition_penalty=1.2))
    built = [r.getMessage().split()[1] for r in caplog.records
             if r.getMessage().startswith("Compiling ")]
    assert built == ["jit(burst_tick)"], built
    assert len(res["s"]["tokens"]) == 2 and res["r"]["cache_len"] == 9
