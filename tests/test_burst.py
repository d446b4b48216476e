"""Burst-mode serving core (runtime.batching decode_burst, runtime.client
burst generation, serving burst scheduling).

One jitted dispatch runs N decode ticks — lax.scan over a T=1 batched
decode body with per-slot active masks and ON-DEVICE sampling — instead of
one dispatch per token. The determinism contract under test everywhere
here: tick i of a slot samples with PRNGKey(step_seed + i), exactly the
key the sequential per-step client ships for that token, and the device
mirrors the host's stop rules (cap, then eos, then the 5-run repeat
heuristic) in host order, so burst output is BIT-IDENTICAL to the
sequential baseline — bursts change the cost structure, never the tokens.
"""

import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.models import (
    init_params,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.models.quant import (
    dequant_tree,
    quantize_params,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.ops import (
    quant_kernel_report,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.ops.sampling import (
    RECENT_WINDOW,
    sample_token,
    SamplingParams,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime.batching import (
    BatchedStageExecutor,
    BatchingStageAdapter,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime.client import (
    make_server_record,
    PipelineClient,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime.messages import (
    StageRequest,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.serving.fair_queue import (
    DeficitRoundRobin,
)

from engines import (
    bits,
    both_policies,
    build_cluster,
    check_clamped_slot,
    engine,
    FAMILIES,
    family_engine,
    full_spec as _full_spec,
    kernel_cfg,
    oracle_generate,
    slot_rows,
    tiny_cfg,
    transfer_counts,
)

GREEDY = SamplingParams(temperature=0.0)
SAMPLED = SamplingParams(temperature=0.9, top_p=0.95, top_k=50,
                         repetition_penalty=1.3)
PROMPT = [5, 9, 23, 7, 81]
PROMPTS = {"a": [5, 9, 23, 7], "b": [11, 3, 40], "c": [17, 29, 2, 31, 8]}


@pytest.fixture(scope="module")
def cfg():
    return tiny_cfg()


@pytest.fixture(scope="module")
def params(cfg):
    return init_params(jax.random.PRNGKey(0), cfg)


def _sample(logits_row, generated, step_seed, sp):
    """The client's host-side sampler, one token (the oracle mirror)."""
    recent = np.zeros((RECENT_WINDOW,), np.int32)
    n = min(len(generated), RECENT_WINDOW)
    if n:
        recent[:n] = np.asarray(generated[-n:], np.int32)
    return int(np.asarray(sample_token(
        jax.random.PRNGKey(step_seed), logits_row,
        jnp.asarray(recent), jnp.asarray(n, jnp.int32),
        jnp.asarray(sp.temperature, jnp.float32),
        jnp.asarray(sp.top_p, jnp.float32),
        jnp.asarray(sp.top_k, jnp.int32),
        jnp.asarray(sp.repetition_penalty, jnp.float32))))


def _per_session(sp, prompts):
    """One SamplingParams for every session, or {session: SamplingParams}."""
    return sp if isinstance(sp, dict) else dict.fromkeys(prompts, sp)


def _sequential(cfg, params, prompts, sp, seed, max_new, eos=None):
    """Per-step decode with host sampling + host stop rules: the baseline
    a burst must match bit-for-bit."""
    ex = engine(cfg, _full_spec(cfg), params, slots=4, max_len=64)
    out = {}
    sps = _per_session(sp, prompts)
    for sid, p in prompts.items():
        sp = sps[sid]
        h = ex.prefill(sid, np.asarray([p], np.int32))
        logits = ex.logits(h[:, -1:])[0, -1]
        generated = [_sample(logits, [], seed, sp)]
        while len(generated) < max_new:
            hrow = ex.decode_batch(
                {sid: np.asarray([[generated[-1]]], np.int32)})[sid]
            logits = ex.logits(hrow)[0, -1]
            tok = _sample(logits, generated, seed + len(generated), sp)
            generated.append(tok)
            if eos is not None and tok == eos:
                break
            if len(generated) >= 5 and len(set(generated[-5:])) == 1:
                break
        out[sid] = generated
    return out


def _bursty(cfg, params, prompts, sp, seed, max_new, n_ticks, eos=None,
            stops=None, make=engine):
    """decode_burst driver: re-ships the stateless per-burst protocol
    (sampling params + recent window + seed) each burst, like the wire
    client does. ``stops``: a dict that collects each session's stop
    reason, burst by burst."""
    ex = make(cfg, _full_spec(cfg), params, slots=4, max_len=64)
    gen = {}
    sps = _per_session(sp, prompts)
    for sid, p in prompts.items():
        h = ex.prefill(sid, np.asarray([p], np.int32))
        gen[sid] = [_sample(ex.logits(h[:, -1:])[0, -1], [], seed, sps[sid])]
    live = set(prompts)
    while live:
        entries = {}
        for sid in sorted(live):
            g = gen[sid]
            sp = sps[sid]
            if len(g) >= max_new:
                live.discard(sid)
                continue
            entries[sid] = {
                "token": g[-1], "seed": seed + len(g),
                "budget": max_new - len(g), "eos": eos,
                "generated": tuple(g[-50:]),
                "temperature": sp.temperature, "top_p": sp.top_p,
                "top_k": sp.top_k,
                "repetition_penalty": sp.repetition_penalty,
            }
        if not entries:
            break
        res = ex.decode_burst(entries, n_ticks)
        for sid, r in res.items():
            gen[sid].extend(r["tokens"])
            if stops is not None:
                stops.setdefault(sid, []).append(r["stop"])
            if r["stop"] is not None:
                live.discard(sid)
    return gen, ex


def _add_burst_peer(cfg, transport, registry, params, name="burst-peer"):
    inner = engine(cfg, _full_spec(cfg), params, slots=4, max_len=64)
    ad = BatchingStageAdapter(inner, window_s=0.0, peer_id=name)
    transport.add_peer(name, ad)
    registry.register(make_server_record(name, _full_spec(cfg),
                                         engine="batched"))
    return ad


# -- engine: one dispatch per burst, bit-identical tokens ---------------------

# What the one layer body serves beside the llama shape: learned positions +
# LayerNorm (gpt2), one sliding window for every layer (mistral-window), a
# window LEAF per layer + softcap + sandwich norms (gemma2). Windows of 4
# under 3-5 prompt tokens + 12 new ones truncate.
@pytest.mark.parity
@pytest.mark.parametrize("family,sp", [
    ("llama", GREEDY), ("llama", SAMPLED), ("gpt2", GREEDY),
    ("mistral-window", GREEDY), ("gemma2", GREEDY)],
    ids=["greedy", "sampled", "gpt2-greedy", "mistral-window-greedy",
         "gemma2-greedy"])
def test_burst_engine_matches_sequential(cfg, params, family, sp):
    if family != "llama":
        cfg = tiny_cfg(family)
        params = init_params(jax.random.PRNGKey(0), cfg)
    ref = _sequential(cfg, params, PROMPTS, sp, seed=0, max_new=12)
    got, ex = _bursty(cfg, params, PROMPTS, sp, seed=0, max_new=12,
                      n_ticks=4)
    for sid in PROMPTS:
        assert got[sid] == ref[sid], (sid, got[sid], ref[sid])
    # Dispatch budget: every burst serves ALL live sessions at once, so
    # the dispatch count is bounded by the longest session's burst count,
    # never the session count.
    assert ex.burst_dispatches <= math.ceil((12 - 1) / 4)
    assert ex.burst_tokens == sum(len(g) - 1 for g in got.values())


def _entry(token, seed, budget):
    return {"token": token, "seed": seed, "budget": budget, "eos": None,
            "generated": (), "temperature": 0.8, "top_p": 0.95, "top_k": 0,
            "repetition_penalty": 1.0}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("family", FAMILIES)
def test_burst_caches_bit_equal_to_slab_round_trip(monkeypatch, family,
                                                   dtype):
    """Tokens and the WHOLE K and V stacks after one 16-tick sampled burst
    are bit for bit what the slab's round trip (the engine's until PR 32,
    kept in tests/test_batching.py) leaves: two sessions run all 16 ticks,
    one spends its budget after 7 and sits the rest out, one sits out all."""

    def drive():
        ex = family_engine(family, dtype)
        res = ex.decode_burst({"a": _entry(5, 11, 16), "b": _entry(6, 12, 7),
                               "c": _entry(7, 13, 16)}, 16)
        return {"tokens": [res[s]["tokens"] for s in "abc"],
                "k": ex.k, "v": ex.v}

    want, got = both_policies(monkeypatch, drive)
    assert got["tokens"] == want["tokens"]
    assert [len(t) for t in got["tokens"]] == [16, 7, 16]
    np.testing.assert_array_equal(bits(got["k"]), bits(want["k"]))
    np.testing.assert_array_equal(bits(got["v"]), bits(want["v"]))


@pytest.mark.parametrize("case", ["parked-inactive", "active-to-max-len"])
def test_burst_append_clamps_as_the_slab_append_did(monkeypatch, case):
    """The clamp's two ends through a 16-tick burst in which other slots
    decode. A slot parked at ``max_len - 1`` and left out keeps its last
    rows bit for bit (every tick reads and writes back its row ``max_len -
    1``). A slot at ``max_len - 16`` with a budget of 16 reaches exactly
    ``max_len``: its last row lands at ``max_len - 1``, where the slab
    append wrote it, and the burst reports the full cache."""
    max_len = 32

    def drive():
        ex = family_engine("qwen2", "float32", max_len)
        d, before = slot_rows(ex, "d")
        entries = {"a": _entry(5, 11, 16), "b": _entry(6, 12, 16)}
        if case == "parked-inactive":
            ex.lengths[d] = max_len - 1
        else:
            ex.lengths[d] = max_len - 16
            entries["d"] = _entry(7, 13, 16)
        res = ex.decode_burst(entries, 16)
        return {"res": res, "k": ex.k, "v": ex.v, "before": before,
                "slot": d, "len": int(ex.lengths[d])}

    want, got = both_policies(monkeypatch, drive)
    np.testing.assert_array_equal(bits(got["k"]), bits(want["k"]))
    np.testing.assert_array_equal(bits(got["v"]), bits(want["v"]))
    check_clamped_slot(got, case, max_len - 16)
    if case == "active-to-max-len":
        assert got["len"] == max_len
        assert got["res"]["d"]["tokens"] == want["res"]["d"]["tokens"]
        assert len(got["res"]["d"]["tokens"]) == 16


# One slot each: greedy (with the filters and the penalty SET, as
# SamplingParams(temperature=0.0) leaves them), filtered, penalised, plain.
MIXED = {"a": SamplingParams(temperature=0.0),
         "b": SamplingParams(temperature=0.8, top_p=0.95, top_k=0,
                             repetition_penalty=1.0),
         "c": SamplingParams(temperature=1.0, top_p=1.0, top_k=0,
                             repetition_penalty=1.5),
         "d": SamplingParams(temperature=1.0, top_p=1.0, top_k=0,
                             repetition_penalty=1.0)}
MIXED_PROMPTS = dict(PROMPTS, d=[2, 44, 6])


@pytest.mark.parity
def test_burst_mixed_knobs_match_row_by_row_sampling(cfg, params):
    """Slots with DIFFERENT knobs share one batched sampler call a tick;
    each must get, bit for bit, the token row-by-row `sample_token` draws
    for it alone, from one executable for every combination."""
    ref = _sequential(cfg, params, MIXED_PROMPTS, MIXED, seed=3, max_new=12)
    got, ex = _bursty(cfg, params, MIXED_PROMPTS, MIXED, seed=3, max_new=12,
                      n_ticks=4)
    for sid in MIXED_PROMPTS:
        assert got[sid] == ref[sid], (sid, got[sid], ref[sid])
    # ... and a round of one knob set alone reuses the same program.
    ex.decode_burst({"a": {"token": got["a"][-1], "seed": 0, "budget": 4,
                           "eos": None, "generated": tuple(got["a"]),
                           "temperature": 0.7, "top_p": 0.9, "top_k": 50,
                           "repetition_penalty": 1.5}}, 4)
    assert ex._get_burst_jit(4)._cache_size() == 1


def _array_shapes(hlo_text):
    """name -> dims of every array-valued instruction of an HLO module."""
    shapes = {}
    for m in re.finditer(r"^\s*(?:ROOT )?(\S+) = \w+\[([\d,]*)\]", hlo_text,
                         re.M):
        shapes[m.group(1)] = [int(d) for d in m.group(2).split(",") if d]
    return shapes


def test_burst_tick_never_permutes_the_vocabulary(cfg, params):
    """The lowered `jit_burst_tick` (ONE program for every knob combination:
    the knobs are traced) holds no gather or scatter whose indices or
    updates are vocabulary-sized (the embedding lookup reads [S] ids, the
    penalty <= 50 ids a row) and no vocabulary-wide sort: the sampler works
    on the unsorted rows."""
    vocab = cfg.vocab_size
    assert vocab not in (4, 32, 64, RECENT_WINDOW)    # dims tell V apart
    ex = engine(cfg, _full_spec(cfg), params, slots=4, max_len=32)
    ex.prefill("a", np.asarray([PROMPT], np.int32))
    _, args = ex._burst_prep(
        {"a": {"token": 1, "seed": 0, "budget": 4, "eos": None,
               "generated": (1,), "temperature": 0.7, "top_p": 0.9,
               "top_k": 50, "repetition_penalty": 1.5}}, 4)
    hlo = ex._get_burst_jit(4).lower(ex.params, *args, ex.k, ex.v) \
        .compiler_ir(dialect="hlo").as_hlo_text()
    shapes = _array_shapes(hlo)
    seen = {"gather": 0, "scatter": 0, "sort": 0}
    for m in re.finditer(
            r"^\s*(?:ROOT )?(\S+) = \S+ (gather|scatter|sort)\(([^)]*)\)",
            hlo, re.M):
        name, op, operands = m.group(1), m.group(2), m.group(3)
        operands = [o.strip().split(" ")[-1] for o in operands.split(",")]
        seen[op] += 1
        if op == "sort":
            assert vocab not in shapes[operands[0]], m.group(0)
            continue
        # gather(operand, indices) -> result; scatter(operand, indices,
        # updates): everything but the operand itself stays small.
        small = operands[1:] + ([name] if op == "gather" else [])
        for o in small:
            assert vocab not in shapes[o], (m.group(0), o, shapes[o])
    # The walk saw the program: the embedding gather, the penalty's scatter.
    assert seen["gather"] >= 2 and seen["scatter"] >= 1, seen


@pytest.mark.parity
def test_burst_engine_eos_mid_burst_truncates(cfg, params):
    ref_full = _sequential(cfg, params, PROMPTS, GREEDY, seed=0, max_new=12)
    eos = ref_full["a"][4]
    ref = _sequential(cfg, params, PROMPTS, GREEDY, seed=0, max_new=12,
                      eos=eos)
    got, _ = _bursty(cfg, params, PROMPTS, GREEDY, seed=0, max_new=12,
                     n_ticks=4, eos=eos)
    for sid in PROMPTS:
        assert got[sid] == ref[sid], (sid, got[sid], ref[sid])
    # The eos cut landed MID-burst for at least one session: emitted
    # counts are not all multiples of the tick count.
    assert any(len(g) < len(ref_full[s]) for s, g in got.items())


# -- dispatch-budget guard: at most ONE jit dispatch per N-tick burst ---------

def test_burst_dispatch_budget_guard(cfg, params):
    """Counting wrapper around the jitted burst program: a 12-token
    client generation at burst=4 must execute exactly ceil(11/4) = 3
    dispatches — one per burst, none hidden elsewhere."""
    client, transport, registry, _params, _plan = build_cluster(
        cfg, splits="2,4")
    ad = _add_burst_peer(cfg, transport, registry, _params)
    ex = ad.inner
    real = ex._get_burst_jit(4)
    calls = []

    def counting(*args, **kwargs):
        calls.append(1)
        return real(*args, **kwargs)

    ex._burst_jits[4] = counting
    try:
        ref = oracle_generate(cfg, _params, PROMPT, 12, SAMPLED)
        res = client.generate(PROMPT, max_new_tokens=12, sampling=SAMPLED,
                              burst=4)
    finally:
        ex._burst_jits[4] = real
    assert res.tokens == ref, (res.tokens, ref)
    assert len(calls) == math.ceil((12 - 1) / 4), len(calls)
    assert ex.burst_dispatches == len(calls)


# -- what a round sends across the host-device boundary -----------------------

def _round_of(ad, tokens, cur, as_array=np.asarray):
    """One burst round of the adapter with a request a session in
    ``tokens``, all in flight together; -> {session: response}."""
    import threading

    got = {}

    def ask(sid):
        got[sid] = ad.forward(StageRequest(
            session_id=sid, hidden=as_array([[tokens[sid]]], np.int32),
            seq_len=1, cur_len=cur[sid], is_prefill=False, max_length=64,
            sampling=SAMPLED, generated_tokens=(tokens[sid],), step_seed=3,
            burst_len=4, burst_budget=4))

    threads = [threading.Thread(target=ask, args=(sid,)) for sid in tokens]
    for th in threads:
        th.start()
    for th in threads:
        th.join(120)
    assert set(got) == set(tokens)
    return got


@pytest.mark.parametrize("sessions", [1, 4], ids=["one", "every-slot"])
def test_a_burst_round_crosses_the_boundary_three_times(cfg, params,
                                                        sessions):
    """Through the adapter, requests as the wire decodes them (ids on the
    host): a round of S sessions sends TWO arrays up and reads ONE back
    whatever S, by `server_burst_transfers_total` over
    `server_burst_dispatches_total`; a request whose ids come as a device
    array costs the round one more read each, and the count says so."""
    ex = engine(cfg, _full_spec(cfg), params, slots=4, max_len=64)
    ad = BatchingStageAdapter(ex, window_s=0.5)
    read = transfer_counts(ex, ad)
    sids = "abcd"[:sessions]
    first = {sid: ad.forward(StageRequest(
        session_id=sid,
        hidden=np.asarray([(PROMPT + PROMPT)[:3 + i]], np.int32),
        seq_len=3 + i, cur_len=0, is_prefill=True, max_length=64,
        sampling=SAMPLED, step_seed=2)).token_id
        for i, sid in enumerate(sids)}
    cur = {sid: 3 + i for i, sid in enumerate(sids)}
    assert read() == (0, 0, 0)              # a prefill is not a round
    got = _round_of(ad, first, cur)
    assert read() == (2, 1, 1), read()
    assert all(len(r.burst_tokens) == 4 for r in got.values())
    again = _round_of(ad, {s: r.burst_tokens[-1] for s, r in got.items()},
                      {s: r.cache_len for s, r in got.items()},
                      as_array=jnp.asarray)
    assert read() == (4, 2 + sessions, 2), read()
    assert all(len(r.burst_tokens) == 4 for r in again.values())


def _thirteen_arrays(ex, entries, n_ticks):
    """`BatchedStageExecutor._burst_prep` as it was before a round's
    arguments were packed (PR 49): thirteen ``[S]``-shaped arrays, each its
    own upload. Kept here, and only here, as the oracle of the packing."""
    S = ex.slots
    tok0 = np.zeros((S,), np.int32)
    seeds = np.zeros((S,), np.int32)
    recent = np.zeros((S, RECENT_WINDOW), np.int32)
    nvalid = np.zeros((S,), np.int32)
    run0 = np.zeros((S,), np.int32)
    left = np.zeros((S,), np.int32)
    eos = np.full((S,), -1, np.int32)
    temp = np.zeros((S,), np.float32)
    top_p = np.ones((S,), np.float32)
    top_k = np.zeros((S,), np.int32)
    rp = np.ones((S,), np.float32)
    alive = np.zeros((S,), bool)
    rows = {}
    for sid, e in entries.items():
        s = ex._slot_of[sid]
        gen = tuple(int(t) for t in e["generated"])
        win = gen[-RECENT_WINDOW:]
        if win:
            recent[s, :len(win)] = win
        nvalid[s] = len(win)
        r = 0
        for t in reversed(gen):
            if t != gen[-1]:
                break
            r += 1
        run0[s] = r
        tok0[s] = int(e["token"])
        seeds[s] = int(e["seed"])
        left[s] = min(int(e["budget"]), n_ticks)
        eos[s] = int(e.get("eos", -1) if e.get("eos") is not None else -1)
        temp[s] = float(e["temperature"])
        top_p[s] = float(e["top_p"])
        top_k[s] = int(e["top_k"])
        rp[s] = float(e["repetition_penalty"])
        alive[s] = True
        rows[sid] = s
    return rows, (tok0, ex.lengths.copy(), alive, seeds, recent, nvalid,
                  run0, left, eos, temp, top_p, top_k, rp)


def _unpacked_burst(ex, entries, n_ticks):
    """A burst from the thirteen arrays: laid into the program's two
    arguments by the layout the program's docstring states, the program
    run, its one result read apart by hand; -> what `decode_burst` returns,
    and the two arguments."""
    rows, (tok0, lengths, alive, seeds, recent, nvalid, run0, left, eos,
           temp, top_p, top_k, rp) = _thirteen_arrays(ex, entries, n_ticks)
    ints = np.concatenate([np.stack([
        tok0, lengths, alive.astype(np.int32), seeds, nvalid, run0, left,
        eos, top_k]), recent.T])
    floats = np.stack([temp, top_p, rp])
    packed, ex.k, ex.v = ex._get_burst_jit(n_ticks)(
        ex.params, ints, floats, ex.k, ex.v)
    flat, S = np.asarray(packed), ex.slots
    toks = flat[:n_ticks * S].reshape(n_ticks, S)
    stop = flat[n_ticks * S:(n_ticks + 1) * S]
    grown = flat[(n_ticks + 1) * S:(n_ticks + 2) * S]
    assert flat.shape == ((n_ticks + 2) * S,) and flat.dtype == np.int32
    out = {}
    for sid, s in rows.items():
        m = int(grown[s] - ex.lengths[s])
        out[sid] = {"tokens": toks[:m, s].tolist(),
                    "stop": {0: None, 1: "eos", 2: "repeat"}[int(stop[s])],
                    "cache_len": int(grown[s])}
        ex.lengths[s] = grown[s]
    return out, (ints, floats)


@pytest.fixture(scope="module")
def twins(cfg, params):
    return [engine(cfg, _full_spec(cfg), params, slots=4,
                   max_len=64) for _ in range(2)]


def _knob_cases():
    from test_sampling_parity import KNOBS

    return sorted(KNOBS.items())


@pytest.mark.parametrize("name,knobs", _knob_cases(),
                         ids=[k for k, _ in _knob_cases()])
def test_the_packed_burst_is_the_thirteen_arrays(cfg, twins, name, knobs):
    """For every knob combination `tests/test_sampling_parity.py` drives
    (greedy, sampled, every filter, mixed in one round): the two packed
    arguments hold the thirteen arrays' values to the bit, in their dtypes,
    and three bursts of twin engines (a budget that ends mid-burst, an eos
    that one session's second token hits, the recent window carried
    along) agree on tokens, stops and ``cache_len``."""
    packed_ex, oracle_ex = twins
    sids = ["a", "b", "c", "d"]
    gen = {}
    for i, sid in enumerate(sids):
        ids = np.asarray([(PROMPT + PROMPT)[i:i + 4 + i]], np.int32)
        for ex in twins:
            ex.end_session(sid)
            ex.prefill(sid, ids)
        gen[sid] = [int(ids[0, -1])]
    for burst in range(3):
        entries = {}
        for i, sid in enumerate(sids):
            t, p, k, rp = knobs["abcd".index(sid) % len(knobs)]
            entries[sid] = {
                "token": gen[sid][-1], "seed": 11 * i + len(gen[sid]),
                "budget": 3 if (sid, burst) == ("b", 1) else 9,
                # from its second burst on, c ends at a token it has drawn
                "eos": gen["c"][2] if sid == "c" and burst else None,
                "generated": tuple(gen[sid]), "temperature": t, "top_p": p,
                "top_k": k, "repetition_penalty": rp}
        _, args = packed_ex._burst_prep(entries, 4)
        want, laid = _unpacked_burst(oracle_ex, entries, 4)
        for a, b in zip(args, laid):
            assert type(a) is np.ndarray and a.dtype == b.dtype
            np.testing.assert_array_equal(a, b)
        got = packed_ex.decode_burst(entries, 4)
        assert got == want, (name, burst, got, want)
        assert len(got["b"]["tokens"]) <= (3 if burst == 1 else 4)
        for sid in sids:
            gen[sid].extend(got[sid]["tokens"])
        sids = [s for s in sids if got[s]["stop"] is None]
    assert len(gen["a"]) > 4


# -- client: burst generation over the stage protocol -------------------------

@pytest.mark.parity
@pytest.mark.parametrize("sp", [GREEDY, SAMPLED], ids=["greedy", "sampled"])
def test_burst_client_matches_oracle(cfg, sp):
    client, transport, registry, params, _plan = build_cluster(
        cfg, splits="2,4")
    _add_burst_peer(cfg, transport, registry, params)
    ref = oracle_generate(cfg, params, PROMPT, 12, sp)
    res = client.generate(PROMPT, max_new_tokens=12, sampling=sp, burst=4)
    assert res.tokens == ref, (res.tokens, ref)


@pytest.mark.parity
def test_full_span_session_builds_no_stage0(cfg):
    """A plain session served whole by a full-span peer — in bursts, or one
    token per round trip without --burst — computes nothing locally: the
    stage-0 factory (a CLI client's weights and device) is never called,
    and both give the oracle's ids."""
    _base, transport, registry, params, plan = build_cluster(
        cfg, splits="2,4")
    _add_burst_peer(cfg, transport, registry, params)
    built = []

    client = PipelineClient(cfg, plan, lambda: built.append(1), transport,
                            registry, settle_seconds=0.0)
    ref = oracle_generate(cfg, params, PROMPT, 10, SAMPLED)
    per_step = client.generate(PROMPT, max_new_tokens=10, sampling=SAMPLED)
    burst = client.generate(PROMPT, max_new_tokens=10, sampling=SAMPLED,
                            burst=4)
    assert per_step.tokens == burst.tokens == ref
    assert built == []
    client.stage0                  # what a classic route does first
    assert built == [1]


@pytest.mark.parity
def test_burst_client_eos_mid_burst(cfg):
    client, transport, registry, params, _plan = build_cluster(
        cfg, splits="2,4")
    _add_burst_peer(cfg, transport, registry, params)
    ref = oracle_generate(cfg, params, PROMPT, 12, SAMPLED)
    eos = ref[5]
    res = client.generate(PROMPT, max_new_tokens=12, sampling=SAMPLED,
                          eos_token_id=eos, burst=4)
    assert res.tokens == ref[:6], (res.tokens, ref)
    assert res.stopped_by == "eos"


@pytest.mark.parity
def test_burst_client_falls_back_without_full_span_peer(cfg):
    # No full-span batched peer live: the session must fall back to the
    # classic per-step path and still produce oracle tokens.
    client, _tx, _reg, params, _plan = build_cluster(cfg, splits="2,4")
    ref = oracle_generate(cfg, params, PROMPT, 8, GREEDY)
    res = client.generate(PROMPT, max_new_tokens=8, sampling=GREEDY,
                          burst=4)
    assert res.tokens == ref, (res.tokens, ref)


@pytest.mark.parity
def test_burst_client_failover_replays_across_burst_boundary(cfg):
    """Kill the serving burst peer mid-generation: the journaled prefix
    (one entry per burst) must replay onto the replica and the final
    tokens stay bit-identical to the no-fault oracle."""
    client, transport, registry, params, _plan = build_cluster(
        cfg, splits="2,4")
    _add_burst_peer(cfg, transport, registry, params, "burst-peer")
    _add_burst_peer(cfg, transport, registry, params, "burst-peer-2")
    ref = oracle_generate(cfg, params, PROMPT, 12, SAMPLED)
    got, result, killed = [], None, False
    for step in client.generate_stepwise(PROMPT, max_new_tokens=12,
                                         sampling=SAMPLED, burst=4):
        got.extend(step.new_tokens)
        if step.done:
            result = step.result
        if not killed and len(got) > 1:
            # The session pins ONE of the two peers; fail whichever holds
            # it (and the replica's next call too — recovery must survive
            # a fault during replay as well).
            for peer in ("burst-peer", "burst-peer-2"):
                transport.fail_next(peer, 1)
            killed = True
    assert result is not None and result.tokens == ref, (result, ref)
    assert client.recoveries >= 1


def test_burst_rejects_speculative_combo(cfg):
    client, transport, registry, params, _plan = build_cluster(
        cfg, splits="2,4")
    _add_burst_peer(cfg, transport, registry, params)
    with pytest.raises(ValueError, match="burst"):
        list(client.generate_stepwise(PROMPT, max_new_tokens=8,
                                      sampling=GREEDY, burst=4,
                                      speculative_k=3))


# -- scheduler: DRR charged N tokens per burst pick ---------------------------

def test_drr_burst_charge_converges_to_weights():
    """One pick serves a whole burst; charge() debits the extra tokens so
    served-TOKEN ratios still track the weights at burst granularity."""
    drr = DeficitRoundRobin({"gold": 4.0, "bronze": 1.0})
    served = {"gold": 0, "bronze": 0}
    burst = 4
    for _ in range(200):
        t = drr.pick({"gold", "bronze"})
        served[t] += burst
        drr.charge(t, burst - 1)
    ratio = served["gold"] / served["bronze"]
    assert abs(ratio - 4.0) <= 1.0, served


def test_drr_pick_converges_under_deep_burst_debt():
    # A tenant burst-charged far into debt must not trip the convergence
    # assertion — pick() re-earns the debt over extra rotations.
    drr = DeficitRoundRobin({"gold": 4.0, "bronze": 1.0})
    assert drr.pick({"bronze"}) == "bronze"
    drr.charge("bronze", 50)
    assert drr.pick({"bronze"}) == "bronze"
    for _ in range(10):
        assert drr.pick({"gold", "bronze"}) in ("gold", "bronze")


# -- quantized burst serving: parity + launch-count guard ---------------------

@pytest.mark.parity
@pytest.mark.parametrize("mode", ["int8", "nf4"])
def test_burst_engine_quantized_matches_dequantized(cfg, params, mode):
    """The burst path over a quantized tree (int8 rides the default
    scale-folded epilogue; nf4 the select-tree dequant on CPU) emits
    tokens IDENTICAL to the burst path over the explicitly materialized
    weights — quantization error lives in the weights, never in the
    burst execution."""
    qparams = quantize_params(params, mode)
    dparams = dequant_tree(qparams)       # stacked 3-D: fully materialized
    got, _ = _bursty(cfg, qparams, PROMPTS, GREEDY, seed=0, max_new=10,
                     n_ticks=4)
    ref, _ = _bursty(cfg, dparams, PROMPTS, GREEDY, seed=0, max_new=10,
                     n_ticks=4)
    for sid in PROMPTS:
        assert got[sid] == ref[sid], (mode, sid, got[sid], ref[sid])


def _kernel_params(kcfg, mode):
    return quantize_params(init_params(jax.random.PRNGKey(0), kcfg), mode)


@pytest.mark.parity
@pytest.mark.parametrize("mode", ["nf4", "int8"])
def test_kernel_launch_count_guard(monkeypatch, mode):
    """Launch aggregation pinned: on a kernel-eligible shape (NF4 under
    NF4_KERNEL=1; int8 by default, its layer stacks reaching the kernel
    whole), ONE N-tick burst traces at most FOUR pallas_call sites (wqkv,
    wo, wgu, wd — the engine-fused layout; lax.scan shares them across
    layers and ticks), and an already-compiled burst dispatches ZERO new
    launches. This is the structural floor: attention and norms sit
    between the matmuls, so per-layer sites cannot merge further."""
    import global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.ops.int8_kernel as IK
    import global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.ops.nf4_kernel as NK

    K = {"nf4": NK, "int8": IK}[mode]
    monkeypatch.setattr(K, "_INTERPRET", True)
    monkeypatch.setattr(K, "_sites", {})
    monkeypatch.setenv("NF4_KERNEL", "1")
    kcfg = kernel_cfg()
    ex = BatchedStageExecutor(           # its own: the test counts its traces
        kcfg, _full_spec(kcfg), _kernel_params(kcfg, mode), slots=2,
        max_len=16)
    # The fused layout is what makes 4 the bound (7 canonical sites).
    assert "wqkv" in ex.params["layers"]["attn"]
    assert "wgu" in ex.params["layers"]["mlp"]
    h = ex.prefill("s", np.asarray([[3, 5, 7]], np.int32))
    tok = int(jnp.argmax(ex.logits(h[:, -1:])[0, -1]))
    monkeypatch.setattr(K, "_launches", 0)

    def burst(t):
        return ex.decode_burst({"s": {
            "token": t, "seed": 0, "budget": 4, "eos": None,
            "generated": (t,), "temperature": 0.0, "top_p": 1.0,
            "top_k": 0, "repetition_penalty": 1.0}}, 2)

    res = burst(tok)
    assert K._launches <= 4, K._launches     # one trace, four sites
    first = K._launches
    burst(int(res["s"]["tokens"][-1]))
    assert K._launches == first              # cached program: zero new
    if mode == "int8":
        assert len(K._sites) == 4 and all(
            w.startswith("pallas stacked") for w in K._sites.values())


@pytest.mark.parity
@pytest.mark.parametrize("sp", [GREEDY, SAMPLED], ids=["greedy", "sampled"])
def test_int8_stacked_kernel_burst_matches_materialize(monkeypatch, sp):
    """Served bursts of the batched int8 engine with every layer stack
    read by the (interpreted) Pallas kernel in place give the tokens AND
    stop reasons of INT8_FOLD=0 (dequant-materialize per layer), greedy
    and seeded-sampled; all four sites report the stacked kernel."""
    import global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.ops.int8_kernel as IK
    monkeypatch.setattr(IK, "_INTERPRET", True)
    monkeypatch.setattr(IK, "_sites", {})
    kcfg = kernel_cfg()
    qp = _kernel_params(kcfg, "int8")

    def serve(fold):
        monkeypatch.setenv("INT8_FOLD", fold)
        stops = {}
        gen, _ = _bursty(kcfg, qp, PROMPTS, sp, seed=3, max_new=10,
                         n_ticks=4, stops=stops,         # its own programs:
                         make=BatchedStageExecutor)      # `_sites` is a trace's
        return gen, stops

    got = serve("1")
    sites = quant_kernel_report()["int8"]["sites"]
    assert {s.split("x", 1)[1] for s in sites} == {
        "128x256", "128x128", "128x512", "256x128"}   # wqkv wo wgu wd
    assert all(w.startswith("pallas stacked") for w in sites.values()), sites
    monkeypatch.setattr(IK, "_sites", {})
    assert serve("0") == got
    assert not IK._sites                  # materialized: no int8_dot ran
