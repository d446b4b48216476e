"""Continuous batching (runtime.batching): N concurrent sessions, one
decode step — token-identical to per-session decoding.

The reference computes one forward per session per token
(src/rpc_handler.py:149-325); the batched executor advances every active
slot in one jitted step over a slot-major KV cache.
"""

import random
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.models import (
    gemma2_config,
    init_kv_cache,
    init_params,
    mistral_config,
    mixtral_config,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.models.partition import (
    parse_splits,
    slice_stage_params,
    StagePlan,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.ops.sampling import (
    SamplingParams,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.parallel.tensor_parallel import (
    validate_tp,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime.batching import (
    BatchedStageExecutor,
    BatchingStageAdapter,
    SlotFull,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime.client import (
    make_server_record,
    PipelineClient,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime.executor import (
    StageExecutionError,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime.messages import (
    StageRequest,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime.transport import (
    LocalTransport,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.scheduling.registry import (
    PlacementRegistry,
)

from engines import (
    engine,
    full_forward,
    full_spec,
    oracle_generate,
    PROMPTS,
    stage_executor as StageExecutor,
    tiny_cfg,
    tiny_engine,
)

# Quarantine-with-teeth (tests/conftest.py pytest_runtest_protocol): the
# DETERMINISTIC single-threaded token-parity tests below carry
# @pytest.mark.parity — the documented victims of load-induced host
# corruption; a failure reruns ONCE in-process, and real logic bugs fail
# both runs. The CONCURRENT adapter tests are deliberately NOT marked: a
# real intermittent race there must stay a failure, not be mislabeled as
# environmental corruption by a passing rerun.


def oracle_tokens(cfg, params, prompt, n_new, max_len=128):
    kc, vc = init_kv_cache(cfg, cfg.num_layers, 1, max_len)
    ids = jnp.asarray(np.asarray(prompt, np.int32)[None, :])
    logits, kc, vc = full_forward(cfg, params, ids, kc, vc, jnp.int32(0))
    out = [int(jnp.argmax(logits[0, -1]))]
    cur = len(prompt)
    for _ in range(n_new - 1):
        logits, kc, vc = full_forward(
            cfg, params, jnp.asarray([[out[-1]]], jnp.int32), kc, vc,
            jnp.int32(cur))
        out.append(int(jnp.argmax(logits[0, -1])))
        cur += 1
    return out


def batched_generate(ex, prompts, n_new):
    """Drive all sessions together through the batched engine (greedy)."""
    toks = {}
    for sid, prompt in prompts.items():
        h = ex.prefill(sid, np.asarray(prompt, np.int32)[None, :])
        toks[sid] = [int(jnp.argmax(ex.logits(h)[0, -1]))]
    for _ in range(n_new - 1):
        inputs = {sid: jnp.asarray([[toks[sid][-1]]], jnp.int32)
                  for sid in prompts}
        outs = ex.decode_batch(inputs)
        for sid, h in outs.items():
            toks[sid].append(int(jnp.argmax(ex.logits(h)[0, -1])))
    return toks


@pytest.mark.parametrize("family", ["llama", "gpt2", "qwen2"])
@pytest.mark.parity
def test_batched_sessions_match_per_session_oracle(family):
    cfg, params, ex = tiny_engine(0, family, slots=4, max_len=64)
    n_new = 6
    got = batched_generate(ex, PROMPTS, n_new)
    for sid, prompt in PROMPTS.items():
        assert got[sid] == oracle_tokens(cfg, params, prompt, n_new), sid
    # The whole point: n_new-1 batched steps TOTAL, not per session.
    assert ex.decode_steps == n_new - 1


@pytest.mark.parity
def test_sessions_join_and_leave_mid_stream():
    cfg, params, ex = tiny_engine(1, slots=2, max_len=64)
    pa, pb, pc = PROMPTS["a"], PROMPTS["b"], PROMPTS["c"]
    ra = oracle_tokens(cfg, params, pa, 6)
    rb = oracle_tokens(cfg, params, pb, 3)
    rc = oracle_tokens(cfg, params, pc, 4)

    ha = ex.prefill("a", np.asarray(pa, np.int32)[None, :])
    ta = [int(jnp.argmax(ex.logits(ha)[0, -1]))]
    hb = ex.prefill("b", np.asarray(pb, np.int32)[None, :])
    tb = [int(jnp.argmax(ex.logits(hb)[0, -1]))]
    # Two steps together.
    for _ in range(2):
        outs = ex.decode_batch({
            "a": jnp.asarray([[ta[-1]]], jnp.int32),
            "b": jnp.asarray([[tb[-1]]], jnp.int32)})
        ta.append(int(jnp.argmax(ex.logits(outs["a"])[0, -1])))
        tb.append(int(jnp.argmax(ex.logits(outs["b"])[0, -1])))
    assert tb == rb
    # b leaves, c takes its slot (slots=2 -> c REUSES b's slot), a continues.
    ex.end_session("b")
    hc = ex.prefill("c", np.asarray(pc, np.int32)[None, :])
    tc = [int(jnp.argmax(ex.logits(hc)[0, -1]))]
    for _ in range(3):
        outs = ex.decode_batch({
            "a": jnp.asarray([[ta[-1]]], jnp.int32),
            "c": jnp.asarray([[tc[-1]]], jnp.int32)})
        ta.append(int(jnp.argmax(ex.logits(outs["a"])[0, -1])))
        tc.append(int(jnp.argmax(ex.logits(outs["c"])[0, -1])))
    assert ta == ra
    assert tc == rc


@pytest.mark.parity
def test_partial_batches_and_stragglers():
    # Sessions decode at different cadences; a step may carry any subset.
    cfg, params, ex = tiny_engine(2, slots=4, max_len=64)
    pa, pb = PROMPTS["a"], PROMPTS["b"]
    ra = oracle_tokens(cfg, params, pa, 5)
    rb = oracle_tokens(cfg, params, pb, 3)
    ha = ex.prefill("a", np.asarray(pa, np.int32)[None, :])
    ta = [int(jnp.argmax(ex.logits(ha)[0, -1]))]
    hb = ex.prefill("b", np.asarray(pb, np.int32)[None, :])
    tb = [int(jnp.argmax(ex.logits(hb)[0, -1]))]
    # a advances alone, then together, then b alone.
    outs = ex.decode_batch({"a": jnp.asarray([[ta[-1]]], jnp.int32)})
    ta.append(int(jnp.argmax(ex.logits(outs["a"])[0, -1])))
    outs = ex.decode_batch({
        "a": jnp.asarray([[ta[-1]]], jnp.int32),
        "b": jnp.asarray([[tb[-1]]], jnp.int32)})
    ta.append(int(jnp.argmax(ex.logits(outs["a"])[0, -1])))
    tb.append(int(jnp.argmax(ex.logits(outs["b"])[0, -1])))
    outs = ex.decode_batch({"b": jnp.asarray([[tb[-1]]], jnp.int32)})
    tb.append(int(jnp.argmax(ex.logits(outs["b"])[0, -1])))
    assert ta[:5] == ra[:len(ta)] and tb == rb


def test_slot_admission_and_reuse():
    cfg, params, ex = tiny_engine(3, slots=2, max_len=32)
    ex.prefill("s1", np.asarray([[1, 2, 3]], np.int32))
    ex.prefill("s2", np.asarray([[4, 5]], np.int32))
    with pytest.raises(SlotFull):
        ex.prefill("s3", np.asarray([[6]], np.int32))
    ex.end_session("s1")
    ex.prefill("s3", np.asarray([[6]], np.int32))     # reuses s1's slot
    # Re-prefilling an EXISTING session must not leak its slot.
    ex.prefill("s3", np.asarray([[6, 7]], np.int32))
    assert ex.slot("s3") is not None


def test_adapter_serves_concurrent_clients_through_transport():
    """BatchingStageAdapter behind LocalTransport: three clients generate
    CONCURRENTLY against one batched final-stage peer; outputs match the
    oracle and the engine ran fewer steps than sequential serving would."""


    cfg = tiny_cfg()
    params = init_params(jax.random.PRNGKey(7), cfg)
    plan = StagePlan.from_splits(cfg.num_layers, parse_splits("4"))
    spec = plan.stages[1]
    inner = engine(cfg, spec, slice_stage_params(cfg, params, spec), slots=4,
                   max_len=64)
    adapter = BatchingStageAdapter(inner, window_s=0.05, peer_id="batched")

    # Diagnostic trace: this test flaked rarely under heavy load with a
    # deterministic-looking 2-step state rewind that no standalone repro
    # ever reproduced; root-caused round 3 to vm.max_map_count exhaustion
    # (tests/conftest.py `pytest_sessionfinish`; the repro script was retired).
    # Keep the trace so any future in-suite failure carries its own event
    # history instead of just a token diff.
    import time as _time

    trace = []
    _orig_forward = adapter.forward

    def traced_forward(req):
        rec = [_time.monotonic(), req.session_id, req.cur_len,
               "prefill" if req.is_prefill else "decode", None]
        trace.append(rec)
        try:
            resp = _orig_forward(req)
        except Exception as exc:
            rec[4] = f"ERR:{exc}"
            raise
        rec[4] = (f"tok={resp.token_id}" if resp.token_id is not None
                  else "hidden")
        return resp

    adapter.forward = traced_forward
    transport = LocalTransport()
    transport.add_peer("batched", adapter)
    registry = PlacementRegistry(rng=random.Random(0))
    registry.register(make_server_record("batched", spec))

    sampling = SamplingParams(temperature=0.0)
    n_new = 6
    prompts = [[5, 9, 23, 7, 81], [44, 2, 3], [100, 11, 12, 13]]
    results = [None] * len(prompts)

    def run(i):
        stage0 = StageExecutor(cfg, plan.stages[0],
                               slice_stage_params(cfg, params, plan.stages[0]),
                               peer_id=f"client{i}")
        client = PipelineClient(cfg, plan, stage0, transport, registry,
                                settle_seconds=0.0, seed=0)
        results[i] = client.generate(prompts[i], max_new_tokens=n_new,
                                     sampling=sampling).tokens

    threads = [threading.Thread(target=run, args=(i,))
               for i in range(len(prompts))]
    for t in threads:
        t.start()
    # Generous deadline: cold XLA compiles under a loaded machine can take
    # minutes; a too-short join leaves results[i] None and fails the parity
    # assert with a misleading diff.
    for t in threads:
        t.join(timeout=600)
    assert all(r is not None for r in results), "client thread(s) timed out"
    for i, prompt in enumerate(prompts):
        want = oracle_generate(cfg, params, prompt, n_new, sampling)
        if results[i] != want:
            t0 = trace[0][0] if trace else 0.0
            dump = "\n".join(
                f"  {t - t0:8.4f}s {sid} cur={cur} {kind} -> {out}"
                for t, sid, cur, kind, out in trace)
            raise AssertionError(
                f"client {i}: got {results[i]} want {want}\n"
                f"adapter event trace:\n{dump}")
    # Coalescing is asserted deterministically (barrier-synchronized) in
    # test_adapter_coalesces_concurrent_decodes — under heavy CPU contention
    # these free-running clients can legitimately serialize, so a step-count
    # bound here would be a load-dependent flake.
    assert inner.decode_steps <= len(prompts) * (n_new - 1)


def test_adapter_coalesces_concurrent_decodes():
    """Deterministic coalescing check: N decode requests enter the adapter
    together (barrier just before forward), so the leader's window must
    merge them into ONE batched step."""
    cfg, params, inner = tiny_engine(21, slots=4, max_len=32)
    adapter = BatchingStageAdapter(inner, window_s=1.0, peer_id="batched")
    prompts = {"a": [5, 9, 23], "b": [44, 2], "c": [100, 11, 12]}
    for sid, p in prompts.items():
        adapter.forward(StageRequest(
            session_id=sid, hidden=jnp.asarray([p], jnp.int32),
            seq_len=len(p), cur_len=0, is_prefill=True, max_length=32))
    # Warm the decode compile OUTSIDE the timed window so the barrier'd
    # round's wall time is pure window, not a 40s first compile.
    inner.decode_batch({"a": jnp.asarray([[7]], jnp.int32)})
    inner.lengths[inner.slot("a")] -= 1  # undo the warm step's advance

    barrier = threading.Barrier(len(prompts))
    tokens = {}

    def run(sid, p):
        barrier.wait()
        r = adapter.forward(StageRequest(
            session_id=sid, hidden=jnp.asarray([[7]], jnp.int32),
            seq_len=1, cur_len=len(p), is_prefill=False, max_length=32))
        tokens[sid] = r.token_id

    before = inner.decode_steps
    threads = [threading.Thread(target=run, args=(sid, p))
               for sid, p in prompts.items()]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=300)
    assert set(tokens) == set(prompts)
    # All three sessions advanced in ONE batched step (the 1s window gives
    # even a loaded machine time to admit barrier-released followers).
    assert inner.decode_steps == before + 1


def test_adapter_refuses_non_batchable_requests():
    cfg, params, inner = tiny_engine(8, slots=2, max_len=32)
    adapter = BatchingStageAdapter(inner)
    base = dict(session_id="s", hidden=jnp.zeros((1, 1), jnp.int32),
                seq_len=1, cur_len=0, is_prefill=False, max_length=32)
    for bad in (dict(hypo_ids=(0,)), dict(num_logprobs=2),
                dict(is_replay=True), dict(train=True),
                # drafts ARE batchable now, but a malformed one (seq_len
                # must be K+1) is still refused before it can desync a slot
                dict(draft_tokens=(1,))):
        with pytest.raises(StageExecutionError):
            adapter.forward(StageRequest(**{**base, **bad}))
    # decode without prefill is the per-session replay contract -> refused
    with pytest.raises(StageExecutionError):
        adapter.forward(StageRequest(**base))


def test_a_first_token_s_head_runs_over_the_last_row_alone():
    """A prefill's first token is sampled from the prompt's last row: the
    adapter hands the head that ONE row (a 14000-row prompt's other rows
    were 1.1 GB of float32 logits nobody read), and the token is the one
    the head over every row gives."""
    cfg, params, inner = tiny_engine(8, slots=2, max_len=32)
    adapter = BatchingStageAdapter(inner)
    seen = []
    head = inner.logits
    inner.logits = lambda h: seen.append(h.shape) or head(h)
    ids = jnp.asarray([[5, 9, 2, 7, 11, 3, 8]], jnp.int32)
    resp = adapter.forward(StageRequest(
        session_id="s", hidden=ids, seq_len=7, cur_len=0, is_prefill=True,
        max_length=32, sampling=SamplingParams(temperature=0.0)))
    assert seen == [(1, 1, cfg.hidden_size)]
    twin = engine(cfg, full_spec(cfg), params, slots=2, max_len=32)
    every = twin.logits(twin.prefill("s", ids))
    assert resp.token_id == int(jnp.argmax(every[0, -1]))


def test_adapter_refuses_stale_cur_len_and_round_survives():
    cfg, params, inner = tiny_engine(9, slots=2, max_len=32)
    adapter = BatchingStageAdapter(inner, window_s=0.0)

    def req(sid, hidden, t, cur, prefill):
        return StageRequest(session_id=sid, hidden=hidden, seq_len=t,
                            cur_len=cur, is_prefill=prefill, max_length=32)

    adapter.forward(req("a", jnp.asarray([[5, 9, 23]], jnp.int32), 3, 0, True))
    adapter.forward(req("b", jnp.asarray([[44, 2]], jnp.int32), 2, 0, True))
    # A stale retry (cur_len behind the server) is REFUSED — continuing
    # would silently desync the KV — and must not poison other sessions.
    with pytest.raises(StageExecutionError, match="cur_len"):
        adapter.forward(req("a", jnp.asarray([[7]], jnp.int32), 1, 1, False))
    r = adapter.forward(req("b", jnp.asarray([[7]], jnp.int32), 1, 2, False))
    assert r.token_id is not None
    # ...and the correctly-positioned request for A works.
    r = adapter.forward(req("a", jnp.asarray([[7]], jnp.int32), 1, 3, False))
    assert r.token_id is not None


@pytest.mark.parity
def test_batched_mistral_sliding_window_matches_oracle():
    """Sliding-window (Mistral) attention on the batched path: windowed
    masks in prefill and decode match the per-session oracle, with prompts
    long enough that the window actually truncates attention."""
    cfg = mistral_config(
        sliding_window=4, vocab_size=257, hidden_size=64, num_layers=4,
        num_heads=4, num_kv_heads=2, intermediate_size=128,
        max_position_embeddings=256)
    assert cfg.sliding_window == 4
    params = init_params(jax.random.PRNGKey(11), cfg)
    ex = engine(cfg, full_spec(cfg), params, slots=4, max_len=64)
    n_new = 6   # prompts up to 7 tokens + 6 generated >> window of 4
    got = batched_generate(ex, PROMPTS, n_new)
    for sid, prompt in PROMPTS.items():
        assert got[sid] == oracle_tokens(cfg, params, prompt, n_new), sid


def test_prefill_failure_frees_slot():
    """A prefill whose jitted dispatch raises must recycle the slot instead
    of leaking it until end_session (advisor finding)."""
    cfg = tiny_cfg()
    params = init_params(jax.random.PRNGKey(12), cfg)
    ex = BatchedStageExecutor(cfg, full_spec(cfg), params,   # its own: the
                              slots=1, max_len=32)           # slot is swapped

    def boom(*a, **k):
        raise RuntimeError("synthetic dispatch failure")

    ex._prefill_jit = boom
    with pytest.raises(RuntimeError, match="synthetic"):
        ex.prefill("s1", np.asarray([[1, 2, 3]], np.int32))
    assert ex.slot("s1") is None
    ex._prefill_jit = None          # rebuild the real jit
    ex.prefill("s2", np.asarray([[4, 5]], np.int32))   # slot is usable again
    assert ex.slot("s2") is not None


@pytest.mark.parity
def test_batched_stage_pipeline_matches_oracle():
    """Two batched stage executors chained as pipeline hops: batched decode
    composes with staged serving (hidden rows flow per session)."""
    cfg = tiny_cfg()
    params = init_params(jax.random.PRNGKey(5), cfg)
    plan = StagePlan.from_splits(cfg.num_layers, parse_splits("4"))
    s0 = engine(cfg, plan.stages[0],
                slice_stage_params(cfg, params, plan.stages[0]), slots=4,
                max_len=64)
    s1 = engine(cfg, plan.stages[1],
                slice_stage_params(cfg, params, plan.stages[1]), slots=4,
                max_len=64)
    prompts = {"a": PROMPTS["a"], "b": PROMPTS["b"]}
    n_new = 5
    toks = {}
    for sid, prompt in prompts.items():
        h0 = s0.prefill(sid, np.asarray(prompt, np.int32)[None, :])
        h1 = s1.prefill(sid, h0)
        toks[sid] = [int(jnp.argmax(s1.logits(h1)[0, -1]))]
    for _ in range(n_new - 1):
        ins0 = {sid: jnp.asarray([[toks[sid][-1]]], jnp.int32)
                for sid in prompts}
        mid = s0.decode_batch(ins0)
        outs = s1.decode_batch(mid)
        for sid, h in outs.items():
            toks[sid].append(int(jnp.argmax(s1.logits(h)[0, -1])))
    for sid, prompt in prompts.items():
        assert toks[sid] == oracle_tokens(cfg, params, prompt, n_new), sid


@pytest.mark.parity
def test_batched_mixtral_moe_matches_oracle():
    """MoE (Mixtral) on the batched path: the dense-routed expert MLP runs
    inside the slot-batched step; token parity with the per-session oracle.
    Short horizon: random-weight routers sit near top-k ties, so long runs
    would test fp noise, not the engine (see test_models_oracle note)."""
    cfg = mixtral_config(
        vocab_size=257, hidden_size=64, num_layers=2, num_heads=4,
        num_kv_heads=2, intermediate_size=96, num_experts=4,
        num_experts_per_tok=2, max_position_embeddings=256)
    params = init_params(jax.random.PRNGKey(13), cfg)
    ex = engine(cfg, full_spec(cfg), params, slots=4, max_len=64)
    prompts = {"a": [5, 9, 23, 7, 81], "b": [44, 2, 3]}
    got = batched_generate(ex, prompts, 4)
    for sid, prompt in prompts.items():
        assert got[sid] == oracle_tokens(cfg, params, prompt, 4), sid


# ---------------------------------------------------------------------------
# Speculative verification on the batched engine (VERDICT r2 task 7):
# draft steps are multi-token batched rounds + per-row accept/reject.
# ---------------------------------------------------------------------------


@pytest.mark.parity
def test_batched_multi_token_step_and_rewind():
    """decode_batch with T>1 (the speculative verify step): a teacher-forced
    multi-token step predicts the same continuation as single-token
    stepping, other sessions' slots are untouched, and rewind() rolls the
    slot back so regeneration from the accepted prefix matches the oracle."""
    cfg, params, ex = tiny_engine(4, slots=2, max_len=64)
    pa, pb = PROMPTS["a"], PROMPTS["b"]
    ra = oracle_tokens(cfg, params, pa, 6)
    rb = oracle_tokens(cfg, params, pb, 3)
    ha = ex.prefill("a", np.asarray(pa, np.int32)[None, :])
    assert int(jnp.argmax(ex.logits(ha)[0, -1])) == ra[0]
    hb = ex.prefill("b", np.asarray(pb, np.int32)[None, :])
    tb = [int(jnp.argmax(ex.logits(hb)[0, -1]))]
    # One T=3 step for "a" only carries ra[0..2]; position i consumes ra[i]
    # so its logits predict ra[i+1]. "b" is inactive (masked).
    outs = ex.decode_batch({"a": jnp.asarray([ra[:3]], jnp.int32)})
    got = [int(jnp.argmax(ex.logits(outs["a"])[0, i])) for i in range(3)]
    assert got == ra[1:4]
    # Rewind "a" past the last two positions (keep [prompt, ra0]) and
    # regenerate single-token: parity with the oracle continuation.
    ex.rewind("a", len(pa) + 1)
    outs = ex.decode_batch({"a": jnp.asarray([[ra[1]]], jnp.int32)})
    assert int(jnp.argmax(ex.logits(outs["a"])[0, -1])) == ra[2]
    # "b" was never disturbed by a's multi-token round or rewind.
    for _ in range(2):
        outs = ex.decode_batch({"b": jnp.asarray([[tb[-1]]], jnp.int32)})
        tb.append(int(jnp.argmax(ex.logits(outs["b"])[0, -1])))
    assert tb == rb


def test_batched_rewind_bounds():
    cfg, params, ex = tiny_engine(4, slots=1, max_len=32)
    ex.prefill("s", np.asarray([[1, 2, 3]], np.int32))
    with pytest.raises(ValueError):
        ex.rewind("s", 4)          # beyond current length
    with pytest.raises(KeyError):
        ex.rewind("nope", 0)
    ex.rewind("s", 2)
    assert int(ex.lengths[ex.slot("s")]) == 2


def test_adapter_coalesces_speculative_rounds():
    """Two draft steps with the same K enter the adapter together: ONE
    batched multi-token step serves both, and each row verifies
    independently (perfect drafts accept K, garbage drafts accept 0)."""
    greedy = SamplingParams(temperature=0.0)
    cfg, params, inner = tiny_engine(6, slots=4, max_len=64)
    adapter = BatchingStageAdapter(inner, window_s=1.0)
    pa, pb = PROMPTS["a"], PROMPTS["b"]
    ra = oracle_tokens(cfg, params, pa, 5)
    rb = oracle_tokens(cfg, params, pb, 5)
    for sid, p in (("a", pa), ("b", pb)):
        adapter.forward(StageRequest(
            session_id=sid, hidden=jnp.asarray([p], jnp.int32),
            seq_len=len(p), cur_len=0, is_prefill=True, max_length=64,
            sampling=greedy))
    # Warm the T=3 compile outside the coalescing window, then roll back.
    inner.decode_batch({"a": jnp.asarray([[1, 2, 3]], jnp.int32)})
    inner.rewind("a", len(pa))

    good = (ra[1], ra[2])                       # perfect drafts for a
    bad = ((rb[1] + 1) % cfg.vocab_size,) * 2   # never-matching drafts for b
    barrier = threading.Barrier(2)
    out = {}

    def run(sid, p, r0, drafts):
        barrier.wait()
        out[sid] = adapter.forward(StageRequest(
            session_id=sid,
            hidden=jnp.asarray([[r0, *drafts]], jnp.int32),
            seq_len=3, cur_len=len(p), is_prefill=False, max_length=64,
            draft_tokens=tuple(drafts), start_from_position=len(p),
            sampling=greedy))

    before = inner.decode_steps
    threads = [threading.Thread(target=run, args=("a", pa, ra[0], good)),
               threading.Thread(target=run, args=("b", pb, rb[0], bad))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=300)
    assert set(out) == {"a", "b"}
    assert inner.decode_steps == before + 1    # ONE coalesced verify round
    assert out["a"].n_accepted == 2 and out["a"].tokens == tuple(ra[1:4])
    assert out["b"].n_accepted == 0 and out["b"].tokens == (rb[1],)
    # Rejected overhang rewound: b's slot holds prompt + [rb0] only.
    assert int(inner.lengths[inner.slot("b")]) == len(pb) + 1
    assert int(inner.lengths[inner.slot("a")]) == len(pa) + 3


@pytest.mark.parity
def test_client_speculative_on_batched_peer():
    """End to end: a speculative session (kind="spec") routes TO a batched
    peer, its draft rounds coalesce there, and greedy output is
    token-identical to the oracle — with far fewer engine steps than
    single-token decoding."""
    from test_speculative import perfect_draft

    cfg = tiny_cfg()
    params = init_params(jax.random.PRNGKey(7), cfg)
    plan = StagePlan.from_splits(cfg.num_layers, parse_splits("4"))
    spec = plan.stages[1]
    inner = engine(cfg, spec, slice_stage_params(cfg, params, spec), slots=4,
                   max_len=64)
    adapter = BatchingStageAdapter(inner, window_s=0.0, peer_id="batched")
    transport = LocalTransport()
    transport.add_peer("batched", adapter)
    registry = PlacementRegistry(rng=random.Random(0))
    registry.register(make_server_record("batched", spec, engine="batched"))
    stage0 = StageExecutor(cfg, plan.stages[0],
                           slice_stage_params(cfg, params, plan.stages[0]),
                           peer_id="client-local")
    client = PipelineClient(cfg, plan, stage0, transport, registry,
                            settle_seconds=0.0, seed=0)
    prompt = [5, 9, 23, 7, 81]
    greedy = SamplingParams(temperature=0.0)
    ref = oracle_generate(cfg, params, prompt, 12, greedy)
    res = client.generate(prompt, max_new_tokens=12, sampling=greedy,
                          speculative_k=4,
                          draft_fn=perfect_draft(ref, len(prompt)))
    assert res.tokens == ref
    # Perfect drafts: 11 post-prefill tokens in ceil(11/5)=3 verify rounds.
    assert inner.decode_steps <= 3


@pytest.mark.parity
def test_client_speculative_sampled_batched_matches_per_session():
    """temperature>0 speculative on the batched peer: same seed + same
    drafts produce the SAME tokens as the per-session executor (the
    verification math is shared — executor.verify_drafts_from_logits — and
    slot-batched logits match the per-session oracle)."""
    cfg = tiny_cfg()
    params = init_params(jax.random.PRNGKey(3), cfg)
    plan = StagePlan.from_splits(cfg.num_layers, parse_splits("4"))
    spec = plan.stages[1]
    prompt = [3, 1, 4, 1, 5, 3, 1, 4]   # repetitive: ngram drafter fires
    sampling = SamplingParams(temperature=0.7, top_p=0.9)

    def run(peer):
        transport = LocalTransport()
        transport.add_peer("peer", peer)
        registry = PlacementRegistry(rng=random.Random(0))
        registry.register(make_server_record(
            "peer", spec, engine=getattr(peer, "engine", "session")))
        stage0 = StageExecutor(cfg, plan.stages[0],
                               slice_stage_params(cfg, params, plan.stages[0]),
                               peer_id="client-local")
        client = PipelineClient(cfg, plan, stage0, transport, registry,
                                settle_seconds=0.0, seed=0)
        return client.generate(prompt, max_new_tokens=10, sampling=sampling,
                               speculative_k=3).tokens

    per_session = run(StageExecutor(
        cfg, spec, slice_stage_params(cfg, params, spec), peer_id="peer"))
    inner = engine(cfg, spec, slice_stage_params(cfg, params, spec), slots=4,
                   max_len=64)
    batched = run(BatchingStageAdapter(inner, window_s=0.0, peer_id="peer"))
    assert batched == per_session


def _tiny_gemma2():
    # sliding_window=4 with 7-token prompts + 6 generated tokens makes the
    # even (windowed) layers actually truncate attention; head_dim=32 !=
    # hidden/heads exercises the decoupled projections. Softcaps are set
    # SMALL on purpose: at the production default (50) a tiny random
    # model's scores sit deep in tanh's linear region and dropping the cap
    # would not change a single argmax — the caps must bite for the parity
    # test to actually cover them.
    return gemma2_config(vocab_size=257, hidden_size=64, num_layers=4,
                         num_heads=4, num_kv_heads=2, intermediate_size=128,
                         head_dim=32, sliding_window=4,
                         query_pre_attn_scalar=16.0,
                         attn_softcap=2.0, final_softcap=3.0,
                         max_position_embeddings=256)


@pytest.mark.parity
def test_batched_gemma2_matches_oracle():
    """gemma2 semantics (sandwich norms, softcaps, alternating per-layer
    windows, query scale) on the batched bodies: tokens must match the
    shared-layer-math oracle per session."""
    cfg = _tiny_gemma2()
    params = init_params(jax.random.PRNGKey(3), cfg)
    ex = engine(cfg, full_spec(cfg), params, slots=4, max_len=64)
    n_new = 6
    got = batched_generate(ex, PROMPTS, n_new)
    for sid, prompt in PROMPTS.items():
        assert got[sid] == oracle_tokens(cfg, params, prompt, n_new), sid


def test_remaining_custom_engines_refuse_gemma2():
    """The sp ring engine and TP shard specs still re-implement the layer
    math without gemma2 semantics — they must refuse, not silently serve a
    different model."""
    with pytest.raises(ValueError, match="gemma2"):
        validate_tp(_tiny_gemma2(), 2)


def test_batched_gemma2_with_prefix_cache():
    """gemma2 semantics and prefix-cache hits compose on the batched
    engine: a warm suffix-continuation (per-layer windows, softcaps,
    sandwich norms) must reproduce the cold full-prefill decode tokens."""
    cfg = _tiny_gemma2()
    params = init_params(jax.random.PRNGKey(4), cfg)
    ex = engine(cfg, full_spec(cfg), params, slots=4, max_len=64,
                prefix_cache_bytes=32 << 20)
    ex.prefix_store.grain = 8
    prompt = np.asarray(list(range(20, 53)), np.int32)[None, :]  # 33 tokens

    def gen(sid):
        h = ex.prefill(sid, prompt, prefix_len=33)
        toks = [int(jnp.argmax(ex.logits(h)[0, -1]))]
        for _ in range(4):
            out = ex.decode_batch({sid: jnp.asarray([[toks[-1]]], jnp.int32)})
            toks.append(int(jnp.argmax(ex.logits(out[sid])[0, -1])))
        return toks

    cold = gen("cold")
    warm = gen("warm")
    assert ex.prefix_store.stats()["grains_reused"] == 4
    assert cold == warm
