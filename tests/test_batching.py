"""Continuous batching (runtime.batching): N concurrent sessions, one
decode step — token-identical to per-session decoding.

The reference computes one forward per session per token
(src/rpc_handler.py:149-325); the batched executor advances every active
slot in one jitted step over a slot-major KV cache.
"""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.models import (
    full_forward,
    init_kv_cache,
    init_params,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.models.partition import (
    ROLE_FULL,
    StagePlan,
    StageSpec,
    parse_splits,
    slice_stage_params,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime.batching import (
    BURST_FLOATS,
    BURST_INTS,
    BatchedStageExecutor,
    SlotFull,
)

from test_runtime_pipeline import kernel_cfg, tiny_cfg

def transfer_counts(eng, ad=None):
    """The round path's transfer and dispatch counters on a registry that
    counts (the process's own is off in tests), for an engine and, where
    given, its adapter: a function that reads (up, down, dispatches)."""
    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.telemetry import (
        catalog,
    )
    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.telemetry.metrics import (
        MetricsRegistry,
    )

    reg = MetricsRegistry(enabled=True)
    moved = eng._m_transfers = catalog.get("server_burst_transfers_total",
                                           reg)
    rounds = eng._m_burst_disp = catalog.get("server_burst_dispatches_total",
                                             reg)
    if ad is not None:
        ad._m_ids_read = moved.labels(dir="down")

    def read():
        by = {dict(c.labels)["dir"]: int(c.value) for c in moved.children()}
        return by.get("up", 0), by.get("down", 0), int(rounds.value)

    return read


# Quarantine-with-teeth (tests/conftest.py pytest_runtest_protocol): the
# DETERMINISTIC single-threaded token-parity tests below carry
# @pytest.mark.parity — the documented victims of load-induced host
# corruption; a failure reruns ONCE in-process, and real logic bugs fail
# both runs. The CONCURRENT adapter tests are deliberately NOT marked: a
# real intermittent race there must stay a failure, not be mislabeled as
# environmental corruption by a passing rerun.


def full_spec(cfg):
    return StageSpec(index=0, role=ROLE_FULL, start=0, end=cfg.num_layers)


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


PROMPTS = {
    "a": [5, 9, 23, 7, 81],
    "b": [44, 2, 3],
    "c": [100, 11, 12, 13, 14, 15, 16],
    "d": [7, 7, 9],
}


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
    cfg = tiny_cfg(family)
    params = init_params(jax.random.PRNGKey(0), cfg)
    ex = BatchedStageExecutor(cfg, full_spec(cfg), params,
                              slots=4, max_len=64)
    n_new = 6
    got = batched_generate(ex, PROMPTS, n_new)
    for sid, prompt in PROMPTS.items():
        assert got[sid] == oracle_tokens(cfg, params, prompt, n_new), sid
    # The whole point: n_new-1 batched steps TOTAL, not per session.
    assert ex.decode_steps == n_new - 1


@pytest.mark.parity
def test_sessions_join_and_leave_mid_stream():
    cfg = tiny_cfg()
    params = init_params(jax.random.PRNGKey(1), cfg)
    ex = BatchedStageExecutor(cfg, full_spec(cfg), params,
                              slots=2, max_len=64)
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
    cfg = tiny_cfg()
    params = init_params(jax.random.PRNGKey(2), cfg)
    ex = BatchedStageExecutor(cfg, full_spec(cfg), params,
                              slots=4, max_len=64)
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
    cfg = tiny_cfg()
    params = init_params(jax.random.PRNGKey(3), cfg)
    ex = BatchedStageExecutor(cfg, full_spec(cfg), params,
                              slots=2, max_len=32)
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
    import random
    import threading

    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.ops.sampling import (
        SamplingParams,
    )
    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime.batching import (
        BatchingStageAdapter,
    )
    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime.client import (
        PipelineClient,
        make_server_record,
    )
    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime.executor import (
        StageExecutor,
    )
    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime.transport import (
        LocalTransport,
    )
    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.scheduling.registry import (
        PlacementRegistry,
    )

    from test_runtime_pipeline import oracle_generate

    cfg = tiny_cfg()
    params = init_params(jax.random.PRNGKey(7), cfg)
    plan = StagePlan.from_splits(cfg.num_layers, parse_splits("4"))
    spec = plan.stages[1]
    inner = BatchedStageExecutor(cfg, spec,
                                 slice_stage_params(cfg, params, spec),
                                 slots=4, max_len=64)
    adapter = BatchingStageAdapter(inner, window_s=0.05, peer_id="batched")

    # Diagnostic trace: this test flaked rarely under heavy load with a
    # deterministic-looking 2-step state rewind that no standalone repro
    # ever reproduced; root-caused round 3 to vm.max_map_count exhaustion
    # (see scripts/run_tests.py header — the repro script was retired).
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
    import threading

    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime.batching import (
        BatchingStageAdapter,
    )
    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime.messages import (
        StageRequest,
    )

    cfg = tiny_cfg()
    params = init_params(jax.random.PRNGKey(21), cfg)
    inner = BatchedStageExecutor(cfg, full_spec(cfg), params,
                                 slots=4, max_len=32)
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
    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime.batching import (
        BatchingStageAdapter,
    )
    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime.executor import (
        StageExecutionError,
    )
    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime.messages import (
        StageRequest,
    )

    cfg = tiny_cfg()
    params = init_params(jax.random.PRNGKey(8), cfg)
    inner = BatchedStageExecutor(cfg, full_spec(cfg), params,
                                 slots=2, max_len=32)
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
    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime.batching import (
        BatchingStageAdapter,
    )
    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.ops.sampling import (
        SamplingParams,
    )
    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime.messages import (
        StageRequest,
    )

    cfg = tiny_cfg()
    params = init_params(jax.random.PRNGKey(8), cfg)
    inner = BatchedStageExecutor(cfg, full_spec(cfg), params,
                                 slots=2, max_len=32)
    adapter = BatchingStageAdapter(inner)
    seen = []
    head = inner.logits
    inner.logits = lambda h: seen.append(h.shape) or head(h)
    ids = jnp.asarray([[5, 9, 2, 7, 11, 3, 8]], jnp.int32)
    resp = adapter.forward(StageRequest(
        session_id="s", hidden=ids, seq_len=7, cur_len=0, is_prefill=True,
        max_length=32, sampling=SamplingParams(temperature=0.0)))
    assert seen == [(1, 1, cfg.hidden_size)]
    twin = BatchedStageExecutor(cfg, full_spec(cfg), params,
                                slots=2, max_len=32)
    every = twin.logits(twin.prefill("s", ids))
    assert resp.token_id == int(jnp.argmax(every[0, -1]))


def test_adapter_refuses_stale_cur_len_and_round_survives():
    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime.batching import (
        BatchingStageAdapter,
    )
    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime.executor import (
        StageExecutionError,
    )
    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime.messages import (
        StageRequest,
    )

    cfg = tiny_cfg()
    params = init_params(jax.random.PRNGKey(9), cfg)
    inner = BatchedStageExecutor(cfg, full_spec(cfg), params,
                                 slots=2, max_len=32)
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
    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.models import (
        mistral_config,
    )

    cfg = mistral_config(
        sliding_window=4, vocab_size=257, hidden_size=64, num_layers=4,
        num_heads=4, num_kv_heads=2, intermediate_size=128,
        max_position_embeddings=256)
    assert cfg.sliding_window == 4
    params = init_params(jax.random.PRNGKey(11), cfg)
    ex = BatchedStageExecutor(cfg, full_spec(cfg), params,
                              slots=4, max_len=64)
    n_new = 6   # prompts up to 7 tokens + 6 generated >> window of 4
    got = batched_generate(ex, PROMPTS, n_new)
    for sid, prompt in PROMPTS.items():
        assert got[sid] == oracle_tokens(cfg, params, prompt, n_new), sid


def test_prefill_failure_frees_slot():
    """A prefill whose jitted dispatch raises must recycle the slot instead
    of leaking it until end_session (advisor finding)."""
    cfg = tiny_cfg()
    params = init_params(jax.random.PRNGKey(12), cfg)
    ex = BatchedStageExecutor(cfg, full_spec(cfg), params,
                              slots=1, max_len=32)

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
    s0 = BatchedStageExecutor(cfg, plan.stages[0],
                              slice_stage_params(cfg, params, plan.stages[0]),
                              slots=4, max_len=64)
    s1 = BatchedStageExecutor(cfg, plan.stages[1],
                              slice_stage_params(cfg, params, plan.stages[1]),
                              slots=4, max_len=64)
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
    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.models import (
        mixtral_config,
    )

    cfg = mixtral_config(
        vocab_size=257, hidden_size=64, num_layers=2, num_heads=4,
        num_kv_heads=2, intermediate_size=96, num_experts=4,
        num_experts_per_tok=2, max_position_embeddings=256)
    params = init_params(jax.random.PRNGKey(13), cfg)
    ex = BatchedStageExecutor(cfg, full_spec(cfg), params,
                              slots=4, max_len=64)
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
    cfg = tiny_cfg()
    params = init_params(jax.random.PRNGKey(4), cfg)
    ex = BatchedStageExecutor(cfg, full_spec(cfg), params, slots=2,
                              max_len=64)
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
    cfg = tiny_cfg()
    params = init_params(jax.random.PRNGKey(4), cfg)
    ex = BatchedStageExecutor(cfg, full_spec(cfg), params, slots=1,
                              max_len=32)
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
    import threading

    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.ops.sampling import (
        SamplingParams,
    )
    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime.batching import (
        BatchingStageAdapter,
    )
    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime.messages import (
        StageRequest,
    )

    greedy = SamplingParams(temperature=0.0)
    cfg = tiny_cfg()
    params = init_params(jax.random.PRNGKey(6), cfg)
    inner = BatchedStageExecutor(cfg, full_spec(cfg), params, slots=4,
                                 max_len=64)
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
    import random

    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.ops.sampling import (
        SamplingParams,
    )
    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime.batching import (
        BatchingStageAdapter,
    )
    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime.client import (
        PipelineClient,
        make_server_record,
    )
    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime.executor import (
        StageExecutor,
    )
    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime.transport import (
        LocalTransport,
    )
    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.scheduling.registry import (
        PlacementRegistry,
    )

    from test_runtime_pipeline import oracle_generate
    from test_speculative import perfect_draft

    cfg = tiny_cfg()
    params = init_params(jax.random.PRNGKey(7), cfg)
    plan = StagePlan.from_splits(cfg.num_layers, parse_splits("4"))
    spec = plan.stages[1]
    inner = BatchedStageExecutor(cfg, spec,
                                 slice_stage_params(cfg, params, spec),
                                 slots=4, max_len=64)
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
    import random

    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.ops.sampling import (
        SamplingParams,
    )
    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime.batching import (
        BatchingStageAdapter,
    )
    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime.client import (
        PipelineClient,
        make_server_record,
    )
    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime.executor import (
        StageExecutor,
    )
    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime.transport import (
        LocalTransport,
    )
    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.scheduling.registry import (
        PlacementRegistry,
    )

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
    inner = BatchedStageExecutor(cfg, spec,
                                 slice_stage_params(cfg, params, spec),
                                 slots=4, max_len=64)
    batched = run(BatchingStageAdapter(inner, window_s=0.0, peer_id="peer"))
    assert batched == per_session


def _tiny_gemma2():
    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.models import (
        gemma2_config,
    )

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
    ex = BatchedStageExecutor(cfg, full_spec(cfg), params,
                              slots=4, max_len=64)
    n_new = 6
    got = batched_generate(ex, PROMPTS, n_new)
    for sid, prompt in PROMPTS.items():
        assert got[sid] == oracle_tokens(cfg, params, prompt, n_new), sid


def test_remaining_custom_engines_refuse_gemma2():
    """The sp ring engine and TP shard specs still re-implement the layer
    math without gemma2 semantics — they must refuse, not silently serve a
    different model."""
    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.parallel.tensor_parallel import (
        validate_tp,
    )

    with pytest.raises(ValueError, match="gemma2"):
        validate_tp(_tiny_gemma2(), 2)


def test_batched_gemma2_with_prefix_cache():
    """gemma2 semantics and prefix-cache hits compose on the batched
    engine: a warm suffix-continuation (per-layer windows, softcaps,
    sandwich norms) must reproduce the cold full-prefill decode tokens."""
    cfg = _tiny_gemma2()
    params = init_params(jax.random.PRNGKey(4), cfg)
    ex = BatchedStageExecutor(cfg, full_spec(cfg), params, slots=4,
                              max_len=64, prefix_cache_bytes=32 << 20)
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


# ---------------------------------------------------------------------------
# int8 layer stacks reach the Pallas kernel WHOLE (runtime.batching
# _split_stacks / _layer_at): no program slices a layer's int8 weight out
# of its stack for the call.
# ---------------------------------------------------------------------------


def _stacked_tree(kind):
    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.models import (
        mixtral_config,
    )
    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.models.quant import (
        quantize_params,
    )

    cfg = (mixtral_config(
        vocab_size=128, hidden_size=64, num_layers=2, num_heads=4,
        num_kv_heads=2, intermediate_size=96, num_experts=4,
        num_experts_per_tok=2, max_position_embeddings=32)
        if kind == "int8-moe" else kernel_cfg())
    params = init_params(jax.random.PRNGKey(0), cfg)
    return quantize_params(params, kind.split("-")[0])["layers"]


@pytest.mark.parametrize("kind", ["none", "nf4", "int8", "int8-moe"])
def test_split_stacks_holds_dense_int8_stacks_only(kind):
    """`_split_stacks` takes the dense [L, K, N] int8 stacks out of what
    lax.scan slices, and nothing else: a bf16 or an NF4 tree comes back
    as the SAME object with nothing held; MoE expert stacks ([L, E, K,
    N]) stay in xs. `_layer_at` puts a view of layer i where each held
    stack was, so the body sees the tree's own structure."""
    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.models.quant import (
        QuantizedLayerView,
        QuantizedTensor,
    )
    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime.batching import (
        _layer_at,
        _split_stacks,
    )

    layers = _stacked_tree(kind)
    xs, held = _split_stacks(layers)
    if kind in ("none", "nf4"):
        assert xs is layers and held == {}
        lp = jax.tree.map(lambda a: a[1], layers)
        assert _layer_at(lp, held, 1) is lp
        return
    dense = {("attn", k) for k in ("wq", "wk", "wv", "wo")}
    if kind == "int8":
        dense |= {("mlp", k) for k in ("wg", "wu", "wd")}
    assert set(held) == dense
    assert all(w.q.ndim == 3 for w in held.values())
    is_q = lambda v: isinstance(v, QuantizedTensor)          # noqa: E731
    left = [v for v in jax.tree.leaves(xs, is_leaf=is_q) if is_q(v)]
    assert all(v.q.ndim == 4 for v in left)                  # expert stacks
    assert len(left) == (3 if kind == "int8-moe" else 0)
    lp = _layer_at(jax.tree.map(lambda a: a[1], xs), held, 1)
    assert jax.tree.structure(
        jax.tree.map(lambda a: 0, lp, is_leaf=lambda v: isinstance(
            v, (QuantizedTensor, QuantizedLayerView)))
    ) == jax.tree.structure(jax.tree.map(lambda a: 0, layers, is_leaf=is_q))
    for path, stack in held.items():
        view = lp[path[0]][path[1]]
        assert isinstance(view, QuantizedLayerView)
        assert view.stack is stack and view.index == 1
        np.testing.assert_array_equal(np.asarray(view.layer().q),
                                      np.asarray(stack.q[1]))


def _all_eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs nested in it (scan,
    cond, pjit bodies), except the bodies of Pallas kernels."""
    for e in jaxpr.eqns:
        yield e
        if e.primitive.name == "pallas_call":
            continue
        for v in e.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else (v,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _all_eqns(sub)


@pytest.mark.parametrize(
    "program", ["burst_tick", "decode_step", "prefill", "prefill_suffix"])
def test_int8_programs_hand_the_kernel_the_whole_stack(monkeypatch, program):
    """In each device program of an int8 llama-shaped engine, every
    pallas_call takes a rank-3 int8 operand (the layer stack itself) and
    NO equation (dynamic_slice, dynamic_index, gather, anything) yields
    an int8 array of rank 2 or more: nothing is there for XLA to write
    out as a staging copy before the custom call."""
    import global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.ops.int8_kernel as IK
    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.models.quant import (
        quantize_params,
    )
    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.ops.sampling import (
        RECENT_WINDOW,
    )

    monkeypatch.setattr(IK, "_INTERPRET", True)
    cfg = kernel_cfg()
    qp = quantize_params(init_params(jax.random.PRNGKey(0), cfg), "int8")
    S = 2
    ex = BatchedStageExecutor(cfg, full_spec(cfg), qp, slots=S, max_len=16)
    i32 = lambda *shape: jnp.zeros(shape, jnp.int32)         # noqa: E731
    f32 = lambda *shape: jnp.ones(shape, jnp.float32)        # noqa: E731
    on = jnp.ones((S,), bool)
    fn, args = {
        "burst_tick": (ex._build_burst(2), (
            ex.params, i32(len(BURST_INTS) + RECENT_WINDOW, S),
            f32(len(BURST_FLOATS), S), ex.k, ex.v)),
        "decode_step": (ex._build_decode(1), (
            ex.params, i32(S, 1), i32(S), on, ex.k, ex.v)),
        "prefill": (ex._build_prefill(), (
            ex.params, i32(1, 8), 0, ex.k, ex.v, 5)),
        "prefill_suffix": (ex._build_prefill_suffix(), (
            ex.params, i32(1, 8), 0, ex.k, ex.v, 4, 3)),
    }[program]
    eqns = list(_all_eqns(jax.make_jaxpr(fn)(*args).jaxpr))
    calls = [e for e in eqns if e.primitive.name == "pallas_call"]
    assert len(calls) == 4                        # wqkv, wo, wgu, wd
    for e in calls:
        int8_in = [v.aval for v in e.invars if v.aval.dtype == jnp.int8]
        assert [a.ndim for a in int8_in] == [3], int8_in
        assert int8_in[0].shape[0] == cfg.num_layers
    made = [(e.primitive.name, v.aval) for e in eqns for v in e.outvars
            if getattr(v.aval, "dtype", None) == jnp.int8
            and v.aval.ndim >= 2]
    assert not made, made


# ---------------------------------------------------------------------------
# The decode step and the burst tick append their T new rows a slot to the
# carried [L, S, max_len, Hkv, Dh] stacks IN PLACE and attend over what they
# read out of the stacks after the write. Until PR 32 each layer's whole
# [S, max_len, Hkv, Dh] slab was sliced out, appended to and written back:
# 72% of the gpt2-xl tick on the v5e. That policy stays HERE, as the oracle,
# in two halves, because XLA's CPU backend contracts the rotary multiply-add
# differently when the fresh rows feed a row scatter than when they feed a
# dynamic_update_slice (float32 rows a last bit apart at layer 0; on the v5e
# every form wrote the same bits): `slab_append` is the old append itself,
# which `_append_rows` must equal bit for bit as plain data movement, and
# `slab_policy_decode_span` is the old ROUND TRIP of a slab around one layer,
# with the rows appended by `_append_rows`.
# ---------------------------------------------------------------------------


def slab_append(slab, new, start, active):
    """The append as it was: a vmap'd `dynamic_update_slice` of T rows a
    slot on one layer's ``[S, max_len, Hkv, Dh]`` slab; an inactive slot
    writes back what it reads at the same (clamped) start."""
    t = new.shape[1]
    return jax.vmap(
        lambda cache, rows, at, act: jax.lax.dynamic_update_slice_in_dim(
            cache, jnp.where(act, rows, jax.lax.dynamic_slice_in_dim(
                cache, at, t, 0)), at, 0))(slab, new, start, active)


def slab_policy_decode_span(cfg, spec, params, x, positions, lengths, active,
                            k_all, v_all, full_read=False):
    """`runtime.batching._decode_span` with the slab's round trip as it
    was: slab out of the stack, rows appended to the SLAB, attention over
    the new slab, slab written back. Same signature and results (a stack
    that runs once: `_run_passes`'s ``steps`` is None), same
    `_decoder_layer`, same `_append_rows` (on a stack of one layer).

    The READ of the new slab is the engine's own (since PR 35 by blocks up
    to the longest active slot, `_attend_cached`, here over the slab as a
    stack of one layer), so that what the two policies can differ in is
    the write alone; ``full_read`` reads all ``max_len`` rows under a mask
    as every tick did until then: the oracle of the bounded read
    (tests/test_bounded_attention.py)."""
    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime import (
        batching as B,
    )

    h = (B.embed_tokens(cfg, params["embed"], x, positions)
         if spec.is_first else x)
    rope = B.make_rope(cfg, positions)
    qpos = positions[:, :, None]
    pos_grid = jnp.arange(k_all.shape[2], dtype=jnp.int32)
    allowed = pos_grid[None, None, :] <= qpos
    if cfg.sliding_window:
        allowed &= pos_grid[None, None, :] > qpos - cfg.sliding_window
    blocks = B.attn_blocks(lengths, active, qpos.shape[1], k_all.shape[2],
                           jnp)
    rest, held = B._split_stacks(params["layers"])

    def body(carry, xs):
        h, k_all, v_all = carry
        lp, i = xs
        k_l = jax.lax.dynamic_index_in_dim(k_all, i, 0, keepdims=True)
        v_l = jax.lax.dynamic_index_in_dim(v_all, i, 0, keepdims=True)

        def slab_round_trip(k, v):
            k_new = B._append_rows(k_l, 0, k.astype(k_l.dtype), lengths,
                                   active)[0]
            v_new = B._append_rows(v_l, 0, v.astype(v_l.dtype), lengths,
                                   active)[0]
            if full_read:
                return (k_new, v_new,
                        (allowed, qpos, pos_grid[None, None, :]),
                        (k_new, v_new))
            return (B._CacheLayer(k_new[None], 0, blocks),
                    B._CacheLayer(v_new[None], 0, blocks),
                    (None, qpos, None), (k_new, v_new))

        h, (k_new, v_new) = B._decoder_layer(
            cfg, B._layer_at(lp, held, i), h, rope, slab_round_trip)
        return (h, jax.lax.dynamic_update_index_in_dim(k_all, k_new, i, 0),
                jax.lax.dynamic_update_index_in_dim(v_all, v_new, i, 0)), None

    (h, k_all, v_all), _ = jax.lax.scan(
        body, (h, k_all, v_all),
        (rest, jnp.arange(k_all.shape[0], dtype=jnp.int32)))
    return h, k_all, v_all, None    # one pass: no passes to count


def bits(a):
    """An array's bytes, for comparisons that a NaN or a -0.0 cannot fool."""
    a = np.asarray(a)
    return a.view(np.uint8 if a.dtype.itemsize == 1 else
                  {2: np.uint16, 4: np.uint32}[a.dtype.itemsize])


def family_engine(family, dtype, max_len=32):
    """A tiny full-span engine of ``family`` with weights and cache in
    ``dtype``, its four slots prefilled with `PROMPTS`."""
    dtype = jnp.dtype(dtype)
    cfg = tiny_cfg(family)
    params = init_params(jax.random.PRNGKey(3), cfg)
    params = jax.tree.map(
        lambda a: a.astype(dtype) if a.dtype == jnp.float32 else a, params)
    ex = BatchedStageExecutor(cfg, full_spec(cfg), params, slots=4,
                              max_len=max_len, dtype=dtype)
    for sid, prompt in PROMPTS.items():
        ex.prefill(sid, np.asarray(prompt, np.int32)[None, :])
    return ex


def slot_rows(ex, sid):
    """``(slot, (K bits, V bits))`` of a session's rows in every layer."""
    d = ex._slot_of[sid]
    return d, (bits(ex.k)[:, d].copy(), bits(ex.v)[:, d].copy())


def check_clamped_slot(got, case, first_new):
    """What the clamp tests assert of slot ``got["slot"]`` beyond equality
    with the oracle: parked and left out, every row is as it was
    (``got["before"]``); taking the step up to exactly ``max_len``, rows
    ``[first_new, max_len)`` are new in every layer and the rest as it was."""
    d = got["slot"]
    for stack, was in zip((got["k"], got["v"]), got["before"]):
        now = bits(stack)[:, d]
        if case == "parked-inactive":
            np.testing.assert_array_equal(now, was)
        else:
            assert np.all(np.any(now[:, first_new:] != was[:, first_new:],
                                 axis=(2, 3)))
            np.testing.assert_array_equal(now[:, :first_new],
                                          was[:, :first_new])


def both_policies(monkeypatch, drive, full_read=False):
    """``drive()`` under the slab's round trip (``full_read``: and the read
    of all ``max_len`` rows) and under the engine's own: ``(oracle's
    result, engine's result)``. Each run builds its engine and its
    programs inside ``drive``, so each traces the policy in force."""
    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime import (
        batching as B,
    )

    with monkeypatch.context() as m:
        m.setattr(B, "_decode_span", partial(slab_policy_decode_span,
                                             full_read=full_read))
        want = drive()
    return want, drive()


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("t_step", [1, 3])
def test_append_rows_is_the_slab_append_in_place(t_step, dtype):
    """`_append_rows` on the whole stack at a traced layer index leaves
    what `slab_append` (a vmap'd `dynamic_update_slice`) leaves on that
    layer's slab, bit for bit, and touches no other layer. Every start is
    there active and inactive: 0, mid-cache, the exact fit ``max_len - T``,
    ``max_len - 1`` (for T = 3 it clamps back to ``max_len - 3``: an
    inactive slot parked there must keep its last rows) and ``max_len``."""
    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime.batching import (
        _append_rows,
    )

    layers, max_len, hkv, dh = 3, 16, 2, 8
    starts = [0, 5, max_len - t_step, max_len - 1, max_len]
    lengths = jnp.asarray(starts + starts, jnp.int32)
    active = jnp.asarray([True] * len(starts) + [False] * len(starts))
    slots = len(starts) * 2
    ks, kn = jax.random.split(jax.random.PRNGKey(t_step))
    stack = jax.random.normal(
        ks, (layers, slots, max_len, hkv, dh)).astype(dtype)
    new = jax.random.normal(kn, (slots, t_step, hkv, dh)).astype(dtype)
    for i in range(layers):
        got = jax.jit(_append_rows)(stack, jnp.int32(i), new, lengths, active)
        want = stack.at[i].set(slab_append(stack[i], new, lengths, active))
        np.testing.assert_array_equal(bits(got), bits(want))
        assert np.any(bits(got)[i] != bits(stack)[i])
        parked = bits(got)[i, len(starts):]
        np.testing.assert_array_equal(parked, bits(stack)[i, len(starts):])


FAMILIES = ["gpt2", "qwen2", "mistral-window", "gemma2"]


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("family", FAMILIES)
def test_decode_steps_bit_equal_to_slab_round_trip(monkeypatch, family, dtype):
    """Hidden states and the WHOLE K and V stacks after a plain decode
    step (T = 1) and a speculative-verify step (T = 3), with one session
    sitting both out, are bit for bit what the slab's round trip leaves."""

    def drive():
        ex = family_engine(family, dtype)
        one = ex.decode_batch({"a": jnp.asarray([[3]], jnp.int32),
                               "b": jnp.asarray([[4]], jnp.int32)})
        three = ex.decode_batch({"a": jnp.asarray([[3, 9, 1]], jnp.int32),
                                 "c": jnp.asarray([[4, 8, 2]], jnp.int32)})
        return {"one.a": one["a"], "one.b": one["b"], "three.a": three["a"],
                "three.c": three["c"], "k": ex.k, "v": ex.v}

    want, got = both_policies(monkeypatch, drive)
    assert np.any(bits(want["k"]))
    for name in want:
        np.testing.assert_array_equal(bits(got[name]), bits(want[name]),
                                      err_msg=name)


@pytest.mark.parametrize("t_step", [1, 3])
@pytest.mark.parametrize("case", ["parked-inactive", "active-to-max-len"])
def test_decode_append_clamps_as_the_slab_append_did(monkeypatch, case,
                                                     t_step):
    """The two ends of the clamp. A slot parked at ``max_len - 1`` that
    sits a step out has its start clamped to ``max_len - T``: it must
    write back the rows it read there, so its last rows stay bit for bit
    while the others decode. A slot at ``max_len - T`` that takes the step
    reaches exactly ``max_len``: its rows land at ``[max_len - T,
    max_len)``, where `slab_append` puts them."""
    max_len = 32

    def drive():
        ex = family_engine("qwen2", "float32", max_len)
        d, before = slot_rows(ex, "d")
        ids = np.asarray([[3, 9, 1][:t_step]], np.int32)
        if case == "parked-inactive":
            ex.lengths[d] = max_len - 1
            ex.decode_batch({"a": ids, "b": ids})
        else:
            ex.lengths[d] = max_len - t_step
            ex.decode_batch({"a": ids, "d": ids})
        return {"k": ex.k, "v": ex.v, "before": before, "slot": d}

    want, got = both_policies(monkeypatch, drive)
    np.testing.assert_array_equal(bits(got["k"]), bits(want["k"]))
    np.testing.assert_array_equal(bits(got["v"]), bits(want["v"]))
    check_clamped_slot(got, case, max_len - t_step)


def _cache_writes_and_slabs(jaxpr, stack_shape):
    """Of every equation under ``jaxpr``: the updates written into an
    operand shaped like the cache stack, and the equations whose output
    is one layer's ``[S, max_len, Hkv, Dh]`` slab."""
    writes, slabs = [], []
    for e in _all_eqns(jaxpr):
        name = e.primitive.name
        if (name in ("dynamic_update_slice", "scatter")
                and e.invars[0].aval.shape == stack_shape):
            upd = e.invars[1 if name == "dynamic_update_slice" else 2]
            writes.append((name, upd.aval.shape))
        slabs += [name for v in e.outvars
                  if getattr(v.aval, "shape", None) == stack_shape[1:]]
    return writes, slabs


@pytest.mark.parametrize("tree", ["int8", "bfloat16"])
@pytest.mark.parametrize("program", ["burst_tick", "decode_step-1",
                                     "decode_step-3"])
def test_tick_writes_rows_and_never_a_slab(program, tree):
    """In the jaxpr of the burst tick and of the decode step, every write
    into a cache stack is a `scatter` whose update holds T rows a slot
    (``[S, T, Hkv, Dh]``: one for K, one for V), and the only equations that
    yield a layer's ``[S, max_len, Hkv, Dh]`` slab are the two reads that
    feed attention: the `squeeze` of `dynamic_index_in_dim` on the stack,
    once for K and once for V in the one layer body. A slab that is never
    an update's operand is one XLA need not copy."""
    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.models.quant import (
        quantize_params,
    )
    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.ops.sampling import (
        RECENT_WINDOW,
    )

    cfg = tiny_cfg("qwen2")
    params = init_params(jax.random.PRNGKey(0), cfg)
    if tree == "int8":
        params, dtype = quantize_params(params, "int8"), jnp.float32
    else:
        dtype = jnp.bfloat16
        params = jax.tree.map(lambda a: a.astype(dtype), params)
    S, M = 3, 24
    ex = BatchedStageExecutor(cfg, full_spec(cfg), params, slots=S,
                              max_len=M, dtype=dtype)
    i32 = lambda *shape: jnp.zeros(shape, jnp.int32)         # noqa: E731
    f32 = lambda *shape: jnp.ones(shape, jnp.float32)        # noqa: E731
    on = jnp.ones((S,), bool)
    if program == "burst_tick":
        T = 1
        fn, args = ex._build_burst(2), (
            ex.params, i32(len(BURST_INTS) + RECENT_WINDOW, S),
            f32(len(BURST_FLOATS), S), ex.k, ex.v)
    else:
        T = int(program[-1])
        fn, args = ex._build_decode(T), (
            ex.params, i32(S, T), i32(S), on, ex.k, ex.v)
    writes, slabs = _cache_writes_and_slabs(
        jax.make_jaxpr(fn)(*args).jaxpr, ex.k.shape)
    rows = (S, T, cfg.num_kv_heads, cfg.head_dim)
    assert writes == [("scatter", rows)] * 2, writes
    assert slabs == ["squeeze", "squeeze"], slabs
