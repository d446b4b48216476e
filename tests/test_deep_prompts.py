"""Inference-time deep prompt tuning (ptune serving).

The vendored reference injects learned per-block prompts into hidden states
during ``rpc_forward`` AND during every per-step inference call
(``petals/server/block_functions.py:57-65,171-226``,
``backend.py:226-233``). Parity contract here: the distributed pipeline
with ``deep_prompts`` must generate token-for-token what a MONOLITHIC
forward with the same prompts generates — across chained spans, chunked
prefill, failover replay, and the TCP wire.
"""


import jax
import jax.numpy as jnp
import numpy as np

from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.models import (
    init_kv_cache,
    init_params,
)
from engines import full_forward
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.models.partition import (
    ROLE_FULL,
    StagePlan,
    StageSpec,
    parse_splits,
    slice_stage_params,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.ops.sampling import (
    SamplingParams,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime.client import (
    PipelineClient,
    make_server_record,
)
from engines import stage_executor as StageExecutor
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime.messages import (
    StageRequest,
)

from engines import build_cluster, tiny_cfg


def make_prompts(cfg, pre_seq, seed=3, scale=0.5):
    """[num_layers, pre_seq, D] learned-prompt stand-in. Scale matters: the
    injection must be large enough to CHANGE the generated tokens, or the
    parity assertions would pass vacuously."""
    return scale * jax.random.normal(
        jax.random.PRNGKey(seed),
        (cfg.num_layers, pre_seq, cfg.hidden_size), jnp.float32)


def oracle_with_prompts(cfg, params, prompt_ids, max_new_tokens, prompts,
                        max_len=256):
    """Greedy monolithic loop with per-layer prompts on EVERY forward."""
    kc, vc = init_kv_cache(cfg, cfg.num_layers, 1, max_len)
    ids = jnp.asarray(np.asarray(prompt_ids, np.int32)[None, :])
    logits, kc, vc = full_forward(cfg, params, ids, kc, vc, jnp.int32(0),
                                  prompts=prompts)
    generated = [int(jnp.argmax(logits[0, len(prompt_ids) - 1]))]
    cur_len = len(prompt_ids)
    for _ in range(1, max_new_tokens):
        if len(generated) >= 5 and len(set(generated[-5:])) == 1:
            break
        nxt = jnp.asarray([[generated[-1]]], jnp.int32)
        logits, kc, vc = full_forward(cfg, params, nxt, kc, vc,
                                      jnp.int32(cur_len), prompts=prompts)
        generated.append(int(jnp.argmax(logits[0, 0])))
        cur_len += 1
    return generated


def test_pipeline_deep_prompts_match_monolithic_oracle():
    """Chained spans + client-side slicing == monolithic injection. pre_seq
    EXCEEDS the prompt length, so the first decode steps fall inside the
    prompt region and exercise the per-step (not just prefill) injection."""
    cfg = tiny_cfg()
    client, _, _, params, _ = build_cluster(cfg, splits="2,4,6")
    sampling = SamplingParams(temperature=0.0)
    prompt = [5, 9, 23, 7]
    prompts = make_prompts(cfg, pre_seq=7)  # > len(prompt): decode injection

    res = client.generate(prompt, max_new_tokens=8, sampling=sampling,
                          deep_prompts=prompts)
    ref = oracle_with_prompts(cfg, params, prompt, 8, prompts)
    assert res.tokens == ref
    # Not vacuous: the prompts must actually steer generation.
    base = client.generate(prompt, max_new_tokens=8, sampling=sampling)
    assert base.tokens != ref
    # Session state cleaned up.
    assert not client._session_prompts


def test_deep_prompts_chunked_prefill_absolute_positions():
    """A prefill long enough to split into several chunks must inject at
    ABSOLUTE positions: chunk 2 (positions >= chunk_len) gets prompt rows
    [chunk_len:...], not a restarted slice. (Chunk-relative injection —
    what a naive port of petals' slicing would do — fails this test.)"""
    cfg = tiny_cfg()
    params = init_params(jax.random.PRNGKey(0), cfg)
    spec = StageSpec(index=0, role=ROLE_FULL, start=0, end=cfg.num_layers)
    # Chunk budget sized to force multi-chunk prefill: per-token footprint is
    # batch * hidden * 4 * layers = 64*4*8 = 2048 bytes; 32 KiB -> 16-token
    # chunks for a 40-token prompt (floored at 16, the smallest bucket).
    ex = StageExecutor(cfg, spec, params, peer_id="chunky",
                       max_chunk_bytes=32 * 1024)
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, cfg.vocab_size, 40).astype(np.int32)
    pre = 24  # prompt region spans chunk 1 AND chunk 2
    prompts = make_prompts(cfg, pre_seq=pre)

    resp = ex.forward(StageRequest(
        session_id="s", hidden=jnp.asarray(prompt[None, :]),
        seq_len=len(prompt), cur_len=0, is_prefill=True, max_length=64,
        sampling=SamplingParams(temperature=0.0), prompts=prompts,
    ))

    kc, vc = init_kv_cache(cfg, cfg.num_layers, 1, 64)
    logits, _, _ = full_forward(cfg, params, jnp.asarray(prompt[None, :]),
                                kc, vc, jnp.int32(0), prompts=prompts)
    assert resp.token_id == int(jnp.argmax(logits[0, -1]))


def test_deep_prompts_survive_failover_replay():
    """A replacement peer must rebuild its KV with the SAME injection —
    journal replay ships the hop's prompt slice too."""
    cfg = tiny_cfg()
    client, transport, _, params, _ = build_cluster(cfg, splits="4",
                                                    replicas=2)
    sampling = SamplingParams(temperature=0.0)
    prompt = [11, 3, 77]
    prompts = make_prompts(cfg, pre_seq=6)
    ref = oracle_with_prompts(cfg, params, prompt, 8, prompts)

    killed = {"done": False}
    orig_call = transport.call

    def flaky_call(peer_id, req, timeout=None):
        if not killed["done"] and not req.is_prefill and req.cur_len >= 5:
            killed["done"] = True
            transport.kill(peer_id)
        return orig_call(peer_id, req, timeout=timeout)

    transport.call = flaky_call
    res = client.generate(prompt, max_new_tokens=8, sampling=sampling,
                          deep_prompts=prompts)
    assert killed["done"], "fault was never injected"
    assert client.recoveries >= 1
    assert res.tokens == ref


def test_deep_prompts_over_tcp_round_trip():
    """Prompts ride the wire as a second payload tensor (classic frame) and
    the TCP pipeline matches the monolithic oracle."""
    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime.net import (
        RegistryServer,
        RemoteRegistry,
        TcpStageServer,
        TcpTransport,
    )

    cfg = tiny_cfg()
    params = init_params(jax.random.PRNGKey(0), cfg)
    plan = StagePlan.from_splits(cfg.num_layers, parse_splits("3,6"))
    reg_server = RegistryServer()
    reg_server.start()
    servers = []
    try:
        for spec in plan.stages[1:]:
            peer = f"dp-s{spec.index}"
            ex = StageExecutor(cfg, spec,
                               slice_stage_params(cfg, params, spec),
                               peer_id=peer)
            srv = TcpStageServer(ex, wire_dtype="f32")
            srv.start()
            rec = make_server_record(peer, spec)
            rec.address = srv.address
            reg_server.registry.register(rec)
            servers.append(srv)
        registry = RemoteRegistry(reg_server.address)
        transport = TcpTransport(registry, wire_dtype="f32")
        stage0 = StageExecutor(cfg, plan.stages[0],
                               slice_stage_params(cfg, params, plan.stages[0]),
                               peer_id="client-local")
        client = PipelineClient(cfg, plan, stage0, transport, registry,
                                settle_seconds=0.0)
        prompt = [5, 9, 23]
        prompts = make_prompts(cfg, pre_seq=5)
        res = client.generate(prompt, max_new_tokens=6,
                              sampling=SamplingParams(temperature=0.0),
                              deep_prompts=prompts)
        ref = oracle_with_prompts(cfg, params, prompt, 6, prompts)
        assert res.tokens == ref
        # Steps past the prompt region drop the tensor and ride the
        # persistent-stream fast path again (steady-state decode must not
        # pay the classic frame re-shipping [span, pre, D] per hop).
        assert sum(s.stream_steps for s in servers) > 0
        transport.close()
    finally:
        for s in servers:
            s.stop()
        reg_server.stop()


def _span_executor_parity(ex, cfg, params, spec):
    """Run prefill + 3 decode steps with prompts on `ex` (covering span
    [spec.start, spec.end)) and assert every hidden matches the prompt-
    injected monolithic stack for those layers."""
    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.models.partition import (
        init_stage_kv,
        stage_forward,
    )

    pre = 6
    prompts = make_prompts(cfg, pre_seq=pre)[spec.start:spec.end]
    rng = np.random.default_rng(1)
    x0 = rng.standard_normal((1, 4, cfg.hidden_size)).astype(np.float32)
    kc, vc = init_stage_kv(cfg, spec, 1, 64)
    cur = 0
    full = slice_stage_params(cfg, params, spec)
    for step in range(4):
        t = 4 if step == 0 else 1
        x = (x0 if step == 0
             else rng.standard_normal((1, 1, cfg.hidden_size)).astype(
                 np.float32))
        resp = ex.forward(StageRequest(
            session_id="s", hidden=jnp.asarray(x), seq_len=t, cur_len=cur,
            is_prefill=(step == 0), max_length=32, prompts=prompts,
        ))
        want, kc, vc = stage_forward(cfg, spec, full, jnp.asarray(x), kc, vc,
                                     jnp.int32(cur), prompts=prompts)
        np.testing.assert_allclose(np.asarray(resp.hidden),
                                   np.asarray(want), atol=2e-4, rtol=2e-4)
        cur += t


def test_deep_prompts_on_tp_engine():
    """TP executors must inject identically (prompts replicated across the
    tp mesh; the router may legally place deep-prompt sessions here)."""
    from jax.sharding import Mesh

    cfg = tiny_cfg()
    params = init_params(jax.random.PRNGKey(0), cfg)
    spec = StageSpec(index=1, role="segment", start=2, end=6)
    mesh = Mesh(np.asarray(jax.devices()[:2]), ("tp",))
    ex = StageExecutor(cfg, spec, slice_stage_params(cfg, params, spec),
                       peer_id="tp", tp_mesh=mesh)
    _span_executor_parity(ex, cfg, params, spec)


def test_deep_prompts_on_offload_engine():
    """Host-offloaded spans inject per streamed layer."""
    cfg = tiny_cfg()
    params = init_params(jax.random.PRNGKey(0), cfg)
    spec = StageSpec(index=1, role="segment", start=2, end=6)
    ex = StageExecutor(cfg, spec, slice_stage_params(cfg, params, spec),
                       peer_id="off", offload=True, keep_layers_resident=1)
    _span_executor_parity(ex, cfg, params, spec)


def test_batched_and_sp_engines_refuse_prompts():
    """Single-session engines must reject deep prompts loudly (silently
    ignoring them would generate un-tuned tokens that LOOK valid)."""
    import pytest

    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime.batching import (
        BatchingStageAdapter,
    )
    from engines import engine as BatchedStageExecutor
    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime.executor import (
        StageExecutionError,
    )

    cfg = tiny_cfg()
    params = init_params(jax.random.PRNGKey(0), cfg)
    spec = StageSpec(index=0, role=ROLE_FULL, start=0, end=cfg.num_layers)
    inner = BatchedStageExecutor(cfg, spec, params, slots=2, max_len=64)
    ad = BatchingStageAdapter(inner, window_s=0.0)
    req = StageRequest(
        session_id="s", hidden=jnp.asarray([[1, 2, 3]], jnp.int32),
        seq_len=3, cur_len=0, is_prefill=True, max_length=32,
        prompts=make_prompts(cfg, pre_seq=4),
    )
    with pytest.raises(StageExecutionError, match="deep-prompt"):
        ad.forward(req)
