"""Sequence-parallel LONG-CONTEXT serving (VERDICT r2 item 4): a TCP stage
server backed by runtime.sp_serve.SpStageAdapter — the session's prefix KV
shards along the sequence axis of a local ("sp",) mesh, so a prompt larger
than ONE device's KV budget serves end-to-end; engine=sp + max_context ride
the registry.

Reference contract (SURVEY §5.7): the reference's only long-context
mechanism is single-server chunked prefill (petals/server/backend.py:129-143)
— its KV must fit one machine. This is the exceed-the-reference axis.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.models import (
    init_params,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.models.partition import (
    StagePlan,
    parse_splits,
    slice_stage_params,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.ops.sampling import (
    SamplingParams,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.parallel.sp_stage import (
    SpStageRunner,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime.client import (
    PipelineClient,
    make_server_record,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime.executor import (
    StageExecutionError,
)
from engines import stage_executor as StageExecutor
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime.kv_cache import (
    KVArena,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime.net import (
    RegistryServer,
    RemoteRegistry,
    TcpStageServer,
    TcpTransport,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime.sp_serve import (
    SpStageAdapter,
)

from engines import oracle_generate, tiny_cfg

SP = 4
PROMPT_LEN = 96


def _mesh():
    devs = jax.devices()
    if len(devs) < SP:
        pytest.skip(f"need {SP} devices, have {len(devs)}")
    return Mesh(np.asarray(devs[:SP]), ("sp",))


def _tight_arena(cfg, spec, prompt_len):
    """An arena sized BELOW one device's cost for this prompt: the
    per-device KV budget the sp mesh beats."""
    probe = KVArena(num_layers=max(spec.num_layers, 1),
                    num_kv_heads=cfg.num_kv_heads, head_dim=cfg.head_dim,
                    max_bytes=1 << 40, dtype=jnp.float32)
    need = probe.bytes_for(
        __import__(
            "global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime.kv_cache",
            fromlist=["round_to_bucket"],
        ).round_to_bucket(prompt_len + 16, probe.buckets))
    return KVArena(num_layers=max(spec.num_layers, 1),
                   num_kv_heads=cfg.num_kv_heads, head_dim=cfg.head_dim,
                   max_bytes=need - 1, dtype=jnp.float32,
                   alloc_timeout=0.2)


@pytest.fixture
def sp_swarm():
    cfg = tiny_cfg()
    params = init_params(jax.random.PRNGKey(0), cfg)
    plan = StagePlan.from_splits(cfg.num_layers, parse_splits("2"))
    spec = plan.stages[1]           # [2, 8), final

    reg_server = RegistryServer(ttl=600.0)
    reg_server.start()
    runner = SpStageRunner(cfg, spec, slice_stage_params(cfg, params, spec),
                           _mesh())
    adapter = SpStageAdapter(runner, peer_id="sp-s1",
                             max_context=PROMPT_LEN + 64)
    srv = TcpStageServer(adapter, wire_dtype="f32")
    srv.start()
    rec = make_server_record("sp-s1", spec, engine="sp")
    rec.max_context = adapter.max_context
    rec.address = srv.address
    reg_server.registry.register(rec)

    yield cfg, params, plan, spec, reg_server, adapter, srv
    srv.stop()
    reg_server.stop()


def _client(cfg, params, plan, reg_addr, threshold=None):
    registry = RemoteRegistry(reg_addr)
    transport = TcpTransport(registry, wire_dtype="f32")
    stage0 = StageExecutor(cfg, plan.stages[0],
                           slice_stage_params(cfg, params, plan.stages[0]),
                           peer_id="client-local")
    return PipelineClient(cfg, plan, stage0, transport, registry,
                          settle_seconds=0.0,
                          long_context_threshold=threshold), transport


def test_long_prompt_beyond_one_device_budget(sp_swarm):
    """The headline contract: a prompt whose KV does NOT fit one device's
    arena budget (the same budget refuses on a per-session executor) runs
    end-to-end through the sp server, token-identical to the oracle."""
    cfg, params, plan, spec, reg_server, adapter, _ = sp_swarm
    rng = np.random.default_rng(0)
    prompt = [int(t) for t in rng.integers(0, cfg.vocab_size, PROMPT_LEN)]
    sampling = SamplingParams(temperature=0.0)

    # One device at this budget refuses the session outright...
    tight = StageExecutor(cfg, spec, slice_stage_params(cfg, params, spec),
                          peer_id="tight",
                          arena=_tight_arena(cfg, spec, PROMPT_LEN))
    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime.messages import (
        StageRequest,
    )

    with pytest.raises(StageExecutionError, match="arena"):
        tight.forward(StageRequest(
            session_id="s", seq_len=PROMPT_LEN, cur_len=0, is_prefill=True,
            max_length=PROMPT_LEN + 16,
            hidden=jnp.zeros((1, PROMPT_LEN, cfg.hidden_size), jnp.float32)))

    # ...while the sp mesh (prefix sharded T/4 per device) serves it.
    client, tx = _client(cfg, params, plan, reg_server.address,
                         threshold=64)
    got = client.generate(prompt, max_new_tokens=6, sampling=sampling).tokens
    ref = oracle_generate(cfg, params, prompt, 6, sampling)
    assert got == ref
    tx.close()


def test_sp_sampled_decode_matches_oracle(sp_swarm):
    cfg, params, plan, spec, reg_server, adapter, _ = sp_swarm
    rng = np.random.default_rng(1)
    prompt = [int(t) for t in rng.integers(0, cfg.vocab_size, 70)]
    sampling = SamplingParams(temperature=0.8, top_p=0.9, top_k=40,
                              repetition_penalty=1.3)
    client, tx = _client(cfg, params, plan, reg_server.address)
    got = client.generate(prompt, max_new_tokens=6, sampling=sampling).tokens
    ref = oracle_generate(cfg, params, prompt, 6, sampling)
    assert got == ref
    tx.close()


def test_sp_concurrent_sessions_coexist(sp_swarm):
    """Multi-session sp (VERDICT r3 item 5): two sessions are admitted
    against the KV byte budget and their caches coexist — decode steps of
    either interleave with no refusal and no state bleed."""
    cfg, params, plan, spec, reg_server, adapter, _ = sp_swarm
    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime.messages import (
        StageRequest,
    )

    registry = RemoteRegistry(reg_server.address)
    tx = TcpTransport(registry, wire_dtype="f32", use_streams=False)

    def req(sid):
        return StageRequest(
            session_id=sid, seq_len=8, cur_len=0, is_prefill=True,
            max_length=32,
            hidden=jnp.zeros((1, 8, cfg.hidden_size), jnp.float32))

    tx.call("sp-s1", req("first"))
    tx.call("sp-s1", req("second"))          # ADMITTED alongside first
    assert set(adapter._sessions) == {"first", "second"}

    def step(sid, cur):
        return StageRequest(
            session_id=sid, seq_len=1, cur_len=cur, is_prefill=False,
            max_length=32,
            hidden=jnp.zeros((1, 1, cfg.hidden_size), jnp.float32))

    # interleaved decode: first, second, first — each against its own cache
    tx.call("sp-s1", step("first", 8))
    tx.call("sp-s1", step("second", 8))
    tx.call("sp-s1", step("first", 9))
    tx.end_session("sp-s1", "first")
    tx.end_session("sp-s1", "second")
    tx.close()


def test_two_sp_generations_complete_concurrently(sp_swarm):
    """The VERDICT r3 item-5 'Done' bar: two client generations against ONE
    sp server (the only server in the registry, so any refusal-driven
    route-around would fail the generation) both complete, token-identical
    to their oracles."""
    import threading

    cfg, params, plan, spec, reg_server, adapter, _ = sp_swarm
    rng = np.random.default_rng(5)
    prompts = [[int(t) for t in rng.integers(0, cfg.vocab_size, PROMPT_LEN)]
               for _ in range(2)]
    sampling = SamplingParams(temperature=0.0)
    results, errors = {}, {}

    def gen(i):
        try:
            client, tx = _client(cfg, params, plan, reg_server.address,
                                 threshold=64)
            try:
                results[i] = client.generate(
                    prompts[i], max_new_tokens=5, sampling=sampling).tokens
            finally:
                tx.close()
        except Exception as exc:   # surfaced after join
            errors[i] = exc

    threads = [threading.Thread(target=gen, args=(i,)) for i in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors, f"a concurrent sp generation failed: {errors}"
    for i in range(2):
        ref = oracle_generate(cfg, params, prompts[i], 5, sampling)
        assert results[i] == ref, f"generation {i} diverged"


def test_sp_budget_queue_and_refusal():
    """A prefill beyond the byte budget QUEUES until a live session frees
    its bytes (no client route-around needed), and only refuses — with a
    retryable 'capacity' error — after queue_wait_s with no space."""
    import threading
    import time

    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime.messages import (
        StageRequest,
    )

    cfg = tiny_cfg()
    params = init_params(jax.random.PRNGKey(0), cfg)
    plan = StagePlan.from_splits(cfg.num_layers, parse_splits("2"))
    spec = plan.stages[1]
    runner = SpStageRunner(cfg, spec, slice_stage_params(cfg, params, spec),
                           _mesh())
    one = runner.session_bytes_per_device(8)
    adapter = SpStageAdapter(runner, peer_id="sp-tight",
                             max_context=128,
                             kv_budget_bytes=one,      # exactly ONE session
                             queue_wait_s=8.0)

    def req(sid):
        return StageRequest(
            session_id=sid, seq_len=8, cur_len=0, is_prefill=True,
            max_length=16,
            hidden=jnp.zeros((1, 8, cfg.hidden_size), jnp.float32))

    adapter.forward(req("a"))

    # Free "a" shortly after "b" starts queueing: "b" must then be admitted
    # WITHOUT an error reaching the client.
    t = threading.Timer(1.0, adapter.drop_session, args=("a",))
    t.start()
    adapter.forward(req("b"))                  # queued ~1s, then admitted
    assert set(adapter._sessions) == {"b"}
    t.join()

    # With no one freeing space, the queue times out into a retryable
    # capacity refusal.
    quick = SpStageAdapter(runner, peer_id="sp-tight2", max_context=128,
                           kv_budget_bytes=one, queue_wait_s=0.3)
    quick.forward(req("c"))
    with pytest.raises(StageExecutionError, match="capacity"):
        quick.forward(req("d"))


def test_registry_advertises_sp_max_context(sp_swarm):
    cfg, params, plan, spec, reg_server, adapter, _ = sp_swarm
    registry = RemoteRegistry(reg_server.address)
    rec = registry.get("sp-s1")
    assert rec.engine == "sp"
    assert rec.max_context == adapter.max_context


def test_long_kind_prefers_sp_peer(sp_swarm):
    """With a session replica AND an sp replica, long prompts route to the
    sp peer, plain short prompts to the batched/session preference order,
    and exotic sessions avoid sp."""
    cfg, params, plan, spec, reg_server, adapter, srv = sp_swarm
    ex = StageExecutor(cfg, spec, slice_stage_params(cfg, params, spec),
                       peer_id="sess-s1")
    srv2 = TcpStageServer(ex, wire_dtype="f32")
    srv2.start()
    try:
        rec = make_server_record("sess-s1", spec)
        rec.address = srv2.address
        reg_server.registry.register(rec)
        client, tx = _client(cfg, params, plan, reg_server.address,
                             threshold=64)
        assert client.route(kind="long")[-1].peer_id == "sp-s1"
        assert client.route(kind="exotic")[-1].peer_id == "sess-s1"
        tx.close()
    finally:
        srv2.stop()


def test_sp_prefill_refuses_budget_beyond_tail(sp_swarm):
    """A declared max_length whose generation budget exceeds tail_max is
    refused AT PREFILL (retryable) — not 512 tokens into decode."""
    cfg, params, plan, spec, reg_server, adapter, _ = sp_swarm
    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime.messages import (
        StageRequest,
    )

    registry = RemoteRegistry(reg_server.address)
    tx = TcpTransport(registry, wire_dtype="f32", use_streams=False)
    with pytest.raises(StageExecutionError, match="tail capacity"):
        tx.call("sp-s1", StageRequest(
            session_id="big", seq_len=8, cur_len=0, is_prefill=True,
            max_length=8 + adapter.runner.tail_max + 1,
            hidden=jnp.zeros((1, 8, cfg.hidden_size), jnp.float32)))
    tx.close()


def test_long_route_skips_undersized_sp_peer(sp_swarm):
    """Routing consults the advertised max_context: a session needing more
    context than an sp peer advertises never routes there."""
    cfg, params, plan, spec, reg_server, adapter, _ = sp_swarm
    ex = StageExecutor(cfg, spec, slice_stage_params(cfg, params, spec),
                       peer_id="sess-big")
    srv2 = TcpStageServer(ex, wire_dtype="f32")
    srv2.start()
    try:
        rec = make_server_record("sess-big", spec)
        rec.address = srv2.address
        reg_server.registry.register(rec)
        client, tx = _client(cfg, params, plan, reg_server.address,
                             threshold=64)
        # Needs more context than sp-s1 advertises -> session replica.
        over = adapter.max_context + 100
        assert client.route(kind="long",
                            min_context=over)[-1].peer_id == "sess-big"
        # Fits -> the sp peer is preferred.
        assert client.route(kind="long",
                            min_context=32)[-1].peer_id == "sp-s1"
        tx.close()
    finally:
        srv2.stop()
