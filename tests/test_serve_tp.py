"""Tensor parallelism in the SERVING path (VERDICT r2 item 3): a TCP stage
server whose executor runs its span through parallel.tensor_parallel's
shard_map over a local ("tp",) mesh, with the session KV arena sharded over
kv heads and byte accounting per device.

Reference contract: the serving backend wraps every block in TP
(petals/server/backend.py:43); memory/throughput sizing is TP-aware
(petals/server/server.py:280-293).
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
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime.client import (
    PipelineClient,
    make_server_record,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime.executor import (
    StageExecutor,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime.kv_cache import (
    KVArena,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime.net import (
    RegistryServer,
    RemoteRegistry,
    TcpStageServer,
    TcpTransport,
)

from engines import oracle_generate, tiny_cfg


def _tp_mesh(n):
    devs = jax.devices()
    if len(devs) < n:
        pytest.skip(f"need {n} devices, have {len(devs)}")
    return Mesh(np.asarray(devs[:n]), ("tp",))


def test_tp_executor_matches_plain_executor():
    """Same stage, same requests: the tp=2 executor's outputs are numerically
    equivalent to the single-device executor's (the serving analogue of the
    fused-mode pp×tp parity tests)."""
    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime.messages import (
        StageRequest,
    )

    cfg = tiny_cfg()
    params = init_params(jax.random.PRNGKey(0), cfg)
    plan = StagePlan.from_splits(cfg.num_layers, parse_splits("2,4"))
    spec = plan.stages[1]
    sliced = slice_stage_params(cfg, params, spec)
    plain = StageExecutor(cfg, spec, sliced, peer_id="plain")
    tp = StageExecutor(cfg, spec, sliced, peer_id="tp",
                       tp_mesh=_tp_mesh(2))

    hidden = jax.random.normal(jax.random.PRNGKey(1),
                               (1, 5, cfg.hidden_size), jnp.float32)
    step1 = jax.random.normal(jax.random.PRNGKey(2),
                              (1, 1, cfg.hidden_size), jnp.float32)

    def drive(ex):
        outs = []
        r = ex.forward(StageRequest(session_id="s", hidden=hidden, seq_len=5,
                                    cur_len=0, is_prefill=True, max_length=16))
        outs.append(np.asarray(r.hidden))
        r = ex.forward(StageRequest(session_id="s", hidden=step1, seq_len=1,
                                    cur_len=5, is_prefill=False, max_length=16))
        outs.append(np.asarray(r.hidden))
        return outs

    for a, b in zip(drive(plain), drive(tp)):
        np.testing.assert_allclose(a, b, atol=2e-5, rtol=2e-5)


def test_tp_serve_generation_matches_oracle():
    """End-to-end over TCP: stage1 tp=2, final stage tp=2, generation is
    token-identical to the single-device oracle."""
    cfg = tiny_cfg()
    params = init_params(jax.random.PRNGKey(0), cfg)
    plan = StagePlan.from_splits(cfg.num_layers, parse_splits("2,4"))
    mesh = _tp_mesh(2)

    reg_server = RegistryServer(ttl=600.0)
    reg_server.start()
    servers = []
    try:
        for spec in plan.stages[1:]:
            peer = f"tp-s{spec.index}"
            ex = StageExecutor(cfg, spec, slice_stage_params(cfg, params, spec),
                               peer_id=peer, tp_mesh=mesh)
            srv = TcpStageServer(ex, wire_dtype="f32")
            srv.start()
            servers.append(srv)
            rec = make_server_record(peer, spec)
            rec.address = srv.address
            reg_server.registry.register(rec)
        registry = RemoteRegistry(reg_server.address)
        transport = TcpTransport(registry, wire_dtype="f32")
        stage0 = StageExecutor(cfg, plan.stages[0],
                               slice_stage_params(cfg, params, plan.stages[0]),
                               peer_id="client-local")
        client = PipelineClient(cfg, plan, stage0, transport, registry,
                                settle_seconds=0.0)
        for sampling in (SamplingParams(temperature=0.0),
                         SamplingParams(temperature=0.8, top_p=0.9, top_k=40,
                                        repetition_penalty=1.3)):
            got = client.generate([5, 9, 23, 7], max_new_tokens=6,
                                  sampling=sampling).tokens
            ref = oracle_generate(cfg, params, [5, 9, 23, 7], 6, sampling)
            assert got == ref, sampling
        transport.close()
    finally:
        for s in servers:
            s.stop()
        reg_server.stop()


def test_tp_arena_accounting_per_device():
    """A tp-sharded arena budgets PER-DEVICE bytes: the same max_bytes holds
    tp× the sessions, and tokens_left doubles at tp=2."""
    base = dict(num_layers=4, num_kv_heads=2, head_dim=8, max_bytes=1 << 20,
                dtype=jnp.float32)
    plain = KVArena(**base)
    tp2 = KVArena(**base, bytes_divisor=2)
    assert tp2.bytes_for(128) == plain.bytes_for(128) // 2
    assert tp2.tokens_left() == 2 * plain.tokens_left()


def test_tp_arena_buffers_sharded():
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = _tp_mesh(2)
    arena = KVArena(num_layers=2, num_kv_heads=2, head_dim=8,
                    max_bytes=1 << 24, dtype=jnp.float32,
                    sharding=NamedSharding(mesh, P(None, None, None, "tp")),
                    bytes_divisor=2)
    h = arena.allocate("s", 64)
    shard_shapes = {d.data.shape for d in h.k.addressable_shards}
    # kv-head axis (3) is split in two across the mesh.
    assert shard_shapes == {(2, 1, 128, 1, 8)}
    arena.free("s")


def test_derive_num_blocks_scales_with_tp():
    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime.server import (
        derive_num_blocks,
    )

    cfg = tiny_cfg()

    class FakeDev:
        platform = "tpu"
        device_kind = "fake"

        def memory_stats(self):
            return {"bytes_limit": 1 << 24, "bytes_in_use": 0}

    kw = dict(dtype_bytes=4, attn_cache_bytes=1 << 20, device=FakeDev())
    n1 = derive_num_blocks(cfg, **kw)
    n2 = derive_num_blocks(cfg, tp=2, **kw)
    assert n1 is not None and n2 is not None
    assert n2 > n1 or n2 == cfg.num_layers  # 2× capacity (capped at model size)
