"""Elastic (load-balancing) server lifecycle + end-to-end module routing.

The reference's canonical LB system test is 4 cloud VMs and a human reading
logs (``scripts/elice_test_load_balancing.sh``, SURVEY.md §4); here joins,
placement, rebalancing, TTL expiry, and generation-through-elastic-spans run
in-process with assertions.
"""

import random

import jax

from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.models import (
    init_params,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.models.partition import (
    StagePlan,
    slice_stage_params,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.ops.sampling import (
    SamplingParams,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime.client import (
    PipelineClient,
)
from engines import stage_executor as StageExecutor
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime.server import (
    ElasticStageServer,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime.transport import (
    LocalTransport,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.scheduling.registry import (
    PlacementRegistry,
)

from engines import oracle_generate, tiny_cfg


MIN_BLOCK = 2  # client-local prefix [0, 2): lb_min_block = splits[0]


def make_swarm(cfg, params):
    transport = LocalTransport()
    registry = PlacementRegistry(rng=random.Random(0))
    provider = lambda spec: slice_stage_params(cfg, params, spec)  # noqa: E731
    return transport, registry, provider


def make_elastic(peer, cfg, provider, registry, transport, num_blocks, **kw):
    return ElasticStageServer(
        peer, cfg, provider, registry, transport,
        num_blocks=num_blocks, total_blocks=cfg.num_layers,
        min_block=MIN_BLOCK, rng=random.Random(hash(peer) % 1000), **kw,
    )


def test_first_joiner_takes_uncovered_range():
    cfg = tiny_cfg()
    params = init_params(jax.random.PRNGKey(0), cfg)
    transport, registry, provider = make_swarm(cfg, params)
    s = make_elastic("srv-a", cfg, provider, registry, transport, num_blocks=6)
    s.start_serving()
    assert (s.spec.start, s.spec.end) == (2, 8)
    assert s.spec.is_last
    rec = registry.get("srv-a")
    assert rec.final_stage and rec.state == "online"


def test_second_joiner_reinforces_weakest_segment():
    cfg = tiny_cfg()
    params = init_params(jax.random.PRNGKey(0), cfg)
    transport, registry, provider = make_swarm(cfg, params)
    a = make_elastic("srv-a", cfg, provider, registry, transport, num_blocks=6)
    a.start_serving()
    b = make_elastic("srv-b", cfg, provider, registry, transport, num_blocks=3)
    b.start_serving()
    # whole remote range equally covered by a -> weakest-first picks the
    # earliest window at the min_block floor
    assert (b.spec.start, b.spec.end) == (2, 5)


def test_min_block_floor_enforced_on_join():
    cfg = tiny_cfg()
    params = init_params(jax.random.PRNGKey(0), cfg)
    transport, registry, provider = make_swarm(cfg, params)
    s = make_elastic("srv-a", cfg, provider, registry, transport, num_blocks=3)
    s.start_serving()
    assert s.spec.start >= MIN_BLOCK


def test_generation_through_elastic_swarm_matches_oracle():
    cfg = tiny_cfg()
    params = init_params(jax.random.PRNGKey(0), cfg)
    transport, registry, provider = make_swarm(cfg, params)
    # two elastic servers: one spanning [2,8) (final), one reinforcing [2,5)
    make_elastic("srv-a", cfg, provider, registry, transport, num_blocks=6).start_serving()
    make_elastic("srv-b", cfg, provider, registry, transport, num_blocks=3).start_serving()

    plan = StagePlan.from_splits(cfg.num_layers, [MIN_BLOCK])
    stage0 = StageExecutor(cfg, plan.stages[0],
                           slice_stage_params(cfg, params, plan.stages[0]),
                           peer_id="client-local")
    client = PipelineClient(cfg, plan, stage0, transport, registry,
                            use_module_routing=True,
                            total_blocks=cfg.num_layers, settle_seconds=0.0)
    hops = client.route()
    assert hops[-1].end_block == cfg.num_layers and hops[-1].expect_token

    sampling = SamplingParams(temperature=0.0)
    res = client.generate([5, 9, 23, 7], max_new_tokens=6, sampling=sampling)
    ref = oracle_generate(cfg, params, [5, 9, 23, 7], 6, sampling)
    assert res.tokens == ref


def test_rebalance_respans_stacked_servers():
    """Three servers stacked on [2,5) + one weak final server: a stacked one
    must re-span toward the bottleneck when rule 2 fires."""
    cfg = tiny_cfg()
    params = init_params(jax.random.PRNGKey(0), cfg)
    transport, registry, provider = make_swarm(cfg, params)

    final = make_elastic("srv-final", cfg, provider, registry, transport,
                         num_blocks=6)
    final.start_serving()          # [2, 8), throughput 1.0
    stacked = []
    for name in ("srv-x", "srv-y", "srv-z"):
        s = make_elastic(name, cfg, provider, registry, transport, num_blocks=3)
        s.throughput = 3.0
        s.start_serving()
        stacked.append(s)
    # manually stack them all on [2,5) to create the imbalance
    for s in stacked:
        s.load_span(s._spec_for(2, 5))

    moved = [s.maybe_rebalance() for s in stacked]
    assert any(moved)
    mover = stacked[moved.index(True)]
    assert (mover.spec.start, mover.spec.end) != (2, 5)
    assert mover.rebalances == 1


def test_ttl_expiry_removes_dead_server():
    cfg = tiny_cfg()
    params = init_params(jax.random.PRNGKey(0), cfg)
    transport, registry, provider = make_swarm(cfg, params)
    registry.ttl = 0.05
    s = make_elastic("srv-a", cfg, provider, registry, transport, num_blocks=6)
    s.start_serving()
    import time

    time.sleep(0.1)  # no heartbeat -> record expires
    assert registry.live_servers() == []
    # registry-level refresh of an expired record is a no-op...
    assert not registry.heartbeat("srv-a")
    # ...the server-level self-heal (re-register) is covered separately in
    # test_heartbeat_self_heals_after_expiry.


def test_shutdown_deregisters_and_stops_serving():
    cfg = tiny_cfg()
    params = init_params(jax.random.PRNGKey(0), cfg)
    transport, registry, provider = make_swarm(cfg, params)
    s = make_elastic("srv-a", cfg, provider, registry, transport, num_blocks=6)
    s.start_serving()
    s.shutdown()
    assert registry.get("srv-a") is None
    assert "srv-a" not in transport.peers()


def test_overlapping_spans_generate_correctly():
    """Regression (review finding): elastic placement can produce OVERLAPPING
    spans (e.g. [2,6) and [4,8)); hops must execute exactly their assigned
    block range, not their whole loaded span."""
    cfg = tiny_cfg()
    params = init_params(jax.random.PRNGKey(0), cfg)
    transport, registry, provider = make_swarm(cfg, params)
    a = make_elastic("srv-a", cfg, provider, registry, transport, num_blocks=4)
    a.start_serving()
    b = make_elastic("srv-b", cfg, provider, registry, transport, num_blocks=4)
    b.start_serving()
    spans = {(a.spec.start, a.spec.end), (b.spec.start, b.spec.end)}
    assert spans == {(2, 6), (4, 8)}  # genuinely overlapping

    plan = StagePlan.from_splits(cfg.num_layers, [MIN_BLOCK])
    stage0 = StageExecutor(cfg, plan.stages[0],
                           slice_stage_params(cfg, params, plan.stages[0]),
                           peer_id="client-local")
    client = PipelineClient(cfg, plan, stage0, transport, registry,
                            use_module_routing=True,
                            total_blocks=cfg.num_layers, settle_seconds=0.0)
    sampling = SamplingParams(temperature=0.0)
    res = client.generate([5, 9, 23, 7], max_new_tokens=6, sampling=sampling)
    ref = oracle_generate(cfg, params, [5, 9, 23, 7], 6, sampling)
    assert res.tokens == ref


def test_heartbeat_self_heals_after_expiry():
    """Regression (review finding): a server that misses a TTL window must
    re-create its record on the next heartbeat, not vanish forever."""
    cfg = tiny_cfg()
    params = init_params(jax.random.PRNGKey(0), cfg)
    transport, registry, provider = make_swarm(cfg, params)
    registry.ttl = 0.05
    s = make_elastic("srv-a", cfg, provider, registry, transport, num_blocks=6)
    s.start_serving()
    import time

    time.sleep(0.1)
    assert registry.live_servers() == []
    s.heartbeat_once()
    assert [r.peer_id for r in registry.live_servers()] == ["srv-a"]


# ---------------------------------------------------------------------------
# Auto capacity sizing (petals/server/server.py:275-326 _choose_num_blocks)
# ---------------------------------------------------------------------------

class _FakeDevice:
    def __init__(self, limit, in_use=0):
        self._stats = ({"bytes_limit": limit, "bytes_in_use": in_use}
                       if limit is not None else None)

    def memory_stats(self):
        return self._stats


def test_derive_num_blocks_matches_arena_accounting():
    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.models.quant import (
        block_bytes,
    )
    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime.server import (
        derive_num_blocks,
    )

    cfg = tiny_cfg()
    per = block_bytes(cfg, dtype_bytes=2)
    arena = 1 << 20
    # Budget sized for exactly 3 blocks AFTER the arena + 15% headroom:
    # usable = limit * 0.85 - arena  =>  limit = (3*per + arena) / 0.85 + eps
    limit = int((3 * per + arena) / 0.85) + 16
    n = derive_num_blocks(cfg, dtype_bytes=2, attn_cache_bytes=arena,
                          device=_FakeDevice(limit))
    assert n == 3
    # bytes_in_use shrinks the budget
    n2 = derive_num_blocks(cfg, dtype_bytes=2, attn_cache_bytes=arena,
                           device=_FakeDevice(limit, in_use=2 * per))
    assert n2 < 3
    # quant packs more blocks into the same budget
    n4 = derive_num_blocks(cfg, dtype_bytes=2, attn_cache_bytes=arena,
                           quant="nf4", device=_FakeDevice(limit))
    assert n4 > n
    # no byte limit (host CPU): None -> caller falls back to its heuristic
    assert derive_num_blocks(cfg, device=_FakeDevice(None)) is None


def test_elastic_server_with_derived_capacity_serves():
    """End-to-end: a server whose num_blocks came from derive_num_blocks
    joins the swarm and serves its span."""
    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.models.quant import (
        block_bytes,
    )
    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime.server import (
        derive_num_blocks,
    )

    cfg = tiny_cfg()
    params = init_params(jax.random.PRNGKey(0), cfg)
    transport, registry, provider = make_swarm(cfg, params)
    per = block_bytes(cfg, dtype_bytes=4)
    arena = 1 << 20
    limit = int((4 * per + arena) / 0.85) + 16
    n = derive_num_blocks(cfg, dtype_bytes=4, attn_cache_bytes=arena,
                          device=_FakeDevice(limit))
    assert n == 4
    es = make_elastic("auto", cfg, provider, registry, transport, n)
    es.start_serving()
    assert es.spec.num_layers == min(n, cfg.num_layers - MIN_BLOCK)
    rec = registry.get("auto")
    assert rec is not None and rec.end_block - rec.start_block == es.spec.num_layers
    es.shutdown()


def test_derive_num_blocks_raises_when_nothing_fits():
    import pytest as _pytest

    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime.server import (
        derive_num_blocks,
    )

    cfg = tiny_cfg()
    with _pytest.raises(RuntimeError, match="cannot fit one"):
        derive_num_blocks(cfg, dtype_bytes=2, attn_cache_bytes=1 << 30,
                          device=_FakeDevice(1 << 30))  # free < arena
