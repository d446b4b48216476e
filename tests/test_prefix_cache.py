"""Prompt-prefix KV reuse (runtime.prefix_cache) — no reference counterpart.

The store is content-addressed with grain-chained rolling digests, so two
prompts sharing a system preamble reuse its grains automatically. A hit is
exact in content (same bytes through the same blocks); outputs are compared
at the chunk-boundary fp tolerance the suite uses for chunked prefill (the
warm suffix runs under a different seq-bucket shape than the cold one-shot
prefill, so fusion differences move the last ulp, not the math).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

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
from engines import stage_executor as StageExecutor
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime.messages import (
    StageRequest,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime.net import (
    _header_to_request,
    _request_header,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime.prefix_cache import (
    PrefixStore,
    chain_digests,
)

from engines import tiny_cfg

GRAIN = 8


# ---------------------------------------------------------------------------
# Store unit tests
# ---------------------------------------------------------------------------

def _seg(val, nbytes=64):
    a = jnp.full((1, 1, GRAIN, 1, 2), float(val), jnp.float32)
    return a, a, jnp.full((1, GRAIN, 2), float(val), jnp.float32)


def test_store_chain_lookup_stops_at_first_missing():
    st = PrefixStore(1 << 20, grain=GRAIN)
    keys = chain_digests([b"a", b"b", b"c"], coords=("t",))
    k, v, out = _seg(1)
    st.put(keys[0], k, v, out)
    st.put(keys[2], k, v, out)  # keys[1] missing -> chain ends after 1
    got = st.lookup_chain(keys, need_out=True)
    assert len(got) == 1
    assert st.hits == 1 and st.grains_reused == 1


def test_store_need_out_breaks_on_kv_only_entry():
    st = PrefixStore(1 << 20, grain=GRAIN)
    keys = chain_digests([b"a", b"b"], coords=("t",))
    k, v, out = _seg(1)
    st.put(keys[0], k, v, None)
    st.put(keys[1], k, v, out)
    assert st.lookup_chain(keys, need_out=True) == []
    assert len(st.lookup_chain(keys, need_out=False)) == 2


def test_store_lru_eviction_bounded():
    k, v, out = _seg(1)
    per = int(k.nbytes + v.nbytes + out.nbytes)
    st = PrefixStore(per * 2, grain=GRAIN)
    keys = chain_digests([b"a", b"b", b"c"], coords=("t",))
    for key in keys:
        assert st.put(key, k, v, out)
    assert len(st) == 2 and st.evictions == 1
    assert st.used_bytes <= st.max_bytes
    # oldest evicted -> chain broken at first key
    assert st.lookup_chain(keys, need_out=True) == []
    # oversized entry refused
    tiny = PrefixStore(per - 1, grain=GRAIN)
    assert not tiny.put(keys[0], k, v, out)


def test_rolling_digest_is_position_dependent():
    d1 = chain_digests([b"aa", b"bb"], coords=("c",))
    d2 = chain_digests([b"bb", b"bb"], coords=("c",))
    # same 2nd-grain bytes, different prefix -> different 2nd digest
    assert d1[1] != d2[1]
    assert chain_digests([b"aa"], coords=("c",)) != chain_digests(
        [b"aa"], coords=("other",))


def test_wire_header_roundtrip_prefix_len():
    req = StageRequest(session_id="s", hidden=jnp.zeros((1, 4, 8)),
                       seq_len=4, cur_len=0, is_prefill=True, max_length=32,
                       prefix_len=4)
    hdr = _request_header(req, {"dtype": "f32", "shape": [1, 4, 8]})
    body = np.zeros((1, 4, 8), np.float32).tobytes()
    back = _header_to_request(hdr, body)
    assert back.prefix_len == 4
    # absent for the common case (legacy header compatibility)
    req0 = StageRequest(session_id="s", hidden=jnp.zeros((1, 4, 8)),
                        seq_len=4, cur_len=0, is_prefill=True, max_length=32)
    assert "prefix_len" not in _request_header(
        req0, {"dtype": "f32", "shape": [1, 4, 8]})


# ---------------------------------------------------------------------------
# Executor integration
# ---------------------------------------------------------------------------

def _seg_executor(cfg, params, cache_mb=64):
    plan = StagePlan.from_splits(cfg.num_layers, parse_splits("2,6"))
    spec = plan.stages[1]  # layers [2, 6)
    ex = StageExecutor(cfg, spec, slice_stage_params(cfg, params, spec),
                       peer_id="seg",
                       prefix_cache_bytes=cache_mb << 20)
    ex.prefix_store.grain = GRAIN  # fine-grained for small test prompts
    return ex


def _prefill(ex, sid, hid, prefix_len):
    return ex.forward(StageRequest(
        session_id=sid, hidden=jnp.asarray(hid), seq_len=hid.shape[1],
        cur_len=0, is_prefill=True, max_length=64, prefix_len=prefix_len))


def _decode(ex, sid, hid, cur_len):
    return ex.forward(StageRequest(
        session_id=sid, hidden=jnp.asarray(hid), seq_len=1, cur_len=cur_len,
        is_prefill=False, max_length=64))


def test_segment_hit_is_bitwise_exact_through_decode():
    cfg = tiny_cfg()
    params = init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(1)
    hid = rng.standard_normal((1, 40, cfg.hidden_size)).astype(np.float32)

    ex = _seg_executor(cfg, params)
    cold = _prefill(ex, "cold", hid, prefix_len=40)
    st = ex.prefix_store.stats()
    # min(40, 39) // 8 = 4 grains registered on the miss
    assert st == {**st, "entries": 4, "misses": 1, "hits": 0}

    warm = _prefill(ex, "warm", hid, prefix_len=40)
    st = ex.prefix_store.stats()
    assert st["hits"] == 1 and st["grains_reused"] == 4
    np.testing.assert_allclose(np.asarray(cold.hidden),
                               np.asarray(warm.hidden), atol=1e-5, rtol=1e-5)
    assert warm.cache_len == 40

    # decode must continue bitwise-identically from the copied KV
    step = rng.standard_normal((1, 1, cfg.hidden_size)).astype(np.float32)
    for i in range(3):
        rc = _decode(ex, "cold", step, 40 + i)
        rw = _decode(ex, "warm", step, 40 + i)
        np.testing.assert_allclose(np.asarray(rc.hidden),
                                   np.asarray(rw.hidden), atol=1e-5, rtol=1e-5)


def test_shared_prefix_divergent_suffix_matches_uncached():
    cfg = tiny_cfg()
    params = init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(2)
    shared = rng.standard_normal((1, 32, cfg.hidden_size)).astype(np.float32)
    tail_a = rng.standard_normal((1, 9, cfg.hidden_size)).astype(np.float32)
    tail_b = rng.standard_normal((1, 9, cfg.hidden_size)).astype(np.float32)
    hid_a = np.concatenate([shared, tail_a], axis=1)
    hid_b = np.concatenate([shared, tail_b], axis=1)

    cached = _seg_executor(cfg, params)
    _prefill(cached, "a", hid_a, prefix_len=41)
    warm_b = _prefill(cached, "b", hid_b, prefix_len=41)
    st = cached.prefix_store.stats()
    # prompts diverge after 32 rows -> exactly 4 shared grains reused
    assert st["hits"] == 1 and st["grains_reused"] == 4

    oracle = StageExecutor(
        cfg, cached.spec, slice_stage_params(cfg, params, cached.spec),
        peer_id="oracle")
    cold_b = _prefill(oracle, "b", hid_b, prefix_len=0)
    np.testing.assert_allclose(np.asarray(cold_b.hidden),
                               np.asarray(warm_b.hidden), atol=1e-5, rtol=1e-5)


def test_final_stage_hit_keeps_sampled_token():
    cfg = tiny_cfg()
    params = init_params(jax.random.PRNGKey(0), cfg)
    plan = StagePlan.from_splits(cfg.num_layers, parse_splits("2,6"))
    spec = plan.stages[-1]  # layers [6, 8) + head
    ex = StageExecutor(cfg, spec, slice_stage_params(cfg, params, spec),
                       peer_id="last", prefix_cache_bytes=64 << 20)
    ex.prefix_store.grain = GRAIN
    rng = np.random.default_rng(3)
    hid = rng.standard_normal((1, 33, cfg.hidden_size)).astype(np.float32)

    def prefill(sid):
        return ex.forward(StageRequest(
            session_id=sid, hidden=jnp.asarray(hid), seq_len=33, cur_len=0,
            is_prefill=True, max_length=64, prefix_len=33,
            sampling=SamplingParams(temperature=0.0)))

    cold = prefill("cold")
    warm = prefill("warm")
    # min(33, 32) // 8 = 4 grains; final stage stores KV-only entries
    assert ex.prefix_store.stats()["grains_reused"] == 4
    assert cold.token_id == warm.token_id
    assert warm.cache_len == 33


def test_prefix_len_clamp_never_skips_last_row():
    """prefix_len == seq_len must leave >= 1 computed row (the final stage
    samples from it): with T = 32 and grain 8, only 3 grains are usable."""
    cfg = tiny_cfg()
    params = init_params(jax.random.PRNGKey(0), cfg)
    ex = _seg_executor(cfg, params)
    hid = np.random.default_rng(4).standard_normal(
        (1, 32, cfg.hidden_size)).astype(np.float32)
    a = _prefill(ex, "a", hid, prefix_len=32)
    warm = _prefill(ex, "b", hid, prefix_len=32)
    assert ex.prefix_store.stats()["grains_reused"] == 3
    np.testing.assert_allclose(np.asarray(a.hidden),
                               np.asarray(warm.hidden), atol=1e-5, rtol=1e-5)


def test_exotic_requests_bypass_store():
    cfg = tiny_cfg()
    params = init_params(jax.random.PRNGKey(0), cfg)
    ex = _seg_executor(cfg, params)
    hid = np.random.default_rng(5).standard_normal(
        (1, 24, cfg.hidden_size)).astype(np.float32)
    prompts = np.zeros((4, 2, cfg.hidden_size), np.float32)
    ex.forward(StageRequest(
        session_id="dp", hidden=jnp.asarray(hid), seq_len=24, cur_len=0,
        is_prefill=True, max_length=64, prefix_len=24,
        prompts=jnp.asarray(prompts)))
    st = ex.prefix_store.stats()
    assert st["entries"] == 0 and st["hits"] == 0 and st["misses"] == 0


def test_end_to_end_client_reuse_token_parity():
    """Two PipelineClient generations with the same prompt: the second hits
    every server's store and produces identical tokens; a shared-prefix
    third prompt reuses only the shared grains and still matches a
    cache-free cluster."""
    from engines import build_cluster

    cfg = tiny_cfg()
    client, transport, registry, params, plan = build_cluster(cfg)
    stores = []
    for pid in transport.peers():
        ex = transport.executor(pid)
        ex.prefix_store = PrefixStore(64 << 20, grain=GRAIN)
        stores.append(ex.prefix_store)
    prompt = list(range(7, 47))  # 40 tokens -> 4 reusable grains of 8
    sampling = SamplingParams(temperature=0.0)

    r1 = client.generate(prompt, max_new_tokens=6, sampling=sampling)
    assert all(s.stats()["misses"] == 1 for s in stores)
    r2 = client.generate(prompt, max_new_tokens=6, sampling=sampling)
    assert r1.tokens == r2.tokens
    assert all(s.stats()["hits"] == 1 for s in stores)
    assert all(s.stats()["grains_reused"] == 4 for s in stores)

    # divergent tail after 32 shared tokens
    prompt3 = prompt[:32] + [101, 102, 103, 104, 105, 106, 107, 108]
    r3 = client.generate(prompt3, max_new_tokens=6, sampling=sampling)
    fresh_client, *_ = build_cluster(cfg)
    r3_oracle = fresh_client.generate(prompt3, max_new_tokens=6,
                                      sampling=sampling)
    assert r3.tokens == r3_oracle.tokens


# ---------------------------------------------------------------------------
# Batched (slot) engine
# ---------------------------------------------------------------------------

def _batched_engine(cfg, params, role_last=False, cache_mb=64):
    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.models.partition import (
        StagePlan,
    )
    from engines import engine as BatchedStageExecutor

    plan = StagePlan.from_splits(cfg.num_layers, parse_splits("2,6"))
    spec = plan.stages[-1] if role_last else plan.stages[1]
    ex = BatchedStageExecutor(
        cfg, spec, slice_stage_params(cfg, params, spec),
        slots=4, max_len=64, prefix_cache_bytes=cache_mb << 20)
    ex.prefix_store.grain = GRAIN
    return ex


def test_batched_engine_hit_parity_through_decode():
    cfg = tiny_cfg()
    params = init_params(jax.random.PRNGKey(0), cfg)
    ex = _batched_engine(cfg, params)
    rng = np.random.default_rng(8)
    hid = rng.standard_normal((1, 40, cfg.hidden_size)).astype(np.float32)

    cold = ex.prefill("cold", hid, prefix_len=40)
    st = ex.prefix_store.stats()
    assert st["entries"] == 4 and st["misses"] == 1

    warm = ex.prefill("warm", hid, prefix_len=40)
    st = ex.prefix_store.stats()
    assert st["hits"] == 1 and st["grains_reused"] == 4
    assert warm.shape == cold.shape  # intermediate: full rows returned
    np.testing.assert_allclose(np.asarray(cold), np.asarray(warm),
                               atol=1e-5, rtol=1e-5)
    assert int(ex.lengths[ex.slot("warm")]) == 40

    # batched decode continues both sessions identically from their KV
    step = rng.standard_normal((1, 1, cfg.hidden_size)).astype(np.float32)
    for _ in range(3):
        outs = ex.decode_batch({"cold": jnp.asarray(step),
                                "warm": jnp.asarray(step)})
        np.testing.assert_allclose(np.asarray(outs["cold"]),
                                   np.asarray(outs["warm"]),
                                   atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("family", ["llama", "mistral-window"])
def test_batched_engine_shared_prefix_matches_cacheless(family):
    """The warm suffix continuation against the FULL prefill of the same
    prompt. ``mistral-window``: a sliding window of 4, so the suffix rows
    see only the last 4 keys, across the copied prefix's edge."""
    cfg = tiny_cfg(family)
    params = init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(9)
    shared = rng.standard_normal((1, 32, cfg.hidden_size)).astype(np.float32)
    tail_a = rng.standard_normal((1, 8, cfg.hidden_size)).astype(np.float32)
    tail_b = rng.standard_normal((1, 8, cfg.hidden_size)).astype(np.float32)

    cached = _batched_engine(cfg, params)
    cached.prefill("a", np.concatenate([shared, tail_a], 1), prefix_len=40)
    warm_b = cached.prefill("b", np.concatenate([shared, tail_b], 1),
                            prefix_len=40)
    assert cached.prefix_store.stats()["grains_reused"] == 4

    oracle = _batched_engine(cfg, params, cache_mb=64)
    cold_b = oracle.prefill("b", np.concatenate([shared, tail_b], 1),
                            prefix_len=0)
    np.testing.assert_allclose(np.asarray(cold_b), np.asarray(warm_b),
                               atol=1e-5, rtol=1e-5)


def test_batched_engine_final_stage_suffix_only():
    """is_last stores KV-only entries and a hit returns just the computed
    suffix (the adapter samples from its last row)."""
    cfg = tiny_cfg()
    params = init_params(jax.random.PRNGKey(0), cfg)
    ex = _batched_engine(cfg, params, role_last=True)
    rng = np.random.default_rng(10)
    hid = rng.standard_normal((1, 33, cfg.hidden_size)).astype(np.float32)

    cold = ex.prefill("cold", hid, prefix_len=33)
    warm = ex.prefill("warm", hid, prefix_len=33)
    assert ex.prefix_store.stats()["grains_reused"] == 4
    assert warm.shape[1] == 33 - 32  # suffix rows only
    np.testing.assert_allclose(np.asarray(cold[:, -1]),
                               np.asarray(warm[:, -1]),
                               atol=1e-5, rtol=1e-5)
    assert int(ex.lengths[ex.slot("warm")]) == 33


def test_batched_adapter_passes_prefix_len():
    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime.batching import (
        BatchingStageAdapter,
    )

    cfg = tiny_cfg()
    params = init_params(jax.random.PRNGKey(0), cfg)
    ex = _batched_engine(cfg, params)
    ad = BatchingStageAdapter(ex, peer_id="b")
    hid = np.random.default_rng(12).standard_normal(
        (1, 24, cfg.hidden_size)).astype(np.float32)
    r1 = ad.forward(StageRequest(
        session_id="s1", hidden=jnp.asarray(hid), seq_len=24, cur_len=0,
        is_prefill=True, max_length=64, prefix_len=24))
    r2 = ad.forward(StageRequest(
        session_id="s2", hidden=jnp.asarray(hid), seq_len=24, cur_len=0,
        is_prefill=True, max_length=64, prefix_len=24))
    assert ex.prefix_store.stats()["hits"] == 1
    np.testing.assert_allclose(np.asarray(r1.hidden), np.asarray(r2.hidden),
                               atol=1e-5, rtol=1e-5)


def test_batched_engine_partial_hit_registers_tail():
    """A prompt sharing only its head with a stored chain reuses the shared
    grains AND registers its own tail, so a repeat of the new prompt is a
    full-chain hit."""
    cfg = tiny_cfg()
    params = init_params(jax.random.PRNGKey(0), cfg)
    ex = _batched_engine(cfg, params)
    rng = np.random.default_rng(13)
    shared = rng.standard_normal((1, 16, cfg.hidden_size)).astype(np.float32)
    tail_b = rng.standard_normal((1, 25, cfg.hidden_size)).astype(np.float32)
    hid_a = np.concatenate(
        [shared, rng.standard_normal((1, 25, cfg.hidden_size))
         .astype(np.float32)], 1)
    hid_b = np.concatenate([shared, tail_b], 1)

    ex.prefill("a", hid_a, prefix_len=41)          # registers 5 grains
    ex.prefill("b1", hid_b, prefix_len=41)         # 2 shared, registers 3
    st = ex.prefix_store.stats()
    assert st["grains_reused"] == 2 and st["entries"] == 8
    ex.prefill("b2", hid_b, prefix_len=41)         # full-chain hit now
    assert ex.prefix_store.stats()["grains_reused"] == 2 + 5


# ---------------------------------------------------------------------------
# Prefix-affinity routing (rendezvous hash over replicas)
# ---------------------------------------------------------------------------

def test_affinity_pick_is_deterministic_and_spreads():
    import random as _random

    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.scheduling.registry import (
        PlacementRegistry,
    )
    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime.client import (
        make_server_record,
    )
    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.models.partition import (
        StagePlan,
        parse_splits,
    )

    cfg = tiny_cfg()
    plan = StagePlan.from_splits(cfg.num_layers, parse_splits("3,6"))
    spec = plan.stages[1]
    picks = set()
    for seed in range(5):  # rng must NOT influence affinity picks
        reg = PlacementRegistry(rng=_random.Random(seed))
        for r in range(3):
            reg.register(make_server_record(f"peer-r{r}", spec))
        picks.add(reg.discover_stage(spec.index, affinity="promptheadA"))
    assert len(picks) == 1
    reg = PlacementRegistry(rng=_random.Random(0))
    for r in range(3):
        reg.register(make_server_record(f"peer-r{r}", spec))
    spread = {reg.discover_stage(spec.index, affinity=f"head{i}")
              for i in range(32)}
    assert len(spread) > 1  # distinct prompt heads spread over replicas


def test_cross_client_affinity_warms_the_same_replica():
    """Two independent clients with the same prompt must pick the SAME
    replica chain (rendezvous affinity), so client B's prefill hits the
    store client A warmed."""
    from engines import build_cluster
    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime.client import (
        PipelineClient,
    )

    cfg = tiny_cfg()
    client_a, transport, registry, params, plan = build_cluster(
        cfg, replicas=3, seed=0)
    stores = {}
    for pid in transport.peers():
        ex = transport.executor(pid)
        ex.prefix_store = PrefixStore(64 << 20, grain=GRAIN)
        stores[pid] = ex.prefix_store
    client_b = PipelineClient(cfg, plan, client_a.stage0, transport,
                              registry, settle_seconds=0.0, seed=99)
    prompt = list(range(11, 51))
    sampling = SamplingParams(temperature=0.0)
    ra = client_a.generate(prompt, max_new_tokens=4, sampling=sampling)
    rb = client_b.generate(prompt, max_new_tokens=4, sampling=sampling)
    assert ra.tokens == rb.tokens
    # exactly the replicas client A warmed got client B's hits
    hit_peers = {p for p, s in stores.items() if s.stats()["hits"] > 0}
    miss_peers = {p for p, s in stores.items() if s.stats()["misses"] > 0}
    assert hit_peers == miss_peers and len(hit_peers) == 2  # 2 remote hops
