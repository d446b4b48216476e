"""Sparse MoE dispatch (models/moe.py) vs the dense all-expert oracle.

The sparse path must be token-identical to the dense formulation whenever no
expert overflows its capacity (combine-order differs, so identical means
allclose/argmax, not bitwise); MOE_SPARSE=0 must restore the dense einsums
bit-for-bit; quantized expert stacks must stay packed on the sparse path and
still match the materialized-dense reference. EP shard_map parity for the
same dispatch rides tests/test_tensor_parallel.py (mixtral tp=2/4 cases).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.models import (
    init_kv_cache,
    init_params,
    mixtral_config,
)
from engines import full_forward
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.models.moe import (
    dispatch_stats,
    moe_capacity,
    moe_capacity_factor,
    moe_sparse_enabled,
    sparse_moe_mlp,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.models.quant import (
    NF4Tensor,
    QuantizedTensor,
    dequant_tree,
    quantize_params,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.models.transformer import (
    _moe_mlp,
    _moe_mlp_dense,
)


def moe_cfg(num_experts=4, top_k=2, num_layers=2):
    return mixtral_config(
        vocab_size=131, hidden_size=32, num_layers=num_layers, num_heads=4,
        num_kv_heads=4, intermediate_size=64, num_experts=num_experts,
        num_experts_per_tok=top_k, max_position_embeddings=64)


def layer_mlp(params, layer=0):
    """One layer's mlp subtree from the stacked [L, ...] init."""
    return jax.tree.map(lambda a: a[layer], params["layers"]["mlp"])


def tokens(cfg, b=2, t=5, seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.standard_normal((b, t, cfg.hidden_size)),
                       jnp.float32)


# -- dense-vs-sparse parity ---------------------------------------------------


@pytest.mark.parametrize("num_experts,top_k", [
    (4, 1), (4, 2), (8, 2), (8, 3),
])
def test_sparse_matches_dense(num_experts, top_k):
    cfg = moe_cfg(num_experts, top_k)
    params = init_params(jax.random.PRNGKey(0), cfg)
    mlp = layer_mlp(params)
    x = tokens(cfg)

    # Precondition, not hope: this batch must be drop-free at the default
    # capacity, or the parity claim is vacuous.
    counts, kept, cap = dispatch_stats(cfg, mlp["router"], x)
    assert kept == x.shape[0] * x.shape[1] * top_k
    assert int(jnp.max(counts)) <= cap

    got = sparse_moe_mlp(cfg, mlp, x, None)
    want = _moe_mlp_dense(cfg, mlp, x, None)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


def test_full_forward_sparse_vs_dense_tokens(monkeypatch):
    """Whole-model parity through full_forward: same argmax tokens with the
    dispatch flipped either way."""
    cfg = moe_cfg(4, 2)
    params = init_params(jax.random.PRNGKey(1), cfg)
    ids = jnp.asarray([[5, 9, 23, 7]], jnp.int32)

    def run():
        kc, vc = init_kv_cache(cfg, cfg.num_layers, 1, 16)
        logits, _, _ = full_forward(cfg, params, ids, kc, vc, jnp.int32(0))
        return logits

    monkeypatch.setenv("MOE_SPARSE", "1")
    assert moe_sparse_enabled()
    sparse = run()
    monkeypatch.setenv("MOE_SPARSE", "0")
    assert not moe_sparse_enabled()
    dense = run()
    np.testing.assert_allclose(np.asarray(sparse), np.asarray(dense),
                               atol=2e-4, rtol=2e-4)
    assert (jnp.argmax(sparse, -1) == jnp.argmax(dense, -1)).all()


def test_kill_switch_is_bitwise_dense(monkeypatch):
    """MOE_SPARSE=0 routes _moe_mlp to the UNMODIFIED dense body — not a
    numerically-close twin; bit-for-bit the same arrays."""
    cfg = moe_cfg(4, 2)
    params = init_params(jax.random.PRNGKey(2), cfg)
    mlp = layer_mlp(params)
    x = tokens(cfg, seed=2)
    monkeypatch.setenv("MOE_SPARSE", "0")
    got = _moe_mlp(cfg, mlp, x, None)
    want = _moe_mlp_dense(cfg, mlp, x, None)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# -- capacity / drops ---------------------------------------------------------


def test_capacity_policy():
    # Default factor 2.0: min(N, ceil(N*K/E * 2)), never 0, never above N.
    assert moe_capacity_factor() == 2.0
    assert moe_capacity(512, 8, 2) == 256
    assert moe_capacity(2, 8, 2) == 1
    assert moe_capacity(4, 4, 4) == 4      # clamped to N
    assert moe_capacity(0, 8, 2) == 1      # floor


def test_capacity_factor_zero_is_drop_free(monkeypatch):
    monkeypatch.setenv("MOE_CAPACITY_FACTOR", "0")
    assert moe_capacity(6, 8, 2) == 6
    cfg = moe_cfg(8, 2)
    params = init_params(jax.random.PRNGKey(3), cfg)
    mlp = layer_mlp(params)
    x = tokens(cfg, b=1, t=6, seed=3)
    _, kept, cap = dispatch_stats(cfg, mlp["router"], x)
    assert cap == 6 and kept == 12
    got = sparse_moe_mlp(cfg, mlp, x, None)
    want = _moe_mlp_dense(cfg, mlp, x, None)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


def test_capacity_overflow_drops_and_stays_finite(monkeypatch):
    """Under a starved capacity factor slots overflow and are DROPPED:
    dispatch_stats reports it, the output stays finite, and the dropped
    slots' contribution is zero (output != dense)."""
    monkeypatch.setenv("MOE_CAPACITY_FACTOR", "0.25")
    cfg = moe_cfg(8, 2)
    params = init_params(jax.random.PRNGKey(4), cfg)
    mlp = layer_mlp(params)
    x = tokens(cfg, b=2, t=8, seed=4)      # N=16 slots=32, cap=ceil(1)=1
    counts, kept, cap = dispatch_stats(cfg, mlp["router"], x)
    assert cap == 1
    assert kept < 32
    assert kept == int(jnp.sum(jnp.minimum(counts, cap)))
    got = sparse_moe_mlp(cfg, mlp, x, None)
    assert bool(jnp.all(jnp.isfinite(got)))
    want = _moe_mlp_dense(cfg, mlp, x, None)
    assert not np.allclose(np.asarray(got), np.asarray(want), atol=1e-5)


# -- structural work: slots an expert computes over tokens --------------------


def test_capacity_share_proportional_to_topk_over_experts():
    """The sparse path's three grouped matmuls run over C slots an expert
    where the dense formulation runs over all N tokens: C / N is the share
    of the dense MLP's work it executes."""
    for e, k in [(8, 1), (8, 2), (16, 2), (16, 4)]:
        n = 512
        ratio = moe_capacity(n, e, k) / n
        expect = min(1.0, k / e * moe_capacity_factor())
        assert abs(ratio - expect) <= 1.0 / n


# -- quantized experts stay packed on the sparse path -------------------------


def _materialized(qp):
    """The SAME quantized weights explicitly dequantized (materialized) —
    the reference the packed path must match."""
    return dict(qp, layers=dequant_tree(qp["layers"]))


@pytest.mark.parametrize("fmt,leaf_cls", [
    ("int8", QuantizedTensor), ("nf4", NF4Tensor),
])
def test_quantized_sparse_matches_materialized_dense(fmt, leaf_cls):
    cfg = moe_cfg(4, 2)
    params = init_params(jax.random.PRNGKey(5), cfg)
    qp = quantize_params(params, fmt)
    # The expert stacks must be packed 3-D leaves going in…
    assert isinstance(qp["layers"]["mlp"]["wg"], leaf_cls)
    deq = _materialized(qp)
    ids = jnp.asarray([[3, 77, 12, 9, 41]], jnp.int32)

    def run(p):
        kc, vc = init_kv_cache(cfg, cfg.num_layers, 1, 16)
        logits, _, _ = full_forward(cfg, p, ids, kc, vc, jnp.int32(0))
        return logits

    got = run(qp)           # sparse path, packed expert stacks
    want = run(deq)         # same weights materialized
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=3e-4, rtol=3e-4)
    assert (jnp.argmax(got, -1) == jnp.argmax(want, -1)).all()


def test_quantized_layer_call_runs_packed(monkeypatch):
    """Layer-level: sparse_moe_mlp consumes the packed [E, ...] quantized
    stacks directly (the grouped-einsum epilogue / lax.map dequant), no
    materialized twin in between."""
    cfg = moe_cfg(4, 2)
    params = init_params(jax.random.PRNGKey(6), cfg)
    x = tokens(cfg, seed=6)
    for fmt in ("int8", "nf4"):
        qp = quantize_params(params, fmt)
        qmlp = layer_mlp(qp)
        dmlp = layer_mlp(_materialized(qp))
        got = sparse_moe_mlp(cfg, qmlp, x, None)
        want = _moe_mlp_dense(cfg, dmlp, x, None)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=3e-4, rtol=3e-4)


# -- telemetry ----------------------------------------------------------------


def test_dispatch_telemetry_records_load():
    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.telemetry.metrics import (
        get_registry,
    )
    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.telemetry.profiling import (
        _metric_sum,
        stats_digest,
    )

    cfg = moe_cfg(4, 2)
    params = init_params(jax.random.PRNGKey(7), cfg)
    mlp = layer_mlp(params)
    x = tokens(cfg, b=1, t=6, seed=7)      # 6 tokens * K=2 = 12 slots
    reg = get_registry()
    reg.reset()
    reg.enable()
    try:
        out = sparse_moe_mlp(cfg, mlp, x, None)
        jax.block_until_ready(out)
        jax.effects_barrier()
        assert _metric_sum(reg, "moe_tokens_total") == 12.0
        assert _metric_sum(reg, "moe_dropped_total") == 0.0
        share = _metric_sum(reg, "moe_max_expert_share")
        assert 0.25 <= share <= 1.0        # hottest of 4 experts
        digest = stats_digest(reg)
        assert digest["moe_drop_frac"] == 0.0
        assert digest["moe_hot_share"] == round(share, 4)
    finally:
        reg.disable()
        reg.reset()


def test_dispatch_telemetry_dark_by_default():
    """Registry disabled at trace time: the sparse path must not embed the
    host callback at all (the hot path stays callback-free)."""
    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.telemetry.metrics import (
        get_registry,
    )
    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.telemetry.profiling import (
        _metric_sum,
    )

    cfg = moe_cfg(4, 2)
    params = init_params(jax.random.PRNGKey(8), cfg)
    mlp = layer_mlp(params)
    x = tokens(cfg, seed=8)
    reg = get_registry()
    reg.reset()
    assert not reg.enabled
    out = sparse_moe_mlp(cfg, mlp, x, None)
    jax.block_until_ready(out)
    jax.effects_barrier()
    assert _metric_sum(reg, "moe_tokens_total") == 0.0
