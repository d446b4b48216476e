"""Fused ICI pipeline vs unpartitioned oracle on a virtual CPU mesh.

The reference cannot express this at all (its stages are separate processes
on separate machines); the fused path must be numerically identical to the
single-device forward for both prefill and decode, including microbatching.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.models import (
    init_kv_cache,
    init_params,
    llama_config,
)
from engines import full_forward
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.parallel.pipeline import (
    IciPipeline,
    stack_pipeline_params,
)


def tiny_cfg():
    return llama_config(vocab_size=257, hidden_size=64, num_layers=8,
                        num_heads=4, num_kv_heads=2, intermediate_size=128,
                        max_position_embeddings=64)


def oracle_prefill(cfg, params, ids_flat, max_len=32):
    """Unpartitioned prefill; returns (logits, kc, vc) so callers can decode."""
    kc, vc = init_kv_cache(cfg, cfg.num_layers, ids_flat.shape[0], max_len)
    logits, kc, vc = full_forward(cfg, params, ids_flat, kc, vc, jnp.int32(0))
    return logits, kc, vc


@pytest.mark.parametrize("num_stages,num_micro", [(4, 1), (4, 2), (2, 3), (8, 2)])
def test_pipeline_prefill_matches_oracle(num_stages, num_micro):
    cfg = tiny_cfg()
    params = init_params(jax.random.PRNGKey(0), cfg)
    pipe = IciPipeline.build(cfg, params, num_stages, num_micro)
    b, t, max_len = 2, 5, 32

    rng = np.random.default_rng(0)
    ids = rng.integers(0, cfg.vocab_size, (num_micro, b, t)).astype(np.int32)
    k, v = pipe.init_kv(b, max_len)
    logits, k, v = pipe.forward(jnp.asarray(ids), k, v, jnp.int32(0))

    ref, _, _ = oracle_prefill(cfg, params,
                               jnp.asarray(ids.reshape(num_micro * b, t)), max_len)
    np.testing.assert_allclose(
        np.asarray(logits).reshape(num_micro * b, t, -1), np.asarray(ref),
        atol=2e-4, rtol=2e-4,
    )


def test_pipeline_decode_matches_oracle():
    cfg = tiny_cfg()
    params = init_params(jax.random.PRNGKey(1), cfg)
    num_stages, num_micro, b, t, max_len = 4, 2, 1, 4, 32
    pipe = IciPipeline.build(cfg, params, num_stages, num_micro)

    rng = np.random.default_rng(1)
    ids = rng.integers(0, cfg.vocab_size, (num_micro, b, t)).astype(np.int32)
    k, v = pipe.init_kv(b, max_len)
    logits, k, v = pipe.forward(jnp.asarray(ids), k, v, jnp.int32(0))
    # two greedy decode steps through the fused pipeline
    outs = [logits]
    cache_len = t
    for _ in range(2):
        nxt = jnp.argmax(outs[-1][:, :, -1:], axis=-1).astype(jnp.int32)
        logits, k, v = pipe.forward(nxt, k, v, jnp.int32(cache_len))
        outs.append(logits)
        cache_len += 1

    # oracle: same sequence unpartitioned
    flat_ids = jnp.asarray(ids.reshape(num_micro * b, t))
    ref, kc, vc = oracle_prefill(cfg, params, flat_ids, max_len)
    ref_list = [ref]
    cl = t
    cur = ref
    for _ in range(2):
        nxt = jnp.argmax(cur[:, -1:], axis=-1).astype(jnp.int32)
        cur, kc, vc = full_forward(cfg, params, nxt, kc, vc, jnp.int32(cl))
        ref_list.append(cur)
        cl += 1

    for got, want in zip(outs, ref_list):
        np.testing.assert_allclose(
            np.asarray(got).reshape(want.shape), np.asarray(want),
            atol=2e-4, rtol=2e-4,
        )


def test_uneven_spans_rejected():
    cfg = tiny_cfg()
    params = init_params(jax.random.PRNGKey(0), cfg)
    with pytest.raises(ValueError):
        stack_pipeline_params(params, 3)  # 8 % 3 != 0


def test_params_actually_sharded_per_stage():
    cfg = tiny_cfg()
    params = init_params(jax.random.PRNGKey(0), cfg)
    pipe = IciPipeline.build(cfg, params, 4, 1)
    leaf = jax.tree.leaves(pipe.layers_stacked)[0]
    assert leaf.shape[0] == 4
    # each stage shard lives on exactly one device
    assert len(leaf.sharding.device_set) == 4


def test_pipeline_with_tensor_parallel_matches_oracle():
    """2-D ("stage","tp") mesh: 4 pipeline stages x 2-way TP on 8 devices."""
    cfg = tiny_cfg()
    params = init_params(jax.random.PRNGKey(2), cfg)
    pipe = IciPipeline.build(cfg, params, num_stages=4, num_micro=2, tp=2)
    b, t, max_len = 1, 4, 32
    rng = np.random.default_rng(3)
    ids = rng.integers(0, cfg.vocab_size, (2, b, t)).astype(np.int32)
    k, v = pipe.init_kv(b, max_len)
    logits, k, v = pipe.forward(jnp.asarray(ids), k, v, jnp.int32(0))

    ref, _, _ = oracle_prefill(cfg, params, jnp.asarray(ids.reshape(2 * b, t)),
                               max_len)
    np.testing.assert_allclose(
        np.asarray(logits).reshape(2 * b, t, -1), np.asarray(ref),
        atol=3e-4, rtol=3e-4,
    )
    # one decode step too
    nxt = jnp.argmax(logits[:, :, -1:], axis=-1).astype(jnp.int32)
    logits2, k, v = pipe.forward(nxt, k, v, jnp.int32(t))
    assert logits2.shape == (2, b, 1, cfg.vocab_size)
