"""Fused NF4 dequant-matmul Pallas kernel (ops.nf4_kernel).

On-chip measurement (round 5, v5e): flagship nf4 fused decode 20.8 ms ->
6.8 ms per step (2359 tokens/s) with NF4_KERNEL=1. CPU CI covers the
kernel's MATH via the Pallas interpreter and the dispatch plumbing; the
speed claim lives in docs/PERFORMANCE.md.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.ops.nf4_kernel as NK
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.models.quant import (
    NF4Tensor,
    _quantize_leaf_nf4,
    dequant_tree,
    quantize_params,
)


@pytest.fixture
def interpret_kernel(monkeypatch):
    monkeypatch.setattr(NK, "_INTERPRET", True)


def test_kernel_matches_dequant_matmul(interpret_kernel):
    """nf4_dot's kernel path (interpreter semantics == Mosaic semantics)
    must match dequant-then-matmul to f32-accumulation noise; the two
    differ only in contraction split (even/odd nibble parity)."""
    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.standard_normal((256, 384)).astype(np.float32)
                    * 0.02, jnp.bfloat16)
    q = _quantize_leaf_nf4(w)
    x = jnp.asarray(rng.standard_normal((8, 256)).astype(np.float32),
                    jnp.bfloat16)
    got = NK.nf4_dot(x, q)
    want = x @ q.dequant().astype(x.dtype)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=0.05, rtol=0.05)


def test_kernel_pads_rows_and_restores_shape(interpret_kernel):
    """Leading shapes and non-multiple-of-8 row counts round-trip."""
    rng = np.random.default_rng(1)
    w = jnp.asarray(rng.standard_normal((128, 128)).astype(np.float32)
                    * 0.02, jnp.bfloat16)
    q = _quantize_leaf_nf4(w)
    x = jnp.asarray(rng.standard_normal((2, 3, 128)).astype(np.float32),
                    jnp.bfloat16)                      # 6 rows -> pad to 8
    got = NK.nf4_dot(x, q)
    assert got.shape == (2, 3, 128)
    want = x @ q.dequant().astype(x.dtype)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=0.05, rtol=0.05)


def test_unsupported_shapes_fall_back_exactly():
    """Shapes the kernel does not cover take the dequant path — enabling
    the flag never changes reachability (odd in_dim, non-128 N, stacked
    3-D leaves, non-TPU backend)."""
    rng = np.random.default_rng(2)
    w = jnp.asarray(rng.standard_normal((100, 96)).astype(np.float32)
                    * 0.02, jnp.bfloat16)              # padded in, odd N
    q = _quantize_leaf_nf4(w)
    x = jnp.asarray(rng.standard_normal((4, 100)).astype(np.float32),
                    jnp.bfloat16)
    got = NK.nf4_dot(x, q)                             # CPU: fallback
    want = x @ q.dequant().astype(x.dtype)
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))


def test_dequant_tree_keeps_2d_nf4_only_under_flag(monkeypatch):
    """NF4_KERNEL=1: per-layer 2-D NF4 leaves stay packed for the matmul
    sites; stacked 3-D leaves still materialize (no kernel path for the
    scan-stacked/MoE forms)."""
    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.models import (
        init_params,
        llama_config,
    )

    cfg = llama_config(vocab_size=128, hidden_size=64, num_layers=2,
                       num_heads=4, num_kv_heads=2, intermediate_size=128,
                       max_position_embeddings=32)
    params = quantize_params(init_params(jax.random.PRNGKey(0), cfg), "nf4")
    layer0 = jax.tree.map(lambda a: a[0], params["layers"])

    monkeypatch.setenv("NF4_KERNEL", "0")
    out = dequant_tree(layer0)
    assert not any(isinstance(v, NF4Tensor)
                   for v in jax.tree.leaves(out, is_leaf=lambda v:
                                            isinstance(v, NF4Tensor)))

    monkeypatch.setenv("NF4_KERNEL", "1")
    out = dequant_tree(layer0)
    kept = [v for v in jax.tree.leaves(out, is_leaf=lambda v:
                                       isinstance(v, NF4Tensor))
            if isinstance(v, NF4Tensor)]
    assert kept, "2-D NF4 leaves should stay packed under the flag"
    stacked = dequant_tree(params["layers"])   # 3-D: must materialize
    assert not any(isinstance(v, NF4Tensor)
                   for v in jax.tree.leaves(stacked, is_leaf=lambda v:
                                            isinstance(v, NF4Tensor)))


def test_layer_forward_close_under_kernel_flag(interpret_kernel,
                                               monkeypatch):
    """End-to-end through a real layer: the kernel-dispatch path's hidden
    states stay close to the dequant path's (same dequant VALUES, only
    contraction order differs)."""
    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.models import (
        init_kv_cache,
        init_params,
        llama_config,
    )
    from engines import full_forward

    cfg = llama_config(vocab_size=128, hidden_size=128, num_layers=2,
                       num_heads=4, num_kv_heads=2, intermediate_size=256,
                       max_position_embeddings=32)
    # f32 activations: the CPU interpreter's dot thunk has no bf16 mode;
    # the bf16 serving path is exercised on-chip (docs/PERFORMANCE.md).
    params = quantize_params(
        init_params(jax.random.PRNGKey(0), cfg), "nf4")
    ids = jnp.asarray([[3, 17, 42, 7]], jnp.int32)

    def run():
        kc, vc = init_kv_cache(cfg, cfg.num_layers, 1, 16,
                               dtype=jnp.bfloat16)
        logits, _, _ = full_forward(cfg, params, ids, kc, vc, jnp.int32(0))
        return np.asarray(logits, np.float32)

    monkeypatch.setenv("NF4_KERNEL", "0")
    base = run()
    monkeypatch.setenv("NF4_KERNEL", "1")
    kern = run()
    np.testing.assert_allclose(kern, base, atol=0.08, rtol=0.08)


def test_batched_engine_under_kernel_flag(interpret_kernel, monkeypatch):
    """The slot-batched serving engine's matmul sites dispatch packed NF4
    leaves too (a raw `@` here crashed at trace time before the fix) —
    tokens must match its dequant-mode twin."""
    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.models import (
        init_params,
        llama_config,
    )
    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.models.partition import (
        ROLE_FULL,
        StageSpec,
    )
    from engines import engine as BatchedStageExecutor

    cfg = llama_config(vocab_size=128, hidden_size=128, num_layers=2,
                       num_heads=4, num_kv_heads=2, intermediate_size=256,
                       max_position_embeddings=32)
    params = quantize_params(init_params(jax.random.PRNGKey(0), cfg), "nf4")
    spec = StageSpec(index=0, role=ROLE_FULL, start=0, end=cfg.num_layers)
    rng = np.random.default_rng(3)
    prompt = rng.integers(0, cfg.vocab_size, 5).astype(np.int32)

    def serve():
        ex = BatchedStageExecutor(cfg, spec, params, slots=2, max_len=16)
        h = ex.prefill("s", prompt[None, :])
        toks = [int(jnp.argmax(ex.logits(h[:, -1:])[0, -1]))]
        for _ in range(3):
            out = ex.decode_batch({"s": jnp.asarray([[toks[-1]]],
                                                    jnp.int32)})
            toks.append(int(jnp.argmax(out["s"][0, -1])))
        return toks

    monkeypatch.setenv("NF4_KERNEL", "1")
    kern = serve()
    monkeypatch.setenv("NF4_KERNEL", "0")
    base = serve()
    assert kern == base
