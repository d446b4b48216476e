"""Scale-folded int8 matmul epilogue (ops.int8_kernel) + quantized
engine-side fusion (models.transformer._concat_out_axis).

The round-7 int8 decode lever: `(x @ q) * s` streams the int8 bytes
straight into the dot instead of materializing a bf16 weight per layer.
CPU CI covers the kernel's MATH via the Pallas interpreter, the XLA
mixed-dtype fallback, the dequant_tree routing, and the exactness of
concatenating quantized leaves; the speed claim lives in
docs/PERFORMANCE.md.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.ops.int8_kernel as IK
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.models.quant import (
    NF4Tensor,
    QuantizedLayerView,
    QuantizedTensor,
    _quantize_leaf,
    _quantize_leaf_nf4,
    dequant_tree,
    int8_fold_enabled,
    quantize_params,
)


@pytest.fixture
def interpret_kernel(monkeypatch):
    monkeypatch.setattr(IK, "_INTERPRET", True)


def test_kernel_matches_dequant_matmul(interpret_kernel):
    """int8_dot's kernel path (interpreter semantics == Mosaic semantics)
    must match dequant-then-matmul to f32-accumulation noise; the values
    are identical, only the scale lands after the K-reduction."""
    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.standard_normal((256, 384)).astype(np.float32)
                    * 0.02)
    q = _quantize_leaf(w)
    x = jnp.asarray(rng.standard_normal((8, 256)).astype(np.float32))
    got = IK.int8_dot(x, q)
    want = x @ q.dequant().astype(x.dtype)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-4, rtol=1e-4)


def test_kernel_pads_rows_and_restores_shape(interpret_kernel):
    """Leading shapes and non-multiple-of-8 row counts round-trip."""
    rng = np.random.default_rng(1)
    w = jnp.asarray(rng.standard_normal((128, 128)).astype(np.float32)
                    * 0.02)
    q = _quantize_leaf(w)
    x = jnp.asarray(rng.standard_normal((2, 3, 128)).astype(np.float32))
    got = IK.int8_dot(x, q)                            # 6 rows -> pad to 8
    assert got.shape == (2, 3, 128)
    want = x @ q.dequant().astype(x.dtype)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-4, rtol=1e-4)


def _stack(rng, layers, k, n):
    w = rng.standard_normal((layers, k, n)).astype(np.float32) * 0.02
    return _quantize_leaf(jnp.asarray(w))


def _slice_of(stack, i):
    return QuantizedTensor(stack.q[i], stack.s[i], stack.dtype)


def _views_dot(x, stack, how):
    """int8_dot on a view of every layer: [L, m, N]. The index is a Python
    int, or the traced counter of a lax.scan (the engines' case)."""
    layers = stack.q.shape[0]
    if how == "int":
        return jnp.stack([IK.int8_dot(x, QuantizedLayerView(stack, i))
                          for i in range(layers)])
    return jax.jit(lambda x, stack: jax.lax.scan(
        lambda c, i: (c, IK.int8_dot(x, QuantizedLayerView(stack, i))),
        0, jnp.arange(layers, dtype=jnp.int32))[1])(x, stack)


@pytest.mark.parametrize("how", ["int", "scan"])
@pytest.mark.parametrize("stripes", [1, 4])
@pytest.mark.parametrize("m", [8, 16])
def test_stacked_kernel_is_the_2d_kernel_on_the_slice(
        interpret_kernel, monkeypatch, m, stripes, how):
    """The stacked form (layer index prefetched, DMA from the [L, K, N]
    stack) gives BIT FOR BIT what the 2-D kernel gives on that layer's
    slice, at every layer index: the same tiles in the same order."""
    k, n = 512, 256
    if stripes > 1:
        # Small enough that a 128-row stripe is all that fits: K runs in
        # several grid steps through the f32 accumulator.
        monkeypatch.setattr(IK, "VMEM_BUDGET", 300_000)
    IK._make_kernel.cache_clear()
    assert k // IK._tiles(n, k, m, 4)[1] == stripes
    monkeypatch.setattr(IK, "_sites", {})
    rng = np.random.default_rng(10 * m + stripes)
    stack = _stack(rng, 3, k, n)
    x = jnp.asarray(rng.standard_normal((m, k)).astype(np.float32))
    try:
        got = np.asarray(_views_dot(x, stack, how))
        assert IK._sites[(m, k, n)].startswith("pallas stacked tn=")
        for i in range(3):
            want = IK.int8_dot(x, _slice_of(stack, i))
            np.testing.assert_array_equal(got[i], np.asarray(want))
        assert IK._sites[(m, k, n)].startswith("pallas tn=")
    finally:
        IK._make_kernel.cache_clear()     # tiles planned at a test budget


@pytest.mark.parametrize("how", ["int", "scan"])
@pytest.mark.parametrize("shape", [(256, 384), (100, 96)],
                         ids=["aligned", "odd"])
def test_view_fallback_is_int8_dot_on_the_sliced_leaf(monkeypatch, shape,
                                                      how):
    """No Pallas (plain CPU, or a shape the kernel does not cover): a
    view is sliced where it is used and takes the XLA fold, bit for bit
    what int8_dot gives on the leaf a scan over the stack hands out."""
    k, n = shape
    monkeypatch.setattr(IK, "_sites", {})
    rng = np.random.default_rng(k)
    stack = _stack(rng, 3, k, n)
    x = jnp.asarray(rng.standard_normal((2, 3, k)).astype(np.float32))
    got = np.asarray(_views_dot(x, stack, how))
    assert IK._sites == {(8, k, n): "xla"}
    for i in range(3):
        want = IK.int8_dot(x, _slice_of(stack, i))
        np.testing.assert_array_equal(got[i], np.asarray(want))


@pytest.mark.parametrize("fold", ["1", "0"])
def test_dequant_tree_treats_a_view_as_the_2d_leaf(monkeypatch, fold):
    """INT8_FOLD=1 passes a view on as it is (as a 2-D leaf); INT8_FOLD=0
    materializes the layer: the values of the sliced leaf's dequant."""
    monkeypatch.setenv("INT8_FOLD", fold)
    stack = _stack(np.random.default_rng(5), 3, 64, 32)
    view = QuantizedLayerView(stack, 1)
    assert view.shape == (64, 32) and view.dtype == stack.dtype
    out = dequant_tree({"attn": {"wo": view}})["attn"]["wo"]
    if fold == "1":
        assert out is view
    else:
        np.testing.assert_array_equal(
            np.asarray(out), np.asarray(_slice_of(stack, 1).dequant()))


def test_xla_fallback_never_materializes_and_is_close():
    """Shapes the kernel does not cover (odd K/N, non-TPU backend) take
    the XLA mixed-dtype dot — STILL the scale-folded epilogue, never a
    materialized weight — and stay within accumulation noise of the
    dequant reference."""
    rng = np.random.default_rng(2)
    w = jnp.asarray(rng.standard_normal((100, 96)).astype(np.float32)
                    * 0.02)
    q = _quantize_leaf(w)
    x = jnp.asarray(rng.standard_normal((4, 100)).astype(np.float32))
    got = IK.int8_dot(x, q)                            # CPU: XLA fold
    want = x @ q.dequant().astype(x.dtype)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-4, rtol=1e-4)


def test_epilogue_fold_is_exact_per_channel():
    """The algebra the whole round rests on: scaling a column AFTER the
    K-reduction equals scaling its weights before — checked column-wise
    in f64 where both orders are exact."""
    rng = np.random.default_rng(3)
    q = rng.integers(-127, 128, (64, 32)).astype(np.int8)
    s = rng.uniform(0.5, 2.0, (1, 32)).astype(np.float32)
    x = rng.standard_normal((4, 64))
    before = x @ (q.astype(np.float64) * s)
    after = (x @ q.astype(np.float64)) * s
    np.testing.assert_allclose(after, before, rtol=1e-12)


def test_dequant_tree_keeps_2d_int8_only_under_fold(monkeypatch):
    """INT8_FOLD=1 (default): per-layer 2-D int8 leaves stay packed for
    the matmul sites; stacked 3-D leaves still materialize (the scan
    carries the stack, the per-layer slice is what reaches _dot).
    INT8_FOLD=0 is the kill switch: everything materializes."""
    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.models import (
        init_params,
        llama_config,
    )

    cfg = llama_config(vocab_size=128, hidden_size=64, num_layers=2,
                       num_heads=4, num_kv_heads=2, intermediate_size=128,
                       max_position_embeddings=32)
    params = quantize_params(init_params(jax.random.PRNGKey(0), cfg),
                             "int8")
    layer0 = jax.tree.map(lambda a: a[0], params["layers"])

    monkeypatch.setenv("INT8_FOLD", "0")
    assert not int8_fold_enabled()
    out = dequant_tree(layer0)
    assert not any(isinstance(v, QuantizedTensor)
                   for v in jax.tree.leaves(out, is_leaf=lambda v:
                                            isinstance(v, QuantizedTensor)))

    monkeypatch.setenv("INT8_FOLD", "1")
    assert int8_fold_enabled()
    out = dequant_tree(layer0)
    kept = [v for v in jax.tree.leaves(out, is_leaf=lambda v:
                                       isinstance(v, QuantizedTensor))
            if isinstance(v, QuantizedTensor)]
    assert kept, "2-D int8 leaves should stay packed under the fold"
    stacked = dequant_tree(params["layers"])   # 3-D: must materialize
    assert not any(isinstance(v, QuantizedTensor)
                   for v in jax.tree.leaves(stacked, is_leaf=lambda v:
                                            isinstance(v, QuantizedTensor)))


def test_fused_layers_concat_quantized_exactly():
    """fuse_qkv_layers / fuse_gate_up_layers fire on quantized trees and
    the fused leaf dequantizes BITWISE to the concat of the parts — the
    launch-aggregation transform must be a pure layout change."""
    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.models import (
        init_params,
        llama_config,
    )
    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.models.transformer import (
        fuse_gate_up_layers,
        fuse_qkv_layers,
    )

    cfg = llama_config(vocab_size=128, hidden_size=64, num_layers=2,
                       num_heads=4, num_kv_heads=2, intermediate_size=128,
                       max_position_embeddings=32)
    params = init_params(jax.random.PRNGKey(0), cfg)
    for mode, cls in (("int8", QuantizedTensor), ("nf4", NF4Tensor)):
        ql = quantize_params(params, mode)["layers"]
        fused = fuse_gate_up_layers(fuse_qkv_layers(ql))
        assert isinstance(fused["attn"]["wqkv"], cls)
        assert isinstance(fused["mlp"]["wgu"], cls)
        want_qkv = jnp.concatenate(
            [ql["attn"][k].dequant() for k in ("wq", "wk", "wv")], axis=-1)
        np.testing.assert_array_equal(
            np.asarray(fused["attn"]["wqkv"].dequant()),
            np.asarray(want_qkv))
        want_gu = jnp.concatenate(
            [ql["mlp"][k].dequant() for k in ("wg", "wu")], axis=-1)
        np.testing.assert_array_equal(
            np.asarray(fused["mlp"]["wgu"].dequant()),
            np.asarray(want_gu))
        # idempotent / mixed-type guard still no-ops
        assert fuse_qkv_layers(fused) is fused
        mixed = dict(ql, attn=dict(ql["attn"], wq=params["layers"]["attn"]
                                   ["wq"][0]))
        assert fuse_qkv_layers(mixed) is mixed


def test_fold_kill_switch_token_parity(monkeypatch):
    """The batched serving engine emits the SAME greedy tokens with the
    epilogue fold on (packed leaves -> int8_dot) and off (round-5
    dequant-materialize) — the fold changes bandwidth, not tokens."""
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
    params = quantize_params(init_params(jax.random.PRNGKey(0), cfg),
                             "int8")
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

    monkeypatch.setenv("INT8_FOLD", "1")
    fold = serve()
    monkeypatch.setenv("INT8_FOLD", "0")
    base = serve()
    assert fold == base
