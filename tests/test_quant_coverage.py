"""Tier-1 wrapper for scripts/check_quant_coverage.py: every quant format
in models/quant.py::QUANT_BITS must have a token-parity test under tests/
and one on the MoE path — a new format cannot ship unverified on either
expert layout."""

import pathlib
import subprocess
import sys

REPO = pathlib.Path(__file__).resolve().parents[1]


def test_every_quant_format_has_parity_tests():
    proc = subprocess.run(
        [sys.executable,
         str(REPO / "scripts" / "check_quant_coverage.py")],
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0, (
        f"quant coverage drift:\n{proc.stdout}{proc.stderr}"
    )


# -- a latent family's leaves (GLM-5): every matmul weight is covered ------

def _glm5_tree():
    import jax
    import jax.numpy as jnp

    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.models import (
        config,
        transformer,
    )

    cfg = config.glm5_config(
        vocab_size=97, hidden_size=64, num_layers=3, num_heads=4,
        intermediate_size=96, max_position_embeddings=512, rope_theta=1e6,
        q_lora_rank=32, kv_lora_rank=16, qk_nope_head_dim=12,
        qk_rope_head_dim=4, v_head_dim=16, index_n_heads=2, index_head_dim=8,
        index_topk=16, n_routed_experts=8, num_experts_per_tok=2,
        moe_intermediate_size=32, first_k_dense=1, experts_held=(0, 4))
    return transformer.init_params(jax.random.PRNGKey(3), cfg, jnp.float32)


HEAD_LEAVES = ("wqb_t", "wkvb_t", "wiq_t")      # [heads, rows a head, in]


def test_int8_covers_a_latent_family_s_weights_as_they_rest():
    """`quantize_params(.., "int8")` packs every matmul weight of both
    layer stacks, the four that rest with the contracted axis last among
    them (three ``[heads, rows a head, in]``, ``wkva_t`` ``[out, in]``),
    and those take their scales along the LAST axis: one a published row,
    the numbers the turned-over ``[in, out]`` form had."""
    import jax.numpy as jnp
    import numpy as np

    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.models import (
        quant,
    )

    params = _glm5_tree()
    packed = quant.quantize_params(params, "int8")
    for stack in ("dense_layers", "layers"):
        attn, was = packed[stack]["attn"], params[stack]["attn"]
        for name in ("wqa", "wkva_t", "wo") + HEAD_LEAVES:
            assert isinstance(attn[name], quant.QuantizedTensor), name
        for name in ("q_norm", "kv_norm", "ik_norm"):
            assert not quant.is_quantized(attn[name]), name
        for name in HEAD_LEAVES:
            leaf, w = attn[name], was[name]
            layers, heads, rows, k = w.shape
            assert leaf.axis == -1 and leaf.q.shape == w.shape
            assert leaf.s.shape == (layers, heads, rows, 1)
            # the [in, out] form of the same matrix, as it was quantised
            flat = jnp.swapaxes(w.reshape(layers, heads * rows, k), -1, -2)
            kn = quant._quantize_leaf(flat)
            assert kn.axis == -2 and kn.s.shape == (layers, 1, heads * rows)
            np.testing.assert_array_equal(
                np.asarray(leaf.s).reshape(layers, -1),
                np.asarray(kn.s).reshape(layers, -1))
            np.testing.assert_array_equal(
                np.asarray(leaf.q).reshape(layers, heads * rows, k),
                np.swapaxes(np.asarray(kn.q), -1, -2))
            np.testing.assert_allclose(
                np.asarray(leaf.dequant()), np.asarray(w),
                atol=float(np.abs(np.asarray(w)).max()) / 127)
        assert attn["wqa"].axis == -2
        kva, w = attn["wkva_t"], was["wkva_t"]             # [out, in]
        assert kva.axis == -1 and kva.s.shape == w.shape[:-1] + (1,)
        np.testing.assert_array_equal(
            np.asarray(kva.s)[..., 0],
            np.asarray(quant._quantize_leaf(jnp.swapaxes(w, -1, -2)).s)[
                :, 0])
    # a view of a held stack hands the axis on with the layer
    view = quant.QuantizedLayerView(
        quant._quantize_leaf(params["layers"]["attn"]["wqa"]), 1)
    assert view.layer().axis == -2


def test_nf4_covers_a_latent_family_s_weights_as_they_rest():
    """NF4 packs the same leaves (blocks of 64 along axis -2, 4.25 bits a
    weight whichever axis that is) and hands their shapes back."""
    import numpy as np

    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.models import (
        quant,
    )

    params = _glm5_tree()
    packed = quant.quantize_params(params, "nf4")
    for stack in ("dense_layers", "layers"):
        for name in ("wqa", "wkva_t", "wo") + HEAD_LEAVES:
            leaf, w = packed[stack]["attn"][name], params[stack]["attn"][name]
            assert isinstance(leaf, quant.NF4Tensor), name
            back = np.asarray(leaf.dequant())
            assert back.shape == w.shape
            assert np.abs(back - np.asarray(w)).max() < 0.2 * np.abs(
                np.asarray(w)).max()


def test_the_int8_kernel_refuses_a_leaf_that_rests_out_by_in():
    import jax.numpy as jnp
    import pytest

    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.models import (
        quant,
    )
    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.ops.int8_kernel import (
        int8_dot,
    )

    leaf = quant._quantize_leaf(jnp.ones((16, 8), jnp.float32), axis=-1)
    with pytest.raises(ValueError, match="_dot_t"):
        int8_dot(jnp.ones((2, 8), jnp.float32), leaf)
