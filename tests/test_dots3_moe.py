"""One chip's share of dots3-note-prev's expert layer (sigmoid-routed
experts beside a shared one, ``routed_scaling_factor`` 1, no group limit)
against the benchmark's plain reference
(``perfbench/references/dots3_plain.py``): at 32 experts in 4 shares of 8
the shares' routed parts plus the shared expert counted ONCE add up to the
uncut reference layer, behind a full layer and behind a sliding one alike
(the expert layer does not know which attention sits before it)."""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.models import (
    config,
    hf_import,
    moe,
)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
D, F, E, K = 32, 16, 32, 8
TYPES = list(config.dots3_layer_types(5))
HF = dict(
    model_type="dots3_note", hidden_size=D, intermediate_size=64,
    moe_intermediate_size=F, num_attention_heads=2, q_lora_rank=16,
    kv_lora_rank=8, qk_nope_head_dim=4, qk_rope_head_dim=4, v_head_dim=8,
    rope_theta=8e7, attention_gate_type="headwise", index_n_heads=2,
    index_head_dim=8, index_topk=8, sliding_window_size=9,
    swa_num_attention_heads=2, swa_q_lora_rank=16, swa_kv_lora_rank=12,
    swa_qk_nope_head_dim=4, swa_qk_rope_head_dim=4, swa_v_head_dim=8,
    swa_rope_theta=5e4, swa_attention_gate_type="headwise",
    apply_mla_qkv_lora_rescale=True, layer_types=TYPES, n_routed_experts=E,
    num_experts_per_tok=K, n_shared_experts=1, vocab_size=31,
    first_k_dense_replace=1, rms_norm_eps=1e-5, routed_scaling_factor=1.0,
    experts_held=E)


def _ref():
    spec = importlib.util.spec_from_file_location(
        "dots3_plain", os.path.join(ROOT, "perfbench", "references",
                                    "dots3_plain.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _ref()


def cfg_holding(first, count):
    return config.dots3_config(
        TYPES, vocab_size=31, hidden_size=D, num_layers=5, num_heads=2,
        intermediate_size=64, q_lora_rank=16, kv_lora_rank=8,
        qk_nope_head_dim=4, qk_rope_head_dim=4, v_head_dim=8,
        index_n_heads=2, index_head_dim=8, index_topk=8,
        sliding_window_size=9, swa_num_heads=2, swa_q_lora_rank=16,
        swa_kv_lora_rank=12, swa_qk_nope_head_dim=4, swa_qk_rope_head_dim=4,
        swa_v_head_dim=8, n_routed_experts=E, num_experts_per_tok=K,
        moe_intermediate_size=F, first_k_dense=1,
        experts_held=(first, count))


@pytest.fixture(scope="module")
def weights():
    return REF.make_weights(HF, 5, 5, jnp.float32)


def rows(n, seed=0):
    return jnp.asarray(np.random.default_rng(seed).standard_normal(
        (1, n, D)), jnp.float32)


@pytest.mark.parametrize("layer", [1, 3])       # behind a full, a sliding
@pytest.mark.parametrize("n", [8, 300])         # one dense round; compacted
def test_the_shares_add_up_to_the_uncut_layer(weights, layer, n):
    """32 experts in 4 shares of 8: every share routes over all 32 and
    computes its own 8; their routed parts and ONE shared expert are the
    whole layer."""
    assert TYPES[layer] == ("full_attention" if layer == 1
                            else "sliding_attention")
    prefix = f"model.layers.{layer}.mlp."
    x = rows(n, layer)
    w = lambda name: weights[name].astype(jnp.float32)
    whole = np.asarray(REF.expert_layer(HF, w, prefix, x[0], held=(0, E)))
    shared = np.asarray(REF.expert_layer(HF, w, prefix, x[0], held=(0, 0)))
    total, seen = shared.copy(), np.zeros((n, E), bool)
    for first in range(0, E, 8):
        cfg = cfg_holding(first, 8)
        p = hf_import._glm5_layer(weights, layer, cfg)["mlp"]
        assert p["wg"].shape == (8, D, F) and p["router"].shape == (D, E)
        y, assigned = jax.jit(lambda p, x, cfg=cfg: moe.held_moe_mlp(
            cfg, p, x))(p, x)
        want = np.asarray(REF.expert_layer(HF, w, prefix, x[0],
                                           held=(first, 8)))
        np.testing.assert_allclose(np.asarray(y)[0], want, atol=2e-5)
        total += np.asarray(y)[0] - shared         # its routed part alone
        seen[:, first:first + 8] = np.asarray(assigned)
    np.testing.assert_allclose(total, whole, atol=5e-5)
    assert (seen.sum(-1) == K).all()             # every choice is somewhere


def test_routing_is_sigmoid_plus_bias_normalised_with_no_scale(weights):
    prefix = "model.layers.2.mlp."
    x = rows(40, 2)
    cfg = cfg_holding(0, E)
    p = hf_import._glm5_layer(weights, 2, cfg)["mlp"]
    topi, w = moe.route_sigmoid(cfg, p, x[0])
    score = jax.nn.sigmoid(x[0] @ weights[prefix + "gate.weight"].T)
    bias = weights[prefix + "gate.e_score_correction_bias"]
    assert float(jnp.abs(bias).max()) > 0                      # no no-op
    want = np.argsort(-np.asarray(score + bias), axis=-1)[:, :K]
    assert (np.sort(np.asarray(topi), -1) == np.sort(want, -1)).all()
    picked = np.take_along_axis(np.asarray(score), np.asarray(topi), -1)
    np.testing.assert_allclose(
        np.asarray(w), picked / picked.sum(-1, keepdims=True), rtol=1e-5)
    dense = np.asarray(REF.routing(
        HF, lambda n: weights[n].astype(jnp.float32), prefix, x[0]))
    assert ((dense > 0).sum(-1) == K).all()
    np.testing.assert_allclose(dense.sum(-1), 1.0, rtol=1e-5)


def test_the_reference_s_share_is_a_sixteenth_unless_told():
    assert REF.held_experts(dict(HF, n_routed_experts=256)) == (0, E)
    hf = {k: v for k, v in HF.items() if k != "experts_held"}
    assert REF.held_experts(dict(hf, n_routed_experts=256)) == (0, 16)
    assert REF.layer_counts(HF, 5) == (2, 3)
    assert REF.layer_counts(dict(HF, layer_types=list(
        config.dots3_layer_types(9))), 9) == (3, 6)
