"""One chip's share of a sigmoid-routed expert layer
(``models.moe.held_moe_mlp``) against the benchmark's plain reference
(``perfbench/references/glm5_plain.py``): THE SHARE TEST (at 32 experts in
4 shares of 8, the shares' routed parts plus the shared expert counted once
add up to the uncut reference layer), the routing itself, the drop-free
rounds of a long step under skewed routing, the int8 form of the held
stacks, and the Mixtral-shaped ``tp`` layer left as it was."""

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
    quant,
)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
E, K, D, F = 32, 8, 48, 24
HF = dict(
    model_type="glm_moe_dsa", hidden_size=D, intermediate_size=64,
    moe_intermediate_size=F, num_attention_heads=2, q_lora_rank=16,
    kv_lora_rank=8, qk_nope_head_dim=4, qk_rope_head_dim=4, v_head_dim=8,
    index_n_heads=2, index_head_dim=8, index_topk=8, n_routed_experts=E,
    num_experts_per_tok=K, n_shared_experts=1, vocab_size=31,
    first_k_dense_replace=0, rms_norm_eps=1e-5, routed_scaling_factor=2.5,
    rope_parameters={"rope_theta": 1e6}, experts_held=E)
PREFIX = "model.layers.0.mlp."


def _ref():
    spec = importlib.util.spec_from_file_location(
        "glm5_plain", os.path.join(ROOT, "perfbench", "references",
                                   "glm5_plain.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _ref()


def cfg_holding(first, count):
    return config.glm5_config(
        vocab_size=31, hidden_size=D, num_layers=1, num_heads=2,
        intermediate_size=64, q_lora_rank=16, kv_lora_rank=8,
        qk_nope_head_dim=4, qk_rope_head_dim=4, v_head_dim=8,
        index_n_heads=2, index_head_dim=8, index_topk=8, n_routed_experts=E,
        num_experts_per_tok=K, moe_intermediate_size=F, first_k_dense=0,
        experts_held=(first, count))


@pytest.fixture(scope="module")
def weights():
    return REF.make_weights(HF, 1, 5, jnp.float32)


def layer_of(weights, first, count):
    """The importer's tree of the one layer, as a chip that holds experts
    ``first .. first + count - 1`` reads the checkpoint."""
    return hf_import._glm5_layer(weights, 0, cfg_holding(first, count))["mlp"]


def rows(n, seed=0):
    return jnp.asarray(np.random.default_rng(seed).standard_normal(
        (1, n, D)), jnp.float32)


@pytest.mark.parametrize("n", [8, 300])      # one dense round; compacted
def test_the_shares_add_up_to_the_uncut_layer(weights, n):
    """32 experts in 4 shares of 8: every share routes over all 32 and
    computes its own 8; their routed parts and ONE shared expert are the
    whole layer."""
    x = rows(n)
    w = lambda name: weights[name].astype(jnp.float32)
    whole = np.asarray(REF.expert_layer(HF, w, PREFIX, x[0], held=(0, E)))
    shared = np.asarray(REF.expert_layer(HF, w, PREFIX, x[0], held=(0, 0)))
    total, seen = shared.copy(), np.zeros((n, E), bool)
    for first in range(0, E, 8):
        cfg = cfg_holding(first, 8)
        y, assigned = jax.jit(lambda p, x, cfg=cfg: moe.held_moe_mlp(
            cfg, p, x))(layer_of(weights, first, 8), x)
        want = np.asarray(REF.expert_layer(HF, w, PREFIX, x[0],
                                           held=(first, 8)))
        np.testing.assert_allclose(np.asarray(y)[0], want, atol=2e-5)
        total += np.asarray(y)[0] - shared         # its routed part alone
        seen[:, first:first + 8] = np.asarray(assigned)
    np.testing.assert_allclose(total, whole, atol=5e-5)
    assert (seen.sum(-1) == K).all()             # every choice is somewhere


def test_routing_is_sigmoid_plus_bias_normalised_and_scaled(weights):
    x = rows(40, 2)
    cfg = cfg_holding(0, E)
    p = layer_of(weights, 0, E)
    topi, w = moe.route_sigmoid(cfg, p, x[0])
    score = jax.nn.sigmoid(x[0] @ weights[PREFIX + "gate.weight"].T)
    bias = weights[PREFIX + "gate.e_score_correction_bias"]
    assert float(jnp.abs(bias).max()) > 0                      # no no-op
    want = np.argsort(-np.asarray(score + bias), axis=-1)[:, :K]
    assert (np.sort(np.asarray(topi), -1) == np.sort(want, -1)).all()
    # ... but the WEIGHTS are the scores without the bias
    picked = np.take_along_axis(np.asarray(score), np.asarray(topi), -1)
    np.testing.assert_allclose(
        np.asarray(w), 2.5 * picked / picked.sum(-1, keepdims=True),
        rtol=1e-5)
    dense = np.asarray(REF.routing(
        HF, lambda n: weights[n].astype(jnp.float32), PREFIX, x[0]))
    assert ((dense > 0).sum(-1) == K).all()
    np.testing.assert_allclose(dense.sum(-1), 2.5, rtol=1e-5)


def test_a_long_step_under_skew_takes_more_rounds_and_drops_nothing(
        weights, monkeypatch):
    """Every row sent to the same experts: the fullest held expert has all
    the step's rows, four times a round's slots, and every one is served."""
    monkeypatch.setattr(moe, "MOE_ROUND_ROWS", 16)
    cfg = cfg_holding(0, 8)
    p = dict(layer_of(weights, 0, 8))
    skew = np.zeros((E,), np.float32)
    skew[[0, 3, 9, 10, 11, 12, 13, 14]] = 4.0      # two held, six elsewhere
    p["router_bias"] = jnp.asarray(skew)
    x = rows(64, 3)
    y, assigned = jax.jit(lambda p, x: moe.held_moe_mlp(cfg, p, x))(p, x)
    assert np.asarray(assigned)[:, [0, 3]].all()
    assert np.asarray(assigned).sum() == 2 * 64
    biased = dict(weights)
    biased[PREFIX + "gate.e_score_correction_bias"] = jnp.asarray(skew)
    want = REF.expert_layer(HF, lambda n: biased[n].astype(jnp.float32),
                            PREFIX, x[0], held=(0, 8))
    np.testing.assert_allclose(np.asarray(y)[0], np.asarray(want), atol=5e-5)


def test_held_stacks_run_quantised(weights):
    cfg = cfg_holding(0, 8)
    p = layer_of(weights, 0, 8)
    x = rows(8, 4)
    want, _ = moe.held_moe_mlp(cfg, p, x)
    q = quant.dequant_tree(quant.quantize_layers(p, "int8"),
                           keep_experts=True)
    assert isinstance(q["wg"], quant.QuantizedTensor)          # stays packed
    assert isinstance(q["shared"]["wd"], quant.QuantizedTensor)
    assert q["router"].dtype == jnp.float32                # full precision
    got, _ = moe.held_moe_mlp(cfg, q, x)
    err = float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))
    assert 1e-4 < err < 0.05


def test_the_mixtral_shaped_layer_is_as_it_was():
    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.models.transformer import (
        _mlp,
        init_layer_params,
    )
    cfg = config.mixtral_config(
        vocab_size=31, hidden_size=32, num_layers=1, num_heads=2,
        num_kv_heads=2, intermediate_size=16, num_experts=4)
    lp = init_layer_params(jax.random.PRNGKey(0), cfg)
    x = rows(6, 5)[..., :32]
    assert "router_bias" not in lp["mlp"] and cfg.held_experts == (0, 4)
    sparse = _mlp(cfg, lp["mlp"], x, None)
    dense = moe.sparse_moe_mlp(cfg, lp["mlp"], x, None)
    np.testing.assert_allclose(np.asarray(sparse), np.asarray(dense))
