"""Pipelined training step vs single-device oracle on the virtual CPU mesh.

The reference's training path (vendored ``rpc_backward``,
``petals/server/handler.py:434-488``) was never runnable; here the full
loss/grad/AdamW step is jitted over the ("stage"[, "tp"]) mesh and must match
the unpartitioned loss + gradients exactly.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.models import (
    gpt2_config,
    init_params,
    llama_config,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.parallel.trainer import (
    PipelineTrainer,
    single_device_loss,
    softmax_xent,
)


def tiny_cfg():
    return llama_config(vocab_size=251, hidden_size=64, num_layers=8,
                        num_heads=4, num_kv_heads=2, intermediate_size=128,
                        max_position_embeddings=64)


def make_batch(cfg, m, b, t, seed=0):
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, cfg.vocab_size, size=(m, b, t)).astype(np.int32)
    # next-token targets with the final position masked out
    targets = np.concatenate(
        [ids[..., 1:], np.full((m, b, 1), -1, np.int32)], axis=-1
    )
    return jnp.asarray(ids), jnp.asarray(targets)


@pytest.mark.parametrize("num_stages,num_micro,tp", [(4, 2, 1), (2, 1, 2), (8, 2, 1)])
def test_pipeline_loss_matches_oracle(num_stages, num_micro, tp):
    cfg = tiny_cfg()
    params = init_params(jax.random.PRNGKey(0), cfg)
    ids, targets = make_batch(cfg, num_micro, 2, 16)

    oracle = float(jax.jit(lambda p: single_device_loss(
        cfg, p, ids, targets))(params))

    mesh_devs = jax.devices()[: num_stages * tp]
    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.parallel.pipeline import (
        make_pipeline_mesh,
    )

    mesh = make_pipeline_mesh(num_stages, mesh_devs, tp=tp)
    tr = PipelineTrainer.build(cfg, params, num_stages=num_stages,
                               num_micro=num_micro, mesh=mesh, tp=tp, lr=0.0)
    loss = tr.step(ids, targets)
    np.testing.assert_allclose(loss, oracle, rtol=2e-4)


def test_pipeline_grads_match_oracle():
    cfg = tiny_cfg()
    params = init_params(jax.random.PRNGKey(1), cfg)
    num_stages, num_micro = 4, 2
    ids, targets = make_batch(cfg, num_micro, 1, 12, seed=3)

    # Oracle grads w.r.t. a replicated scalar knob: scale every layer weight.
    # Comparing full grad trees across the stacked [S, L/S] layout is fiddly;
    # instead compare d(loss)/d(embed wte) — it feeds every stage (stage-0
    # input AND tied/untied head) so any backward-schedule bug corrupts it.
    def oracle_loss(wte):
        p2 = dict(params)
        p2["embed"] = dict(params["embed"], wte=wte)
        return single_device_loss(cfg, p2, ids, targets)

    g_oracle = jax.jit(jax.grad(oracle_loss))(params["embed"]["wte"])

    tr = PipelineTrainer.build(cfg, params, num_stages=num_stages,
                               num_micro=num_micro, lr=0.0)

    # lr=0: step() computes grads but leaves params unchanged; recover the
    # embed grad from the AdamW first-moment buffer (mu = (1-b1)*g after one
    # step from zero init).
    tr.step(ids, targets)
    mu = tr.opt_state["mu"]["embed"]["wte"]
    g_pipe = np.asarray(mu) / 0.1  # (1 - b1) with b1=0.9
    np.testing.assert_allclose(
        g_pipe, np.asarray(g_oracle), rtol=2e-3, atol=2e-5
    )


@pytest.mark.parametrize("num_stages,num_micro,virtual", [
    (4, 4, 2),     # classic Megatron shape: V=2 chunks per device
    (2, 2, 4),     # deep interleave on a short pipeline
])
def test_interleaved_loss_matches_oracle(num_stages, num_micro, virtual):
    """Interleaved virtual-stage schedule (VERDICT r3 item 7): same loss as
    the unpartitioned oracle — the chunk rotation + wrap-edge parking must
    be pure scheduling, invisible in the math."""
    cfg = tiny_cfg()
    params = init_params(jax.random.PRNGKey(4), cfg)
    ids, targets = make_batch(cfg, num_micro, 2, 12, seed=11)

    oracle = float(jax.jit(lambda p: single_device_loss(
        cfg, p, ids, targets))(params))
    tr = PipelineTrainer.build(cfg, params, num_stages=num_stages,
                               num_micro=num_micro, lr=0.0,
                               virtual_stages=virtual)
    loss = tr.step(ids, targets)
    np.testing.assert_allclose(loss, oracle, rtol=2e-4)


def test_interleaved_grads_match_oracle():
    """AD's mirrored backward through the interleaved schedule: the embed
    grad (feeds stage-0 input AND the tied/untied head) matches the
    unpartitioned gradient."""
    cfg = tiny_cfg()
    params = init_params(jax.random.PRNGKey(5), cfg)
    num_stages, num_micro, virtual = 2, 2, 2
    ids, targets = make_batch(cfg, num_micro, 1, 10, seed=13)

    def oracle_loss(wte):
        p2 = dict(params)
        p2["embed"] = dict(params["embed"], wte=wte)
        return single_device_loss(cfg, p2, ids, targets)

    g_oracle = jax.jit(jax.grad(oracle_loss))(params["embed"]["wte"])
    tr = PipelineTrainer.build(cfg, params, num_stages=num_stages,
                               num_micro=num_micro, lr=0.0,
                               virtual_stages=virtual)
    tr.step(ids, targets)
    g_pipe = np.asarray(tr.opt_state["mu"]["embed"]["wte"]) / 0.1
    np.testing.assert_allclose(
        g_pipe, np.asarray(g_oracle), rtol=2e-3, atol=2e-5
    )


def test_interleaved_rejects_too_few_microbatches():
    cfg = tiny_cfg()
    params = init_params(jax.random.PRNGKey(0), cfg)
    with pytest.raises(ValueError, match="num_micro >= num_stages"):
        PipelineTrainer.build(cfg, params, num_stages=4, num_micro=2,
                              virtual_stages=2)


def test_training_reduces_loss():
    cfg = gpt2_config(vocab_size=128, hidden_size=32, num_layers=4,
                      num_heads=4, intermediate_size=64,
                      max_position_embeddings=32)
    params = init_params(jax.random.PRNGKey(2), cfg)
    ids, targets = make_batch(cfg, 2, 2, 16, seed=7)
    tr = PipelineTrainer.build(cfg, params, num_stages=2, num_micro=2, lr=3e-3)
    first = tr.step(ids, targets)
    for _ in range(10):
        last = tr.step(ids, targets)
    assert last < first * 0.8, (first, last)


def test_softmax_xent_ignores_masked():
    logits = jnp.zeros((1, 1, 4, 8))
    targets = jnp.array([[[1, 2, -1, -1]]], dtype=jnp.int32)
    # uniform logits -> loss = log(8) over the 2 valid positions
    np.testing.assert_allclose(
        float(softmax_xent(logits, targets)), float(np.log(8.0)), rtol=1e-6
    )


def test_checkpoint_resume_matches_uninterrupted(tmp_path):
    """Save mid-training, rebuild a FRESH trainer from the same init,
    restore, continue: the loss trajectory must equal an uninterrupted run
    (weights + optimizer moments + step count all round-trip)."""
    cfg = tiny_cfg()
    params = init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    batches = [
        (rng.integers(0, cfg.vocab_size, (2, 1, 8)).astype(np.int32),
         rng.integers(0, cfg.vocab_size, (2, 1, 8)).astype(np.int32))
        for _ in range(4)
    ]

    tr_a = PipelineTrainer.build(cfg, params, num_stages=2, num_micro=2,
                                 lr=3e-3)
    losses_a = [tr_a.step(jnp.asarray(i), jnp.asarray(t)) for i, t in batches]

    tr_b = PipelineTrainer.build(cfg, params, num_stages=2, num_micro=2,
                                 lr=3e-3)
    for i, t in batches[:2]:
        tr_b.step(jnp.asarray(i), jnp.asarray(t))
    ckpt = str(tmp_path / "trainer.npz")
    tr_b.save(ckpt)

    tr_c = PipelineTrainer.build(cfg, params, num_stages=2, num_micro=2,
                                 lr=3e-3)
    tr_c.restore(ckpt)
    losses_c = [tr_c.step(jnp.asarray(i), jnp.asarray(t))
                for i, t in batches[2:]]
    np.testing.assert_allclose(losses_c, losses_a[2:], rtol=1e-6)


def test_checkpoint_restore_rejects_mismatched_tree(tmp_path):
    cfg = tiny_cfg()
    params = init_params(jax.random.PRNGKey(0), cfg)
    tr = PipelineTrainer.build(cfg, params, num_stages=2, num_micro=2)
    ckpt = str(tmp_path / "t.npz")
    tr.save(ckpt)
    cfg2 = dataclasses.replace(cfg, num_layers=cfg.num_layers // 2)
    params2 = init_params(jax.random.PRNGKey(0), cfg2)
    tr2 = PipelineTrainer.build(cfg2, params2, num_stages=2, num_micro=2)
    with pytest.raises(ValueError):
        tr2.restore(ckpt)


def test_checkpoint_cross_pipeline_depth_and_bf16(tmp_path):
    """A checkpoint saved at pp=2 resumes at pp=4 (layers saved
    stage-merged), and bf16 leaves survive the npz round trip."""
    cfg = tiny_cfg()
    params = jax.tree.map(lambda x: x.astype(jnp.bfloat16),
                          init_params(jax.random.PRNGKey(0), cfg))
    tr2 = PipelineTrainer.build(cfg, params, num_stages=2, num_micro=2,
                                lr=3e-3)
    ids, targets = make_batch(cfg, 2, 1, 8, seed=3)
    tr2.step(ids, targets)
    ckpt = str(tmp_path / "pp2.npz")
    tr2.save(ckpt)

    tr4 = PipelineTrainer.build(cfg, params, num_stages=4, num_micro=2,
                                lr=3e-3)
    tr4.restore(ckpt)
    # The restored pp=4 trainer holds the SAME weights: next-step losses on
    # identical data agree closely (schedule differs, math is identical up
    # to reduction order).
    l2 = tr2.step(ids, targets)
    l4 = tr4.step(ids, targets)
    np.testing.assert_allclose(l4, l2, rtol=2e-2)
