"""Batch>1 final-stage sampling: every row samples from its OWN logits.

Round-1 `_sample_last` read `logits[0]` only — a batch-B non-beam session
silently sampled row 0 for all rows. `_sample_rows` fixes that: per-row
sampling with a row-decorrelated seed fold, row 0 bit-identical to the
historical single-row path (reference sampler semantics:
``src/rpc_handler.py:268-307``).
"""

import jax
import jax.numpy as jnp
import numpy as np

from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.models import (
    init_kv_cache,
    init_params,
)
from engines import full_forward
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.models.partition import (
    ROLE_FULL,
    StageSpec,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.ops.sampling import (
    RECENT_WINDOW,
    SamplingParams,
    sample_token,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime.executor import (
    _sample_rows,
)
from engines import stage_executor as StageExecutor
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime.messages import (
    StageRequest,
)

from engines import tiny_cfg


def full_spec(cfg):
    return StageSpec(index=0, role=ROLE_FULL, start=0, end=cfg.num_layers)


PROMPTS = np.asarray(
    [[5, 9, 23, 7, 81],
     [44, 2, 3, 19, 6],
     [100, 11, 12, 13, 14]], np.int32)


def batch_logits(cfg, params):
    b, t = PROMPTS.shape
    kc, vc = init_kv_cache(cfg, cfg.num_layers, b, 32)
    logits, _, _ = full_forward(cfg, params, jnp.asarray(PROMPTS), kc, vc,
                                jnp.int32(0))
    return logits  # [B, T, V]


def test_greedy_batch_rows_sample_their_own_logits():
    cfg = tiny_cfg()
    params = init_params(jax.random.PRNGKey(0), cfg)
    ex = StageExecutor(cfg, full_spec(cfg), params)
    resp = ex.forward(StageRequest(
        session_id="s", hidden=jnp.asarray(PROMPTS),
        seq_len=PROMPTS.shape[1], cur_len=0, is_prefill=True, max_length=32,
        sampling=SamplingParams(temperature=0.0)))
    logits = batch_logits(cfg, params)
    want = [int(t) for t in np.asarray(jnp.argmax(logits[:, -1], axis=-1))]
    assert resp.token_ids is not None and len(resp.token_ids) == 3
    assert list(resp.token_ids) == want
    assert resp.token_id == want[0]
    # The rows genuinely differ for these prompts — the old row-0-only bug
    # would have failed this.
    assert len(set(want)) > 1


def test_sampled_batch_parity_with_per_row_oracle():
    """temperature>0: row i's token equals sampling row i's logits with the
    fold-in(seed, i) key (row 0 uses the unfolded key — bit-identical to the
    batch-1 path)."""
    cfg = tiny_cfg()
    params = init_params(jax.random.PRNGKey(1), cfg)
    sp = SamplingParams(temperature=0.9, top_p=0.95, top_k=40,
                        repetition_penalty=1.2)
    logits = batch_logits(cfg, params)
    seed = 1234
    generated = (7, 7, 9)
    req = StageRequest(
        session_id="s", hidden=jnp.asarray(PROMPTS),
        seq_len=PROMPTS.shape[1], cur_len=0, is_prefill=True, max_length=32,
        sampling=sp, generated_tokens=generated, step_seed=seed)
    rows = _sample_rows(logits.astype(jnp.float32), PROMPTS.shape[1], req)

    recent = np.zeros((RECENT_WINDOW,), np.int32)
    recent[:len(generated)] = generated
    base = jax.random.PRNGKey(seed)
    for i in range(PROMPTS.shape[0]):
        rng = base if i == 0 else jax.random.fold_in(base, i)
        want = int(sample_token(
            rng, logits[i, -1].astype(jnp.float32),
            jnp.asarray(recent), jnp.asarray(len(generated), jnp.int32),
            jnp.asarray(sp.temperature, jnp.float32),
            jnp.asarray(sp.top_p, jnp.float32),
            jnp.asarray(sp.top_k, jnp.int32),
            jnp.asarray(sp.repetition_penalty, jnp.float32)))
        assert int(rows[i]) == want, i


def test_batch1_token_ids_absent():
    cfg = tiny_cfg()
    params = init_params(jax.random.PRNGKey(2), cfg)
    ex = StageExecutor(cfg, full_spec(cfg), params)
    resp = ex.forward(StageRequest(
        session_id="s", hidden=jnp.asarray(PROMPTS[:1]),
        seq_len=PROMPTS.shape[1], cur_len=0, is_prefill=True, max_length=32,
        sampling=SamplingParams(temperature=0.0)))
    assert resp.token_ids is None and resp.token_id is not None
