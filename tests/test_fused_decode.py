"""Fused multi-step decode (runtime.fused_decode): token parity with the
per-step full_forward oracle — the fused engine must generate exactly
what serving generates (greedy)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.models import (
    init_kv_cache,
    init_params,
)
from engines import full_forward
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime.fused_decode import (
    make_fused_decode,
)

from engines import tiny_cfg


@pytest.mark.parametrize("family", ["llama", "gpt2", "gemma2"])
@pytest.mark.parametrize("batch", [1, 4])
def test_fused_decode_matches_oracle(family, batch):
    cfg = tiny_cfg(family)
    params = init_params(jax.random.PRNGKey(0), cfg)
    prefill, steps, max_len = 5, 7, 32
    rng = np.random.default_rng(3)
    prompts = rng.integers(0, cfg.vocab_size, (batch, prefill)).astype(np.int32)

    # oracle: per-step full_forward greedy, one row at a time
    want = []
    for b in range(batch):
        kc, vc = init_kv_cache(cfg, cfg.num_layers, 1, max_len)
        logits, kc, vc = full_forward(cfg, params, jnp.asarray(prompts[b:b+1]),
                                      kc, vc, jnp.int32(0))
        toks = [int(jnp.argmax(logits[0, -1]))]
        cur = prefill
        for _ in range(steps - 1):
            logits, kc, vc = full_forward(
                cfg, params, jnp.asarray([[toks[-1]]], jnp.int32), kc, vc,
                jnp.int32(cur))
            toks.append(int(jnp.argmax(logits[0, -1])))
            cur += 1
        want.append(toks)

    # fused: one program for all steps, all rows
    kc, vc = init_kv_cache(cfg, cfg.num_layers, batch, max_len)
    logits, kc, vc = full_forward(cfg, params, jnp.asarray(prompts), kc, vc,
                                  jnp.int32(0))
    tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
    first = [int(t) for t in np.asarray(tok)]
    fn = make_fused_decode(cfg, steps - 1, batch)
    toks, kc, vc = fn(params, tok, kc, vc, jnp.int32(prefill),
                      jnp.int32(steps - 1))
    got = np.concatenate([np.asarray(first)[None], np.asarray(toks)], axis=0)
    for b in range(batch):
        assert list(got[:, b]) == want[b], b


def test_fused_decode_quantized_runs():
    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.models.quant import (
        quantize_params,
    )

    cfg = tiny_cfg()
    params = quantize_params(init_params(jax.random.PRNGKey(1), cfg), "int8")
    kc, vc = init_kv_cache(cfg, cfg.num_layers, 2, 16)
    fn = make_fused_decode(cfg, 3, 2)
    toks, _, _ = fn(params, jnp.zeros((2,), jnp.int32), kc, vc,
                    jnp.int32(1), jnp.int32(3))
    assert np.asarray(toks).shape == (3, 2)


def test_fused_sampled_decode_matches_per_token_oracle():
    """make_fused_sample_decode folds the FULL sampler into the scan with
    the per-token oracle's exact key schedule (PRNGKey(seed+step)) — output
    must be bit-identical to stepping full_forward + sample_token by
    hand."""
    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.ops.sampling import (
        RECENT_WINDOW,
        make_recent_buffer,
        push_recent,
        sample_token,
    )
    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime.fused_decode import (
        make_fused_sample_decode,
    )

    cfg = tiny_cfg()
    params = init_params(jax.random.PRNGKey(4), cfg)
    prompt = [5, 9, 23, 7]
    seed, steps = 77, 9
    sp = (jnp.asarray(0.9, jnp.float32), jnp.asarray(0.95, jnp.float32),
          jnp.asarray(40, jnp.int32), jnp.asarray(1.4, jnp.float32))

    # per-token oracle
    kc, vc = init_kv_cache(cfg, cfg.num_layers, 1, 32)
    ids = jnp.asarray(np.asarray(prompt, np.int32)[None, :])
    logits, kc, vc = full_forward(cfg, params, ids, kc, vc, jnp.int32(0))
    want = []
    for step in range(steps):
        recent = np.zeros((RECENT_WINDOW,), np.int32)
        n = min(len(want), RECENT_WINDOW)
        if n:
            recent[:n] = np.asarray(want[-n:], np.int32)
        src = logits[0, -1] if step == 0 else logits[0, 0]
        tok = int(sample_token(jax.random.PRNGKey(seed + step), src,
                               jnp.asarray(recent), jnp.asarray(n, jnp.int32),
                               *sp))
        want.append(tok)
        if step < steps - 1:
            logits, kc, vc = full_forward(
                cfg, params, jnp.asarray([[tok]], jnp.int32), kc, vc,
                jnp.int32(len(prompt) + step))

    # fused: first token by hand (schedule step 0), rest in ONE program
    kc, vc = init_kv_cache(cfg, cfg.num_layers, 1, 32)
    logits, kc, vc = full_forward(cfg, params, ids, kc, vc, jnp.int32(0))
    recent, nvalid = make_recent_buffer()
    tok0 = sample_token(jax.random.PRNGKey(seed), logits[0, -1], recent,
                        nvalid, *sp)
    recent, nvalid = push_recent(recent, nvalid, tok0)
    fn = make_fused_sample_decode(cfg, steps - 1)
    toks, kc, vc, recent, nvalid = fn(
        params, tok0, kc, vc, jnp.int32(len(prompt)), jnp.int32(steps - 1),
        jnp.int32(seed + 1), recent, nvalid, *sp)
    got = [int(tok0)] + [int(t) for t in np.asarray(toks[: steps - 1])]
    assert got == want
