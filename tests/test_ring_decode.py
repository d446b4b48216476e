"""Multi-session ring decode vs per-session oracle on the virtual CPU mesh.

The rotation schedule (stage s advances session group (t - s) mod G at tick
t, sampled tokens riding the wrap edge back to stage 0) must be
token-identical to decoding every session independently on one device —
the whole point is filling the decode bubble WITHOUT changing results.
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
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.parallel.ring_decode import (
    RingDecoder,
    ring_generate,
)


def tiny_cfg():
    return llama_config(vocab_size=257, hidden_size=64, num_layers=8,
                        num_heads=4, num_kv_heads=2, intermediate_size=128,
                        max_position_embeddings=64)


def oracle_greedy(cfg, params, prompt, n_tokens, max_len=48):
    """Single-session unpartitioned greedy loop (fp32 argmax)."""
    kc, vc = init_kv_cache(cfg, cfg.num_layers, 1, max_len)
    ids = jnp.asarray(np.asarray(prompt, np.int32)[None])
    logits, kc, vc = full_forward(cfg, params, ids, kc, vc, jnp.int32(0))
    toks = []
    cur = len(prompt)
    tok = int(jnp.argmax(logits[0, -1].astype(jnp.float32)))
    toks.append(tok)
    for _ in range(n_tokens - 1):
        logits, kc, vc = full_forward(
            cfg, params, jnp.asarray([[tok]], jnp.int32), kc, vc,
            jnp.int32(cur))
        cur += 1
        tok = int(jnp.argmax(logits[0, -1].astype(jnp.float32)))
        toks.append(tok)
    return toks


def _prompts(rng, g, b, t, vocab):
    return rng.integers(0, vocab, (g, b, t)).astype(np.int32)


@pytest.mark.parametrize("num_stages,num_groups,slot_b", [
    (4, 4, 1),    # G == S: token consumed the tick it arrives (no buffer)
    (4, 6, 1),    # G > S: wrap tokens park in the buffer for G-S ticks
    (2, 2, 2),    # slot-batched session groups
])
def test_ring_decode_matches_per_session_oracle(num_stages, num_groups,
                                                slot_b):
    cfg = tiny_cfg()
    params = init_params(jax.random.PRNGKey(0), cfg)
    pipe = IciPipeline.build(cfg, params, num_stages, num_micro=num_groups)
    rd = RingDecoder.build(pipe, max_steps=16)

    rng = np.random.default_rng(3)
    t, n_tokens = 5, 8
    ids = _prompts(rng, num_groups, slot_b, t, cfg.vocab_size)
    k, v = pipe.init_kv(slot_b, max_len=48)
    toks = np.asarray(
        ring_generate(pipe, rd, jnp.asarray(ids), k, v, n_tokens))

    for g in range(num_groups):
        for b in range(slot_b):
            ref = oracle_greedy(cfg, params, ids[g, b], n_tokens)
            assert toks[:, g, b].tolist() == ref, (
                f"session (g={g}, b={b}) diverged: ring "
                f"{toks[:, g, b].tolist()} vs oracle {ref}")


def test_ring_decode_chunked_matches_single_call():
    """Two 3-step chunks must equal one 6-step call — lens/token carry is
    exact across chunk boundaries (the stop-condition check point)."""
    cfg = tiny_cfg()
    params = init_params(jax.random.PRNGKey(1), cfg)
    S, G, B, t = 4, 4, 1, 4
    pipe = IciPipeline.build(cfg, params, S, num_micro=G)
    rd = RingDecoder.build(pipe, max_steps=8)
    rng = np.random.default_rng(7)
    ids = jnp.asarray(_prompts(rng, G, B, t, cfg.vocab_size))

    k, v = pipe.init_kv(B, max_len=48)
    logits, k, v = pipe.forward(ids, k, v, jnp.int32(0))
    tok0 = jnp.argmax(
        logits[:, :, -1].astype(jnp.float32), -1).astype(jnp.int32)
    lens = jnp.full((G,), t, jnp.int32)

    k1, v1 = jax.tree.map(jnp.copy, (k, v))
    one, _, _ = rd.decode(tok0, k1, v1, lens, 6)

    k2, v2 = jax.tree.map(jnp.copy, (k, v))
    a, k2, v2 = rd.decode(tok0, k2, v2, lens, 3)
    b_, _, _ = rd.decode(a[2], k2, v2, lens + 3, 3)

    got = np.concatenate([np.asarray(a[:3]), np.asarray(b_[:3])])
    np.testing.assert_array_equal(got, np.asarray(one[:6]))


def test_ring_decode_with_tensor_parallel_stages():
    """pp x tp composition: 2 stages x 2-way TP on 4 devices, 2 session
    groups — the ring carry and the per-stage psums must coexist."""
    cfg = tiny_cfg()
    params = init_params(jax.random.PRNGKey(2), cfg)
    pipe = IciPipeline.build(cfg, params, num_stages=2, num_micro=2, tp=2)
    rd = RingDecoder.build(pipe, max_steps=8)
    rng = np.random.default_rng(11)
    ids = _prompts(rng, 2, 1, 4, cfg.vocab_size)
    k, v = pipe.init_kv(1, max_len=32)
    toks = np.asarray(
        ring_generate(pipe, rd, jnp.asarray(ids), k, v, 6))
    for g in range(2):
        ref = oracle_greedy(cfg, params, ids[g, 0], 6, max_len=32)
        assert toks[:, g, 0].tolist() == ref


def test_ring_continuous_batching_replaces_one_group():
    """A finished session's group slot is re-prefilled between chunks while
    the OTHER groups' caches stay live: the joined session must match a
    fresh oracle on its new prompt, and the survivors must keep producing
    exactly their original oracle continuations."""
    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.parallel.ring_decode import (
        make_ring_prefill_group,
    )

    cfg = tiny_cfg()
    params = init_params(jax.random.PRNGKey(4), cfg)
    S, G, B, t = 2, 3, 1, 4
    pipe = IciPipeline.build(cfg, params, S, num_micro=G)
    rd = RingDecoder.build(pipe, max_steps=8)
    prefill_one = make_ring_prefill_group(pipe)

    rng = np.random.default_rng(13)
    ids = _prompts(rng, G, B, t, cfg.vocab_size)
    k, v = pipe.init_kv(B, max_len=48)
    logits, k, v = pipe.forward(jnp.asarray(ids), k, v, jnp.int32(0))
    tok0 = jnp.argmax(
        logits[:, :, -1].astype(jnp.float32), -1).astype(jnp.int32)
    lens = jnp.full((G,), t, jnp.int32)

    # chunk 1: 3 steps for everyone
    a, k, v = rd.decode(tok0, k, v, lens, 3)
    lens = lens + 3

    # "session in group 1 finished": re-prefill its slot with a NEW prompt
    new_prompt = rng.integers(0, cfg.vocab_size, (B, 5)).astype(np.int32)
    ntok0, k, v = prefill_one(jnp.asarray(new_prompt), k, v, 1)
    lens = lens.at[1].set(5)
    tok1 = a[2].at[1].set(ntok0)   # group 1 restarts from its new token

    # chunk 2: 4 more steps
    b_, k, v = rd.decode(tok1, k, v, lens, 4)

    # survivors (groups 0, 2): tokens across both chunks == their oracle
    for g in (0, 2):
        ref = oracle_greedy(cfg, params, ids[g, 0], 8)
        got = ([int(tok0[g, 0])] + np.asarray(a[:3, g, 0]).tolist()
               + np.asarray(b_[:4, g, 0]).tolist())
        assert got[:8] == ref, f"survivor group {g} diverged"

    # joined session: new-prompt oracle
    refj = oracle_greedy(cfg, params, new_prompt[0], 5)
    gotj = [int(ntok0[0])] + np.asarray(b_[:4, 1, 0]).tolist()
    assert gotj == refj, "re-prefilled group diverged from fresh oracle"


def test_ring_decode_rejects_fewer_groups_than_stages():
    cfg = tiny_cfg()
    params = init_params(jax.random.PRNGKey(0), cfg)
    pipe = IciPipeline.build(cfg, params, num_stages=4, num_micro=2)
    with pytest.raises(ValueError, match="sessions >= stages"):
        RingDecoder.build(pipe)


# ---------------------------------------------------------------------------
# Sampled ring decode: the full reference sampler inside the rotation
# ---------------------------------------------------------------------------

def _sp_args(sp):
    return (jnp.asarray(sp.temperature, jnp.float32),
            jnp.asarray(sp.top_p, jnp.float32),
            jnp.asarray(sp.top_k, jnp.int32),
            jnp.asarray(sp.repetition_penalty, jnp.float32))


def oracle_sampled(cfg, params, prompt, n_tokens, seed, sp, row=0,
                   max_len=48):
    """Single-session unpartitioned SAMPLED loop with the fused sampled
    engine's exact key schedule: token i uses PRNGKey(seed + i), row > 0
    folds the row index (executor._sample_rows contract)."""
    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.ops.sampling import (
        make_recent_buffer,
        push_recent,
        sample_token,
    )

    def key(i):
        base = jax.random.PRNGKey(seed + i)
        return base if row == 0 else jax.random.fold_in(base, row)

    args = _sp_args(sp)
    kc, vc = init_kv_cache(cfg, cfg.num_layers, 1, max_len)
    ids = jnp.asarray(np.asarray(prompt, np.int32)[None])
    logits, kc, vc = full_forward(cfg, params, ids, kc, vc, jnp.int32(0))
    recent, nvalid = make_recent_buffer()
    tok = sample_token(key(0), logits[0, -1], recent, nvalid, *args)
    recent, nvalid = push_recent(recent, nvalid, tok)
    toks = [int(tok)]
    cur = len(prompt)
    for i in range(1, n_tokens):
        logits, kc, vc = full_forward(
            cfg, params, jnp.asarray([[toks[-1]]], jnp.int32), kc, vc,
            jnp.int32(cur))
        cur += 1
        tok = sample_token(key(i), logits[0, -1], recent, nvalid, *args)
        recent, nvalid = push_recent(recent, nvalid, tok)
        toks.append(int(tok))
    return toks


@pytest.mark.parametrize("num_stages,num_groups,slot_b", [
    (4, 4, 1),    # batch-1 fast path (unfolded key)
    (2, 3, 2),    # vmapped rows with folded keys
])
def test_ring_sampled_matches_per_session_oracle(num_stages, num_groups,
                                                 slot_b):
    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.ops.sampling import (
        RECENT_WINDOW,
        SamplingParams,
        push_recent,
        sample_token,
    )

    cfg = tiny_cfg()
    params = init_params(jax.random.PRNGKey(0), cfg)
    S, G, B = num_stages, num_groups, slot_b
    pipe = IciPipeline.build(cfg, params, S, num_micro=G)
    rd = RingDecoder.build(pipe, max_steps=16, sampled=True)
    sp = SamplingParams(temperature=0.8, top_p=0.9, top_k=20,
                        repetition_penalty=1.5)
    seed = 11
    args = _sp_args(sp)

    rng = np.random.default_rng(5)
    t, n_tokens = 5, 8
    ids = _prompts(rng, G, B, t, cfg.vocab_size)
    k, v = pipe.init_kv(B, max_len=48)
    logits, k, v = pipe.forward(jnp.asarray(ids), k, v, jnp.int32(0))

    # First token per session: key schedule step 0 on the prefill logits.
    tok0 = np.zeros((G, B), np.int32)
    recent = np.zeros((G, B, RECENT_WINDOW), np.int32)
    nvalid = np.zeros((G, B), np.int32)
    for g in range(G):
        for b in range(B):
            base = jax.random.PRNGKey(seed)
            kb = base if b == 0 else jax.random.fold_in(base, b)
            tok = sample_token(kb, logits[g, b, -1].astype(jnp.float32),
                               jnp.asarray(recent[g, b]),
                               jnp.asarray(nvalid[g, b]), *args)
            r2, n2 = push_recent(jnp.asarray(recent[g, b]),
                                 jnp.asarray(nvalid[g, b]), tok)
            tok0[g, b] = int(tok)
            recent[g, b], nvalid[g, b] = np.asarray(r2), int(n2)

    lens = jnp.full((G,), t, jnp.int32)
    toks, k, v, recent2, nvalid2 = rd.decode_sampled(
        jnp.asarray(tok0), k, v, lens, n_tokens - 1,
        seed_base=jnp.full((G,), seed + 1, jnp.int32),
        recent=jnp.asarray(recent), nvalid=jnp.asarray(nvalid),
        temps=jnp.full((G,), sp.temperature, jnp.float32),
        top_ps=jnp.full((G,), sp.top_p, jnp.float32),
        top_ks=jnp.full((G,), sp.top_k, jnp.int32),
        reps=jnp.full((G,), sp.repetition_penalty, jnp.float32))
    toks = np.asarray(toks)

    for g in range(G):
        for b in range(B):
            ref = oracle_sampled(cfg, params, ids[g, b], n_tokens, seed, sp,
                                 row=b)
            got = [int(tok0[g, b])] + toks[: n_tokens - 1, g, b].tolist()
            assert got == ref, (
                f"sampled session (g={g}, b={b}) diverged: ring {got} "
                f"vs oracle {ref}")
    # Sampler state threads out for chunked continuation.
    assert np.asarray(nvalid2).min() == n_tokens


def test_ring_sampled_chunked_matches_single_call():
    """Sampler state (recent window + key schedule offset) must thread
    exactly across chunk boundaries."""
    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.ops.sampling import (
        RECENT_WINDOW,
        SamplingParams,
    )

    cfg = tiny_cfg()
    params = init_params(jax.random.PRNGKey(1), cfg)
    S, G, B, t = 2, 2, 1, 4
    pipe = IciPipeline.build(cfg, params, S, num_micro=G)
    rd = RingDecoder.build(pipe, max_steps=8, sampled=True)
    sp = SamplingParams(temperature=0.7, top_p=0.95, top_k=40,
                        repetition_penalty=1.3)
    seed = 23
    rng = np.random.default_rng(9)
    ids = jnp.asarray(_prompts(rng, G, B, t, cfg.vocab_size))

    k, v = pipe.init_kv(B, max_len=48)
    logits, k, v = pipe.forward(ids, k, v, jnp.int32(0))
    tok0 = jnp.argmax(
        logits[:, :, -1].astype(jnp.float32), -1).astype(jnp.int32)
    lens = jnp.full((G,), t, jnp.int32)
    recent0 = jnp.zeros((G, B, RECENT_WINDOW), jnp.int32)
    nvalid0 = jnp.zeros((G, B), jnp.int32)
    kw = dict(temps=jnp.full((G,), sp.temperature, jnp.float32),
              top_ps=jnp.full((G,), sp.top_p, jnp.float32),
              top_ks=jnp.full((G,), sp.top_k, jnp.int32),
              reps=jnp.full((G,), sp.repetition_penalty, jnp.float32))

    k1, v1 = jax.tree.map(jnp.copy, (k, v))
    one, _, _, _, _ = rd.decode_sampled(
        tok0, k1, v1, lens, 6, seed_base=jnp.full((G,), seed, jnp.int32),
        recent=recent0, nvalid=nvalid0, **kw)

    k2, v2 = jax.tree.map(jnp.copy, (k, v))
    a, k2, v2, r2, n2 = rd.decode_sampled(
        tok0, k2, v2, lens, 3, seed_base=jnp.full((G,), seed, jnp.int32),
        recent=recent0, nvalid=nvalid0, **kw)
    b_, _, _, _, _ = rd.decode_sampled(
        a[2], k2, v2, lens + 3, 3,
        seed_base=jnp.full((G,), seed + 3, jnp.int32), recent=r2,
        nvalid=n2, **kw)

    got = np.concatenate([np.asarray(a[:3]), np.asarray(b_[:3])])
    np.testing.assert_array_equal(got, np.asarray(one[:6]))


# ---------------------------------------------------------------------------
# Ring x speculative: drafted tokens ride the rotation, verified in-program
# ---------------------------------------------------------------------------

def test_ring_spec_round_greedy_output_independent_of_drafts():
    """The speculative invariant: greedy output must be token-identical to
    plain greedy decoding for ANY draft quality — perfect drafts (all
    accepted, K+1 tokens/round), garbage drafts (all rejected, 1
    token/round), and anything between only change the SPEED."""
    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.ops.sampling import (
        RECENT_WINDOW,
    )
    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.parallel.ring_decode import (
        make_ring_spec_round,
    )

    cfg = tiny_cfg()
    params = init_params(jax.random.PRNGKey(0), cfg)
    S, G, K, t, n_tokens = 2, 3, 3, 4, 8
    pipe = IciPipeline.build(cfg, params, S, num_micro=G)
    round_fn = make_ring_spec_round(pipe, K)

    rng = np.random.default_rng(2)
    ids = _prompts(rng, G, 1, t, cfg.vocab_size)
    refs = [oracle_greedy(cfg, params, ids[g, 0], n_tokens)
            for g in range(G)]

    k, v = pipe.init_kv(1, max_len=48)
    logits, k, v = pipe.forward(jnp.asarray(ids), k, v, jnp.int32(0))
    tok0 = np.asarray(jnp.argmax(
        logits[:, :, -1].astype(jnp.float32), -1)).astype(np.int32)

    sessions = [[int(tok0[g, 0])] for g in range(G)]
    lens = np.full((G,), t, np.int32)
    recent = jnp.zeros((G, 1, RECENT_WINDOW), jnp.int32)
    nvalid = jnp.zeros((G, 1), jnp.int32)
    kw = dict(temps=jnp.zeros((G,), jnp.float32),       # greedy
              top_ps=jnp.full((G,), 0.9, jnp.float32),
              top_ks=jnp.full((G,), 20, jnp.int32),
              reps=jnp.full((G,), 1.3, jnp.float32))
    rounds = 0
    while any(len(s) < n_tokens for s in sessions):
        tokens_in = np.zeros((G, 1, K + 1), np.int32)
        for g in range(G):
            done = len(sessions[g])
            tokens_in[g, 0, 0] = sessions[g][-1]
            if g == 0:      # perfect drafts: the oracle's next tokens
                fut = refs[g][done:done + K]
                tokens_in[g, 0, 1:1 + len(fut)] = fut
            elif g == 1:    # garbage drafts (all-rejected path)
                tokens_in[g, 0, 1:] = (np.asarray(refs[g][:K]) + 7) % 257
            else:           # half-decent drafts: first right, rest wrong
                fut = refs[g][done:done + 1]
                tokens_in[g, 0, 1:1 + len(fut)] = fut
        toks, nacc, k, v, recent, nvalid = round_fn(
            tokens_in, k, v, lens, seed_base=np.full((G,), 5, np.int32),
            recent=recent, nvalid=nvalid, **kw)
        toks, nacc = np.asarray(toks), np.asarray(nacc)
        rounds += 1
        for g in range(G):
            if len(sessions[g]) >= n_tokens:
                continue
            na = int(nacc[g, 0])
            sessions[g].extend(int(x) for x in toks[g, 0, : na + 1])
            lens[g] += na + 1
        assert rounds < 4 * n_tokens, "spec rounds failed to make progress"

    for g in range(G):
        assert sessions[g][:n_tokens] == refs[g], (
            f"session {g} diverged under speculative rounds: "
            f"{sessions[g][:n_tokens]} vs {refs[g]}")
    # Perfect-draft session must have taken big strides (accept > 0).
    assert rounds < n_tokens, (
        "perfect drafts never accepted: rounds should be well under "
        "one-per-token")


def test_ring_decode_gemma2_embed_scale_and_semantics():
    """Regression for the hand-rolled embed that dropped gemma's
    sqrt(hidden) scale (fixed by routing through the shared embed_tokens):
    ring decode of a gemma2 config (embed scale, sandwich norms, softcaps,
    alternating per-layer windows) must match the per-session oracle."""
    from engines import tiny_cfg as shared_tiny_cfg

    cfg = shared_tiny_cfg("gemma2")  # 4 layers, biting softcaps, window=4
    params = init_params(jax.random.PRNGKey(2), cfg)
    S = G = 4
    pipe = IciPipeline.build(cfg, params, S, num_micro=G)
    rd = RingDecoder.build(pipe, max_steps=16)
    rng = np.random.default_rng(9)
    ids = _prompts(rng, G, 1, 5, cfg.vocab_size)
    k, v = pipe.init_kv(1, max_len=48)
    toks = np.asarray(
        ring_generate(pipe, rd, jnp.asarray(ids), k, v, 8))
    for g in range(G):
        ref = oracle_greedy(cfg, params, ids[g, 0], 8)
        assert toks[:, g, 0].tolist() == ref, g
