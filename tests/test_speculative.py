"""Speculative decoding: drafting, final-stage verification, token parity.

No reference counterpart — this attacks the reference's dominant latency term
(one WAN round trip per generated token, SURVEY.md §3.2 hot loop 2): the
client drafts K tokens per round, the pipeline processes them as ONE
multi-token step, the final stage greedily verifies (executor._verify_drafts)
and the rejected tail is rolled back via the session-rewind mechanism
(petals ``start_from_position`` semantics reused as speculative rollback).

The invariant tested throughout: speculative greedy output is TOKEN-IDENTICAL
to non-speculative greedy output, for any draft quality.
"""

import random

import jax
import jax.numpy as jnp
import numpy as np

from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.models import (
    init_params,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.models.partition import (
    StagePlan,
    parse_splits,
    slice_stage_params,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.ops.sampling import (
    SamplingParams,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime.client import (
    PipelineClient,
    make_server_record,
)
from engines import stage_executor as StageExecutor
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime.speculative import (
    ngram_draft,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime.transport import (
    LocalTransport,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.scheduling.registry import (
    PlacementRegistry,
)

from engines import build_cluster, oracle_generate, tiny_cfg

GREEDY = SamplingParams(temperature=0.0)
PROMPT = [5, 9, 23, 7, 81]


def perfect_draft(oracle_tokens, prompt_len):
    """Draft fn that always proposes the model's true continuation."""

    def fn(context, k):
        pos = len(context) - prompt_len
        return tuple(oracle_tokens[pos:pos + k])

    return fn


def garbage_draft(vocab):
    rng = random.Random(123)

    def fn(context, k):
        return tuple(rng.randrange(vocab) for _ in range(k))

    return fn


# ---------------------------------------------------------------------------
# Drafter
# ---------------------------------------------------------------------------

def test_ngram_draft_basic_lookup():
    # suffix [1, 2] occurred earlier, followed by 3, 4.
    assert ngram_draft([1, 2, 3, 4, 9, 1, 2], 2) == (3, 4)


def test_ngram_draft_prefers_most_recent_match():
    # [7] occurs twice; the RECENT occurrence is followed by 5.
    assert ngram_draft([7, 1, 7, 5, 9, 7], 1, max_ngram=1) == (5,)


def test_ngram_draft_prefers_longer_ngrams():
    ctx = [1, 2, 9, 5, 2, 9, 7, 0, 2, 9]
    # 2-gram [2,9] matches at index 4 (recent), followed by 7, 0.
    assert ngram_draft(ctx, 2) == (7, 0)


def test_ngram_draft_no_match_and_caps():
    assert ngram_draft([1, 2, 3], 3) == ()            # no repeat at all
    assert ngram_draft([4, 4], 3, max_ngram=1) == (4,)  # only 1 follower
    assert ngram_draft([], 3) == ()
    assert ngram_draft([1, 2], 0) == ()


# ---------------------------------------------------------------------------
# End-to-end parity (the core invariant)
# ---------------------------------------------------------------------------

def test_speculative_matches_oracle_with_perfect_drafts():
    cfg = tiny_cfg()
    client, transport, _, params, _ = build_cluster(cfg, splits="4")
    ref = oracle_generate(cfg, params, PROMPT, 12, GREEDY)

    res = client.generate(
        PROMPT, max_new_tokens=12, sampling=GREEDY,
        speculative_k=4, draft_fn=perfect_draft(ref, len(PROMPT)),
    )
    assert res.tokens == ref
    # Perfect drafts: every round accepts K+1 tokens -> round trips collapse.
    # Non-speculative would need 12 remote calls; prefill(1) + ceil(11/5)=3.
    assert transport.calls <= 1 + 4


def test_speculative_matches_oracle_with_garbage_drafts():
    cfg = tiny_cfg()
    client, _, _, params, _ = build_cluster(cfg, splits="4")
    ref = oracle_generate(cfg, params, PROMPT, 10, GREEDY)
    res = client.generate(
        PROMPT, max_new_tokens=10, sampling=GREEDY,
        speculative_k=3, draft_fn=garbage_draft(cfg.vocab_size),
    )
    # All drafts rejected every round -> one real token per round, but the
    # rejected-overhang rollback must keep the KV consistent throughout.
    assert res.tokens == ref


def test_speculative_with_default_ngram_drafter():
    cfg = tiny_cfg("gpt2")
    # A repetitive prompt gives the n-gram drafter something to find.
    prompt = [3, 1, 4, 1, 5, 3, 1, 4]
    client, _, _, params, _ = build_cluster(cfg, splits="4")
    ref = oracle_generate(cfg, params, prompt, 10, GREEDY)
    res = client.generate(prompt, max_new_tokens=10, sampling=GREEDY,
                          speculative_k=3)
    assert res.tokens == ref


def test_speculative_multi_hop_pipeline():
    cfg = tiny_cfg()
    client, transport, _, params, _ = build_cluster(cfg, splits="2,4,6")
    ref = oracle_generate(cfg, params, PROMPT, 12, GREEDY)
    res = client.generate(
        PROMPT, max_new_tokens=12, sampling=GREEDY,
        speculative_k=4, draft_fn=perfect_draft(ref, len(PROMPT)),
    )
    assert res.tokens == ref
    # 3 hops x (prefill + 3 spec rounds) = 12 calls vs 36 non-speculative.
    assert transport.calls <= 3 * (1 + 3)


# (Round 1 rejected temperature>0 speculative decoding outright; round 2
# supports it via rejection-sampling verification — see the
# test_speculative_verify_* and test_speculative_generation_with_sampling_*
# tests below for the replacing coverage.)


def test_speculative_survives_failover():
    cfg = tiny_cfg()
    client, transport, registry, params, plan = build_cluster(
        cfg, splits="4", replicas=2)
    ref = oracle_generate(cfg, params, PROMPT, 12, GREEDY)

    res = None
    # Inject a transient failure on whichever peer actually serves the
    # session (captured from the first tapped call — the route is
    # affinity-keyed, so pre-computing client.route() could watch a
    # replica the generation never uses): the speculative round must fail
    # over, REPLAY the (amended) journal into the replica, and keep
    # producing oracle-identical tokens.
    done_prefill = {"n": 0, "peer": None}

    def tap(peer_id, req):
        done_prefill["n"] += 1
        if done_prefill["peer"] is None:
            done_prefill["peer"] = peer_id
        if done_prefill["n"] == 3:  # prefill + 1 spec round done; fail next
            transport.fail_next(done_prefill["peer"], 1)

    transport.on_call = tap
    res = client.generate(
        PROMPT, max_new_tokens=12, sampling=GREEDY,
        speculative_k=3, draft_fn=perfect_draft(ref, len(PROMPT)),
    )
    assert res.tokens == ref
    assert client.recoveries >= 1


def test_speculative_push_chain():
    cfg = tiny_cfg()
    params = init_params(jax.random.PRNGKey(0), cfg)
    plan = StagePlan.from_splits(cfg.num_layers, parse_splits("2,4,6"))
    transport = LocalTransport()
    registry = PlacementRegistry(rng=random.Random(0))
    for spec in plan.stages[1:]:
        peer = f"peer-s{spec.index}"
        ex = StageExecutor(cfg, spec, slice_stage_params(cfg, params, spec),
                           peer_id=peer)
        transport.add_peer(peer, ex)
        registry.register(make_server_record(peer, spec))
    stage0 = StageExecutor(cfg, plan.stages[0],
                           slice_stage_params(cfg, params, plan.stages[0]),
                           peer_id="client-local")
    client = PipelineClient(cfg, plan, stage0, transport, registry,
                            use_push_chain=True, settle_seconds=0.0, seed=0)
    ref = oracle_generate(cfg, params, PROMPT, 12, GREEDY)
    res = client.generate(
        PROMPT, max_new_tokens=12, sampling=GREEDY,
        speculative_k=4, draft_fn=perfect_draft(ref, len(PROMPT)),
    )
    assert res.tokens == ref


def test_speculative_eos_inside_accepted_run():
    cfg = tiny_cfg()
    client, _, _, params, _ = build_cluster(cfg, splits="4")
    ref = oracle_generate(cfg, params, PROMPT, 12, GREEDY)
    # Pick an "EOS" whose FIRST occurrence is past the first round, so it
    # lands mid-accepted-run (a token seen earlier would stop immediately).
    j = next(i for i in range(1, len(ref)) if ref[i] not in ref[:i])
    eos = ref[j]
    res = client.generate(
        PROMPT, max_new_tokens=12, sampling=GREEDY, eos_token_id=eos,
        speculative_k=4, draft_fn=perfect_draft(ref, len(PROMPT)),
    )
    # Generation must stop AT the EOS token even when it lands mid-round.
    assert res.tokens == ref[:j + 1]
    assert res.stopped_by == "eos"


def test_speculative_over_tcp_wire():
    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime.net import (
        TcpStageServer,
        TcpTransport,
    )

    cfg = tiny_cfg()
    params = init_params(jax.random.PRNGKey(0), cfg)
    plan = StagePlan.from_splits(cfg.num_layers, parse_splits("4"))
    registry = PlacementRegistry(rng=random.Random(0))
    spec = plan.stages[1]
    ex = StageExecutor(cfg, spec, slice_stage_params(cfg, params, spec),
                       peer_id="tcp-final")
    srv = TcpStageServer(ex, port=0, wire_dtype="f32")
    srv.start()
    try:
        rec = make_server_record("tcp-final", spec)
        rec.address = srv.address
        registry.register(rec)
        transport = TcpTransport(registry, wire_dtype="f32")
        stage0 = StageExecutor(cfg, plan.stages[0],
                               slice_stage_params(cfg, params, plan.stages[0]),
                               peer_id="client-local")
        client = PipelineClient(cfg, plan, stage0, transport, registry,
                                settle_seconds=0.0, seed=0)
        ref = oracle_generate(cfg, params, PROMPT, 10, GREEDY)
        res = client.generate(
            PROMPT, max_new_tokens=10, sampling=GREEDY,
            speculative_k=3, draft_fn=perfect_draft(ref, len(PROMPT)),
        )
        assert res.tokens == ref
        transport.close()
    finally:
        srv.stop()


# ---------------------------------------------------------------------------
# Rejection-sampling verification (temperature > 0)
# ---------------------------------------------------------------------------

def test_speculative_verify_accept_and_reject_paths():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.ops.sampling import (
        RECENT_WINDOW,
        speculative_verify,
    )

    V, K = 16, 3
    recent = np.zeros((RECENT_WINDOW,), np.int32)
    # logits put ~all mass on token 5 at every position
    logits = np.full((K + 1, V), -20.0, np.float32)
    logits[:, 5] = 20.0
    toks, n_acc = speculative_verify(
        jax.random.PRNGKey(0), jnp.asarray(logits), [5, 5, 5], recent, 0,
        0.8, 1.0, 0, 1.0)
    assert n_acc == K and toks[:K] == [5, 5, 5] and len(toks) == K + 1
    # draft 9 has ~zero mass -> rejected at position 0, correction != 9
    toks, n_acc = speculative_verify(
        jax.random.PRNGKey(1), jnp.asarray(logits), [9, 5, 5], recent, 0,
        0.8, 1.0, 0, 1.0)
    assert n_acc == 0 and len(toks) == 1 and toks[0] != 9


def test_speculative_verify_preserves_distribution():
    """The first output position's law must equal the target sampler's law
    regardless of what the (deterministic) draft proposed — the speculative
    sampling correctness property, checked empirically against the oracle
    sample_probs distribution."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.ops.sampling import (
        RECENT_WINDOW,
        sample_probs,
        speculative_verify,
    )

    V = 12
    rng = np.random.default_rng(7)
    logits = jnp.asarray(rng.standard_normal((2, V)).astype(np.float32) * 2)
    recent = np.zeros((RECENT_WINDOW,), np.int32)
    temp, top_p, top_k, rp = 0.9, 1.0, 0, 1.0
    target = np.asarray(sample_probs(
        logits[0], jnp.asarray(recent), jnp.asarray(0, jnp.int32),
        jnp.asarray(temp, jnp.float32), jnp.asarray(top_p, jnp.float32),
        jnp.asarray(top_k, jnp.int32), jnp.asarray(rp, jnp.float32)))
    draft = int(np.argmax(target))          # draft the LIKELIEST token —
    n = 1500                                # max acceptance bias if wrong
    counts = np.zeros(V)
    for s in range(n):
        toks, _ = speculative_verify(
            jax.random.PRNGKey(s), logits, [draft], recent, 0,
            temp, top_p, top_k, rp)
        counts[toks[0]] += 1
    emp = counts / n
    # ~3 sigma for a multinomial with n=1500: ~0.039 absolute. An
    # acceptance-bias bug shifts mass by O(p_draft) ~ 0.3 — far outside
    # this band; n=1500 keeps the check decisive at a third of the wall
    # cost of the original n=4000 (this was the single slowest test).
    np.testing.assert_allclose(emp, target, atol=0.045)


def test_speculative_generation_with_sampling_runs():
    """End-to-end: temperature>0 + speculative drafts through the pipeline
    generates without error (the output law matches non-speculative
    sampling by the verifier property; token equality is not expected —
    the randomness path differs)."""
    import jax

    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.models import (
        init_params,
    )
    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.ops.sampling import (
        SamplingParams,
    )

    from engines import build_cluster, tiny_cfg

    cfg = tiny_cfg()
    client, _, _, _, _ = build_cluster(cfg)
    res = client.generate([5, 9, 23, 7, 81], max_new_tokens=8,
                          sampling=SamplingParams(temperature=0.9),
                          speculative_k=3)
    assert 1 <= len(res.tokens) <= 8
    assert all(0 <= t < cfg.vocab_size for t in res.tokens)
