"""Distributed beam search vs a single-device oracle.

Servers must reorder per-session KV rows by hypo_ids before each step
(petals backend.py:154-158) and the final stage returns top-N logprobs; the
client's beam bookkeeping then has to match an unpartitioned implementation
token-for-token, including after mid-search failover (journal replay must
re-apply recorded reorders in order).
"""

import jax
import jax.numpy as jnp
import numpy as np

from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.models import (
    init_kv_cache,
)
from engines import full_forward
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.ops.sampling import (
    SamplingParams,
)

from engines import build_cluster, oracle_generate, tiny_cfg


def oracle_beam(cfg, params, prompt_ids, max_new_tokens, num_beams,
                length_penalty=1.0, eos_token_id=None, max_len=64):
    """Unpartitioned beam search with the same candidate policy (top-2B)."""
    nb = num_beams
    topn = 2 * nb
    prompt_len = len(prompt_ids)
    kc, vc = init_kv_cache(cfg, cfg.num_layers, nb, max_len)
    ids = jnp.broadcast_to(
        jnp.asarray(np.asarray(prompt_ids, np.int32))[None, :],
        (nb, prompt_len),
    )
    logits, kc, vc = full_forward(cfg, params, ids, kc, vc, jnp.int32(0))
    logp = jax.nn.log_softmax(logits[:, prompt_len - 1].astype(jnp.float32), -1)
    vals, idx = jax.lax.top_k(logp, topn)
    beams = [[int(t)] for t in np.asarray(idx[0][:nb])]
    scores = [float(s) for s in np.asarray(vals[0][:nb])]
    parents = [0] * nb
    finished = []
    cur_len = prompt_len

    def norm(score, length):
        return score / (max(length, 1) ** length_penalty)

    for _ in range(1, max_new_tokens):
        order = jnp.asarray(parents, jnp.int32)
        kc = jnp.take(kc, order, axis=1)
        vc = jnp.take(vc, order, axis=1)
        step = jnp.asarray(np.asarray([b[-1] for b in beams], np.int32)[:, None])
        logits, kc, vc = full_forward(cfg, params, step, kc, vc,
                                      jnp.int32(cur_len))
        cur_len += 1
        logp = jax.nn.log_softmax(logits[:, 0].astype(jnp.float32), -1)
        vals, idx = jax.lax.top_k(logp, topn)
        vals, idx = np.asarray(vals), np.asarray(idx)
        cands = []
        for i in range(nb):
            for j in range(topn):
                cands.append((scores[i] + float(vals[i, j]), i, int(idx[i, j])))
        cands.sort(key=lambda c: c[0], reverse=True)
        new_beams, new_scores, new_parents = [], [], []
        for score, parent, tok in cands:
            if eos_token_id is not None and tok == eos_token_id:
                finished.append((norm(score, len(beams[parent]) + 1),
                                 beams[parent] + [tok]))
                continue
            new_beams.append(beams[parent] + [tok])
            new_scores.append(score)
            new_parents.append(parent)
            if len(new_beams) == nb:
                break
        beams, scores, parents = new_beams, new_scores, new_parents
        if finished and len(finished) >= nb:
            if max(f[0] for f in finished) >= norm(max(scores), len(beams[0])):
                break

    for score, beam in zip(scores, beams):
        finished.append((norm(score, len(beam)), beam))
    finished.sort(key=lambda f: f[0], reverse=True)
    return finished[0][1], finished[0][0]


def test_beam_matches_oracle():
    cfg = tiny_cfg()
    client, _, _, params, _ = build_cluster(cfg, splits="2,4,6")
    prompt = [5, 9, 23, 7, 81]
    res = client.beam_search(prompt, max_new_tokens=6, num_beams=3)
    ref_tokens, ref_score = oracle_beam(cfg, params, prompt, 6, 3)
    assert res.tokens == ref_tokens
    np.testing.assert_allclose(res.score, ref_score, rtol=1e-4)


def test_beam_one_equals_greedy_prefix():
    cfg = tiny_cfg()
    client, _, _, params, _ = build_cluster(cfg, splits="2,4,6")
    prompt = [11, 3, 42]
    res = client.beam_search(prompt, max_new_tokens=6, num_beams=1)
    greedy = oracle_generate(cfg, params, prompt, 6,
                             SamplingParams(temperature=0.0))
    # greedy oracle may stop early on the 5-repeat rule; compare the overlap
    n = min(len(res.tokens), len(greedy))
    assert res.tokens[:n] == greedy[:n]


def test_beam_failover_replays_hypo_reorders():
    """Kill the pinned middle server mid-search: the replacement rebuilds its
    KV from the journal INCLUDING the recorded hypo reorders, so the final
    hypothesis must be identical to the undisturbed run."""
    cfg = tiny_cfg()
    client, transport, _, params, _ = build_cluster(cfg, splits="2,4,6",
                                                    replicas=2)
    prompt = [5, 9, 23, 7, 81]
    ref_tokens, _ = oracle_beam(cfg, params, prompt, 6, 3)

    seen = [0]

    def on_call(peer_id, req):
        if not req.is_prefill and not req.is_replay and "s2" in peer_id:
            seen[0] += 1
            if seen[0] == 3:
                transport.kill(peer_id)

    transport.on_call = on_call
    res = client.beam_search(prompt, max_new_tokens=6, num_beams=3)
    assert res.tokens == ref_tokens
    assert client.recoveries >= 1


def test_beam_sessions_freed():
    cfg = tiny_cfg()
    client, transport, _, _, _ = build_cluster(cfg, splits="2,4,6")
    client.beam_search([5, 9, 23], max_new_tokens=4, num_beams=2)
    for p in transport.peers():
        assert transport.executor(p).arena.active_sessions() == ()
    assert client.stage0.arena.active_sessions() == ()


def test_beam_prefill_runs_once_at_batch1():
    """The prompt must be prefilled at batch 1 (the first decode step's
    (0,)*nb reorder expands KV to num_beams rows) — not num_beams times."""
    cfg = tiny_cfg()
    client, transport, _, params, _ = build_cluster(cfg, splits="2,4,6")
    prefill_batches = []

    def on_call(peer_id, req):
        if req.is_prefill:
            prefill_batches.append(np.asarray(req.hidden).shape[0])

    transport.on_call = on_call
    res = client.beam_search([5, 9, 23, 7, 81], max_new_tokens=6, num_beams=3)
    assert prefill_batches and all(b == 1 for b in prefill_batches)
    ref_tokens, _ = oracle_beam(cfg, params, [5, 9, 23, 7, 81], 6, 3)
    assert res.tokens == ref_tokens


def test_beam_arena_accounting_balanced_after_expansion():
    """Batch growth via resize_batch must be returned in full on free()."""
    cfg = tiny_cfg()
    client, transport, _, _, _ = build_cluster(cfg, splits="2,4,6")
    client.beam_search([5, 9, 23], max_new_tokens=5, num_beams=4)
    for p in transport.peers():
        assert transport.executor(p).arena.used_bytes == 0
    assert client.stage0.arena.used_bytes == 0


def test_beam_failover_with_coalesced_journal():
    """With a tiny journal bound, reorder-carrying entries must coalesce by
    permutation composition and still replay to the exact same KV: kill a
    middle server late in the search and require oracle-identical output."""
    cfg = tiny_cfg()
    client, transport, _, params, _ = build_cluster(cfg, splits="2,4,6",
                                                    replicas=2)
    client.journal_max_entries = 2  # force composition merges every step
    prompt = [5, 9, 23, 7, 81]
    ref_tokens, _ = oracle_beam(cfg, params, prompt, 8, 3)

    seen = [0]

    def on_call(peer_id, req):
        if not req.is_prefill and not req.is_replay and "s2" in peer_id:
            seen[0] += 1
            if seen[0] == 5:
                transport.kill(peer_id)

    transport.on_call = on_call
    res = client.beam_search(prompt, max_new_tokens=8, num_beams=3)
    assert res.tokens == ref_tokens
    assert client.recoveries >= 1
    for entries in client.journal.values():
        for lst in entries.values():
            assert len(lst) <= 3  # bound holds despite per-step reorders


def test_hypo_ids_out_of_range_rejected():
    """jnp.take clamps silently; the executor must range-check instead."""
    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime.executor import (
        StageExecutionError,
    )
    from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime.messages import (
        StageRequest,
    )

    cfg = tiny_cfg()
    client, transport, _, _, _ = build_cluster(cfg, splits="2,4,6")
    ex = transport.executor(transport.peers()[0])
    hid = np.zeros((2, 3, cfg.hidden_size), np.float32)
    ex.forward(StageRequest(session_id="s", hidden=jnp.asarray(hid),
                            seq_len=3, cur_len=0, is_prefill=True,
                            max_length=16))
    step = np.zeros((2, 1, cfg.hidden_size), np.float32)
    try:
        ex.forward(StageRequest(session_id="s", hidden=jnp.asarray(step),
                                seq_len=1, cur_len=3, is_prefill=False,
                                max_length=16, hypo_ids=(0, 5)))
        raised = False
    except StageExecutionError:
        raised = True
    assert raised
