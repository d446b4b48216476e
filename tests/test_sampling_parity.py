"""The threshold sampler (ops.sampling) draws from the SAME distribution as
the documented semantics and as the sampler it replaced.

Two oracles, both independent of the code under test:

  * `reference_probs`: plain NumPy of the module docstring (the reference's
    ``src/rpc_handler.py:327-403``): penalty over the recent window, triple
    guard, temperature softmax, top-k zero-out, top-p on the SORTED cumsum,
    renormalise.
  * `_old_sample_token`: the previous implementation (two sorts, a
    vocabulary-wide gather and scatter, a vocabulary-wide penalty), copied
    here verbatim as the token oracle: same key -> same token.

The one permitted difference is the tie rule at the nucleus boundary
(entries exactly equal to the smallest kept probability are all kept),
pinned by `test_nucleus_boundary_ties_are_all_kept`.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.ops.sampling import (
    RECENT_WINDOW,
    _batched_probs,
    sample_probs,
    sample_token,
    sample_tokens,
    sampler_stages,
)

# (temperature, top_p, top_k, repetition_penalty)
CELLS = (0.8, 0.95, 0, 1.0)          # what both benchmark cells send
DEFAULTS = (0.7, 0.9, 50, 1.5)       # the reference's defaults
GREEDY = (0.0, 0.9, 50, 1.5)
FILTERS_OFF = (1.0, 1.0, 0, 1.0)
KNOBS = {"cells": [CELLS], "defaults": [DEFAULTS], "greedy": [GREEDY],
         "filters_off": [FILTERS_OFF],
         "mixed": [CELLS, DEFAULTS, GREEDY, FILTERS_OFF]}
VOCABS = (257, 50257, 152064)
ROWS, KEYS_PER_ROW = 32, 16          # 512 seeded draws a case


# -- oracle 1: the previous implementation, verbatim --------------------------

def _old_apply_repetition_penalty(logits, recent_tokens, num_valid,
                                  repetition_penalty):
    vocab = logits.shape[-1]
    valid = jnp.arange(recent_tokens.shape[0]) < num_valid
    safe = jnp.where(valid, recent_tokens, 0)
    counts = jnp.zeros((vocab,), jnp.float32).at[safe].add(
        valid.astype(jnp.float32))
    penalty = repetition_penalty ** counts
    penalized = jnp.where(logits > 0, logits / penalty, logits * penalty)
    logits = jnp.where(counts > 0, penalized, logits)
    n = num_valid
    t1 = recent_tokens[jnp.clip(n - 1, 0, RECENT_WINDOW - 1)]
    t2 = recent_tokens[jnp.clip(n - 2, 0, RECENT_WINDOW - 1)]
    t3 = recent_tokens[jnp.clip(n - 3, 0, RECENT_WINDOW - 1)]
    is_triple = (n >= 3) & (t1 == t2) & (t2 == t3)
    strong = repetition_penalty ** 3
    cur = logits[t1]
    hit = jnp.where(cur > 0, cur / strong, cur * strong)
    return logits.at[t1].set(jnp.where(is_triple, hit, cur))


def _old_top_k_filter(probs, top_k):
    vocab = probs.shape[-1]
    sorted_desc = jnp.sort(probs, axis=-1)[::-1]
    kth = sorted_desc[jnp.clip(top_k - 1, 0, vocab - 1)]
    apply = (top_k > 0) & (top_k < vocab)
    return jnp.where(apply & (probs < kth), 0.0, probs)


def _old_top_p_filter(probs, top_p):
    order = jnp.argsort(-probs, axis=-1)
    sorted_probs = probs[order]
    cum = jnp.cumsum(sorted_probs, axis=-1)
    keep = cum <= top_p
    keep = keep.at[0].set(True)
    filtered = sorted_probs * keep
    filtered = filtered / jnp.maximum(filtered.sum(), 1e-20)
    scattered = jnp.zeros_like(probs).at[order].set(filtered)
    apply = (top_p > 0.0) & (top_p < 1.0)
    return jnp.where(apply, scattered, probs)


def _old_sample_probs(logits, recent_tokens, num_valid, temperature, top_p,
                      top_k, repetition_penalty):
    logits = logits.astype(jnp.float32)
    apply_rp = (repetition_penalty != 1.0) & (num_valid > 0)
    logits = jnp.where(
        apply_rp,
        _old_apply_repetition_penalty(logits, recent_tokens, num_valid,
                                      repetition_penalty),
        logits)
    temp = jnp.maximum(temperature, 1e-5)
    probs = jax.nn.softmax(logits / temp, axis=-1)
    probs = _old_top_k_filter(probs, top_k)
    probs = _old_top_p_filter(probs, top_p)
    return probs / jnp.maximum(probs.sum(), 1e-20)


def _old_draw(rng, logits, probs, temperature):
    """The rest of the previous ``sample_token``, after its ``sample_probs``
    (split so that one row's distribution serves several keys)."""
    sampled = jax.random.categorical(rng, jnp.log(jnp.maximum(probs, 1e-20)))
    greedy = jnp.argmax(logits, axis=-1)
    return jnp.where(temperature <= 0.0, greedy, sampled).astype(jnp.int32)


_old_probs_rows = jax.jit(jax.vmap(_old_sample_probs))
_old_draw_rows = jax.jit(jax.vmap(_old_draw))


# -- oracle 2: the documented semantics in plain NumPy ------------------------

def reference_probs(logits, recent, nvalid, temperature, top_p, top_k, rp):
    """One row. -> (probs fp32 [V], ambiguous bool [V]): `ambiguous` marks
    the sorted positions whose cumulative mass lies within fp32 rounding of
    ``top_p``, where a cumsum in another order may fall either way."""
    f32 = np.float32
    logits = logits.astype(f32).copy()
    vocab = logits.shape[0]
    window = [int(t) for t in recent[:nvalid]]
    if rp != 1.0 and window:
        for tok in set(window):
            pen = f32(rp) ** f32(window.count(tok))
            x = logits[tok]
            logits[tok] = x / pen if x > 0 else x * pen
        if len(window) >= 3 and len(set(window[-3:])) == 1:
            strong = f32(rp) ** f32(3)
            x = logits[window[-1]]
            logits[window[-1]] = x / strong if x > 0 else x * strong
    x = logits / max(f32(temperature), f32(1e-5))
    e = np.exp(x - x.max())
    probs = (e / e.sum(dtype=f32)).astype(f32)
    ambiguous = np.zeros((vocab,), bool)
    if 0 < top_k < vocab:
        kth = np.sort(probs)[::-1][top_k - 1]
        probs = np.where(probs < kth, f32(0), probs)
    if 0.0 < top_p < 1.0:
        order = np.argsort(-probs, kind="stable")
        cum = np.cumsum(probs[order].astype(np.float64))
        keep = cum <= top_p
        keep[0] = True
        # The tie rule: everything equal to the smallest kept value stays.
        p_min = probs[order][keep].min()
        ambiguous[order] = (np.abs(cum - top_p) <= 2e-6) & (probs[order] > 0)
        probs = np.where(probs >= p_min, probs, f32(0))
        probs = (probs / probs.sum(dtype=f32)).astype(f32)
    return (probs / probs.sum(dtype=f32)).astype(f32), ambiguous


# -- cases ---------------------------------------------------------------------

def _window(vocab):
    """A recent window holding repeats and a closing triple (all ids real)."""
    ids = [5, 5, 9, vocab - 1, 7, 9, 5, 3, 3, 3]
    recent = np.zeros((RECENT_WINDOW,), np.int32)
    recent[:len(ids)] = ids
    return recent, len(ids)


def _rows(vocab, n, seed):
    """fp32 logits with the spread of a trained head (std 3): no exact ties,
    so the old sort order and the threshold rule keep the same set."""
    rs = np.random.RandomState(seed)
    return (rs.standard_normal((n, vocab)) * 3.0).astype(np.float32)


def _knob_arrays(knob_rows, n):
    cols = list(zip(*(knob_rows[i % len(knob_rows)] for i in range(n))))
    return (jnp.asarray(cols[0], jnp.float32), jnp.asarray(cols[1], jnp.float32),
            jnp.asarray(cols[2], jnp.int32), jnp.asarray(cols[3], jnp.float32))


@pytest.mark.parametrize("vocab", VOCABS)
@pytest.mark.parametrize("knobs", list(KNOBS), ids=list(KNOBS))
def test_same_distribution_as_the_documented_semantics(vocab, knobs):
    """Kept set equal to the sorted-cumsum reference (boundary positions
    within fp32 rounding of top_p aside) and probabilities within 1e-6
    relative, one row per knob set of the case."""
    knob_rows = KNOBS[knobs]
    n = 2 * len(knob_rows)
    logits = _rows(vocab, n, seed=vocab + 1)
    recent, nvalid = _window(vocab)
    temp, top_p, top_k, rp = _knob_arrays(knob_rows, n)
    got = np.asarray(jax.jit(_batched_probs)(
        jnp.ones((n,), bool), jnp.asarray(logits),
        jnp.broadcast_to(jnp.asarray(recent), (n, RECENT_WINDOW)),
        jnp.full((n,), nvalid, jnp.int32), temp, top_p, top_k, rp))
    exact = 0
    for i in range(n):
        want, ambiguous = reference_probs(
            logits[i], recent, nvalid, *knob_rows[i % len(knob_rows)])
        differ = (got[i] > 0) != (want > 0)
        assert not (differ & ~ambiguous).any(), (i, np.nonzero(differ)[0])
        np.testing.assert_allclose(got[i].sum(), 1.0, rtol=1e-5)
        if not differ.any():
            exact += 1
            np.testing.assert_allclose(got[i], want, rtol=1e-6, atol=1e-30)
    assert exact >= n - 1, (exact, n)


@pytest.mark.parametrize("vocab", VOCABS)
@pytest.mark.parametrize("knobs", list(KNOBS), ids=list(KNOBS))
def test_same_tokens_as_the_previous_sampler(vocab, knobs):
    """Same key, same logits -> the token the previous implementation drew,
    on >= 99.5% of 512 seeded draws (32 rows of logits, 16 keys each); the
    batched call with per-row knobs is the code under test."""
    knob_rows = KNOBS[knobs]
    recent, nvalid = _window(vocab)
    recent = jnp.broadcast_to(jnp.asarray(recent), (ROWS, RECENT_WINDOW))
    nvalid = jnp.full((ROWS,), nvalid, jnp.int32)
    knob_arrays = _knob_arrays(knob_rows, ROWS)
    logits = jnp.asarray(_rows(vocab, ROWS, seed=7 * vocab))
    old_probs = _old_probs_rows(logits, recent, nvalid, *knob_arrays)
    same = 0
    for j in range(KEYS_PER_ROW):
        keys = jax.vmap(jax.random.PRNGKey)(
            jnp.arange(ROWS, dtype=jnp.int32) + 1000 * j)
        want = np.asarray(_old_draw_rows(keys, logits, old_probs,
                                         knob_arrays[0]))
        got = np.asarray(sample_tokens(keys, logits, recent, nvalid,
                                       *knob_arrays))
        assert got.dtype == np.int32 and got.shape == (ROWS,)
        same += int((got == want).sum())
    draws = ROWS * KEYS_PER_ROW
    assert same >= 0.995 * draws, (same, draws)


def test_nucleus_boundary_ties_are_all_kept():
    """probs (0.4, 0.2, 0.2, 0.2), top_p 0.7: the sorted cumsum keeps 0.4 and
    the FIRST 0.2 the sort happens to place (what the previous sampler did);
    the threshold rule keeps every entry equal to the smallest kept one,
    as top-k always did at its boundary."""
    logits = jnp.log(jnp.asarray([2.0, 4.0, 2.0, 2.0], jnp.float32))
    recent = jnp.zeros((RECENT_WINDOW,), jnp.int32)
    args = (recent, jnp.int32(0), jnp.float32(1.0), jnp.float32(0.7),
            jnp.int32(0), jnp.float32(1.0))
    new = np.asarray(sample_probs(logits, *args))
    np.testing.assert_allclose(new, [0.2, 0.4, 0.2, 0.2], rtol=1e-6)
    old = np.asarray(_old_sample_probs(logits, *args))
    assert sorted(np.round(old, 4)) == [0.0, 0.0, 0.3333, 0.6667]
    # A boundary that is not a tie is untouched: (0.4, 0.3, 0.2, 0.1).
    logits = jnp.log(jnp.asarray([3.0, 4.0, 1.0, 2.0], jnp.float32))
    np.testing.assert_allclose(
        np.asarray(sample_probs(logits, *args)),
        np.asarray(_old_sample_probs(logits, *args)), rtol=1e-6)


def test_off_rows_pass_through_a_running_stage_unchanged():
    """A row whose own knobs are off, in a round where another row's are
    on, leaves the filter and the penalty bit for bit as it would alone."""
    vocab = 257
    logits = jnp.asarray(_rows(vocab, 4, seed=3))
    recent, nvalid = _window(vocab)
    keys = jax.vmap(jax.random.PRNGKey)(jnp.arange(4, dtype=jnp.int32))
    knob_rows = [FILTERS_OFF, DEFAULTS, GREEDY, CELLS]
    got = np.asarray(sample_tokens(
        keys, logits, jnp.asarray(recent), jnp.int32(nvalid),
        *_knob_arrays(knob_rows, 4)))
    for i, row in enumerate(knob_rows):
        alone = sample_token(
            keys[i], logits[i], jnp.asarray(recent), jnp.int32(nvalid),
            jnp.float32(row[0]), jnp.float32(row[1]), jnp.int32(row[2]),
            jnp.float32(row[3]))
        assert int(alone) == int(got[i]), (i, row)


@pytest.mark.parametrize("rows, label", [
    ([GREEDY, GREEDY], "greedy"),
    ([FILTERS_OFF, GREEDY], "plain"),
    ([CELLS, GREEDY], "filter"),
    ([(1.0, 1.0, 0, 1.5)], "penalty"),
    ([(1.0, 1.0, 50, 1.0), (1.0, 1.0, 0, 1.2)], "filter+penalty"),
    ([(1.0, 1.0, 257, 1.0), (1.0, 0.0, 0, 1.0)], "plain"),
])
def test_sampler_stages_labels(rows, label):
    cols = [np.asarray(c) for c in zip(*rows)]
    assert sampler_stages(cols[0].astype(np.float32),
                          cols[1].astype(np.float32),
                          cols[2].astype(np.int32),
                          cols[3].astype(np.float32), 257) == label
