"""Token sampling with reference-parity semantics, fully jittable.

Mirrors the server-side sampler of the reference (``src/rpc_handler.py:327-403``),
which runs ON THE FINAL STAGE (sampling params travel in request metadata):

  1. temperature <= 0  -> greedy argmax.
  2. count-scaled repetition penalty over the last 50 generated tokens:
     penalty = rp ** count(token); positive logits are divided, negative
     multiplied (sign-aware, ``rpc_handler.py:343-359``).
  3. triple-repeat guard: if the last 3 generated tokens are identical, apply a
     strong rp**3 penalty to that token (``:361-372``).
  4. probs = softmax(logits / max(temperature, 1e-5)).
  5. top-k filter on probs (unrenormalized zero-out, ``:376-382``).
  6. top-p nucleus on the sorted probs: keep cumsum <= top_p, always keep the
     first, renormalize the kept mass (``:384-396``).
  7. renormalize and sample.

Differences by design (TPU): the "recent tokens" window is a fixed-size int32
ring buffer so the whole sampler is one compiled XLA program with static
shapes. The vocabulary is never permuted: steps 5 and 6 are thresholds on the
UNSORTED row (the k-th largest probability; the smallest probability of the
nucleus prefix), each found by bisection on the fp32 bit pattern with masked
sums over the row, so there is no sort, no gather and no scatter over the
vocabulary, and step 2-3 touch the <= 50 recent ids only. Hence two tie rules,
both measure-zero for real logits: ties at the top-k boundary keep all tied
entries, and so do ties at the NUCLEUS boundary: entries exactly equal to the
smallest kept probability are all kept (the reference's sort order picks some
of them). A stage that no row of a call asks for is skipped at run time
(``lax.cond`` on the traced knobs): greedy rows take the argmax alone.

One implementation: `sample_tokens` over [S, V] rows (the burst tick, the
executor's batch rows, the ring); `sample_token` / `sample_probs` are its
S = 1 case.
"""

from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import jax.numpy as jnp

RECENT_WINDOW = 50  # reference: generated_tokens[-50:]


def sampling_scalars(temperature, top_p, top_k, repetition_penalty):
    """The traced-scalar 4-tuple every engine passes to `sample_token` —
    one constructor so the knob order can never skew between call sites."""
    return (jnp.asarray(temperature, jnp.float32),
            jnp.asarray(top_p, jnp.float32),
            jnp.asarray(top_k, jnp.int32),
            jnp.asarray(repetition_penalty, jnp.float32))


@dataclasses.dataclass(frozen=True)
class SamplingParams:
    """Per-session sampling config; travels in request metadata like the
    reference wire protocol (SURVEY.md Appendix B)."""

    temperature: float = 0.7
    top_p: float = 0.9
    top_k: int = 50
    repetition_penalty: float = 1.5  # reference default, rpc_handler.py:164

    @property
    def greedy(self) -> bool:
        return self.temperature <= 0.0


def make_recent_buffer() -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Empty recent-token buffer: (tokens[RECENT_WINDOW], num_valid)."""
    return jnp.zeros((RECENT_WINDOW,), jnp.int32), jnp.zeros((), jnp.int32)


def push_recent(tokens: jnp.ndarray, num_valid: jnp.ndarray, new_token: jnp.ndarray):
    """Append a token, shifting left once the window is full (jittable)."""
    full = num_valid >= RECENT_WINDOW
    shifted = jnp.where(full, jnp.roll(tokens, -1), tokens)
    idx = jnp.where(full, RECENT_WINDOW - 1, num_valid)
    tokens = shifted.at[idx].set(new_token.astype(jnp.int32))
    return tokens, jnp.minimum(num_valid + 1, RECENT_WINDOW)


def apply_repetition_penalty(
    logits: jnp.ndarray,
    recent_tokens: jnp.ndarray,
    num_valid: jnp.ndarray,
    repetition_penalty: jnp.ndarray,
) -> jnp.ndarray:
    """Count-scaled, sign-aware repetition penalty over the recent window,
    computed AT THE RECENT IDS ONLY: gather their <= 50 logits, count each
    id's repeats inside the window (a 50 x 50 compare), penalise, write the
    <= 50 values back. Nothing here is vocabulary-sized but the row itself.

    logits: [V] float32. recent_tokens: [RECENT_WINDOW] int32 (newest last).
    """
    vocab = logits.shape[-1]
    window = recent_tokens.shape[0]
    valid = jnp.arange(window) < num_valid
    ids = jnp.where(valid, recent_tokens, 0)
    vals = logits[ids]
    counts = jnp.sum((ids[:, None] == ids[None, :]) & valid[None, :],
                     axis=-1).astype(jnp.float32)
    penalty = repetition_penalty ** counts
    vals = jnp.where(vals > 0, vals / penalty, vals * penalty)

    # Triple-repeat strong penalty (rp**3) on the token repeated 3x in a
    # row, on top of its count penalty (it is one of the recent ids).
    n = num_valid
    t1 = recent_tokens[jnp.clip(n - 1, 0, window - 1)]
    t2 = recent_tokens[jnp.clip(n - 2, 0, window - 1)]
    t3 = recent_tokens[jnp.clip(n - 3, 0, window - 1)]
    is_triple = (n >= 3) & (t1 == t2) & (t2 == t3)
    strong = repetition_penalty ** 3
    hit = jnp.where(vals > 0, vals / strong, vals * strong)
    vals = jnp.where(is_triple & (ids == t1), hit, vals)
    # Empty window slots point past the row and are dropped; a repeated id
    # writes the same value from each of its slots.
    return logits.at[jnp.where(valid, ids, vocab)].set(vals, mode="drop")


def row_keys(base: jax.Array, rows: int) -> jax.Array:
    """The [rows] keys of one step of a session with batch rows: row 0 keeps
    the unfolded step key by contract (batch-1 output never changes), row i
    folds i in."""
    return jnp.stack([base] + [jax.random.fold_in(base, i)
                               for i in range(1, rows)])


def _row_knobs(rows: int, *knobs):
    """Scalars or [S] arrays -> [S] arrays (a session's knobs are shared by
    its batch rows; the burst holds one set per slot)."""
    return tuple(jnp.broadcast_to(k, (rows,)) for k in knobs)


def _stages(top_p, top_k, repetition_penalty, vocab: int):
    """Per-row masks of what each row's knobs switch on: (top-k, top-p,
    penalty). Operators only, so the host's numpy knob arrays
    (`sampler_stages`) and the traced ones give the same answer."""
    k_on = (top_k > 0) & (top_k < vocab)
    p_on = (top_p > 0.0) & (top_p < 1.0)
    return k_on, p_on, repetition_penalty != 1.0


def sampler_stages(temperature, top_p, top_k, repetition_penalty,
                   vocab: int) -> str:
    """Which of the sampler's stages a round with these knobs runs, as the
    label of ``server_sampler_rounds_total``: ``greedy`` (argmax only),
    ``plain`` (softmax + draw), ``filter`` (the top-k / top-p threshold
    searches), ``penalty``, ``filter+penalty``. Host-side mirror of the
    ``lax.cond`` predicates in `sample_tokens`: a greedy row switches
    nothing on, whatever its other knobs say; a penalised row with an empty
    recent window skips the penalty on device until its first token lands."""
    sampled = ~(temperature <= 0.0)
    k_on, p_on, rp_on = _stages(top_p, top_k, repetition_penalty, vocab)
    if not sampled.any():
        return "greedy"
    on = [name for name, mask in (("filter", k_on | p_on),
                                  ("penalty", rp_on)) if (mask & sampled).any()]
    return "+".join(on) or "plain"


def _order_bits(probs):
    """Non-negative fp32 -> int32 with the same order: the bit pattern."""
    return jax.lax.bitcast_convert_type(probs, jnp.int32)


def _first_true(pred, hi):
    """Per row, the smallest int32 ``t`` in [0, hi] with ``pred(t)`` true,
    for a ``pred`` that is monotone in ``t`` (false, then true) and true at
    ``hi``. 31 halvings close any interval of non-negative fp32 bit
    patterns; each is one masked pass over the rows, no sort."""
    def halve(_, bounds):
        lo, hi = bounds              # pred false at lo (or lo == -1), true at hi
        mid = lo + (hi - lo + 1) // 2
        ok = pred(mid)
        return jnp.where(ok, lo, mid), jnp.where(ok, mid, hi)

    return jax.lax.fori_loop(0, 31, halve, (jnp.full_like(hi, -1), hi))[1]


def _top_k_rows(probs, top_k, k_on):
    """Zero what lies under the k-th largest probability of each row [S, V]
    (unrenormalised; ties at the boundary all stay). The k-th largest value
    is the largest threshold that still keeps k entries."""
    bits = _order_bits(probs)
    kth = _first_true(
        lambda t: jnp.sum(bits >= t[:, None], axis=-1) < top_k,
        bits.max(axis=-1) + 1) - 1
    return jnp.where(k_on[:, None] & (bits < kth[:, None]), 0.0, probs)


def _top_p_rows(probs, top_p, p_on):
    """Nucleus on the UNSORTED rows [S, V]: keep ``probs >= p_min`` and
    renormalise, where ``p_min`` is the smallest probability of the sorted
    prefix with cumsum <= top_p (first always kept), found as a threshold
    on the mass above it. No order of the vocabulary is built, so there is
    nothing to sort, gather or scatter back."""
    bits = _order_bits(probs)
    top = bits.max(axis=-1)

    def mass(t):
        return jnp.sum(jnp.where(bits >= t[:, None], probs, 0.0), axis=-1)

    # Distinct values: the prefix ends at the smallest t with mass(t) <= top_p.
    t = _first_true(lambda mid: mass(mid) <= top_p, top + 1)
    # Equal values just under t: the sorted prefix takes them one at a time,
    # so if ONE more fits, that value is the smallest kept and (the tie
    # rule) all its equals stay.
    under = jnp.max(jnp.where(bits < t[:, None], bits, 0), axis=-1)
    one_more = mass(t) + jax.lax.bitcast_convert_type(under, jnp.float32)
    p_min = jnp.minimum(jnp.where(one_more <= top_p, under, t), top)
    nucleus = jnp.where(bits >= p_min[:, None], probs, 0.0)
    nucleus = nucleus / jnp.maximum(
        nucleus.sum(axis=-1, keepdims=True), 1e-20)
    return jnp.where(p_on[:, None], nucleus, probs)


def _batched_probs(wanted, logits, recent, nvalid, temperature, top_p, top_k,
                   repetition_penalty):
    """[S, V] logits + [S, RECENT_WINDOW] windows + [S] knobs -> [S, V]
    categorical distributions, for the rows in the [S] mask ``wanted`` (the
    others come back unfiltered). A stage no wanted row asks for is not run:
    the ``lax.cond``s sit OUTSIDE the row dimension, on traced knobs, so one
    executable serves every combination; a row whose own knob is off goes
    through a running stage unchanged."""
    logits = logits.astype(jnp.float32)
    k_on, p_on, rp_on = _stages(
        top_p, top_k, repetition_penalty, logits.shape[-1])
    k_on, p_on = k_on & wanted, p_on & wanted
    rp_on = rp_on & wanted & (nvalid > 0)
    # A row that is off takes part with an EMPTY window: nothing is written.
    logits = jax.lax.cond(
        jnp.any(rp_on),
        lambda x: jax.vmap(apply_repetition_penalty)(
            x, recent, jnp.where(rp_on, nvalid, 0), repetition_penalty),
        lambda x: x,
        logits)
    temp = jnp.maximum(temperature, 1e-5)
    probs = jax.nn.softmax(logits / temp[:, None], axis=-1)
    probs = jax.lax.cond(
        jnp.any(k_on), lambda p: _top_k_rows(p, top_k, k_on), lambda p: p,
        probs)
    probs = jax.lax.cond(
        jnp.any(p_on), lambda p: _top_p_rows(p, top_p, p_on), lambda p: p,
        probs)
    return probs / jnp.maximum(probs.sum(axis=-1, keepdims=True), 1e-20)


@jax.jit
def sample_probs(
    logits: jnp.ndarray,
    recent_tokens: jnp.ndarray,
    num_valid: jnp.ndarray,
    temperature: jnp.ndarray,
    top_p: jnp.ndarray,
    top_k: jnp.ndarray,
    repetition_penalty: jnp.ndarray,
) -> jnp.ndarray:
    """Final categorical distribution after penalty + temp + top-k + top-p.

    logits: [V]. Returns probs [V] summing to 1 (greedy handled by caller).
    The S = 1 case of `_batched_probs`.
    """
    return _batched_probs(
        jnp.ones((1,), bool), logits[None], recent_tokens[None],
        *_row_knobs(1, num_valid, temperature, top_p, top_k,
                    repetition_penalty))[0]


def speculative_verify(
    rng: jax.Array,
    logits: jnp.ndarray,
    drafts,
    recent_tokens: jnp.ndarray,
    num_valid,
    temperature: float,
    top_p: float,
    top_k: int,
    repetition_penalty: float,
):
    """Rejection-sampling verification of K drafted tokens under SAMPLING.

    logits: [K+1, V] fp32 — position i's logits were computed AFTER
    consuming [last_accepted, d_1..d_i]; drafts: K python ints. Returns
    (tokens, n_accepted) with len(tokens) == n_accepted + 1 (accepted run +
    one correction/bonus token).

    The client's draft proposal (n-gram prompt lookup) is DETERMINISTIC —
    a point mass q = δ(d_i) — so the standard accept rule min(1, p/q)
    reduces to: accept d_i with probability p_i(d_i); on rejection sample
    the correction from the residual (p_i - q)+ ∝ p_i with d_i zeroed.
    This preserves the target distribution EXACTLY per position (the
    speculative-sampling correctness result for deterministic proposals),
    so temperature>0 serving gets the same round-trip amortization as
    greedy without changing its output law.

    The repetition-penalty window evolves as drafts are accepted, so each
    position's target p_i is evaluated against the window INCLUDING the
    accepted prefix — identical to what non-speculative decoding would
    have used. Host-side loop over K (small); each position is one compiled
    sample_probs call.
    """
    k = len(drafts)
    tokens = []
    rt, nv = jnp.asarray(recent_tokens), jnp.asarray(num_valid, jnp.int32)
    args = (
        jnp.asarray(temperature, jnp.float32),
        jnp.asarray(top_p, jnp.float32),
        jnp.asarray(top_k, jnp.int32),
        jnp.asarray(repetition_penalty, jnp.float32),
    )
    for i in range(k):
        rng, key_u, key_r = jax.random.split(rng, 3)
        probs = sample_probs(logits[i], rt, nv, *args)
        d = int(drafts[i])
        if float(jax.random.uniform(key_u)) < float(probs[d]):
            tokens.append(d)
            rt, nv = push_recent(rt, nv, jnp.asarray(d, jnp.int32))
            continue
        # Reject: correction from the residual (p with the draft zeroed,
        # renormalized). p(d) == 1 makes the residual empty — measure-zero
        # for real logits, but guard by falling back to p itself.
        residual = probs.at[d].set(0.0)
        z = residual.sum()
        residual = jnp.where(z > 0, residual / jnp.maximum(z, 1e-20), probs)
        tok = int(jax.random.categorical(
            key_r, jnp.log(jnp.maximum(residual, 1e-20))))
        tokens.append(tok)
        return tokens, i
    # All K accepted: bonus token from the final position's target.
    rng, key_b = jax.random.split(rng)
    probs = sample_probs(logits[k], rt, nv, *args)
    tokens.append(int(jax.random.categorical(
        key_b, jnp.log(jnp.maximum(probs, 1e-20)))))
    return tokens, k


def speculative_verify_jit(
    key: jax.Array,
    logits: jnp.ndarray,        # [K+1, V] fp32
    drafts: jnp.ndarray,        # [K] int32
    recent_tokens: jnp.ndarray,
    num_valid: jnp.ndarray,
    temperature: jnp.ndarray,
    top_p: jnp.ndarray,
    top_k: jnp.ndarray,
    repetition_penalty: jnp.ndarray,
):
    """Fully-traceable speculative verification (the in-jit counterpart of
    `speculative_verify`, for engines that verify INSIDE a compiled
    program — parallel.ring_decode's spec round).

    Greedy (temperature <= 0): accept while draft[i] == argmax(logits[i])
    (unpenalized, matching ``executor.verify_drafts_from_logits`` — the
    reference applies greedy before penalties, src/rpc_handler.py:334-335);
    correction/bonus = the argmax. Sampled: deterministic-proposal
    rejection sampling — accept draft i with probability p_i(draft_i)
    under the full penalized/filtered target, correction from the residual
    with the draft zeroed, bonus from p_K — preserving the sampling law
    exactly (same argument as `speculative_verify`). The recent window
    evolves WITH each accepted token, so every position's target equals
    what non-speculative decoding would have used.

    Returns (tokens [K+1] int32 — positions > n_accepted are zero —,
    n_accepted, new recent, new num_valid). len of the real run is
    n_accepted + 1 (accepted prefix + correction/bonus)."""
    k = drafts.shape[0]
    greedy_mode = temperature <= 0.0
    knobs = (temperature, top_p, top_k, repetition_penalty)

    def body(i, carry):
        stopped, n_acc, recent, nvalid, toks, key = carry
        key, ku, kr = jax.random.split(key, 3)
        probs = sample_probs(logits[i], recent, nvalid, *knobs)
        am = jnp.argmax(logits[i], axis=-1).astype(jnp.int32)
        is_bonus = i >= k             # position K: no draft to check
        d = drafts[jnp.clip(i, 0, k - 1)]
        accept_s = jax.random.uniform(ku) < probs[d]
        accept = jnp.where(greedy_mode, d == am, accept_s) & ~is_bonus
        # Correction (reject) / bonus (i == K) token.
        residual = probs.at[d].set(jnp.where(is_bonus, probs[d], 0.0))
        z = residual.sum()
        residual = jnp.where(z > 0, residual / jnp.maximum(z, 1e-20), probs)
        corr_s = jax.random.categorical(
            kr, jnp.log(jnp.maximum(residual, 1e-20))).astype(jnp.int32)
        tok = jnp.where(accept, d, jnp.where(greedy_mode, am, corr_s))
        write = ~stopped
        toks = jnp.where(write, toks.at[i].set(tok), toks)
        r2, n2 = push_recent(recent, nvalid, tok)
        recent = jnp.where(write, r2, recent)
        nvalid = jnp.where(write, n2, nvalid)
        n_acc = n_acc + jnp.where(accept & write, 1, 0)
        stopped = stopped | (~accept & write)   # reject OR bonus ends the run
        return (stopped, n_acc, recent, nvalid, toks, key)

    # Initial carry DERIVED from the inputs so it inherits their
    # varying-axis types under shard_map (a literal jnp.zeros carry would
    # be device-invariant while the loop body's outputs vary over e.g. the
    # ring's "stage" axis — lax.fori_loop rejects the mismatch).
    nv0 = jnp.asarray(num_valid, jnp.int32)
    zero = nv0 * 0
    toks0 = jnp.zeros((k + 1,), jnp.int32) + zero
    stopped, n_acc, recent, nvalid, toks, _ = jax.lax.fori_loop(
        0, k + 1, body,
        (zero < 0, zero, jnp.asarray(recent_tokens), nv0, toks0, key))
    return toks, n_acc, recent, nvalid


@jax.jit
def sample_tokens(
    rngs: jax.Array,
    logits: jnp.ndarray,
    recent_tokens: jnp.ndarray,
    num_valid: jnp.ndarray,
    temperature: jnp.ndarray,
    top_p: jnp.ndarray,
    top_k: jnp.ndarray,
    repetition_penalty: jnp.ndarray,
) -> jnp.ndarray:
    """THE sampler: one key and one token per row. rngs: [S] keys, logits:
    [S, V], recent_tokens: [S, RECENT_WINDOW] (or one [RECENT_WINDOW] window
    shared by the rows), num_valid and the knobs: scalars or [S]. -> [S]
    int32 tokens.

    All knobs are traced so every (temperature, top_p, top_k, rp)
    combination reuses one executable; what the round's knobs do not ask for
    is skipped at run time (`_batched_probs`), and a round whose every row
    is greedy takes the argmax alone.
    """
    rows, _ = logits.shape
    recent_tokens = jnp.broadcast_to(
        recent_tokens, (rows, recent_tokens.shape[-1]))
    num_valid, *knobs = _row_knobs(
        rows, num_valid, temperature, top_p, top_k, repetition_penalty)
    greedy_row = knobs[0] <= 0.0
    # Greedy reads the RAW logits (the reference takes the argmax before
    # any penalty, src/rpc_handler.py:334-335).
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)

    def draw(_):
        probs = _batched_probs(~greedy_row, logits, recent_tokens, num_valid,
                               *knobs)
        sampled = jax.vmap(jax.random.categorical)(
            rngs, jnp.log(jnp.maximum(probs, 1e-20)))
        return jnp.where(greedy_row, greedy, sampled.astype(jnp.int32))

    return jax.lax.cond(jnp.all(greedy_row), lambda _: greedy, draw, None)


def sample_token(
    rng: jax.Array,
    logits: jnp.ndarray,
    recent_tokens: jnp.ndarray,
    num_valid: jnp.ndarray,
    temperature: jnp.ndarray,
    top_p: jnp.ndarray,
    top_k: jnp.ndarray,
    repetition_penalty: jnp.ndarray,
) -> jnp.ndarray:
    """One sampling step. logits: [V] -> scalar int32 token: the S = 1 case
    of `sample_tokens`, so every engine draws through one implementation.
    """
    return sample_tokens(
        rng[None], logits[None], recent_tokens, num_valid, temperature,
        top_p, top_k, repetition_penalty)[0]


# Jitted entry for HOST-LOOP callers (per-token CLI paths): one compiled
# executable serves every sampling config (all knobs are traced scalars).
# In-scan engines trace `sample_token` directly inside their own jits.
sample_token_jit = jax.jit(sample_token)
