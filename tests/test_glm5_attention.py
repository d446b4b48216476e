"""A latent family under a learned selection (GLM-5, ``glm_moe_dsa``) on the
batched stage engine, at a small size with seeded weights, against the
benchmark's plain reference (``perfbench/references/glm5_plain.py``, which
imports nothing of the program): chunked prefill, decode steps and burst
rounds through BOTH stacks (a latent row and an index key a position a
layer) across the selection's edge at row ``index_topk``, across prefill
chunks and index blocks, a rewind; the absorbed form of a decode step
against the expanded form of a prefill chunk; the exact top-k mask against
``jax.lax.top_k`` with ties; the counters; the importer's permutation; and
every engine that cannot hold the state refusing the family by name."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu import (
    main,
    telemetry,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.models import (
    config,
    hf_import,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.models.partition import (
    slice_stage_params,
    StagePlan,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.models.transformer import (
    init_params,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.ops import (
    slot_attention as FA,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime import (
    batching,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.telemetry import (
    catalog as tm,
)

from engines import (
    greedy_entry,
    reference_engine,
    reference_logits,
    reference_weights,
    stage_executor as StageExecutor,
)

TOPK = 16
HF = dict(
    model_type="glm_moe_dsa", hidden_size=64, intermediate_size=96,
    moe_intermediate_size=32, num_attention_heads=4, q_lora_rank=32,
    kv_lora_rank=16, qk_nope_head_dim=12, qk_rope_head_dim=4, v_head_dim=16,
    index_n_heads=2, index_head_dim=8, index_topk=TOPK, n_routed_experts=8,
    num_experts_per_tok=2, n_shared_experts=1, vocab_size=97,
    first_k_dense_replace=1, rms_norm_eps=1e-5, routed_scaling_factor=2.5,
    rope_parameters={"rope_theta": 1e6}, experts_held=4)
LAYERS = 3


def small_config(**kw):
    return config.glm5_config(**{**dict(
        vocab_size=97, hidden_size=64, num_layers=LAYERS, num_heads=4,
        intermediate_size=96, max_position_embeddings=512, rope_theta=1e6,
        q_lora_rank=32, kv_lora_rank=16, qk_nope_head_dim=12,
        qk_rope_head_dim=4, v_head_dim=16, index_n_heads=2, index_head_dim=8,
        index_topk=TOPK, n_routed_experts=8, num_experts_per_tok=2,
        moe_intermediate_size=32, first_k_dense=1, experts_held=(0, 4)),
        **kw})


@pytest.fixture(scope="module")
def weights():
    return reference_weights("glm5", HF, LAYERS, 7)


@pytest.fixture
def small_blocks(monkeypatch):
    """Chunks of 16 prompt rows and blocks of 8 index keys: a 37-row prompt
    is three chunks, its last one a bucket, over five blocks."""
    monkeypatch.setattr(batching, "LATENT_CHUNK", 16)
    monkeypatch.setattr(batching, "INDEX_BLOCK", 8)


def engine(weights, *, slots=2, max_len=64, cfg=None):
    return reference_engine(cfg or small_config(), weights, slots=slots,
                            max_len=max_len)


def logits_of(eng, h):
    return np.asarray(eng.logits(h), np.float32)[0]


def burst_entry(token, generated=()):
    return greedy_entry(token, generated=generated)


@pytest.mark.parametrize("n", [5, 15, 16, 37])
def test_prefill_and_decode_steps_agree_with_the_reference(
        weights, small_blocks, n):
    """Prompts under, at and past the selection's edge (row 16) and the
    chunk's (16 rows), then decode steps that cross both."""
    ids = np.random.default_rng(n).integers(0, 97, (n + 6,)).astype(np.int32)
    want = reference_logits("glm5", HF, LAYERS, weights, ids)
    eng = engine(weights)
    got = logits_of(eng, eng.prefill("a", ids[None, :n]))
    np.testing.assert_allclose(got, want[:n], atol=2e-5)
    for j in range(6):
        out = eng.decode_batch({"a": ids[None, n + j:n + j + 1]})
        np.testing.assert_allclose(logits_of(eng, out["a"])[0], want[n + j],
                                   atol=2e-5)


def test_burst_rounds_two_slots_and_a_rewind(weights, small_blocks):
    """Two sessions of different lengths side by side (one under the edge,
    one past it), greedy burst rounds judged on the reference's rows, then
    a rewind and the same tokens again."""
    rng = np.random.default_rng(3)
    lens = {"a": 11, "b": 29}
    seqs = {k: rng.integers(0, 97, (n,)).astype(np.int32)
            for k, n in lens.items()}
    eng = engine(weights)
    for sid, seq in seqs.items():
        eng.prefill(sid, seq[None])
    consumed = {k: [int(t) for t in v] for k, v in seqs.items()}
    fed = {"a": 5, "b": 9}
    emitted = {k: [] for k in seqs}
    for _ in range(2):                      # 8 ticks: "a" crosses row 16
        res = eng.decode_burst(
            {sid: burst_entry(tok) for sid, tok in fed.items()}, 4)
        for sid, r in res.items():
            assert len(r["tokens"]) == 4
            consumed[sid] += [fed[sid]] + r["tokens"][:-1]
            emitted[sid] += r["tokens"]
            fed[sid] = r["tokens"][-1]
    for sid, ids in consumed.items():
        want = reference_logits("glm5", HF, LAYERS, weights,
                                np.asarray(ids, np.int32))
        picked = want[lens[sid]:].argmax(-1)
        assert list(picked) == emitted[sid], sid
    # a rewind is a length: the rows past it are masked until rewritten
    eng.rewind("a", lens["a"])
    eng.rewind("b", lens["b"])
    again = eng.decode_burst({"a": burst_entry(5), "b": burst_entry(9)}, 4)
    assert again["a"]["tokens"] == emitted["a"][:4]
    assert again["b"]["tokens"] == emitted["b"][:4]
    assert again["b"]["cache_len"] == lens["b"] + 4


def test_the_absorbed_step_is_the_expanded_chunk(weights, small_blocks):
    """One function of the rows: the last row of a prompt through the
    prefill chunk's EXPANDED form equals the same token through a decode
    step's ABSORBED form, under and past the selection's edge."""
    ids = np.random.default_rng(1).integers(0, 97, (40,)).astype(np.int32)
    for n in (9, 33):
        whole, stepped = engine(weights), engine(weights)
        expanded = logits_of(whole, whole.prefill("a", ids[None, :n]))[-1]
        stepped.prefill("a", ids[None, :n - 1])
        out = stepped.decode_batch({"a": ids[None, n - 1:n]})
        np.testing.assert_allclose(logits_of(stepped, out["a"])[0], expanded,
                                   atol=2e-5)


def test_a_latent_row_padded_to_lane_tiles_changes_nothing(
        weights, small_blocks, monkeypatch):
    """Where the backend would not keep a 20-number row minor the stack
    holds it padded (the v5e: 576 -> 640): the programs read its first
    ``kv_lora_rank + qk_rope_head_dim`` numbers whatever the pad."""
    ids = np.random.default_rng(2).integers(0, 97, (30,)).astype(np.int32)
    plain = engine(weights)
    monkeypatch.setattr(batching, "kv_fold_width", lambda *a: 24)
    padded = engine(weights)
    assert plain.k.shape[-1] == 20 and padded.k.shape[-1] == 24
    assert padded.v.shape == plain.v.shape
    a = logits_of(plain, plain.prefill("a", ids[None, :26]))
    b = logits_of(padded, padded.prefill("a", ids[None, :26]))
    np.testing.assert_array_equal(a, b)
    for j in range(26, 30):
        x, y = (logits_of(e, e.decode_batch({"a": ids[None, j:j + 1]})["a"])
                for e in (plain, padded))
        np.testing.assert_array_equal(x, y)


TIE_CASES = [1, 3, 8, 31, 32, 40]


def tied_scores(k):
    """Rows with many equal values (ties go to the lower position),
    negatives, a row of one value, masked entries, tiny values of both
    signs: whatever ``k``, some row's k-th value is shared."""
    rng = np.random.default_rng(k)
    scores = rng.integers(-3, 4, (64, 32)).astype(np.float32) * 0.25
    scores[5] = 1.5                                    # every entry ties
    scores[6, :20] = batching.NEG_INF                  # masked entries
    scores[7] = rng.standard_normal(32) * 1e-30        # tiny, both signs
    return scores


@pytest.mark.parametrize("k", TIE_CASES)
def test_select_topk_is_lax_top_k_with_ties(k):
    """The mask form against ``jax.lax.top_k``: many equal values (ties go
    to the lower position), negatives, and rows of one value."""
    scores = tied_scores(k)
    mask = np.asarray(batching.select_topk(jnp.asarray(scores), k))
    if k >= 32:
        assert mask.all()
        return
    _, top = jax.lax.top_k(jnp.asarray(scores), k)
    want = np.zeros_like(mask)
    np.put_along_axis(want, np.asarray(top), True, axis=-1)
    np.testing.assert_array_equal(mask, want)
    assert (mask.sum(-1) == k).all()


@pytest.mark.parametrize("k", TIE_CASES)
def test_the_kernel_s_selection_is_lax_top_k_with_ties(k, monkeypatch):
    """The selection as the kernel makes it (`ops.slot_attention._threshold`)
    against ``jax.lax.top_k``, entry for entry, on
    `test_select_topk_is_lax_top_k_with_ties`'s rows: 64 slots of 32 rows
    whose row i is the unit vector i, under a zero query, so that lane i of
    a slot's sum is 1 / (rows admitted) where row i was admitted and 0
    where it was not."""
    monkeypatch.setattr(FA, "_INTERPRET", True)
    scores = tied_scores(k)
    slots, n = scores.shape
    stack = jnp.broadcast_to(jnp.eye(n, dtype=jnp.float32), (1, slots, n, n))
    plan = FA.read_plan(jnp.full((slots,), n // 16, jnp.int32),
                        jnp.full((slots,), n, jnp.int32), n // 16)
    out = np.asarray(FA.slot_attention(
        jnp.zeros((slots, 8, n), jnp.float32), stack, None, 0, plan, rows=16,
        hkv=1, select=(jnp.asarray(scores), min(k, n))))
    _, top = jax.lax.top_k(jnp.asarray(scores), min(k, n))
    want = np.zeros(scores.shape, bool)
    np.put_along_axis(want, np.asarray(top), True, axis=-1)
    np.testing.assert_array_equal(out[:, 0] > 0, want)
    np.testing.assert_allclose(out.sum(-1), 1.0, atol=1e-6)


# the last position held (the query's own) of eight slots of 64 rows, blocks
# of 16: under ``index_topk`` = 16, exactly at it (16 rows seen), one row
# past it, a last block partly filled, an inactive slot, the slot's end
LAST = np.array([3, 15, 16, 17, 40, 63, 30, 50], np.int32)
ACTIVE = np.array([1, 1, 1, 1, 1, 1, 0, 1], bool)


@pytest.mark.parametrize("width", [20, 24])
def test_the_masked_read_is_the_top_k_and_gather_arm(
        width, small_blocks, monkeypatch):
    """`_attend_latent`'s decode arm in its two forms on the same stacks:
    the kernel over each slot's own blocks under the selection as a mask,
    and ``jax.lax.top_k`` + gather, the definition. ``width`` 24: the row
    padded as the v5e holds it (576 -> 640)."""
    monkeypatch.setattr(FA, "_INTERPRET", True)
    monkeypatch.setattr(batching, "LATENT_BLOCK", 16)
    cfg = small_config()
    slots, m, layers = 8, 64, 2
    rng = np.random.default_rng(width)

    def normal(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.float32)

    row = cfg.kv_lora_rank + cfg.qk_rope_head_dim
    stack = normal(layers, slots, m, width) * (np.arange(width) < row)
    index = normal(layers, slots, m, cfg.index_head_dim)
    # index keys in steps of a quarter: exact ties at the k-th value
    index = jnp.round(index * 2) / 2
    q = {"nope": normal(slots, 1, cfg.num_heads, cfg.qk_nope_head_dim),
         "rope": normal(slots, 1, cfg.num_heads, cfg.qk_rope_head_dim),
         "iq": jnp.round(normal(slots, 1, cfg.index_n_heads,
                                cfg.index_head_dim)),
         "iw": jnp.ones((slots, 1, cfg.index_n_heads), jnp.float32)}
    lp = {"attn": {"wkvb_t": normal(
        cfg.num_heads, cfg.qk_nope_head_dim + cfg.v_head_dim,
        cfg.kv_lora_rank)}}
    lengths, active = jnp.asarray(LAST), jnp.asarray(ACTIVE)
    keys = batching._CacheLayer(index, 1, batching.index_blocks(
        lengths, active, m, jnp))
    plan = FA.read_plan(batching.attn_blocks(
        lengths, active, 1, m, jnp, per_slot=True, block=16), lengths + 1,
        m // 16)

    @jax.jit
    def both(q, stack):
        return [batching._attend_latent(
            cfg, lp, q, batching._CacheLayer(stack, 1, blocks), keys,
            lengths[:, None, None]) for blocks in (plan, keys.blocks)]

    streamed, gathered = both(q, stack)
    np.testing.assert_allclose(np.asarray(streamed)[ACTIVE],
                               np.asarray(gathered)[ACTIVE], atol=2e-5,
                               rtol=2e-5)
    # exact ties at the edge were there to break
    scores = np.asarray(batching._index_scores(q, index[1]))[:, 0]
    assert any(np.sum(scores[s, :LAST[s] + 1] == np.sort(
        scores[s, :LAST[s] + 1])[-TOPK]) > 1 for s in (3, 4, 5, 7))


def test_a_burst_round_by_the_kernel_is_the_round_without_it(
        weights, small_blocks, monkeypatch):
    """The small engine's burst rounds with the kernel engaged (the hook
    `ops.slot_attention.engaged` reads; blocks of 16 latent rows) against
    the same rounds through ``jax.lax.top_k`` + gather: the same tokens,
    slot by slot, across row ``index_topk``."""
    seqs = {"a": np.arange(11, dtype=np.int32) * 7 % 97,
            "b": np.arange(29, dtype=np.int32) * 5 % 97}
    emitted = []
    for hook in (None, True):
        monkeypatch.setattr(FA, "_INTERPRET", hook)
        monkeypatch.setattr(batching, "LATENT_BLOCK", 16)
        eng = engine(weights)
        assert eng._cache_read(1, False) == ("kernel" if hook else "select")
        for sid, seq in seqs.items():
            eng.prefill(sid, seq[None])
        fed, got = {"a": 5, "b": 9}, {"a": [], "b": []}
        for _ in range(2):                  # 8 ticks: "a" crosses row 16
            res = eng.decode_burst(
                {sid: burst_entry(tok) for sid, tok in fed.items()}, 4)
            for sid, r in res.items():
                got[sid] += r["tokens"]
                fed[sid] = r["tokens"][-1]
        emitted.append(got)
    assert emitted[0] == emitted[1]


def test_one_stack_for_keys_and_values_is_copied_once_a_pair():
    """``v_stack=None``: a pair's block is BOTH operands, so the kernel
    starts one copy where two stacks start two (the first pair's, and the
    next pair's one ahead), and its call names the stack first."""
    def starts(v_given):
        sd = jax.ShapeDtypeStruct
        stack = sd((2, 4, 64, 128), jnp.float32)
        text = str(jax.make_jaxpr(lambda q, k, v, plan: FA.slot_attention(
            q, k, v if v_given else None, 0, plan, rows=16, hkv=1))(
                sd((4, 8, 128), jnp.float32), stack, stack,
                sd((1 + 2 * 16 + 8,), jnp.int32)))
        return text.count("dma_start"), text.count("dma_wait")

    assert starts(True) == (4, 2)
    assert starts(False) == (2, 1)


def test_which_slots_stream_their_rows_and_which_gather(monkeypatch):
    """`cache_read` for a latent family: the kernel where it is the chip's
    (or a test's hook) and a slot holds at most `LATENT_DENSE` x
    ``index_topk`` rows; the definition, ``jax.lax.top_k`` + gather,
    everywhere else."""
    cfg = small_config()
    assert batching.cache_read(cfg, {}, True, max_len=64) == "select"
    monkeypatch.setattr(FA, "_INTERPRET", True)
    edge = batching.LATENT_DENSE * TOPK
    assert batching.cache_read(cfg, {}, True, max_len=64) == "kernel"
    assert batching.cache_read(cfg, {}, True, max_len=edge) == "kernel"
    assert batching.cache_read(cfg, {}, True, max_len=edge + 16) == "select"
    assert batching.cache_read(cfg, {}, True, t=2, max_len=64) == "select"
    # glm-5 as served: 16384 = 8 x 2048 rows a slot
    served = dataclasses.replace(cfg, index_topk=2048)
    assert batching.cache_read(served, {}, True, max_len=16384) == "kernel"
    assert batching.latent_block(16384) == batching.LATENT_BLOCK == 1024
    assert batching.latent_block(64) == 64 and batching.latent_block(96) == 96


@pytest.mark.parametrize("hook", [None, True])
def test_the_rows_streamed_are_counted_from_the_lengths(
        weights, small_blocks, monkeypatch, hook):
    """``server_latent_rows_streamed_total``: where the tick reads by the
    kernel, each active slot's own blocks of `latent_block` rows; where it
    gathers, nothing. The rows SELECTED keep their counter either way."""
    monkeypatch.setattr(FA, "_INTERPRET", hook)
    monkeypatch.setattr(batching, "LATENT_BLOCK", 16)
    telemetry.enable()
    try:
        eng = engine(weights, slots=2, max_len=64)
        streamed, read = (tm.get(n) for n in (
            "server_latent_rows_streamed_total",
            "server_attn_rows_read_total"))
        s0, r0 = streamed.value, read.value
        # three ticks; slot 0 begins them at 14, 15, 16 rows (its query one
        # more: 15, 16, 17 seen: 1, 1, 2 blocks of 16), slot 1 is inactive
        # in the last and begins at 40, 41 (41, 42 seen: 3 blocks each)
        lengths = np.array([[14, 40], [15, 41], [16, 42]], np.int32)
        active = np.array([[1, 1], [1, 1], [1, 0]], bool)
        eng._count_attn_rows(lengths, active, 1)
        assert streamed.value - s0 == ((1 + 1 + 2 + 3 + 3) * 16 if hook
                                       else 0)
        assert read.value - r0 == 15 + 16 + 16 + 16 + 16
    finally:
        telemetry.disable()


def test_the_slot_holds_two_rows_a_position_and_counts_what_it_reads(
        weights, small_blocks):
    telemetry.enable()
    try:
        _counts_what_it_reads(weights)
    finally:
        telemetry.disable()


def _counts_what_it_reads(weights):
    eng = engine(weights, slots=2, max_len=64)
    assert tm.get("server_kv_stack_bytes").value == \
        eng.k.nbytes + eng.v.nbytes
    cfg = eng.cfg
    assert eng.k.shape == (LAYERS, 2, 64, 16 + 4)
    assert eng.v.shape == (LAYERS, 2, 64, 8)
    assert batching.cache_read(cfg, eng.params["layers"], True) == "select"
    scored, read = (tm.get(n) for n in ("server_index_rows_scored_total",
                                        "server_attn_rows_read_total"))
    moe = [tm.get(n) for n in batching.MOE_COUNTERS]
    eng.prefill("a", np.arange(20, dtype=np.int32)[None])
    s0, r0, m0 = scored.value, read.value, [m.value for m in moe]
    eng.decode_burst({"a": burst_entry(3)}, 4)
    # four ticks at lengths 20 .. 23: three blocks of 8 keys to the longest
    # (and only) active slot, both slots computing; 16 rows selected a tick
    assert scored.value - s0 == 4 * 3 * 8 * 2
    assert read.value - r0 == 4 * TOPK
    total, held, hit, slots = (m.value - b for m, b in zip(moe, m0))
    # one active row a tick, two expert layers, two choices of eight
    assert total == 4 * 2 * 2 and slots == 4 * 2 * 4
    assert 0 <= hit <= held <= total and hit <= slots


# -- the four weights that rest with the contracted axis last ----------------

def published_kn(weights, cfg, layer):
    """``wqb`` / ``wkva`` / ``wkvb`` / ``wiq`` of ``layer`` as the importer
    held them until PR 57: ``[in, out]``, the published matrix TURNED OVER,
    the rotated dims (interleaved pairs as published) moved to the halves
    `ops.rotary` rotates."""
    att = f"model.layers.{layer}.self_attn."
    nope, r, di = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.index_head_dim
    halves = lambda n: list(range(0, n, 2)) + list(range(1, n, 2))
    q_rows = [h * (nope + r) + j for h in range(cfg.num_heads)
              for j in list(range(nope)) + [nope + j for j in halves(r)]]
    iq_rows = [h * di + j for h in range(cfg.index_n_heads)
               for j in halves(r) + list(range(r, di))]
    kl = cfg.kv_lora_rank
    kva_rows = list(range(kl)) + [kl + j for j in halves(r)]
    w = lambda name: np.asarray(weights[att + name], np.float32)
    return {"wqb": w("q_b_proj.weight")[q_rows].T,
            "wkva": w("kv_a_proj_with_mqa.weight")[kva_rows].T,
            "wkvb": w("kv_b_proj.weight").T,
            "wiq": w("indexer.wq_b.weight")[iq_rows].T}


def old_decode_read(cfg, wkvb, q, rows, scores, p):
    """A decode step's read as `_attend_latent` wrote it until PR 57
    (``wkvb`` ``[kv_lora_rank, H x (nope + v)]``, sliced on its minor axis):
    top_k, the selected rows, the absorbed products."""
    kl, nope, vd = cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.v_head_dim
    w = wkvb.reshape(kl, cfg.num_heads, nope + vd)
    dt = q["nope"].dtype
    _, sel = jax.lax.top_k(scores, min(cfg.index_topk, rows.shape[1]))
    got = jnp.take_along_axis(rows, sel[..., None], axis=1)   # [S, k, .]
    c_kv = got[..., :kl].astype(dt)
    k_r = got[..., kl:kl + q["rope"].shape[-1]].astype(dt)
    q_abs = jnp.einsum("shn,lhn->shl", q["nope"][:, 0],
                       w[..., :nope].astype(dt))
    sc = (jnp.einsum("shl,skl->shk", q_abs, c_kv,
                     preferred_element_type=jnp.float32)
          + jnp.einsum("shr,skr->shk", q["rope"][:, 0], k_r,
                       preferred_element_type=jnp.float32)
          ) * cfg.head_dim ** -0.5
    probs = jax.nn.softmax(jnp.where(
        (sel <= p)[:, None, :], sc, batching.NEG_INF), axis=-1)
    o_lat = jnp.einsum("shk,skl->shl", probs.astype(dt), c_kv)
    out = jnp.einsum("shl,lhv->shv", o_lat, w[..., nope:].astype(dt))
    return out.reshape(rows.shape[0], 1, -1)


def old_chunk_read(cfg, wkvb, q, rows, chosen):
    """A prefill chunk's EXPANDED form with the old weight, dense: every row
    of the slot through ``"kl,lhe->khe"``, one softmax under ``chosen``."""
    kl, nope, vd = cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.v_head_dim
    dt = q["nope"].dtype
    w = wkvb.reshape(kl, cfg.num_heads, nope + vd).astype(dt)
    got = rows[0].astype(dt)
    kv = jnp.einsum("kl,lhe->khe", got[:, :kl], w)
    sc = (jnp.einsum("thn,khn->htk", q["nope"][0], kv[..., :nope],
                     preferred_element_type=jnp.float32)
          + jnp.einsum("thr,kr->htk", q["rope"][0],
                       got[:, kl:kl + q["rope"].shape[-1]],
                       preferred_element_type=jnp.float32)
          ) * cfg.head_dim ** -0.5
    probs = jax.nn.softmax(jnp.where(chosen[None], sc, batching.NEG_INF), -1)
    out = jnp.einsum("htk,khv->htv", probs.astype(dt), kv[..., nope:],
                     preferred_element_type=jnp.float32)
    return out.transpose(1, 0, 2).reshape(1, chosen.shape[0], -1).astype(dt)


@pytest.mark.parametrize("dtype,tol", [("float32", 0.0), ("bfloat16", 2e-2)])
def test_the_weights_as_published_give_what_the_turned_over_ones_gave(
        weights, small_blocks, monkeypatch, dtype, tol):
    """The import of a seeded checkpoint, whose ``wqb_t`` / ``wkvb_t`` /
    ``wiq_t`` rest ``[heads, rows a head, in]`` and ``wkva_t`` ``[out,
    in]``, against the SAME checkpoint's ``[in, out]`` forms pushed through
    the expressions the layer had until PR 57
    (written out above and here): `_latent_proj`'s outputs, a decode step's
    read on BOTH arms, a prefill chunk's. ``qk_nope_head_dim`` is 12: a
    head's key rows end in the middle of a tile. float32: the queries and
    the index key bit-equal; the latent row (``wkva_t``'s 20 outputs, which
    the CPU's product sums in another order than ``[in, out]``'s) and the
    reads to float32's rounding; bfloat16: inside its rounding."""
    monkeypatch.setattr(FA, "_INTERPRET", True)
    monkeypatch.setattr(batching, "LATENT_BLOCK", 16)
    cfg = small_config()
    params = hf_import.convert_state_dict(cfg, weights, dtype=dtype)
    layer = 1                                   # the first expert layer
    attn = jax.tree.map(lambda x: x[0], params["layers"]["attn"])
    old = {k: jnp.asarray(v, dtype)
           for k, v in published_kn(weights, cfg, layer).items()}
    heads = {cfg.num_heads * cfg.head_dim: cfg.num_heads,
             cfg.index_n_heads * cfg.index_head_dim: cfg.index_n_heads}
    rng = np.random.default_rng(57)
    normal = lambda *shape: jnp.asarray(rng.standard_normal(shape), dtype)
    close = lambda got, want: np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        atol=max(tol, 2e-6) * float(jnp.abs(want.astype(jnp.float32)).max()))

    def projections(a, positions, turned_over):
        """`_latent_proj`, or the same with the three products as they
        were: ``x @ w`` of the ``[in, out]`` weights, ``wqb``'s and
        ``wiq``'s ``.reshape(.., H, Dh)``."""
        rope = batching.make_rope(cfg, positions)
        if not turned_over:
            return batching._latent_proj(cfg, attn, a, rope)
        with monkeypatch.context() as m:
            m.setattr(batching, "_dot_t", lambda x, w: (
                (x @ w).reshape(*x.shape[:-1], heads[w.shape[-1]], -1)
                if w.shape[-1] in heads else x @ w))
            return batching._latent_proj(
                cfg, {**attn, "wqb_t": old["wqb"], "wiq_t": old["wiq"],
                      "wkva_t": old["wkva"]}, a, rope)

    # a decode step: 8 slots of 64 rows, one query row a slot
    slots, m = len(LAST), 64
    lengths, active = jnp.asarray(LAST), jnp.asarray(ACTIVE)
    a = normal(slots, 1, cfg.hidden_size)
    q, row, key = projections(a, lengths[:, None], False)
    q0, row0, key0 = projections(a, lengths[:, None], True)
    for exact, got, want in ((True, q, q0), (False, row, row0),
                             (True, key, key0)):
        for x, y in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            assert x.shape == y.shape and x.dtype == y.dtype
            if tol or not exact:
                close(x, y)
            else:
                np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    width = cfg.kv_lora_rank + cfg.qk_rope_head_dim
    stack = normal(2, slots, m, width)
    index = normal(2, slots, m, cfg.index_head_dim)
    keys = batching._CacheLayer(index, 1, batching.index_blocks(
        lengths, active, m, jnp))
    plan = FA.read_plan(batching.attn_blocks(
        lengths, active, 1, m, jnp, per_slot=True, block=16), lengths + 1,
        m // 16)
    scores = jnp.where(
        jnp.arange(m)[None, :] <= lengths[:, None],
        batching._index_scores(q, index[1])[:, 0], batching.NEG_INF)
    want = old_decode_read(cfg, old["wkvb"], q0, stack[1], scores,
                           lengths[:, None])
    for blocks in (plan, keys.blocks):      # the kernel's arm, the gather's
        got = batching._attend_latent(
            cfg, {"attn": attn}, q, batching._CacheLayer(stack, 1, blocks),
            keys, lengths[:, None, None])
        assert got.shape == want.shape and got.dtype == want.dtype
        close(got[ACTIVE], want[ACTIVE])

    # a prefill chunk: 16 rows at positions 30 .. 45 of a 64-row slot
    t, p0 = 16, 30
    positions = p0 + jnp.arange(t, dtype=jnp.int32)[None]
    a = normal(1, t, cfg.hidden_size)
    q, _, _ = projections(a, positions, False)
    q0, _, _ = projections(a, positions, True)
    rows, ikeys = stack[0, :1], index[0, :1]
    causal = jnp.arange(m)[None, :] <= positions[0][:, None]
    scores = jnp.where(causal, batching._index_scores(q, ikeys)[0],
                       batching.NEG_INF)
    want = old_chunk_read(cfg, old["wkvb"], q0, rows,
                          causal & batching.select_topk(scores, TOPK))
    got = batching._attend_latent(cfg, {"attn": attn}, q, rows, ikeys,
                                  positions[0][:, None])
    assert got.shape == want.shape and got.dtype == want.dtype
    close(got, want)


def test_random_init_and_the_import_build_the_same_tree(weights):
    """Keys, shapes and dtypes, both layer stacks: what `init_params` draws
    is what `convert_state_dict` makes of a checkpoint, the three weights
    by head among them."""
    cfg = small_config()
    drawn = jax.eval_shape(
        lambda: init_params(jax.random.PRNGKey(0), cfg, jnp.float32))
    made = hf_import.convert_state_dict(cfg, weights, dtype=jnp.float32)
    form = lambda tree: jax.tree.map(lambda x: (x.shape, str(x.dtype)), tree)
    for stack in ("dense_layers", "layers"):
        assert form(drawn[stack]) == form(made[stack]), stack
    attn = made["layers"]["attn"]
    h, hi = cfg.num_heads, cfg.index_n_heads
    assert attn["wqb_t"].shape == (LAYERS - 1, h, cfg.head_dim,
                                   cfg.q_lora_rank)
    assert attn["wkvb_t"].shape == (
        LAYERS - 1, h, cfg.qk_nope_head_dim + cfg.v_head_dim,
        cfg.kv_lora_rank)
    assert attn["wiq_t"].shape == (LAYERS - 1, hi, cfg.index_head_dim,
                                   cfg.q_lora_rank)
    assert attn["wkva_t"].shape == (
        LAYERS - 1, cfg.kv_lora_rank + cfg.qk_rope_head_dim, cfg.hidden_size)
    assert not {"wqb", "wkva", "wkvb", "wiq"} & set(attn)


def test_the_importer_permutes_interleaved_pairs_to_halves():
    half = hf_import._half_layout(8)
    assert list(half) == [0, 2, 4, 6, 1, 3, 5, 7]
    cfg = hf_import.config_from_hf(type("C", (), dict(
        HF, num_hidden_layers=5, max_position_embeddings=512,
        num_key_value_heads=4))())
    assert (cfg.kv_lora_rank, cfg.index_topk, cfg.first_k_dense,
            cfg.num_experts, cfg.head_dim, cfg.rope_theta,
            cfg.held_experts) == (16, TOPK, 1, 8, 16, 1e6, (0, 8))


def test_every_other_engine_refuses_the_family_by_name(weights):
    cfg = small_config()
    why = config.single_pass_unsupported(cfg, "this engine")
    assert "latent row of 16 + 4" in why and "index key of 8" in why
    assert config.custom_engine_unsupported(cfg) == why
    for name in ("glm5", "glm5-rehearsal"):
        assert config.single_pass_unsupported(
            config.get_config(name), "x") is not None
    params = hf_import.convert_state_dict(cfg, weights, dtype=jnp.float32)
    part = StagePlan.even(cfg.num_layers, 3).stages[0]
    with pytest.raises(NotImplementedError, match="latent row"):
        slice_stage_params(cfg, params, part)
    with pytest.raises(NotImplementedError, match="latent row"):
        batching.BatchedStageExecutor(cfg, part, params, slots=1, max_len=32)
    whole = StagePlan.even(cfg.num_layers, 1).stages[0]
    with pytest.raises(NotImplementedError, match="prefix cache"):
        batching.BatchedStageExecutor(cfg, whole, params, slots=1,
                                      max_len=32, prefix_cache_bytes=1 << 20)
    eng = engine(weights, slots=1, max_len=32)
    eng.prefill("a", np.arange(4, dtype=np.int32)[None])
    with pytest.raises(NotImplementedError, match="speculative verify"):
        eng.decode_batch({"a": np.zeros((1, 3), np.int32)})
    with pytest.raises(NotImplementedError, match="latent row"):
        StageExecutor(cfg, whole, params)


def test_main_refuses_before_a_weight_is_made():
    base = ["--model", "glm5-rehearsal", "--num_layers", "2"]
    parse = main.build_parser().parse_args
    ok = parse(base + ["--mode", "serve", "--stage", "0", "--batched"])
    main._refuse_unheld_state(ok, main.load_config(ok))      # the one home
    for more in (["--mode", "serve", "--stage", "0"],
                 ["--mode", "serve", "--stage", "1", "--batched"],
                 ["--mode", "serve", "--stage", "0", "--batched",
                  "--prefix_cache_mb", "8"],
                 ["--mode", "local"], ["--mode", "oracle"],
                 ["--mode", "fused"]):
        args = parse(base + more)
        with pytest.raises(SystemExit, match="latent row"):
            main._refuse_unheld_state(args, main.load_config(args))
    with pytest.raises(SystemExit):
        main.load_config(parse(["--model", "glm5", "--num_layers", "79"]))
    cfg = main.load_config(parse(["--model", "glm5", "--num_layers", "6"]))
    assert (cfg.num_layers, cfg.first_k_dense, cfg.vocab_size,
            cfg.held_experts, cfg.num_experts, cfg.hidden_size) == (
        6, 1, 19360, (0, 16), 256, 6144)
    assert dataclasses.replace(cfg, num_layers=2).num_layers == 2
