"""A family whose older rows are summaries (EvaByte's EVA attention) on the
batched stage engine, against the plain reference the benchmark keeps
(``perfbench/references/evabyte_plain.py``, which imports nothing of the
program): prefill, batched decode steps and burst ticks across chunk and
window edges, at tiny widths with ``window_size`` 32 and ``chunk_size`` 4
so that windows close often.

Tolerances, and why. ``ERR`` is the largest |engine - reference| over the
largest |reference| of the compared rows. In float32 (the suite pins
``highest`` matmul precision) the two differ by summation order only:
measured 7e-7 - 6e-6, the limit 5e-5. In bfloat16 the engine rounds its
weights' products, K/V rows and summaries to 8 bits of mantissa where the
reference computes in float32 on the same bfloat16-rounded weights:
measured 0.02 - 0.04, the limit 0.1; a wrong row or a wrong window reads
0.3 and more. The two broken-mechanism engines are held to the float32
limit: past row W they have to read over it."""

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
    config as config_mod,
    hf_import,
    quant,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.models.partition import (
    StagePlan,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.models.transformer import (
    init_params,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.ops import (
    slot_attention,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime import (
    batching,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime.batching import (
    BatchedStageExecutor,
    BatchingStageAdapter,
    windowed_blocks,
    windowed_rows,
    WindowGone,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime.executor import (
    StageExecutionError,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.runtime.messages import (
    SamplingParams,
    StageRequest,
)
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.telemetry import (
    catalog as tm,
)

from engines import (
    engine,
    reference_engine,
    reference_logits,
    reference_weights,
)

W, C, LAYERS, VOCAB, SLOTS, MAX_LEN = 32, 4, 2, 50, 8, 160
HF = {"hidden_size": 64, "intermediate_size": 96, "num_attention_heads": 4,
      "num_key_value_heads": 4, "vocab_size": VOCAB, "num_pred_heads": 2,
      "window_size": W, "chunk_size": C, "rms_norm_eps": 1e-5,
      "rope_theta": 100000, "init_std": 0.2, "num_hidden_layers": LAYERS,
      "max_position_embeddings": 4096, "tie_word_embeddings": False,
      "model_type": "evabyte", "attention_class": "eva"}
LIMIT = {"float32": 5e-5, "bfloat16": 0.1}
GREEDY = {"seed": 0, "eos": None, "temperature": 0.0, "top_p": 1.0,
          "top_k": 0, "repetition_penalty": 1.0}


def tiny_cfg(**kw):
    return config_mod.evabyte_config(
        vocab_size=VOCAB, hidden_size=64, num_layers=LAYERS, num_heads=4,
        num_kv_heads=4, intermediate_size=96, max_position_embeddings=4096,
        rope_theta=100000.0, window_size=W, chunk_size=C, num_pred_heads=2,
        **kw)


def make_engine(dtype, *, slots=SLOTS, max_len=MAX_LEN, seed=7, quantise=None,
                **kw):
    weights = reference_weights("evabyte", HF, LAYERS, seed, dtype)
    return reference_engine(tiny_cfg(), weights, dtype, quantise, slots=slots,
                            max_len=max_len, **kw), weights


_RIGS = {}


def rig_of(name):
    """One engine a dtype for the whole file: its programs compile once."""
    if name not in _RIGS:
        eng, weights = make_engine(jnp.dtype(name))
        _RIGS[name] = {"eng": eng, "weights": weights, "name": name,
                       "limit": LIMIT[name]}
    return _RIGS[name]


@pytest.fixture
def rig(request):
    """float32 unless the test's ``dtype`` parameter says otherwise: every
    case runs in float32, the edges again in bfloat16."""
    return rig_of(request.node.callspec.params.get("dtype", "float32")
                  if hasattr(request.node, "callspec") else "float32")


def both(*values):
    """(dtype, value) cases in float32; a test adds its bfloat16 ones."""
    return [("float32", v) for v in values]


def want_logits(weights, ids):
    return reference_logits("evabyte", HF, LAYERS, weights, ids, bucket=W)


def err(got, want):
    return float(np.abs(np.asarray(got, np.float32) - want).max()
                 / np.abs(want).max())


def ids_of(n, seed=0):
    return np.random.default_rng(seed).integers(0, VOCAB, (n,)).astype(
        np.int32)


def clear(eng):
    for sid in list(eng._slot_of):
        eng.end_session(sid)


# -- prefill -----------------------------------------------------------------

@pytest.mark.parametrize("dtype, n", both(
    5, W - 1, W, W + 1, 2 * W, 2 * W + C - 1, 3 * W + 7, MAX_LEN) + [
    ("bfloat16", W - 1), ("bfloat16", W + 1), ("bfloat16", 3 * W + 7)])
def test_prefill_is_the_reference_at_every_row(rig, dtype, n):
    """Under, at and over one and several windows: every row of the
    prompt, so a window's queries saw their own rows and exactly the
    summaries of the windows before."""
    eng = rig["eng"]
    clear(eng)
    ids = ids_of(n, n)
    h = eng.prefill("a", ids[None])
    assert h.shape[:2] == (1, n) and int(eng.lengths[eng.slot("a")]) == n
    assert err(eng.logits(h)[0], want_logits(rig["weights"], ids)) \
        < rig["limit"]


def test_a_prompt_runs_through_a_bounded_set_of_shapes():
    """Whole windows and a bucketed tail: 13 prompt lengths, three program
    shapes, and nothing compiles for a length once they are built."""
    eng, _ = make_engine(jnp.float32, slots=2,   # its own: compiles counted
                         make=BatchedStageExecutor)
    assert eng.window_shapes() == [8, 16, W]
    for n in (3, 8, 9, 16, 17, W, W + 1, W + 9, 2 * W, 2 * W + 16,
              3 * W + 31, 4 * W, MAX_LEN):
        eng.prefill("a", ids_of(n)[None])
    assert eng._prefill_jit._cache_size() == 3


# -- decode steps and bursts across the edges --------------------------------

@pytest.mark.parametrize("dtype, n", both(C - 2, W - 3, 2 * W - 2, 3 * W + 1)
                         + [("bfloat16", W - 3), ("bfloat16", 2 * W - 2)])
def test_decode_steps_cross_a_chunk_and_a_window_edge(rig, dtype, n):
    """Prefill to just under an edge, then single steps over it: each
    step's row is the reference's full forward at that position."""
    eng = rig["eng"]
    clear(eng)
    steps = 2 * C + 3
    ids = ids_of(n + steps, 100 + n)
    want = want_logits(rig["weights"], ids)
    eng.prefill("a", ids[None, :n])
    for j in range(steps):
        out = eng.decode_batch({"a": ids[None, n + j:n + j + 1]})
        assert err(eng.logits(out["a"])[0, 0], want[n + j]) < rig["limit"], j


def burst_through(eng, prompts, rounds, ticks=16):
    """Greedy bursts: what every session consumed, and where each emitted
    token is judged (the position of the token consumed just before)."""
    consumed = {sid: [int(t) for t in ids] for sid, ids in prompts.items()}
    judged = {sid: [] for sid in prompts}
    nxt = {sid: 1 + i for i, sid in enumerate(prompts)}
    for _ in range(rounds):
        res = eng.decode_burst(
            {sid: dict(GREEDY, token=nxt[sid], budget=ticks,
                       generated=(nxt[sid],)) for sid in prompts}, ticks)
        for sid in prompts:
            toks = res[sid]["tokens"]
            start = len(consumed[sid])
            judged[sid] += [(start + j, t) for j, t in enumerate(toks)]
            consumed[sid] += [nxt[sid]] + toks[:-1]
            nxt[sid] = (toks[-1] + 1) % VOCAB
            assert res[sid]["cache_len"] == len(consumed[sid])
    return consumed, judged


def off_argmax(weights, consumed, judged):
    """Tokens that are not the reference's best at their position by more
    than a near-tie (a gap over 2% of the row's spread)."""
    want = want_logits(weights, np.asarray(consumed, np.int32))
    bad = 0
    for pos, tok in judged:
        row = want[pos]
        bad += (row.max() - row[tok]) > 0.02 * (row.max() - row.min())
    return bad


@pytest.mark.parametrize("dtype, n", both(W - 7, 2 * W - 5)
                         + [("bfloat16", 2 * W - 5)])
def test_a_16_tick_burst_crosses_a_chunk_and_a_window_edge(rig, dtype, n):
    """The edge falls in the MIDDLE of the burst's scan: ticks before it
    write the old window's last rows and close its last chunk, the tick
    after it reads those summaries."""
    eng = rig["eng"]
    clear(eng)
    prompt = ids_of(n, 200 + n)
    eng.prefill("a", prompt[None])
    consumed, judged = burst_through(eng, {"a": prompt}, rounds=2)
    assert len(judged["a"]) >= 16 and len(consumed["a"]) > n + 16
    assert off_argmax(rig["weights"], consumed["a"], judged["a"]) == 0


def test_eight_slots_at_different_phases_share_a_round(rig):
    """One burst round over seven sessions at seven phases of window and
    chunk, the eighth slot idle: each session's tokens are the
    reference's, and the idle slot's rows are not touched."""
    eng = rig["eng"]
    clear(eng)
    lens = [3, C - 1, W - 2, W + C, 2 * W - 9, 2 * W + 1, 3 * W - 1]
    idle = ids_of(W + 5, 999)
    eng.prefill("idle", idle[None])
    at = eng.slot("idle")
    before = jax.tree.map(lambda x: np.asarray(x[:, at]).copy(),
                          (eng.k, eng.v))
    prompts = {f"s{i}": ids_of(n, 300 + i) for i, n in enumerate(lens)}
    for sid, ids in prompts.items():
        eng.prefill(sid, ids[None])
    consumed, judged = burst_through(eng, prompts, rounds=1)
    for sid in prompts:
        assert off_argmax(rig["weights"], consumed[sid], judged[sid]) == 0
    after = jax.tree.map(lambda x: np.asarray(x[:, at]), (eng.k, eng.v))
    for was, now in zip(jax.tree.leaves(before), jax.tree.leaves(after)):
        np.testing.assert_array_equal(was, now)
    assert int(eng.lengths[at]) == W + 5
    # and the idle session goes on as if alone
    more = ids_of(4, 998)
    want = want_logits(rig["weights"], np.concatenate([idle, more]))
    for j in range(4):
        out = eng.decode_batch({"idle": more[None, j:j + 1]})
        assert err(eng.logits(out["idle"])[0, 0],
                   want[W + 5 + j]) < rig["limit"]


def test_a_reused_slot_carries_nothing_over(rig):
    """A long session leaves exact rows and summaries behind; the next
    session in that slot reads none of them."""
    eng = rig["eng"]
    clear(eng)
    eng.prefill("old", ids_of(4 * W + 9, 1)[None])
    slot = eng.slot("old")
    eng.end_session("old")
    ids = ids_of(2 * W + 6, 2)
    while eng.slot("new") != slot:      # take the same slot again
        eng.end_session("new")
        eng._free.sort(key=lambda s: s != slot)
        eng._free.reverse()
        eng.prefill("new", ids[None, :W - 2])
    want = want_logits(rig["weights"], ids)
    for j in range(W - 2, len(ids)):
        out = eng.decode_batch({"new": ids[None, j:j + 1]})
        assert err(eng.logits(out["new"])[0, 0], want[j]) < rig["limit"], j


# -- rewind --------------------------------------------------------------------

def test_rewind_inside_a_window_is_a_fresh_prefill_to_that_length(rig):
    eng = rig["eng"]
    clear(eng)
    ids = ids_of(2 * W + 20, 5)
    eng.prefill("a", ids[None, :2 * W + 14])
    eng.rewind("a", 2 * W + 3)          # back over two closed chunks
    other = ids_of(2 * W + 20, 6)
    seq = np.concatenate([ids[:2 * W + 3], other[2 * W + 3:]])
    want = want_logits(rig["weights"], seq)
    for j in range(2 * W + 3, len(seq)):
        out = eng.decode_batch({"a": seq[None, j:j + 1]})
        assert err(eng.logits(out["a"])[0, 0], want[j]) < rig["limit"], j


@pytest.mark.parametrize("length, to", [(2 * W + 5, 2 * W - 1),
                                        (2 * W + 5, 3), (W + 1, W - 1),
                                        (2 * W, W - 1)])
def test_rewind_across_a_window_edge_is_refused_by_name(rig, length, to):
    eng = rig["eng"]
    clear(eng)
    eng.prefill("a", ids_of(length)[None])
    with pytest.raises(WindowGone) as exc:
        eng.rewind("a", to)
    text = str(exc.value)
    assert "exact rows" in text and "prefill" in text
    assert "evabyte" not in text.lower()
    assert int(eng.lengths[eng.slot("a")]) == length
    eng.rewind("a", length)             # no move is no rewind
    eng.rewind("a", (length - 1) // W * W)   # the window's first row is held


# -- the comparison can see the mechanism --------------------------------------

def _zeroed(cfg, lp, k, v):
    return jnp.zeros_like(k[..., 0, :, :]), jnp.zeros_like(v[..., 0, :, :])


def _own_window_too(cfg, q_pos):
    return (q_pos // cfg.eva_window + 1) * (cfg.eva_window // cfg.eva_chunk)


@pytest.mark.parametrize("name, patch", [
    ("summaries zeroed", ("_pool_chunks", _zeroed)),
    ("summaries of its own window seen", ("_summaries_visible",
                                          _own_window_too))])
def test_a_broken_mechanism_fails_the_same_comparison(monkeypatch, name,
                                                      patch):
    """Up to row W both broken engines ARE the reference's (an engine that
    sees its own window's summaries is not: it reads the rows of chunks
    not closed yet); past it each reads over the limit, in prefill and in
    decode: the comparison can see the mechanism."""
    monkeypatch.setattr(batching, *patch)
    eng, weights = make_engine(jnp.float32, slots=2)
    ids = ids_of(2 * W + 12, 11)
    want = want_logits(weights, ids)
    got = np.asarray(eng.logits(eng.prefill("a", ids[None, :2 * W + 4]))[0])
    if patch[0] == "_pool_chunks":
        assert err(got[:W], want[:W]) < LIMIT["float32"]
    assert err(got[W:], want[W:2 * W + 4]) > 100 * LIMIT["float32"]
    for j in range(2 * W + 4, len(ids)):
        out = eng.decode_batch({"a": ids[None, j:j + 1]})
        assert err(eng.logits(out["a"])[0, 0],
                   want[j]) > 100 * LIMIT["float32"]


# -- the state, its bounds and its counters ------------------------------------

def test_a_slot_holds_a_window_and_a_summary_row_a_chunk():
    cfg = tiny_cfg()
    assert windowed_rows(cfg, MAX_LEN) == (W, 4 * (W // C))
    assert windowed_rows(cfg, W) == (W, 0)
    assert windowed_rows(cfg, W + 1) == (W, W // C)
    assert windowed_rows(cfg, 20) == (20, 0)
    big = config_mod.get_config("evabyte")
    assert windowed_rows(big, 16384) == (2048, 16384 // 16 - 128)
    eng, _ = make_engine(jnp.bfloat16, slots=3)
    assert eng.k.exact.shape == eng.v.exact.shape == (LAYERS, 3, W, 4, 16)
    assert eng.k.sums.shape == eng.v.sums.shape == (LAYERS, 3, 32, 4, 16)


def test_the_read_bounds_are_one_function_for_program_and_host():
    big = config_mod.get_config("evabyte")
    ROWS = windowed_rows(big, 16384)
    lengths = np.asarray([[100, 2047, 2048, 5000, 16383, 0, 9000, 4096]])
    active = np.asarray([[True] * 5 + [False] + [True] * 2])
    exact, sums = windowed_blocks(big, lengths, active, ROWS)
    # longest phase 2047 -> 16 blocks; most earlier windows 7 -> 7 blocks
    assert (int(exact[0]), int(sums[0])) == (16, 7)
    only = np.zeros_like(active)
    only[0, 0] = True
    exact, sums = windowed_blocks(big, lengths, only, ROWS)
    assert (int(exact[0]), int(sums[0])) == (1, 0)   # touches no summary
    only[0, 0], only[0, 7] = False, True             # row 0 of window 2
    exact, sums = windowed_blocks(big, lengths, only, ROWS)
    assert (int(exact[0]), int(sums[0])) == (1, 2)
    traced = jax.jit(lambda l, a: windowed_blocks(big, l, a, ROWS, jnp))(
        lengths, active)
    assert [int(x[0]) for x in traced] == [16, 7]
    none = windowed_blocks(big, lengths, np.zeros_like(active), ROWS)
    assert [int(x[0]) for x in none] == [0, 0]
    # a slot at a time (the kernel's plan): ceil((p % 2048 + 1) / 128) of
    # the window stack, p // 2048 blocks of 128 summaries, the idle slot 0
    own = [[1, 16, 1, 8, 16, 0, 7, 1], [0, 0, 1, 2, 7, 0, 4, 2]]
    exact, sums = windowed_blocks(big, lengths, active, ROWS, per_slot=True)
    assert [list(exact[0]), list(sums[0])] == own
    traced = jax.jit(lambda l, a: windowed_blocks(
        big, l, a, ROWS, jnp, per_slot=True))(lengths, active)
    assert [list(np.asarray(x[0])) for x in traced] == own
    short = windowed_blocks(big, lengths % 2048, active, (2048, 0),
                            per_slot=True)
    assert list(short[0][0]) == own[0] and not short[1].any()


# Positions of four slots: all in their first window (no summary is
# visible: the summary read makes no trip), in the middle of later windows,
# and on the two sides of a window's edge.
PHASES = {"first-window": [0, 5, 17, W - 1],
          "mid-window": [W + 9, 2 * W + 17, 11, 2 * W + 8],
          "window-edge": [W - 1, W, 2 * W - 1, 2 * W]}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("phase", sorted(PHASES))
def test_the_two_loops_merge_into_one_softmax(monkeypatch, phase, dtype):
    """`_attend_windowed`'s decode read at 8-row blocks (four of the window
    stack, two of 16 summary rows), both stacks NaN past their bound:
    ONE softmax per query over rows ``0 .. p % W`` of its window and the
    summaries of its earlier windows, against that softmax over the dense
    rows in float64."""
    import types

    monkeypatch.setattr(batching, "ATTN_BLOCK", 8)
    cfg = types.SimpleNamespace(
        num_heads=2, num_kv_heads=2, head_dim=8, query_scale=0.0,
        attn_softcap=0.0, sliding_window=None, eva_window=W, eva_chunk=C)
    rows = (W, 2 * (W // C))
    pos = np.asarray(PHASES[phase], np.int32)
    active = np.asarray([True] * 4)
    s = len(pos)
    kq, *keys = jax.random.split(jax.random.PRNGKey(3), 5)
    q = jax.random.normal(kq, (s, 1, 2, 8)).astype(dtype)
    stacks = [jax.random.normal(key, (2, s, n, 2, 8)).astype(dtype)
              for key, n in zip(keys, rows + rows)]      # K, K~, V, V~
    bounds = [int(n) * 8 for n in windowed_blocks(cfg, pos, active, rows)]
    assert bounds == {"first-window": [W, 0], "mid-window": [24, 16],
                      "window-edge": [W, 16]}[phase]
    dirty = [x.at[:, :, bound:].set(jnp.nan)
             for x, bound in zip(stacks, bounds + bounds)]

    @jax.jit
    def read(q, k, ks, v, vs):
        blocks = windowed_blocks(cfg, jnp.asarray(pos), jnp.asarray(active),
                                 rows, jnp)
        layer = lambda own, sums: batching._WindowedRead(   # noqa: E731
            batching._CacheLayer(own, jnp.int32(1), blocks[0]),
            batching._CacheLayer(sums, jnp.int32(1), blocks[1]))
        return batching._attend_windowed(
            cfg, {}, q, layer(k, ks), layer(v, vs),
            jnp.asarray(pos)[:, None, None])

    got = np.asarray(read(q, *dirty), np.float32)
    assert got.shape == (s, 1, 16) and np.isfinite(got).all()
    k, ks, v, vs = (np.asarray(x[1], np.float64) for x in stacks)
    for i, p in enumerate(pos):
        n_own, n_sum = p % W + 1, p // W * (W // C)
        keys_i = np.concatenate([k[i, :n_own], ks[i, :n_sum]])   # [n, 2, 8]
        vals_i = np.concatenate([v[i, :n_own], vs[i, :n_sum]])
        sc = np.einsum("hd,nhd->hn", np.asarray(q[i, 0], np.float64),
                       keys_i) * 8 ** -0.5
        w = np.exp(sc - sc.max(-1, keepdims=True))
        want = np.einsum("hn,nhd->hd", w / w.sum(-1, keepdims=True), vals_i)
        tol = 2e-6 if dtype == "float32" else 2e-2
        np.testing.assert_allclose(got[i, 0], want.reshape(-1), atol=tol,
                                   rtol=tol, err_msg=f"slot {i} at {p}")


def test_the_decode_loops_run_a_window_s_blocks_not_the_slot_s():
    """One query row a head at the published rows (a 16384-position slot:
    2048 exact rows and 896 summary rows a layer): the decode step holds
    TWO loops over blocks beside the layer scan and no conditional, one
    around 128-row slices of the window stacks and one around 128-row
    slices of the summary stacks, their trip counts `windowed_blocks`'
    (at most 16 and 7, whatever the slot's length in positions: nothing in
    the program is 16384 long)."""
    cfg = dataclasses.replace(config_mod.get_config("evabyte"),
                              num_layers=1, hidden_size=256, num_heads=2,
                              num_kv_heads=2, intermediate_size=64)
    params = init_params(jax.random.PRNGKey(0), cfg, jnp.bfloat16)
    spec = StagePlan.even(1, 1).stages[0]
    eng = engine(cfg, spec, params, slots=2, max_len=16384, dtype=jnp.bfloat16)
    assert eng._cache_read(1, False) == "loop"
    text = eng._build_decode(1).lower(
        eng.params, jnp.zeros((2, 1), jnp.int32),
        jnp.zeros((2,), jnp.int32), jnp.ones((2,), bool), eng.k,
        eng.v).as_text()
    assert text.count("stablehlo.case") == 0
    assert text.count("stablehlo.while") == 3       # the layers, two reads
    assert "16384" not in text
    reads = [line.split(":", 1)[1] for line in text.splitlines()
             if "stablehlo.dynamic_slice" in line and "x2x128x2x128x" in line]
    for rows in (2048, 896):        # a block of K and of V out of each stack
        assert sum(f"x{rows}x2x128x" in line for line in reads) == 2, reads
    assert len(reads) == 4
    big = config_mod.get_config("evabyte")
    last = np.asarray([[16383, 2047]])
    assert [int(n[0]) for n in windowed_blocks(
        big, last, last > 0, windowed_rows(big, 16384))] == [16, 7]


def test_the_counters_follow_the_bounds():
    telemetry.enable()
    try:
        _counters_follow_the_bounds()
    finally:
        telemetry.disable()


def _counters_follow_the_bounds():
    eng, _ = make_engine(jnp.float32, slots=2)
    got = lambda name: tm.get(name).value
    base = {n: got(n) for n in (
        "server_attn_rows_read_total", "server_attn_summary_rows_read_total",
        "server_kv_chunks_summarised_total",
        "server_kv_positions_written_total", "server_state_rows_held_total",
        "server_positions_held_total", "server_attn_rows_span_total")}
    n = 2 * W + 2
    eng.prefill("a", ids_of(n)[None])
    moved = lambda name: got(name) - base[name]
    assert moved("server_kv_chunks_summarised_total") == n // C
    assert moved("server_kv_positions_written_total") == n
    for j in range(3):        # positions n, n + 1 (closes a chunk), n + 2
        eng.decode_batch({"a": ids_of(1)[None]})
    assert moved("server_kv_chunks_summarised_total") == n // C + 1
    assert moved("server_kv_positions_written_total") == n + 3
    # ONE block a stack here: 3 ticks x 2 slots x (32 exact | 32 summary)
    assert moved("server_attn_rows_read_total") == 3 * 2 * W
    assert moved("server_attn_summary_rows_read_total") == 3 * 2 * 32
    assert moved("server_attn_rows_span_total") == 3 * 2 * MAX_LEN
    # per round: 2 windows of 8 summaries + the rows in use of the third
    held = sum(16 + (n + j) % W + 1 for j in range(3))
    assert moved("server_state_rows_held_total") == held
    assert moved("server_positions_held_total") == sum(
        n + 1 + j for j in range(3))


def test_the_counters_follow_each_slot_s_own_blocks_under_the_kernel(
        monkeypatch):
    """Heads that fill the lanes (``head_dim`` 128) and the kernel engaged:
    "a" is two rows into its third window and sees 16 summaries, "b" is in
    its first window and sees none. ONE 32-row block a stack: every tick
    reads a's and b's window block (2 x 32) and a's summary block (32),
    where the shared bound reads 2 slots x 32 of each; the rows that come
    back are the loop engine's."""
    cfg = config_mod.evabyte_config(
        vocab_size=VOCAB, hidden_size=256, num_layers=LAYERS, num_heads=2,
        num_kv_heads=2, intermediate_size=96, max_position_embeddings=4096,
        rope_theta=100000.0, window_size=W, chunk_size=C, num_pred_heads=2)
    params = init_params(jax.random.PRNGKey(5), cfg, jnp.float32)
    spec = StagePlan.even(LAYERS, 1).stages[0]
    got = lambda name: tm.get(name).value                    # noqa: E731
    names = ("server_attn_rows_read_total",
             "server_attn_summary_rows_read_total")
    telemetry.enable()
    try:
        seen = []
        for hook in (True, None):
            monkeypatch.setattr(slot_attention, "_INTERPRET", hook)
            eng = engine(cfg, spec, params, slots=2, max_len=MAX_LEN,
                         dtype=jnp.float32)
            assert eng._cache_read(1, False) == (
                "kernel" if hook else "loop")
            eng.prefill("a", ids_of(2 * W + 2)[None])
            eng.prefill("b", ids_of(5, 1)[None])
            base = [got(n) for n in names]
            rows = [eng.decode_batch({"a": ids_of(1, j)[None],
                                      "b": ids_of(1, j + 9)[None]})
                    for j in range(3)]
            moved = [got(n) - b for n, b in zip(names, base)]
            # then a 16-tick burst in which a new "b" (W - 3 = 29 rows)
            # crosses its window's edge and "a" closes four chunks
            eng.end_session("b")
            eng.prefill("b", ids_of(W - 3, 1)[None])
            seen.append((rows, moved, burst_through(
                eng, {"a": ids_of(2 * W + 5), "b": ids_of(W - 3, 1)}, 1)))
    finally:
        telemetry.disable()
    (rows, by_kernel, burst), (want, shared, burst_want) = seen
    assert burst == burst_want
    assert by_kernel == [3 * 2 * W, 3 * 1 * 32]
    assert shared == [3 * 2 * W, 3 * 2 * 32]
    for tick, ref_tick in zip(rows, want):
        for sid in "ab":
            np.testing.assert_allclose(np.asarray(tick[sid]),
                                       np.asarray(ref_tick[sid]),
                                       atol=2e-5, rtol=2e-5)


# -- the importer, the control, the refusals -------------------------------------

def test_hf_import_round_trip_of_the_published_names():
    class Cfg:
        pass

    hf_cfg = Cfg()
    for key, val in HF.items():
        setattr(hf_cfg, key, val)
    cfg = hf_import.config_from_hf(hf_cfg)
    assert cfg == tiny_cfg()
    assert (cfg.eva_window, cfg.eva_chunk, cfg.pred_heads,
            cfg.norm_offset, cfg.fp32_residual) == (W, C, 2, True, True)
    weights = reference_weights("evabyte", HF, LAYERS, 3)
    assert {n.split(".", 3)[-1] for n in weights if ".layers.0." in n} == {
        "input_layernorm.weight", "post_attention_layernorm.weight",
        "self_attn.q_proj.weight", "self_attn.k_proj.weight",
        "self_attn.v_proj.weight", "self_attn.o_proj.weight",
        "self_attn.adaptive_mu_k", "self_attn.adaptive_phi",
        "mlp.gate_proj.weight", "mlp.up_proj.weight",
        "mlp.down_proj.weight"}
    params = hf_import.convert_state_dict(cfg, weights, dtype=jnp.float32)
    attn = params["layers"]["attn"]
    assert attn["mu"].shape == attn["phi"].shape == (LAYERS, 4, 16)
    np.testing.assert_array_equal(
        attn["mu"][1], np.asarray(
            weights["model.layers.1.self_attn.adaptive_mu_k"]).reshape(4, 16))
    np.testing.assert_array_equal(
        attn["phi"][0], np.asarray(
            weights["model.layers.0.self_attn.adaptive_phi"]).reshape(4, 16))
    assert params["lm_head"]["w"].shape == (64, 2 * VOCAB)    # both heads
    np.testing.assert_array_equal(
        params["lm_head"]["w"], np.asarray(weights["lm_head.weight"]).T)
    same = jax.tree.structure(init_params(jax.random.PRNGKey(0), cfg))
    assert jax.tree.structure(params) == same


def test_the_int8_control_leaves_the_vectors_alone():
    eng, _ = make_engine(jnp.bfloat16, slots=2, quantise="int8")
    attn = eng.params["layers"]["attn"]
    assert isinstance(attn["wqkv"], quant.QuantizedTensor)
    for name in ("mu", "phi"):
        assert isinstance(attn[name], jax.Array)
        assert attn[name].dtype == jnp.bfloat16
    ids = ids_of(W + 9, 21)
    h = eng.prefill("a", ids[None])      # it runs, and is not the bf16 engine
    assert np.isfinite(np.asarray(eng.logits(h))).all()


def test_steps_of_several_rows_are_refused_by_name():
    eng, _ = make_engine(jnp.float32, slots=2)
    eng.prefill("a", ids_of(6)[None])
    with pytest.raises(NotImplementedError) as exc:
        eng.decode_batch({"a": ids_of(3)[None]})
    assert "window's edge" in str(exc.value) and "prefill" in str(exc.value)


def test_the_prefix_cache_is_refused_by_name():
    cfg = tiny_cfg()
    params = init_params(jax.random.PRNGKey(0), cfg)
    spec = StagePlan.even(LAYERS, 1).stages[0]
    with pytest.raises(NotImplementedError) as exc:
        engine(cfg, spec, params, slots=2, max_len=64,
               prefix_cache_bytes=1 << 20)
    assert "older rows are summaries" in str(exc.value)
    assert "prefix cache" in str(exc.value)


def test_the_one_predicate_names_the_state_and_not_the_model():
    cfg = tiny_cfg()
    reason = config_mod.single_pass_unsupported(cfg, "the fused engine")
    assert "older rows are summaries" in reason and "fused engine" in reason
    assert f"{W} exact K/V rows" in reason and "evabyte" not in reason.lower()
    assert config_mod.custom_engine_unsupported(cfg) is not None
    plain = dataclasses.replace(cfg, eva_window=0, eva_chunk=0)
    assert config_mod.single_pass_unsupported(plain, "x") is None


def test_the_adapter_serves_prefill_and_bursts_and_warms_every_shape():
    """Through `BatchingStageAdapter`: the warm-up builds every prefill
    shape, the decode step and the burst; a session then prefills and
    bursts across a window edge and nothing is built for it."""
    eng, weights = make_engine(jnp.float32, slots=2,     # its own: the
                               make=BatchedStageExecutor)  # compiles counted
    adapter = BatchingStageAdapter(eng, window_s=0.0)
    adapter.warmup(burst=16)
    built = (eng._prefill_jit._cache_size(),
             eng._burst_jits[16]._cache_size())
    assert built == (3, 1)
    prompt = ids_of(2 * W - 6, 31)
    sampling = SamplingParams(temperature=0.0)
    first = adapter.forward(StageRequest(
        session_id="s", hidden=jnp.asarray(prompt[None]), seq_len=len(prompt),
        cur_len=0, is_prefill=True, max_length=MAX_LEN, sampling=sampling,
        step_seed=0))
    want = want_logits(weights, prompt)
    assert first.token_id == int(want[-1].argmax())
    resp = adapter.forward(StageRequest(
        session_id="s", hidden=jnp.asarray([[first.token_id]]), seq_len=1,
        cur_len=len(prompt), is_prefill=False, max_length=MAX_LEN,
        sampling=sampling, step_seed=1, burst_len=16, burst_budget=16,
        generated_tokens=(first.token_id,)))
    toks = list(resp.burst_tokens)
    consumed = [int(t) for t in prompt] + [first.token_id] + toks[:-1]
    judged = [(len(prompt) + j, t) for j, t in enumerate(toks)]
    assert len(toks) >= 8 and off_argmax(weights, consumed, judged) == 0
    assert (eng._prefill_jit._cache_size(),
            eng._burst_jits[16]._cache_size()) == built
    # a step of several rows (a draft block, a replay chunk) is refused
    with pytest.raises(StageExecutionError) as exc:
        adapter.forward(StageRequest(
            session_id="s", hidden=jnp.zeros((1, 3), jnp.int32), seq_len=3,
            cur_len=resp.cache_len, is_prefill=False, is_replay=True,
            max_length=MAX_LEN, sampling=sampling, step_seed=2))
    assert "window's edge" in str(exc.value)


@pytest.mark.parametrize("argv, refused", [
    (["--mode", "serve", "--stage", "0", "--batched", "--burst", "16"], None),
    (["--mode", "serve", "--stage", "0", "--batched", "--prefix_cache_mb",
      "4"], "the prefix cache"),
    (["--mode", "serve", "--stage", "0", "--batched", "--speculative_k",
      "2"], "speculative verify"),
    (["--mode", "serve", "--stage", "1"], "the per-session executor"),
    (["--mode", "serve", "--stage", "1", "--batched"],
     "a stage server over part of the stack"),
    (["--mode", "oracle"], "--mode oracle"),
    (["--mode", "local"], "--mode local"),
    (["--mode", "fused"], "--mode fused")])
def test_the_predicate_is_asked_where_the_engine_is_chosen(argv, refused):
    """`main._refuse_unheld_state`, before a weight is made: the full-span
    batched server holds the state; every other choice is refused by the
    one predicate's text, which names the mechanism and not the model."""
    args = main.build_parser().parse_args(["--model", "evabyte", *argv])
    cfg = main.load_config(args)
    if refused is None:
        return main._refuse_unheld_state(args, cfg)
    with pytest.raises(SystemExit) as exc:
        main._refuse_unheld_state(args, cfg)
    text = str(exc.value)
    assert refused in text and "older rows are summaries" in text
    assert "evabyte" not in text.lower()
    # a family that keeps a row a position and runs once is never refused
    plain = main.build_parser().parse_args(["--model", "gpt2", *argv])
    main._refuse_unheld_state(plain, main.load_config(plain))
    # a looped stack: the oracle and the whole batched server run it (its
    # prefix cache and verify too), the others still refuse
    looped = dataclasses.replace(main.load_config(plain), loop_steps=3)
    if argv == ["--mode", "oracle"] or "0" in argv:
        main._refuse_unheld_state(plain, looped)
    else:
        with pytest.raises(SystemExit):
            main._refuse_unheld_state(plain, looped)
