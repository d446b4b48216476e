"""Two kinds of latent layer ALTERNATING in one stack (dots3-note-prev,
``dots3_note``) on the batched stage engine, at a small size with seeded
weights, against the benchmark's plain reference
(``perfbench/references/dots3_plain.py``, which imports nothing of the
program): chunked prefill, decode steps and burst rounds through all THREE
stacks (a full layer's latent rows and index keys at the slot's length, a
sliding layer's latent rows as a ring) across the ring's wrap, the window's
edge and the selection's edge, a prompt shorter than the window, a rewind
inside the ring and one past it; the absorbed form of a decode step against
the expanded form of a prefill chunk for BOTH geometries; the program's
layer order against ``layer_types``; the stacks' shapes; the counters; and
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

TOPK, WINDOW, RING = 24, 17, 128
TYPES = list(config.dots3_layer_types(9))
HF = dict(
    model_type="dots3_note", hidden_size=64, intermediate_size=96,
    moe_intermediate_size=32, num_attention_heads=4, q_lora_rank=32,
    kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=8,
    rope_theta=8e7, attention_gate_type="headwise",
    index_n_heads=2, index_head_dim=16, index_topk=TOPK,
    sliding_window_size=WINDOW, swa_num_attention_heads=2,
    swa_q_lora_rank=24, swa_kv_lora_rank=24, swa_qk_nope_head_dim=12,
    swa_qk_rope_head_dim=4, swa_v_head_dim=8, swa_rope_theta=5e4,
    swa_attention_gate_type="headwise", apply_mla_qkv_lora_rescale=True,
    layer_types=TYPES, n_routed_experts=8, num_experts_per_tok=2,
    n_shared_experts=1, vocab_size=97, first_k_dense_replace=1,
    rms_norm_eps=1e-5, routed_scaling_factor=1.0, experts_held=4)
LAYERS = 5                      # the dense layer and ONE whole period


def small_config(layers=LAYERS, **kw):
    return config.dots3_config(TYPES, **{**dict(
        vocab_size=97, hidden_size=64, num_layers=layers, num_heads=4,
        intermediate_size=96, max_position_embeddings=4096, rope_theta=8e7,
        q_lora_rank=32, kv_lora_rank=16, qk_nope_head_dim=8,
        qk_rope_head_dim=8, v_head_dim=8, index_n_heads=2, index_head_dim=16,
        index_topk=TOPK, sliding_window_size=WINDOW, swa_num_heads=2,
        swa_q_lora_rank=24, swa_kv_lora_rank=24, swa_qk_nope_head_dim=12,
        swa_qk_rope_head_dim=4, swa_v_head_dim=8, n_routed_experts=8,
        num_experts_per_tok=2, moe_intermediate_size=32, first_k_dense=1,
        experts_held=(0, 4)), **kw})


@pytest.fixture(scope="module")
def weights():
    return reference_weights("dots3", HF, 9, 7)


@pytest.fixture(scope="module")
def ids():
    return np.random.default_rng(0).integers(0, 97, (200,)).astype(np.int32)


@pytest.fixture(scope="module")
def want(weights, ids):
    """The reference's logits of the 200-row sequence, five layers deep."""
    return reference_logits("dots3", HF, LAYERS, weights, ids)


@pytest.fixture
def small_blocks(monkeypatch):
    """Chunks of 16 prompt rows and blocks of 16 index keys: a 150-row
    prompt is ten chunks, its last one a bucket, and wraps the 128-row
    ring."""
    monkeypatch.setattr(batching, "LATENT_CHUNK", 16)
    monkeypatch.setattr(batching, "INDEX_BLOCK", 16)


def engine(weights, *, slots=2, max_len=256, cfg=None):
    return reference_engine(cfg or small_config(), weights, slots=slots,
                            max_len=max_len)


def logits_of(eng, h):
    return np.asarray(eng.logits(h), np.float32)[0]


def burst_entry(token, generated=()):
    return greedy_entry(token, generated=generated)


@pytest.mark.parametrize("n,steps", [(5, 20), (16, 4), (17, 4), (23, 4),
                                     (40, 4), (120, 12), (150, 6)])
def test_prefill_and_decode_steps_agree_with_the_reference(
        weights, ids, want, small_blocks, n, steps):
    """Prompts under the window (17 rows), at it, at the selection's edge
    (24), past both, and past the ring's 128 rows; decode steps that cross
    the window's edge (from 5), and the ring's wrap (from 120)."""
    eng = engine(weights)
    got = logits_of(eng, eng.prefill("a", ids[None, :n]))
    np.testing.assert_allclose(got, want[:n], atol=3e-5)
    for j in range(n, n + steps):
        out = eng.decode_batch({"a": ids[None, j:j + 1]})
        np.testing.assert_allclose(logits_of(eng, out["a"])[0], want[j],
                                   atol=3e-5)


def test_two_periods_run_in_the_published_order(weights, ids, small_blocks):
    """Nine layers: the dense layer and TWO periods, the pattern repeating
    inside the program; a prompt, then steps over the ring's wrap."""
    cfg = small_config(9)
    assert cfg.layer_period == (1, 2, 3)
    want = reference_logits("dots3", HF, 9, weights, ids[:140])
    eng = engine(weights, cfg=cfg)
    got = logits_of(eng, eng.prefill("a", ids[None, :125]))
    np.testing.assert_allclose(got, want[:125], atol=5e-5)
    for j in range(125, 140):
        out = eng.decode_batch({"a": ids[None, j:j + 1]})
        np.testing.assert_allclose(logits_of(eng, out["a"])[0], want[j],
                                   atol=5e-5)


def scan_order(cfg, params):
    """The checkpoint's layer index of every layer the scans visit, in
    their order, by the layer's own first norm: ``[(kind, ln1 weight)]``."""
    out = []
    for layers, first in batching._layer_groups(params):
        if not isinstance(layers, batching._Period):
            out += [("full", w) for w in np.asarray(layers["ln1"]["w"])]
            continue
        periods, n = layers.counts
        full = np.asarray(layers.full["ln1"]["w"])
        sliding = np.asarray(layers.sliding["ln1"]["w"]).reshape(
            periods, n, -1)
        for p in range(periods):
            out.append(("full", full[p]))
            out += [("sliding", w) for w in sliding[p]]
    return out


@pytest.mark.parametrize("layers", [5, 9])
def test_the_program_s_layer_order_is_layer_types(weights, layers):
    cfg = small_config(layers)
    params = hf_import.convert_state_dict(cfg, weights, dtype=jnp.float32)
    order = scan_order(cfg, params)
    assert [kind + "_attention" for kind, _ in order] == TYPES[:layers]
    for i, (_, ln1) in enumerate(order):
        np.testing.assert_array_equal(ln1, np.asarray(
            weights[f"model.layers.{i}.input_layernorm.weight"]))
    # ... and a sliding layer has the other geometry and no indexer
    attn = params["sliding_layers"]["attn"]
    assert attn["wqb_t"].shape[1:] == (2, 16, 24)
    assert attn["wkva_t"].shape[1:] == (24 + 4, 64)
    assert attn["wgate"].shape[1:] == (64, 2)
    assert not {"wiq_t", "wik", "wiw"} & set(attn)
    assert params["layers"]["attn"]["wgate"].shape[1:] == (64, 4)


@pytest.mark.parametrize("layers", [6, 7, 8])
def test_a_stack_that_ends_inside_a_period_is_refused(layers):
    with pytest.raises(NotImplementedError, match="whole periods"):
        small_config(layers).layer_period


def test_burst_rounds_two_slots_and_rewinds(weights, small_blocks):
    """Two sessions side by side (one under the window and the selection's
    edge, one past the ring's wrap), greedy burst rounds judged on the
    reference's rows; a rewind inside the ring gives the same tokens again;
    one past what the ring still holds is refused."""
    rng = np.random.default_rng(3)
    lens = {"a": 11, "b": 131}
    seqs = {k: rng.integers(0, 97, (n,)).astype(np.int32)
            for k, n in lens.items()}
    eng = engine(weights)
    for sid, seq in seqs.items():
        eng.prefill(sid, seq[None])
    consumed = {k: [int(t) for t in v] for k, v in seqs.items()}
    fed = {"a": 5, "b": 9}
    emitted = {k: [] for k in seqs}
    for _ in range(4):              # 16 ticks: "a" crosses rows 17 and 24
        res = eng.decode_burst(
            {sid: burst_entry(tok) for sid, tok in fed.items()}, 4)
        for sid, r in res.items():
            assert len(r["tokens"]) == 4
            consumed[sid] += [fed[sid]] + r["tokens"][:-1]
            emitted[sid] += r["tokens"]
            fed[sid] = r["tokens"][-1]
    for sid, seq in consumed.items():
        want = reference_logits("dots3", HF, LAYERS, weights,
                                np.asarray(seq, np.int32))
        assert list(want[lens[sid]:].argmax(-1)) == emitted[sid], sid
    # a rewind is a length: a ring row past it is outside every window
    # until it is rewritten
    eng.rewind("a", lens["a"])
    eng.rewind("b", lens["b"])
    again = eng.decode_burst({"a": burst_entry(5), "b": burst_entry(9)}, 4)
    assert again["a"]["tokens"] == emitted["a"][:4]
    assert again["b"]["tokens"] == emitted["b"][:4]
    assert again["b"]["cache_len"] == lens["b"] + 4
    # "b" holds 135 positions: the window at 20 needs row 4, which position
    # 132 overwrote; the window at 23 needs row 7, which no position has
    with pytest.raises(batching.WindowGone, match="overwritten"):
        eng.rewind("b", 20)
    eng.rewind("b", 23)
    with pytest.raises(ValueError):
        eng.rewind("b", 24)


def test_a_rewind_far_back_inside_a_slot_that_never_wrapped(weights, ids,
                                                            want):
    eng = engine(weights)
    eng.prefill("a", ids[None, :100])
    eng.rewind("a", 3)
    out = eng.decode_batch({"a": ids[None, 3:4]})
    np.testing.assert_allclose(logits_of(eng, out["a"])[0], want[3],
                               atol=3e-5)


@pytest.mark.parametrize("n", [9, 33, 140])
def test_the_absorbed_step_is_the_expanded_chunk(weights, ids, small_blocks,
                                                 n):
    """One function of the rows, for BOTH geometries (every layer of the
    stack is between the two): the last row of a prompt through the prefill
    chunk's EXPANDED form equals the same token through a decode step's
    ABSORBED form: under the window, past it and the selection's edge, and
    past the ring's wrap."""
    whole, stepped = engine(weights), engine(weights)
    expanded = logits_of(whole, whole.prefill("a", ids[None, :n]))[-1]
    stepped.prefill("a", ids[None, :n - 1])
    out = stepped.decode_batch({"a": ids[None, n - 1:n]})
    np.testing.assert_allclose(logits_of(stepped, out["a"])[0], expanded,
                               atol=3e-5)


@pytest.mark.parametrize("geometry", ["full", "sliding"])
def test_one_layer_of_each_geometry_absorbed_against_expanded(geometry):
    """`_attend_latent` / `_attend_window` on one layer alone: a decode
    step's ABSORBED read of the stack against a prefill chunk's EXPANDED
    read of the same rows, eight slots at lengths on both sides of the
    window, the selection and the ring's wrap."""
    cfg = small_config()
    kind = cfg.sliding_kind if geometry == "sliding" else cfg
    rng = np.random.default_rng(5)

    def normal(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.float32)

    row = kind.kv_lora_rank + kind.qk_rope_head_dim
    h = kind.num_heads
    lp = {"attn": {"wkvb_t": normal(
        h, kind.qk_nope_head_dim + kind.v_head_dim, kind.kv_lora_rank)}}
    m = 160
    rows = normal(m, row)                       # position p's latent row
    keys = jnp.round(normal(m, cfg.index_head_dim) * 2) / 2
    last = [3, 16, 17, 23, 24, 100, 130, 159]   # the query's own position
    for t in last:
        q = {"nope": normal(1, 1, h, kind.qk_nope_head_dim),
             "rope": normal(1, 1, h, kind.qk_rope_head_dim)}
        if geometry == "full":
            q.update(iq=jnp.round(normal(1, 1, 2, cfg.index_head_dim)),
                     iw=jnp.ones((1, 1, 2), jnp.float32))
            # expanded: the slot's layer with row t written, one query row
            expanded = batching._attend_latent(
                kind, lp, q, rows[None, :t + 1], keys[None, :t + 1],
                jnp.full((1, 1), t, jnp.int32))
            stack = rows[None, None]
            absorbed = batching._attend_latent(
                kind, lp, q,
                batching._CacheLayer(stack, 0, jnp.int32(m // 16)),
                batching._CacheLayer(keys[None, None], 0,
                                     batching.index_blocks(
                                         np.array([t]), np.array([True]), m,
                                         jnp)),
                jnp.full((1, 1, 1), t, jnp.int32))
        else:
            # the ring after position t: p at row p % RING
            held = np.arange(max(0, t - RING + 1), t + 1)
            ring = jnp.zeros((RING, row)).at[held % RING].set(rows[held])
            absorbed = batching._attend_window(
                kind, lp, q, batching._CacheLayer(ring[None, None], 0, None),
                jnp.full((1, 1, 1), t, jnp.int32))
            # expanded: the ring BEFORE t beside t's own fresh row
            before = held[:-1]
            ring0 = jnp.zeros((RING, row)).at[before % RING].set(
                rows[before])
            expanded = batching._attend_window(
                kind, lp, q, batching._RingChunk(
                    ring0[None], rows[None, t:t + 1], jnp.int32(t)),
                jnp.full((1, 1), t, jnp.int32))
        np.testing.assert_allclose(np.asarray(absorbed),
                                   np.asarray(expanded), atol=2e-5,
                                   rtol=2e-5, err_msg=f"{geometry} {t}")


@pytest.mark.parametrize("window,max_len,rows", [
    (513, 16384, 640), (512, 16384, 640), (65, 1800, 128), (17, 256, 128),
    (513, 256, 256), (127, 4096, 128), (128, 4096, 256)])
def test_the_ring_is_the_window_rounded_up_with_room_for_a_rewind(
        window, max_len, rows):
    assert batching.ring_rows(window, max_len) == rows


@pytest.mark.parametrize("max_len", [256, 1024])
def test_a_sliding_layer_s_stack_has_no_axis_of_the_slot_s_length(
        weights, max_len):
    """THREE stacks: latent rows and index keys of the two full layers at
    the slot's length, the three sliding layers' rings at 128 rows
    whatever the slot holds."""
    telemetry.enable()
    try:
        eng = engine(weights, slots=3, max_len=max_len)
        assert isinstance(eng.k, batching._LatentStacks)
        assert eng.k.rows.shape == (2, 3, max_len, 16 + 8)
        assert eng.v.shape == (2, 3, max_len, 16)
        assert eng.k.ring.shape == (3, 3, RING, 24 + 4)
        assert max_len not in eng.k.ring.shape
        assert tm.get("server_kv_stack_bytes").value == sum(
            x.nbytes for x in (eng.k.rows, eng.k.ring, eng.v))
    finally:
        telemetry.disable()


def test_a_ring_row_padded_to_lane_tiles_changes_nothing(
        weights, ids, small_blocks, monkeypatch):
    """Where the backend would not keep a 28-number row minor the stacks
    hold it padded (the v5e: 1088 -> 1152, 576 -> 640): the programs read a
    row's own numbers whatever the pad."""
    plain = engine(weights)
    monkeypatch.setattr(batching, "kv_fold_width", lambda *a: 32)
    padded = engine(weights)
    assert plain.k.ring.shape[-1] == 28 and padded.k.ring.shape[-1] == 32
    a = logits_of(plain, plain.prefill("a", ids[None, :140]))
    b = logits_of(padded, padded.prefill("a", ids[None, :140]))
    np.testing.assert_allclose(a, b, atol=1e-6)
    for j in range(140, 144):
        x, y = (logits_of(e, e.decode_batch({"a": ids[None, j:j + 1]})["a"])
                for e in (plain, padded))
        np.testing.assert_allclose(x, y, atol=1e-6)


def test_the_counters_of_the_window_and_of_the_rows_held(weights,
                                                         small_blocks):
    telemetry.enable()
    try:
        eng = engine(weights, slots=2, max_len=256)
        names = ("server_window_rows_read_total",
                 "server_window_rows_span_total",
                 "server_state_rows_held_total",
                 "server_positions_held_total",
                 "server_attn_rows_read_total",
                 "server_index_rows_scored_total")
        series = [tm.get(n) for n in names]
        moe = [tm.get(n) for n in batching.MOE_COUNTERS]
        eng.prefill("a", np.arange(10, dtype=np.int32)[None])
        eng.prefill("b", np.arange(150, dtype=np.int32)[None] % 97)
        before = [s.value for s in series]
        m0 = [m.value for m in moe]
        eng.decode_burst({"a": burst_entry(3), "b": burst_entry(4)}, 4)
        read, span, held, pos, sel, scored = (
            s.value - b for s, b in zip(series, before))
        # four ticks, both slots' whole rings; "a"'s window holds 11 .. 14
        # rows, "b"'s the full 17
        assert read == 4 * 2 * RING
        assert span == (11 + 12 + 13 + 14) + 4 * WINDOW
        # the round left 14 and 154 positions: a full layer holds each, a
        # sliding layer 14 and the ring's 128; two full and three sliding
        assert pos == 14 + 154
        assert held == int((2 * 14 + 3 * 14) / 5 + (2 * 154 + 3 * 128) / 5)
        # ONE full layer's, as a family of one kind counts them
        assert sel == (11 + 12 + 13 + 14) + 4 * TOPK
        assert scored == 4 * 10 * 16 * 2       # ten blocks of 16 keys
        total, got, hit, slots = (m.value - b for m, b in zip(moe, m0))
        # two active rows a tick, FOUR expert layers, two choices of eight
        assert total == 4 * 2 * 4 * 2 and slots == 4 * 4 * 4
        assert 0 <= hit <= got <= total and hit <= slots
    finally:
        telemetry.disable()


def test_random_init_and_the_import_build_the_same_tree(weights):
    cfg = small_config(9)
    drawn = jax.eval_shape(
        lambda: init_params(jax.random.PRNGKey(0), cfg, jnp.float32))
    made = hf_import.convert_state_dict(cfg, weights, dtype=jnp.float32)
    form = lambda tree: jax.tree.map(lambda x: (x.shape, str(x.dtype)), tree)
    for stack in ("dense_layers", "layers", "sliding_layers"):
        assert form(drawn[stack]) == form(made[stack]), stack
    assert jax.tree.leaves(made["sliding_layers"])[0].shape[0] == 6
    assert jax.tree.leaves(made["layers"])[0].shape[0] == 2


def test_the_published_config_is_read_key_for_key():
    cfg = hf_import.config_from_hf(type("C", (), dict(
        HF, num_hidden_layers=9, max_position_embeddings=4096,
        num_key_value_heads=4, tie_word_embeddings=False))())
    assert cfg.model_type == "dots3_note"
    kind = cfg.sliding_kind
    assert (cfg.kv_lora_rank, cfg.index_topk, cfg.num_heads, cfg.head_dim,
            cfg.rope_theta, cfg.attention_gate, cfg.lora_rescale) == (
        16, TOPK, 4, 16, 8e7, True, True)
    assert (kind.kv_lora_rank, kind.q_lora_rank, kind.index_topk,
            kind.num_heads, kind.head_dim, kind.v_head_dim, kind.rope_theta,
            kind.sliding_window, kind.attention_gate) == (
        24, 24, 0, 2, 16, 8, 5e4, WINDOW, True)
    assert not cfg.sliding_window and cfg.sliding_window_size == WINDOW
    assert cfg.layer_kinds == tuple(t.split("_")[0] for t in TYPES)


def test_every_other_engine_refuses_the_family_by_name(weights):
    cfg = small_config()
    why = config.single_pass_unsupported(cfg, "this engine")
    assert "layers of two kinds alternate" in why
    assert "ring of 17 latent rows of 24 + 4" in why
    assert "latent row of 16 + 8" in why and "index key of 16" in why
    assert config.custom_engine_unsupported(cfg) == why
    for name in ("dots3", "dots3-rehearsal"):
        assert "ring" in config.single_pass_unsupported(
            config.get_config(name), "x")
    params = hf_import.convert_state_dict(cfg, weights, dtype=jnp.float32)
    part = StagePlan.even(cfg.num_layers, 5).stages[0]
    with pytest.raises(NotImplementedError, match="two kinds"):
        slice_stage_params(cfg, params, part)
    with pytest.raises(NotImplementedError, match="two kinds"):
        batching.BatchedStageExecutor(cfg, part, params, slots=1, max_len=32)
    whole = StagePlan.even(cfg.num_layers, 1).stages[0]
    with pytest.raises(NotImplementedError, match="prefix cache"):
        batching.BatchedStageExecutor(cfg, whole, params, slots=1,
                                      max_len=32, prefix_cache_bytes=1 << 20)
    eng = engine(weights, slots=1, max_len=32)
    eng.prefill("a", np.arange(4, dtype=np.int32)[None])
    with pytest.raises(NotImplementedError, match="speculative verify"):
        eng.decode_batch({"a": np.zeros((1, 3), np.int32)})
    with pytest.raises(NotImplementedError, match="two kinds"):
        StageExecutor(cfg, whole, params)


def test_main_refuses_before_a_weight_is_made():
    base = ["--model", "dots3-rehearsal", "--num_layers", "5"]
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
        with pytest.raises(SystemExit, match="two kinds"):
            main._refuse_unheld_state(args, main.load_config(args))
    cfg = main.load_config(parse(["--model", "dots3", "--num_layers", "9"]))
    assert (cfg.num_layers, cfg.first_k_dense, cfg.vocab_size,
            cfg.held_experts, cfg.num_experts, cfg.hidden_size,
            cfg.layer_period, cfg.sliding_window_size, cfg.index_topk,
            cfg.num_experts_per_tok) == (
        9, 1, 19008, (0, 16), 256, 5120, (1, 2, 3), 513, 2048, 8)
    assert dataclasses.replace(cfg, num_layers=5).layer_period == (1, 1, 3)
