"""The dots3-note-prev configuration and its cell: the file holds the
catalog row's config key for key at the top level and again under
``hf_config`` with only the four keys of ``reduced`` changed (the router's
256 among them, which stays 256: what is cut is what one chip HOLDS; and
``layer_types``, a list as long as the stack), the cell takes the
benchmark's ``doc-sat8`` mix unedited, the reference's count of a tick
charges every held expert once whatever the routing, a full layer the rows
it scores and selects and a sliding layer its window, the readers the cell
brings read their counters and find nothing in a program without them, and
the whole harness rehearses on the CPU at the family's rehearsal preset."""

import json
import os
import subprocess
import sys

import pytest

from perfbench.harness import check, readers, roofline, traffic
from perfbench.harness.manifest import WIDTH_RE, Manifest, load_module

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
CELL, CONFIG, TRAFFIC = "dots3-doc-sat8", "dots3-note-prev", "doc-sat8"
PERIOD = ["full_attention"] + ["sliding_attention"] * 3
PUBLISHED = {
    "apply_mla_qkv_lora_rescale": True, "attention_bias": False,
    "attention_gate_type": "headwise", "first_k_dense_replace": 1,
    "hidden_act": "silu", "hidden_size": 5120, "index_head_dim": 128,
    "index_n_heads": 64, "index_topk": 2048, "intermediate_size": 13824,
    "kv_lora_rank": 512,
    "layer_types": ["full_attention"] + PERIOD * 11 + ["full_attention"],
    "max_position_embeddings": 524288, "model_type": "dots3_note",
    "moe_intermediate_size": 1536, "moe_layer_freq": 1,
    "n_routed_experts": 256, "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 46, "num_key_value_heads": 128,
    "q_lora_rank": 1024, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 80000000,
    "routed_scaling_factor": 1, "scoring_func": "sigmoid",
    "sliding_window_size": 513, "swa_attention_gate_type": "headwise",
    "swa_kv_lora_rank": 1024, "swa_num_attention_heads": 64,
    "swa_num_key_value_heads": 64, "swa_q_lora_rank": 1024,
    "swa_qk_nope_head_dim": 192, "swa_qk_rope_head_dim": 64,
    "swa_rope_theta": 50000, "swa_v_head_dim": 128,
    "tie_word_embeddings": False, "topk_method": "noaux_tc",
    "v_head_dim": 128, "vocab_size": 152064}
REDUCED = ["num_hidden_layers", "layer_types", "n_routed_experts",
           "vocab_size"]
SERVED = dict(PUBLISHED, num_hidden_layers=9,
              layer_types=PUBLISHED["layer_types"][:9], vocab_size=19008)
# the issue's arithmetic, in parameters
FULL = (5120 * 1024 + 1024 * 128 * 192 + 5120 * 576 + 512 * 128 * 256
        + 128 * 128 * 5120 + 5120 * 128)                          # 134.68 M
INDEXER = 1024 * 64 * 128 + 5120 * 128 + 5120 * 64                # 9.37 M
SLIDING = (5120 * 1024 + 1024 * 64 * 256 + 5120 * 1088 + 1024 * 64 * 320
           + 64 * 128 * 5120 + 5120 * 64)                         # 90.83 M
EXPERT = 3 * 5120 * 1536                                          # 23.59 M
ROUTER = 5120 * 256
DENSE_MLP = 3 * 5120 * 13824
NEW_METRICS = ["window_read_roofline_share", "window_rows_read_share",
               "moe_roofline_share.mixed", "sparse_attn_roofline_share.mixed"]
APPENDED = ["state_rows_held_share", "kv_stack_gb", "attn_rows_read_share",
            "step_roofline_share", "index_rows_selected_share",
            "latent_rows_streamed_share", "moe_held_assignment_share",
            "moe_experts_hit_share"]


@pytest.fixture(scope="module")
def man():
    return Manifest(ROOT)


@pytest.fixture(scope="module")
def body(man):
    return man.config(CONFIG)


@pytest.fixture(scope="module")
def ref(man, body):
    return load_module(man.reference_file(body))


def test_the_benchmark_validates_with_the_new_cell(man, body):
    man.validate()
    entry = man.config_entry(CONFIG)
    assert entry["reduced"] == body["reduced"] == REDUCED
    assert not any(WIDTH_RE.search(key) for key in REDUCED)
    assert entry["source"] == body["source"] == (
        "https://huggingface.co/dots-studio/dots3-note-prev/blob/main/"
        "config.json")
    cell = man.workload(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, TRAFFIC, 1)
    # appended: the last of their lists
    assert man.data["workloads"][-1] == cell
    assert man.data["configs"][-1] == entry
    assert {m["name"] for m in man.metrics_for(CELL, "end_to_end")} == {
        "gap_p75_ms", "setup_s"}
    layer = {m["name"] for m in man.metrics_for(CELL, "per_layer")}
    assert set(NEW_METRICS) | set(APPENDED) | {
        "device_ms_per_tick", "device_idle_share",
        "client_tokens_per_s"} <= layer
    # evabyte's and the looped stack's own, the int8 kernel's, the seven
    # round-period metrics, and the two shares whose reader looks for ONE
    # stack over every layer are not this cell's
    assert not {"summary_rows_read_share", "chunks_summarised_per_position",
                "loop_exit_step_mean", "int8_kernel_roofline_share",
                "round_period_ms", "burst_launch_lag_ms",
                "moe_roofline_share", "sparse_attn_roofline_share"} & layer
    assert not any(name.endswith(".open") for name in layer)
    by_name = {m["name"]: m for m in man.data["per_layer"]}
    assert all(by_name[n]["workloads"] == [CELL] for n in NEW_METRICS)
    assert [m["name"] for m in man.data["per_layer"][-4:]] == NEW_METRICS
    assert all(by_name[n]["workloads"][-1] == CELL for n in APPENDED)
    # everywhere glm-5's cell is listed beside others, so is this
    for m in man.data["per_layer"]:
        if "glm5-doc-sat8" in m.get("workloads", ()) and len(
                m["workloads"]) > 2:
            assert CELL in m["workloads"], m["name"]
    for key in ("lora_rescale", "attention_gate", "sliding_window",
                "indexer", "rope", "init", "precision", "tensor_names"):
        assert key in body["assumed"], key
    assert set(body["not_served"]) == {
        "vision_tower", "audio_encoder", "multi_token_prediction",
        "other_engines", "experts_elsewhere"}
    dep = body["deployment"]
    assert (dep["chips_a_layer"], dep["experts_held"]) == (16, [0, 16])
    assert dep["model_args"] == ["--model", "dots3", "--num_layers", "9"]
    assert dep["servers"][0]["args"] == [
        "--mode", "serve", "--stage", "0", "--batched", "--burst", "16",
        "--slots", "8", "--max_session_len", "16384", "--dtype", "bfloat16",
        "--quant", "none"]
    chk = body["check"]
    assert (chk["layers"], chk["sessions"], chk["decode_steps"],
            chk["burst_rounds"], chk["control"]) == (5, 8, 4, 128, "int8")
    # the check holds the dense layer and ONE whole period: both kinds of
    # attention behind experts
    assert chk["layers"] == 1 + body["layer_period"] == 5
    assert SERVED["layer_types"][:5] == ["full_attention"] + PERIOD
    assert "reduced_to" not in chk
    assert set(chk["limits"]) == {"logit_rel_rms", "burst_gap"}


@pytest.mark.parametrize("key", sorted(PUBLISHED))
def test_a_published_key_is_in_the_file_at_its_value(body, key):
    """The file holds the keys twice (its "layout" says why), letter for
    letter; only three VALUES differ, and ``n_routed_experts`` stays the
    router's 256 though it is listed in ``reduced``: what is cut is what
    one chip holds. ``rope_scaling`` is published null."""
    hf = body["hf_config"]
    assert key in hf and key in body
    assert body[key] == hf[key] == SERVED[key]
    assert type(body[key]) is type(hf[key]) is type(SERVED[key])
    assert (body[key] is None) == (key == "rope_scaling")
    if SERVED[key] != PUBLISHED[key]:
        assert key in REDUCED and key in body["reduced_why"]


def test_the_file_holds_the_catalog_row(body):
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog of architectures on this machine")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "dots3-note-prev")
    assert row["config"] == PUBLISHED
    assert body["source"] == row["source_url"]
    for held in (body, body["hf_config"]):
        differs = {k for k, v in row["config"].items()
                   if held.get(k, "absent") != v}
        assert differs == set(REDUCED) - {"n_routed_experts"}
    assert set(body["hf_config"]) == set(row["config"])
    assert body["n_routed_experts"] == 256 and set(body["reduced_why"]) == \
        set(REDUCED)
    # both geometries' widths, the window, the selection and the experts a
    # token are as published
    for key in row["config"]:
        if WIDTH_RE.search(key) or key in (
                "sliding_window_size", "index_topk", "num_experts_per_tok",
                "swa_num_attention_heads", "num_attention_heads"):
            assert body[key] == row["config"][key], key


def test_the_cut_is_the_issue_s_arithmetic(body, ref):
    hf = body["hf_config"]
    assert ref.attention_params(hf, False) == FULL + INDEXER
    assert ref.attention_params(hf, True) == SLIDING
    assert FULL == pytest.approx(134.68e6, rel=1e-4)
    assert INDEXER == pytest.approx(9.37e6, rel=1e-3)
    assert SLIDING == pytest.approx(90.83e6, rel=1e-4)
    assert ref.held_experts(hf) == (0, 16)
    assert ref.layer_counts(hf, 9) == (3, 6)
    assert ref.moe_params(hf) == ROUTER + 17 * EXPERT
    dense_layer = FULL + INDEXER + DENSE_MLP
    full_layer = FULL + INDEXER + ROUTER + 17 * EXPERT
    sliding_layer = SLIDING + ROUTER + 17 * EXPERT
    assert dense_layer * 2 == pytest.approx(0.713e9, rel=2e-3)
    assert full_layer * 2 == pytest.approx(1.093e9, rel=2e-3)
    assert sliding_layer * 2 == pytest.approx(0.986e9, rel=2e-3)
    weights = (dense_layer + 2 * full_layer + 6 * sliding_layer
               + 2 * 19008 * 5120) * 2
    assert weights == pytest.approx(9.206e9, rel=2e-3)
    assert ref.row_bytes(hf) == (1152, 256, 2176)
    full_state = 3 * 8 * 16384 * (1152 + 256)
    assert full_state == pytest.approx(0.554e9, rel=2e-3)
    ring_state = 6 * 8 * 640 * 1152 * 2        # as it rests: 640 x 1152
    assert ring_state == pytest.approx(0.071e9, rel=5e-3)
    # a row a position in every layer, and one K and one V row a head
    assert (3 * 1408 + 6 * 2176) * 8 * 16384 == pytest.approx(2.27e9,
                                                              rel=5e-3)
    # (35.4 G numbers, the issue's "36 GB": 70.9 GB in bfloat16)
    assert 8 * 16384 * 2 * (3 * 128 * (192 + 128) + 6 * 64 * (256 + 128)) \
        == pytest.approx(70.9e9, rel=2e-3)
    # a whole expert layer fits no chip beside anything else
    assert (ROUTER + 257 * EXPERT) * 2 == pytest.approx(12.1e9, rel=5e-3)


@pytest.mark.parametrize("sessions, position", [
    (8.0, 300.0), (8.0, 2048.0), (6.4, 9000.0), (8.0, 15149.0)])
def test_tick_cost_charges_what_a_tick_reads(body, ref, sessions, position):
    hf = body["hf_config"]
    cost = ref.tick_cost(hf, layers=9, sessions=sessions, kv_rows=position,
                         weight_bytes=2)
    dense = FULL + INDEXER + DENSE_MLP
    full = FULL + INDEXER + ROUTER + 17 * EXPERT
    sliding = SLIDING + ROUTER + 17 * EXPERT
    assert cost["weight_bytes"] == (dense + 2 * full + 6 * sliding) * 2
    assert cost["weight_bytes"] + 19008 * 5120 * 2 == pytest.approx(
        9.01e9, rel=2e-3)                      # the issue's 9.01 GB a tick
    assert cost["head_bytes"] == 19008 * 5120 * 2
    selected, seen = min(position, 2048), min(position, 513)
    assert cost["kv_bytes"] == pytest.approx(
        3 * sessions * (position * 256 + selected * 1152)
        + 6 * sessions * seen * 2176)
    assert cost["bytes"] == (cost["weight_bytes"] + cost["head_bytes"]
                             + cost["kv_bytes"])
    assert ref.moe_tick_bytes(hf, 9) == 8 * (ROUTER + 17 * EXPERT) * 2
    assert ref.moe_tick_bytes(hf, 9) == pytest.approx(6.44e9, rel=5e-3)
    assert ref.sparse_attn_tick_bytes(hf, 9, 100.0, 10.0) == 3 * (
        100 * 256 + 10 * 1152)
    assert ref.window_tick_bytes(hf, 9, 8 * 513.0) == 6 * 8 * 513 * 2176
    assert ref.window_tick_bytes(hf, 5, 8 * 513.0) == 3 * 8 * 513 * 2176
    least, bound = roofline.roofline_s(cost, "TPU v5 lite")
    assert bound == "memory" and 0.0109 < least < 0.0116


def counters(**series):
    text = "".join(f"{k} {v}\n" for k, v in series.items())
    return {"p": readers.parse_prometheus(text)}


# Operation names as a traced run could hold them: a tick's copy of one
# sliding layer's ring out of the stack and the two products over it; then
# what the window's reader must NOT take: the ring stack's own update, a
# loop that carries it, a full layer's stack.
RING_OPS = {
    "%fusion.107 = bf16[8,640,1152]{2,1,0:T(8,128)(2,1)} fusion(bf16[6,8,640"
    ",1152]{3,2,1,0:T(8,128)(2,1)} %p.1, s32[]{:T(128)} %p.2), kind=kLoop":
        {"seconds": 0.003, "count": 96},
    "%fusion.41 = f32[8,64,640]{2,1,0:T(8,128)} fusion(bf16[8,640,1152]{2,1"
    ",0:T(8,128)(2,1)} %fusion.107, bf16[8,64,1152]{2,1,0} %p.3), "
    "kind=kOutput": {"seconds": 0.002, "count": 96},
}
OTHER_OPS = {
    "%fusion.13 = bf16[6,8,640,1152]{3,2,1,0:T(8,128)(2,1)} fusion(bf16[6,8"
    ",640,1152]{3,2,1,0:T(8,128)(2,1)} %p.1, s32[8]{0} %p.2, bf16[8,1152]{1"
    ",0} %p.3), kind=kInput": {"seconds": 0.5, "count": 96},
    "%while.134 = (s32[], bf16[8,1,5120], bf16[6,8,640,1152]) while((s32[],"
    " bf16[8,1,5120], bf16[6,8,640,1152]) %tuple.1)":
        {"seconds": 0.9, "count": 16},
    "%fusion.9 = f32[8,2048]{1,0} fusion(bf16[3,8,16384,128]{3,2,1,0} %p.1)":
        {"seconds": 0.7, "count": 48},
}


def fixture_ctx(man, body, ops=None):
    before = dict(server_window_rows_read_total=0,
                  server_window_rows_span_total=0,
                  server_state_rows_held_total=0,
                  server_positions_held_total=0,
                  server_burst_dispatches_total=0,
                  server_kv_stack_bytes=674758656)
    # 10 rounds of 16 ticks, eight active slots past row 513
    after = dict(server_window_rows_read_total=160 * 8 * 640,
                 server_window_rows_span_total=160 * 8 * 513,
                 server_state_rows_held_total=10 * 8 * 3700,
                 server_positions_held_total=10 * 8 * 10000,
                 server_burst_dispatches_total=10,
                 server_kv_stack_bytes=674758656)
    return {
        "counters_before": counters(**before),
        "counters_after": counters(**after),
        "records": [{"sent": 1.0, "due": None, "error": None,
                     "prompt_len": 9000, "deliveries": [[2.0, 16],
                                                        [3.0, 16]]}],
        "w0": 0.0, "w1": 10.0, "traffic": man.traffic(TRAFFIC),
        "config": body, "reference_file": man.reference_file(body),
        "hf": body["hf_config"], "device": {"kind": "TPU v5 lite"},
        "trace": {"programs": {"jit_burst_tick(1)": {
            "whole": 1, "count": 1, "mean_s": 0.2, "seconds": 0.2}},
            "ops": dict(ops or {})}}


def test_the_new_readers_on_a_fixture(man, body):
    ctx = fixture_ctx(man, body, {**RING_OPS, **OTHER_OPS})
    assert readers.read_metric(man, "window_rows_read_share", ctx) == \
        pytest.approx(100 * 640 / 513)
    assert readers.read_metric(man, "state_rows_held_share", ctx) == \
        pytest.approx(37.0)
    assert readers.read_metric(man, "kv_stack_gb", ctx) == pytest.approx(
        (3 * 8 * 16384 * (640 + 128) + 6 * 8 * 640 * 1152) * 2 / 1e9)
    # ONE run of 16 ticks in the trace: 16 x 6 layers x 8 x 513 rows x
    # 2176 B at 819 GB/s over the 5 ms of the two operations that read the
    # ring (not its update, the loop or a full layer's stack)
    least = 16 * 6 * 8 * 513 * 2176 / 819e9
    assert readers.read_metric(man, "window_read_roofline_share", ctx) == \
        pytest.approx(100 * least / 0.005, rel=1e-6)


@pytest.mark.parametrize("name", NEW_METRICS)
def test_a_reader_finds_nothing_in_a_program_without_its_source(
        man, body, name):
    """The parent commit has none of the series, and a trace of another
    family none of the operations: the metric is left out of the line and
    nothing raises."""
    ctx = fixture_ctx(man, body)
    ctx["counters_before"] = ctx["counters_after"] = {
        "p": readers.parse_prometheus("server_burst_tokens_total 5\n")}
    ctx["trace"]["ops"] = {"%fusion.1 = bf16[8,1,1600]{2,0,1} fusion(bf16"
                           "[48,6400,1600]{2,1,0} %p)": {"seconds": 1.0,
                                                          "count": 10}}
    assert readers.read_metric(man, name, ctx) is None
    ctx.pop("counters_after")
    ctx["trace"] = None
    assert readers.read_metric(man, name, ctx) is None


def test_the_slot_test_takes_the_cell(man, body):
    t = man.traffic(TRAFFIC)
    assert check.slot_len(body) == 16384
    assert traffic.slot_rows(t) <= check.slot_len(body)
    chk = body["check"]
    assert check.check_lengths(t, 3) == [2040, 8186, 14000]
    rows = 14000 + chk["decode_steps"] + 16 * chk["burst_rounds"] + 1
    assert rows == 16053 <= 16384
    # decode after the 2040-row prompt crosses the selection's edge, and
    # every prompt wraps the 640-row ring
    assert 2040 < 2048 <= 2040 + chk["decode_steps"] + 16 * chk[
        "burst_rounds"]
    assert min(t["prompt_lens"]) > 640
    dry = chk["dry_run_hf_config"]
    assert (dry["index_topk"], dry["sliding_window_size"],
            dry["experts_held"], dry["num_hidden_layers"]) == (256, 65, 8, 5)
    assert sum(p // 8 > 256 for p in t["prompt_lens"]) == 7
    assert min(p // 8 for p in t["prompt_lens"]) > 128      # the dry ring
    assert set(dry) == set(PUBLISHED) | {"experts_held"}
    assert dry["layer_types"] == SERVED["layer_types"][:5]


def test_the_program_s_preset_is_the_file_s(body):
    import importlib

    config = importlib.import_module(
        "global_capstone_design_distributed_inference_of_llms_over_the_"
        "internet_tpu.models.config")

    def sizes(cfg):
        kind = cfg.sliding_kind
        return (cfg.hidden_size, cfg.num_heads, cfg.intermediate_size,
                cfg.moe_intermediate_size, cfg.q_lora_rank, cfg.kv_lora_rank,
                cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim,
                cfg.index_n_heads, cfg.index_head_dim, cfg.index_topk,
                cfg.num_experts, cfg.num_experts_per_tok,
                cfg.n_shared_experts, cfg.routed_scaling_factor,
                cfg.first_k_dense, cfg.vocab_size, cfg.norm_eps,
                cfg.rope_theta, cfg.tie_word_embeddings,
                cfg.sliding_window_size, kind.num_heads, kind.q_lora_rank,
                kind.kv_lora_rank, kind.qk_nope_head_dim,
                kind.qk_rope_head_dim, kind.v_head_dim, kind.rope_theta,
                cfg.attention_gate, kind.attention_gate, cfg.lora_rescale)

    def of(hf):
        return (hf["hidden_size"], hf["num_attention_heads"],
                hf["intermediate_size"], hf["moe_intermediate_size"],
                hf["q_lora_rank"], hf["kv_lora_rank"],
                hf["qk_nope_head_dim"], hf["qk_rope_head_dim"],
                hf["v_head_dim"], hf["index_n_heads"], hf["index_head_dim"],
                hf["index_topk"], hf["n_routed_experts"],
                hf["num_experts_per_tok"], hf["n_shared_experts"],
                hf["routed_scaling_factor"], hf["first_k_dense_replace"],
                hf["vocab_size"], hf["rms_norm_eps"], hf["rope_theta"],
                hf["tie_word_embeddings"], hf["sliding_window_size"],
                hf["swa_num_attention_heads"], hf["swa_q_lora_rank"],
                hf["swa_kv_lora_rank"], hf["swa_qk_nope_head_dim"],
                hf["swa_qk_rope_head_dim"], hf["swa_v_head_dim"],
                hf["swa_rope_theta"],
                hf["attention_gate_type"] == "headwise",
                hf["swa_attention_gate_type"] == "headwise",
                hf["apply_mla_qkv_lora_rescale"])

    cfg = config.get_config("dots3")
    assert sizes(cfg) == of(body["hf_config"])
    assert cfg.num_layers == PUBLISHED["num_hidden_layers"]
    assert list(cfg.layer_types) == PUBLISHED["layer_types"]
    assert cfg.held_experts == tuple(body["deployment"]["experts_held"])
    small = config.get_config(body["dry_run_model_args"][1])
    dry = body["check"]["dry_run_hf_config"]
    assert sizes(small) == of(dry)
    assert small.held_experts == (0, dry["experts_held"])


def run_check(seed, control, cache):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=cache, PYTHONPATH=ROOT)
    env.pop("XLA_FLAGS", None)
    argv = [sys.executable, "-m", "perfbench.harness.check",
            "--config", os.path.join(ROOT, "perfbench", "configs",
                                     CONFIG + ".json"),
            "--traffic", os.path.join(ROOT, "perfbench", "traffic",
                                      TRAFFIC + ".json"),
            "--seeds", str(seed), "--dry-run-cpu"]
    res = subprocess.run(argv + (["--control"] if control else []), cwd=ROOT,
                         env=env, capture_output=True, text=True, timeout=900)
    line = next(l for l in res.stdout.splitlines() if l.startswith("CHECK "))
    return res.returncode, json.loads(line[6:])


@pytest.mark.slow
def test_the_rehearsal_s_control_reads_above_the_sound_engine(tmp_path):
    """(Outside tier-1, ``-m slow``: a rehearsal check at five layers takes
    ~165 s of a whole CPU, and the traced rehearsal below already runs one;
    on the chip the control fails ``burst_gap`` on each of six paired
    seeds: PERF.md section 6, PR 58.) The program one precision down (``--quant int8``) through the same
    drive at the rehearsal preset. The limits are set at published widths
    on the chip; here, at a quarter of every width (and 8 of 32 experts
    held), the control has to read above what the sound engine reads over
    its seeds (rows' mean error 0.0072-0.0082, ``burst_gap`` 0.00014-
    0.00021 over 4096 greedy tokens on four seeds: the traced rehearsal
    below holds the sound engine under both) by both: 0.0121 and 0.00045
    on this seed."""
    rc, control = run_check(2 ** 31 + 135, True, str(tmp_path / "cache"))
    assert control["quant"] == "int8" and control["finite"]
    assert control["layers"] == 5 and control["burst_tokens"] > 1000
    assert control["logit_rel_rms_mean"] > 0.0095
    assert control["burst_gap"] > 0.0003


@pytest.mark.slow
def test_traced_dry_run_of_the_cell(tmp_path):
    """(Outside tier-1, ``-m slow``: ~4 min of three CPU cores, a rehearsal
    check at five layers beside the served rehearsal; the engine's paths
    are held to the reference by ``tests/test_dots3_attention.py`` and the
    harness's by the other cells' rehearsals.) The whole harness on the CPU: the family's rehearsal preset at 5
    layers (the dense layer and one whole period) serves prompts of
    255-1750 rows across its 256-row selection edge and round its 128-row
    ring, the check runs against the reference, and the cell's own metrics
    are on the line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    env.pop("XLA_FLAGS", None)
    res = subprocess.run(
        [sys.executable, os.path.join(ROOT, "perfbench", "run.py"),
         "--workload", CELL, "--seed", str(2 ** 31 + 134),
         "--seconds", "4", "--trace", "1", "--dry-run-cpu",
         "--out", str(tmp_path / "out")],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=1200)
    assert res.returncode == 0, res.stdout[-3000:] + res.stderr[-2000:]
    lines = res.stdout.strip().splitlines()
    last = json.loads(lines[-1])
    assert last["cpu_dry_run"] is True and last["correct"] is True
    assert last["failed"] == 0 and last["attempted"] > 0
    metrics = last["metrics"]
    slot = 1920         # the rehearsal's slot, as glm-5's test derives it
    # two full layers x 4 slots x 1920 rows x (128 + 16 and 32 numbers),
    # three sliding layers x 4 slots x a 128-row ring x (256 + 16), bf16
    assert metrics["cpu_dry_run.kv_stack_gb"]["value"] == pytest.approx(
        (2 * 4 * slot * (128 + 16 + 32) + 3 * 4 * 128 * (256 + 16)) * 2
        / 1e9)
    with open(tmp_path / "out" / "metrics_after.jsonl") as f:
        total = readers.parse_prometheus(json.loads(f.readline())["text"])
    assert total["server_index_rows_scored_total"] > \
        total["server_attn_rows_read_total"] > 0
    assert total["server_window_rows_read_total"] > \
        total["server_window_rows_span_total"] > 0
    assert total["server_state_rows_held_total"] < \
        total["server_positions_held_total"]
    held = (total["server_moe_assignments_held_total"]
            / total["server_moe_assignments_total"])
    assert 0.15 < held < 0.35                       # 8 of 32 experts held
    assert metrics["cpu_dry_run.window_rows_read_share"]["value"] > 100
    # two of five layers hold a row a position, three a ring of 128
    assert 40 < metrics["cpu_dry_run.state_rows_held_share"]["value"] < 70
    # device metrics need a device trace: none is printed from a CPU
    for name in ("step_roofline_share", "window_read_roofline_share",
                 "moe_roofline_share.mixed",
                 "sparse_attn_roofline_share.mixed"):
        assert "cpu_dry_run." + name not in metrics
    check_line = json.loads(
        next(l for l in lines if l.startswith("CHECK "))[6:])
    assert check_line["finite"] and check_line["layers"] == 5
    assert check_line["pass"] and check_line["quant"] == "none"
    assert check_line["logit_rel_rms_mean"] < 0.0095
    assert check_line["burst_gap"] < 0.0003
    assert check_line["burst_rounds"] == 128
    assert check_line["burst_tokens"] > 1000
    run = json.loads(next(l for l in lines if l.startswith("RUN "))[4:])
    assert run["compiles_in_window"] == 0 and run["stopped_early"] == 0
