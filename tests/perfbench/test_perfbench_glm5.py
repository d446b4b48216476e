"""The GLM-5 configuration and its cell: the file holds the catalog row's
config key for key at the top level and again under ``hf_config`` with only
the four keys of ``reduced`` changed (the router's 256 among them, which
stays 256: what is cut is what one chip HOLDS), the cell takes the
benchmark's ``doc-sat8`` mix unedited, the reference's count of a tick
charges every held expert once whatever the routing and the rows a tick
scores and selects, the readers the cell brings read their counters and
find nothing in a program without them, and the whole harness rehearses on
the CPU at the family's rehearsal preset."""

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
CELL, CONFIG, TRAFFIC = "glm5-doc-sat8", "glm-5", "doc-sat8"
PUBLISHED = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 3,
    "hidden_act": "silu", "head_dim": 64, "hidden_size": 6144,
    "index_head_dim": 128, "index_n_heads": 32, "index_topk": 2048,
    "indexer_rope_interleave": True, "intermediate_size": 12288,
    "kv_lora_rank": 512, "max_position_embeddings": 202752,
    "moe_intermediate_size": 2048, "moe_layer_freq": 1,
    "model_type": "glm_moe_dsa", "n_group": 1, "n_routed_experts": 256,
    "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 64, "num_experts_per_tok": 8,
    "num_hidden_layers": 78, "num_key_value_heads": 64,
    "num_nextn_predict_layers": 1, "q_lora_rank": 2048, "qk_head_dim": 256,
    "qk_nope_head_dim": 192, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-05,
    "rope_interleave": True,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
    "tie_word_embeddings": False, "topk_group": 1, "topk_method": "noaux_tc",
    "v_head_dim": 256, "vocab_size": 154880}
REDUCED = ["num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
           "vocab_size"]
SERVED = dict(PUBLISHED, num_hidden_layers=6, first_k_dense_replace=1,
              vocab_size=19360)
# the issue's arithmetic, in parameters
ATTENTION = 12582912 + 33554432 + 3538944 + 14680064 + 100663296  # 165.02 M
INDEXER = 2048 * 32 * 128 + 6144 * 128 + 6144 * 32                # 9.37 M
EXPERT = 3 * 6144 * 2048                                          # 37.75 M
ROUTER = 6144 * 256
NEW_METRICS = ["index_rows_selected_share", "moe_held_assignment_share",
               "moe_experts_hit_share", "moe_roofline_share",
               "sparse_attn_roofline_share"]


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
        "https://huggingface.co/zai-org/GLM-5/blob/main/config.json")
    cell = man.workload(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, TRAFFIC, 1)
    assert cell in man.data["workloads"] and entry in man.data["configs"]
    assert {m["name"] for m in man.metrics_for(CELL, "end_to_end")} == {
        "gap_p75_ms", "setup_s"}
    layer = {m["name"] for m in man.metrics_for(CELL, "per_layer")}
    assert set(NEW_METRICS) | {
        "kv_stack_gb", "attn_rows_read_share", "step_roofline_share",
        "device_ms_per_tick", "device_idle_share",
        "client_tokens_per_s"} <= layer
    # evabyte's own three, the looped stack's, the int8 kernel's and the
    # seven round-period metrics are not this cell's
    assert not {"summary_rows_read_share", "state_rows_held_share",
                "chunks_summarised_per_position", "loop_exit_step_mean",
                "int8_kernel_roofline_share", "round_period_ms",
                "burst_launch_lag_ms"} & layer
    assert not any(name.endswith(".open") for name in layer)
    by_name = {m["name"]: m for m in man.data["per_layer"]}
    assert all(by_name[n]["workloads"] == [CELL] for n in NEW_METRICS)
    # everywhere evabyte's cell is listed but for its own three, so is this
    for m in man.data["per_layer"]:
        if "evabyte-doc-sat8" in m.get("workloads", ()) and len(
                m["workloads"]) > 1:
            assert CELL in m["workloads"], m["name"]
    for key in ("index_keys", "rope", "indexer", "init", "ep_size",
                "tensor_names", "precision"):
        assert key in body["assumed"], key
    assert "multi_token_prediction" in body["not_served"]
    dep = body["deployment"]
    assert (dep["chips_a_layer"], dep["experts_held"]) == (16, [0, 16])
    assert dep["model_args"] == ["--model", "glm5", "--num_layers", "6"]
    assert dep["servers"][0]["args"] == [
        "--mode", "serve", "--stage", "0", "--batched", "--burst", "16",
        "--slots", "8", "--max_session_len", "16384", "--dtype", "bfloat16",
        "--quant", "none"]
    chk = body["check"]
    assert (chk["layers"], chk["sessions"], chk["decode_steps"],
            chk["burst_rounds"], chk["control"]) == (2, 8, 4, 128, "int8")
    # the check holds both kinds of layer: the dense one and an expert one
    assert chk["layers"] >= body["layer_period"] == 2
    assert chk["layers"] > SERVED["first_k_dense_replace"]
    assert set(chk["limits"]) == {"logit_rel_rms", "burst_gap"}


@pytest.mark.parametrize("key", sorted(PUBLISHED))
def test_a_published_key_is_in_the_file_at_its_value(body, key):
    """The file holds the keys twice (its "layout" says why), letter for
    letter, no published key as null; only three VALUES differ, and
    ``n_routed_experts`` stays the router's 256 though it is listed in
    ``reduced``: what is cut is what one chip holds."""
    hf = body["hf_config"]
    assert key in hf and key in body
    assert body[key] == hf[key] == SERVED[key]
    assert type(body[key]) is type(hf[key]) is type(SERVED[key])
    assert body[key] is not None
    if SERVED[key] != PUBLISHED[key]:
        assert key in REDUCED and key in body["reduced_why"]


def test_the_file_holds_the_catalog_row(body):
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog of architectures on this machine")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "GLM-5")
    assert row["config"] == PUBLISHED
    assert body["source"] == row["source_url"]
    for held in (body, body["hf_config"]):
        differs = {k for k, v in row["config"].items()
                   if held.get(k, "absent") != v}
        assert differs == set(REDUCED) - {"n_routed_experts"}
    assert set(body["hf_config"]) == set(row["config"])
    assert body["n_routed_experts"] == 256 and set(body["reduced_why"]) == \
        set(REDUCED)


def test_the_cut_is_the_issue_s_arithmetic(body, ref):
    hf = body["hf_config"]
    assert ref.attention_params(hf) == ATTENTION + INDEXER
    assert ATTENTION == pytest.approx(165.02e6, rel=1e-4)
    assert INDEXER == pytest.approx(9.37e6, rel=1e-3)
    assert ref.held_experts(hf) == (0, 16)
    assert ref.moe_params(hf) == ROUTER + 17 * EXPERT
    dense_layer = ATTENTION + INDEXER + 3 * 6144 * 12288
    expert_layer = ATTENTION + INDEXER + ROUTER + 17 * EXPERT
    assert dense_layer * 2 == pytest.approx(0.802e9, rel=2e-3)
    assert expert_layer * 2 == pytest.approx(1.635e9, rel=2e-3)
    weights = (dense_layer + 5 * expert_layer + 2 * 19360 * 6144) * 2
    assert weights == pytest.approx(9.455e9, rel=2e-3)
    assert ref.row_bytes(hf) == (1152, 256)
    state = 6 * 8 * 16384 * (1152 + 256)
    assert state == pytest.approx(1.107e9, rel=1e-3)
    # one K and one V row a head a position would not fit four chips
    assert 6 * 8 * 16384 * 64 * (256 + 256) * 2 == pytest.approx(51.5e9,
                                                                 rel=1e-3)


@pytest.mark.parametrize("sessions, position", [
    (8.0, 1000.0), (8.0, 2048.0), (6.4, 9000.0), (8.0, 15149.0)])
def test_tick_cost_charges_what_a_tick_reads(body, ref, sessions, position):
    hf = body["hf_config"]
    cost = ref.tick_cost(hf, layers=6, sessions=sessions, kv_rows=position,
                         weight_bytes=2)
    dense = ATTENTION + INDEXER + 3 * 6144 * 12288
    expert = ATTENTION + INDEXER + ROUTER + 17 * EXPERT
    assert cost["weight_bytes"] == (dense + 5 * expert) * 2
    assert cost["head_bytes"] == 19360 * 6144 * 2
    selected = min(position, 2048)
    assert cost["kv_rows_read"] == selected
    assert cost["kv_bytes"] == pytest.approx(
        6 * sessions * (position * 256 + selected * 1152))
    assert cost["bytes"] == (cost["weight_bytes"] + cost["head_bytes"]
                             + cost["kv_bytes"])
    # every held expert is charged whatever the routing: the bytes do not
    # follow the tokens
    assert ref.moe_tick_bytes(hf, 6) == 5 * (ROUTER + 17 * EXPERT) * 2
    # a row a head a position, as the stock count has it, would be 34 GB
    stock = roofline.tick_cost(
        dict(hf, num_key_value_heads=64), layers=6, sessions=sessions,
        kv_rows=position, weight_bytes=2)
    assert cost["kv_bytes"] < 0.1 * stock["kv_bytes"]
    least, bound = roofline.roofline_s(cost, "TPU v5 lite")
    assert bound == "memory" and 0.0112 < least < 0.0121


def counters(**series):
    text = "".join(f"{k} {v}\n" for k, v in series.items())
    return {"p": readers.parse_prometheus(text)}


def fixture_ctx(man, body):
    before = dict(server_attn_rows_read_total=100,
                  server_index_rows_scored_total=1000,
                  server_moe_assignments_total=640,
                  server_moe_assignments_held_total=40,
                  server_moe_experts_hit_total=18,
                  server_moe_expert_slots_total=80,
                  server_kv_stack_bytes=1107296256,
                  server_batch_fill_sessions_sum=0,
                  server_batch_fill_sessions_count=0)
    after = dict(server_attn_rows_read_total=100 + 16384,
                 server_index_rows_scored_total=1000 + 65536,
                 server_moe_assignments_total=640 + 6400,
                 server_moe_assignments_held_total=40 + 400,
                 server_moe_experts_hit_total=18 + 176,
                 server_moe_expert_slots_total=80 + 800,
                 server_kv_stack_bytes=1107296256,
                 server_batch_fill_sessions_sum=70,
                 server_batch_fill_sessions_count=10)
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
            "whole": 3, "count": 4, "mean_s": 0.256}}, "ops": {}}}


def test_the_counter_readers_on_a_fixture(man, body):
    ctx = fixture_ctx(man, body)
    assert readers.read_metric(man, "index_rows_selected_share", ctx) == \
        pytest.approx(25.0)
    assert readers.read_metric(man, "moe_held_assignment_share", ctx) == \
        pytest.approx(6.25)
    assert readers.read_metric(man, "moe_experts_hit_share", ctx) == \
        pytest.approx(22.0)
    assert readers.read_metric(man, "kv_stack_gb", ctx) == pytest.approx(
        6 * 8 * 16384 * (576 + 128) * 2 / 1e9)
    # through the configuration's own tick_cost: 9.2 GB of weights and
    # head + 0.2 GB of rows at 819 GB/s over a 16 ms tick
    share = readers.read_metric(man, "step_roofline_share", ctx)
    assert 65.0 < share < 80.0
    assert ctx["notes"]["step_roofline_bound"] == "memory"


# Operation names as a traced run of the cell held them (my chip run, PR 55,
# seed 2147600003; cut as `harness/trace.py` cuts them): the tick's index
# scores, its sort (a tuple result), its gather of slots x 2048 rows; then
# what the reader must NOT take: the tick's single-row read and the stack's
# update (`kv_update`), a prefill chunk's slices of both stacks, a loop.
TICK_OPS = {
    "%fusion.724 = f32[8,2048]{1,0:T(8,128)S(1)} fusion(bf16[6,8,16384,128]"
    "{3,2,1,0:T(8,128)(2,1)} %get-tuple-element.4000, s32[]{:T(128)} "
    "%get-tuple-element.4003": 0.028,
    "%sort.25 = (f32[8,16384]{1,0:T(8,128)}, s32[8,16384]{1,0:T(8,128)S(1)})"
    " sort(f32[8,16384]{1,0:T(8,128)S(1)} %bitcast_select_fusion.8, "
    "s32[8,16384]{1,0:T(8,128)S(1)} %iota.269), dimensions={1}": 0.039,
    "%fusion.701 = bf16[16384,640]{1,0:T(8,128)(2,1)S(1)} fusion(bf16"
    "[6,8,16384,640]{3,2,1,0:T(8,128)(2,1)} %fusion.694, s32[16384]"
    "{0:T(1024)S(1)} %bitcast.798), kind=kCustom": 0.114}
OTHER_OPS = {
    "%fusion.682 = bf16[8,640]{1,0:T(8,128)(2,1)S(1)} fusion(bf16"
    "[6,8,16384,640]{3,2,1,0:T(8,128)(2,1)} %get-tuple-element.4121, "
    "s32[8]{0:T(128)S(1)} %bitcast.819), kind=kCustom": 0.0003,
    "%fusion.694 = bf16[6,8,16384,640]{3,2,1,0:T(8,128)(2,1)} fusion(bf16"
    "[6,8,16384,640]{3,2,1,0:T(8,128)(2,1)} %get-tuple-element.4121, "
    "s32[8]{0:T(128)S(1)} %get-tuple-element.3901": 0.0005,
    "%constant_dynamic-slice_fusion.8 = bf16[5,1,16384,640]{3,2,1,0:T(8,128)"
    "(2,1)} fusion(bf16[6,8,16384,640]{3,2,1,0:T(8,128)(2,1)} %k_all.1, "
    "s32[]{:T(128)S(6)} %add.372": 0.0093,
    "%dynamic-slice_bitcast_fusion.8 = bf16[1,16384,128]{2,1,0:T(8,128)(2,1)"
    "S(1)} fusion(bf16[6,8,16384,128]{3,2,1,0:T(8,128)(2,1)} %v_all.1, "
    "s32[]{:T(128)S(6)} %select_n.48), kind=kLoop": 0.0002,
    "%while.106 = (s32[]{:T(128)}, f32[8,1,16384]{2,0,1:T(8,128)S(1)}, "
    "s32[]{:T(128)}, bf16[6,8,16384,128]{3,2,1,0:T(8,128)(2,1)}, bf16"
    "[8,1,32,128]{3,2,0,1:T(8,128)(2,1)S(1)}": 0.2}


def test_the_sparse_read_s_reader_takes_the_tick_s_three_operations(
        man, body, ref):
    """Index scores, the sort and the gather, by shape beside the tick's
    own row counts, and nothing of a prefill chunk, of `kv_update` or of a
    loop: the reader's seconds are the three scopes' (indexer +
    topk_select + latent_read)."""
    ctx = fixture_ctx(man, body)
    ctx["counters_before"]["p"]["server_burst_dispatches_total"] = 0
    ctx["counters_after"]["p"]["server_burst_dispatches_total"] = 1
    ctx["trace"]["programs"]["jit_burst_tick(1)"]["seconds"] = 0.256 * 4
    ops = {k: {"seconds": s, "count": 1}
           for k, s in {**TICK_OPS, **OTHER_OPS}.items()}
    ctx["trace"]["ops"] = ops
    # the fixture's counters: 65536 rows scored and 16384 selected in ONE
    # round of 16 ticks; the trace holds 4 rounds of the tick program
    least = 64 * ref.sparse_attn_tick_bytes(
        body["hf_config"], 6, 65536 / 16, 16384 / 16) / 819e9
    assert readers.read_metric(man, "sparse_attn_roofline_share", ctx) == \
        pytest.approx(100 * least / sum(TICK_OPS.values()))
    ctx["trace"]["ops"] = {k: ops[k] for k in OTHER_OPS}
    assert readers.read_metric(man, "sparse_attn_roofline_share", ctx) is None


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
    # decode after the 2040-row prompt crosses the selection's edge
    assert 2040 < 2048 <= 2040 + chk["decode_steps"] + 16 * chk[
        "burst_rounds"]
    # seven of the eight prompts end past it; the rehearsal's cross its own
    assert sum(p > 2048 for p in t["prompt_lens"]) == 7
    dry = chk["dry_run_hf_config"]
    assert dry["index_topk"] == 256 and dry["experts_held"] == 8
    assert sum(p // 8 > 256 for p in t["prompt_lens"]) == 7
    assert set(dry) == set(PUBLISHED) | {"experts_held"}


def test_the_program_s_preset_is_the_file_s(body):
    import importlib

    config = importlib.import_module(
        "global_capstone_design_distributed_inference_of_llms_over_the_"
        "internet_tpu.models.config")

    def sizes(cfg):
        return (cfg.hidden_size, cfg.num_heads, cfg.intermediate_size,
                cfg.moe_intermediate_size, cfg.q_lora_rank, cfg.kv_lora_rank,
                cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim,
                cfg.index_n_heads, cfg.index_head_dim, cfg.index_topk,
                cfg.num_experts, cfg.num_experts_per_tok,
                cfg.n_shared_experts, cfg.routed_scaling_factor,
                cfg.first_k_dense, cfg.vocab_size, cfg.norm_eps,
                cfg.rope_theta, cfg.tie_word_embeddings)

    def of(hf):
        return (hf["hidden_size"], hf["num_attention_heads"],
                hf["intermediate_size"], hf["moe_intermediate_size"],
                hf["q_lora_rank"], hf["kv_lora_rank"],
                hf["qk_nope_head_dim"], hf["qk_rope_head_dim"],
                hf["v_head_dim"], hf["index_n_heads"], hf["index_head_dim"],
                hf["index_topk"], hf["n_routed_experts"],
                hf["num_experts_per_tok"], hf["n_shared_experts"],
                hf["routed_scaling_factor"], hf["first_k_dense_replace"],
                hf["vocab_size"], hf["rms_norm_eps"],
                hf["rope_parameters"]["rope_theta"],
                hf["tie_word_embeddings"])

    cfg = config.get_config("glm5")
    assert sizes(cfg) == of(body["hf_config"])
    assert cfg.num_layers == PUBLISHED["num_hidden_layers"]
    assert cfg.held_experts == tuple(body["deployment"]["experts_held"])
    assert cfg.head_dim == PUBLISHED["qk_head_dim"]
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


def test_the_rehearsal_s_control_reads_above_the_sound_engine(tmp_path):
    """The program one precision down (``--quant int8``) through the same
    drive at the rehearsal preset. The limits are set at published widths
    on the chip; here, at a quarter of every width (and 8 of 32 experts
    held: a routed expert's near-tie is a held expert's four times as
    often), the control has to read above what the sound engine reads over
    its seeds (rows' mean error 0.0051-0.0073, ``burst_gap`` 0.00003-
    0.00019 over 4096 greedy tokens: the traced rehearsal below holds the
    sound engine under both) by both: 0.0105-0.0136 and 0.00022-0.00042
    over four seeds."""
    rc, control = run_check(2 ** 31 + 135, True, str(tmp_path / "cache"))
    assert control["quant"] == "int8" and control["finite"]
    assert control["burst_tokens"] > 1000
    assert control["logit_rel_rms_mean"] > 0.009
    assert control["burst_gap"] > 0.0003


def test_traced_dry_run_of_the_cell(tmp_path):
    """The whole harness on the CPU: the family's rehearsal preset at 2
    layers (the dense one and an expert one) serves prompts of 255-1750
    rows across its 256-row selection edge, the check runs against the
    reference, and the cell's own metrics are on the line."""
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
    slot = traffic.least_slot({
        "prompt_lens": [n // 8 for n in [2040, 3000, 4600, 6100, 8186,
                                         10000, 12200, 14000]],
        "token_budgets": [n // 8 for n in [390, 520, 650, 760, 790, 900,
                                           1030, 1150]],
        "pairing": [6, 7, 0, 1, 2, 3, 4, 5], "route": {"burst": 4}})
    assert slot == 1920
    # [2 layers, 4 slots, 1920 rows] x (128 + 16 and 32 numbers) x bf16
    assert metrics["cpu_dry_run.kv_stack_gb"]["value"] == pytest.approx(
        2 * 4 * slot * (128 + 16 + 32) * 2 / 1e9)
    with open(tmp_path / "out" / "metrics_after.jsonl") as f:
        total = readers.parse_prometheus(json.loads(f.readline())["text"])
    assert total["server_index_rows_scored_total"] > \
        total["server_attn_rows_read_total"] > 0
    held = (total["server_moe_assignments_held_total"]
            / total["server_moe_assignments_total"])
    assert 0.15 < held < 0.35                       # 8 of 32 experts held
    assert 0 < total["server_moe_experts_hit_total"] <= \
        total["server_moe_expert_slots_total"]
    for name in NEW_METRICS[:3]:
        got = metrics.get("cpu_dry_run." + name)
        assert got is None or 0 < got["value"] <= 100
    # device metrics need a device trace: none is printed from a CPU
    for name in ("step_roofline_share", "moe_roofline_share",
                 "sparse_attn_roofline_share"):
        assert "cpu_dry_run." + name not in metrics
    check_line = json.loads(
        next(l for l in lines if l.startswith("CHECK "))[6:])
    assert check_line["finite"] and check_line["layers"] == 2
    assert check_line["pass"] and check_line["burst_gap"] < 0.0003
    assert check_line["quant"] == "none"
    assert check_line["burst_rounds"] == 128
    assert check_line["burst_tokens"] > 1000
    run = json.loads(next(l for l in lines if l.startswith("RUN "))[4:])
    assert run["compiles_in_window"] == 0 and run["stopped_early"] == 0
