"""The EvaByte configuration and its cell: the file holds the published
config key for key with only the depth cut, the slot test takes the cell
and the pairing test its mix, the reference's count of a tick charges the
rows a tick READS (a window's exact rows and one summary per chunk of the
earlier windows) and not the positions a session has sent, the three
readers the cell brings read their counters and find nothing in a program
without them, and the whole harness rehearses on the CPU at the family's
rehearsal preset (registry, server, load generator, traced window, the
check across window edges, its int8 control)."""

import json
import os
import subprocess
import sys

import pytest

from perfbench.harness import check, readers, roofline, traffic
from perfbench.harness.manifest import Manifest, load_module

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
CELL, CONFIG, TRAFFIC = "evabyte-doc-sat8", "evabyte-6.5b", "doc-sat8"
# The catalog row's ``config`` (the catalog itself is compared below where
# the machine has it).
PUBLISHED = {
    "attention_bias": False, "attention_class": "eva", "chunk_size": 16,
    "fp32_ln": False, "fp32_logits": True, "fp32_skip_add": True,
    "hidden_act": "silu", "hidden_size": 4096, "init_cutoff_factor": None,
    "init_fn": "v2", "init_std": 0.01275, "intermediate_size": 11008,
    "lazy_init": True, "max_position_embeddings": 32768,
    "max_seq_length": 32768, "mixedp_attn": True, "model_type": "evabyte",
    "norm_add_unit_offset": True, "num_attention_heads": 32,
    "num_chunks": None, "num_hidden_layers": 32, "num_key_value_heads": 32,
    "num_pred_heads": 8, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 100000, "tie_word_embeddings": False, "vocab_size": 320,
    "window_size": 2048}
SERVED_LAYERS = 16
LAYER = 4 * 4096 * 4096 + 3 * 4096 * 11008       # q, k, v, o; gate, up, down
ROW_BYTES = 2 * 32 * 128 * 2                     # K and V, bfloat16: 16 KB
PROMPTS = [2040, 3000, 4600, 6100, 8186, 10000, 12200, 14000]
BUDGETS = [390, 520, 650, 760, 790, 900, 1030, 1150]


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
    assert entry["reduced"] == body["reduced"] == ["num_hidden_layers"]
    assert entry["source"] == body["source"]
    cell = man.workload(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, TRAFFIC, 1)
    assert man.data["workloads"][-1] == cell          # appended, last
    assert man.data["configs"][-1] == entry
    assert {m["name"] for m in man.metrics_for(CELL, "end_to_end")} == {
        "gap_p75_ms", "setup_s"}
    layer = {m["name"] for m in man.metrics_for(CELL, "per_layer")}
    assert {"summary_rows_read_share", "state_rows_held_share",
            "chunks_summarised_per_position", "kv_stack_gb",
            "attn_rows_read_share", "step_roofline_share",
            "device_ms_per_tick", "device_idle_share",
            "client_tokens_per_s"} <= layer
    assert not {"int8_kernel_roofline_share", "loop_exit_step_mean"} & layer
    assert not any(name.endswith(".open") for name in layer)
    # the three it brings are the last entries, and this cell's alone
    tail = man.data["per_layer"][-3:]
    assert [m["name"] for m in tail] == [
        "summary_rows_read_share", "state_rows_held_share",
        "chunks_summarised_per_position"]
    assert all(m["workloads"] == [CELL] for m in tail)
    for key in ("pooling", "one_softmax", "rotated_keys", "windows", "head",
                "init", "precision", "tensor_names"):
        assert key in body["assumed"], key
    assert "multi_byte_decoding" in body["not_served"]
    chk = body["check"]
    assert (chk["layers"], chk["sessions"], chk["decode_steps"],
            chk["burst_rounds"], chk["control"]) == (4, 8, 4, 8, "int8")
    assert chk["layers"] >= body["layer_period"] == 1
    assert body["weight_bytes"] == 2
    assert body["deployment"]["model_args"] == [
        "--model", "evabyte", "--num_layers", "16"]
    assert body["deployment"]["servers"][0]["args"] == [
        "--mode", "serve", "--stage", "0", "--batched", "--burst", "16",
        "--slots", "8", "--max_session_len", "16384", "--dtype", "bfloat16",
        "--quant", "none"]
    # the rehearsal crosses windows: an eighth of 2040 and of 8186 sit one
    # row under an edge of its 256-row window
    dry = chk["dry_run_hf_config"]
    assert dry["window_size"] == 256 and dry["chunk_size"] == 16
    assert (2040 // 8 + 1) % 256 == 0 == (8186 // 8 + 1) % 256
    assert set(dry) == set(PUBLISHED)


@pytest.mark.parametrize("key", sorted(PUBLISHED))
def test_a_published_key_is_in_the_file_at_its_value(body, key):
    """Letter for letter, a null stays null; the file holds the keys twice
    (its "layout" says why): at the top level for the driver's catalog
    check, under hf_config for the harness. Only the depth is cut."""
    hf = body["hf_config"]
    assert key in hf and key in body
    want = SERVED_LAYERS if key == "num_hidden_layers" else PUBLISHED[key]
    assert body[key] == hf[key] == want
    assert type(body[key]) is type(hf[key]) is type(want)


def test_the_file_holds_the_catalog_row(body):
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog of architectures on this machine")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "EvaByte")
    assert row["config"] == PUBLISHED
    assert body["source"] == row["source_url"]
    for held in (body, body["hf_config"]):
        assert {k for k, v in row["config"].items()
                if held.get(k, "absent") != v} == {"num_hidden_layers"}
    assert set(body["hf_config"]) == set(row["config"])


def test_the_traffic_is_the_issue_s(man):
    t = man.traffic(TRAFFIC)
    assert (t["kind"], t["sessions"], t["think_s"]) == ("closed", 8, 0)
    assert t["route"] == {"kind": "full_span", "burst": 16}
    assert t["prompt_lens"] == PROMPTS and t["token_budgets"] == BUDGETS
    assert all(b % 16 for b in BUDGETS)      # off a burst's 16-token lattice
    assert t["sampling"] == {"temperature": 0.8, "top_p": 0.95, "top_k": 0,
                             "repetition_penalty": 1.0}
    assert (t["ramp_finished_requests"], t["request_timeout_s"],
            t["trace_seconds"]) == (8, 180, 6)
    # the repo's affine rule, first candidate
    a, c, pairing = traffic.affine_pairings(PROMPTS, BUDGETS)[0]
    assert (a, c, pairing) == (t["pairing_rule"]["a"], t["pairing_rule"]["c"],
                               t["pairing"])
    assert t["pairing_rule"]["candidate"] == 1
    # every stated session lies past the first window for all of its decode
    assert min(p for p, _ in traffic.pairs_of(t)) + 16 > 2048


def test_the_slot_test_takes_the_cell(man, body):
    t = man.traffic(TRAFFIC)
    assert check.slot_len(body) == 16384
    assert traffic.slot_rows(t) == 14000 + 900 - 1 <= 14000 + 1150 - 1
    assert traffic.slot_rows(t) <= check.slot_len(body)
    # the check's longest pass: the longest prompt, its steps and rounds
    chk = body["check"]
    assert check.check_lengths(t, 3) == [2040, 8186, 14000]
    rows = 14000 + chk["decode_steps"] + 16 * chk["burst_rounds"] + 1
    assert rows == 14133 <= 16384
    # decode after the 2040-row prompt crosses the FIRST window edge
    assert 2040 < 2048 <= 2040 + chk["decode_steps"] + 16 * chk["burst_rounds"]
    assert 8186 < 8192 <= 8186 + chk["decode_steps"] + 16 * chk["burst_rounds"]


def test_the_program_s_preset_is_the_file_s(body):
    import importlib

    config = importlib.import_module(
        "global_capstone_design_distributed_inference_of_llms_over_the_"
        "internet_tpu.models.config")
    cfg = config.get_config("evabyte")
    assert (cfg.hidden_size, cfg.num_layers, cfg.num_heads,
            cfg.num_kv_heads, cfg.head_dim, cfg.intermediate_size,
            cfg.vocab_size, cfg.eva_window, cfg.eva_chunk, cfg.pred_heads,
            cfg.norm_eps, cfg.rope_theta, cfg.max_position_embeddings,
            cfg.tie_word_embeddings, cfg.norm_offset, cfg.fp32_residual
            ) == (
        PUBLISHED["hidden_size"], PUBLISHED["num_hidden_layers"],
        PUBLISHED["num_attention_heads"], PUBLISHED["num_key_value_heads"],
        128, PUBLISHED["intermediate_size"], PUBLISHED["vocab_size"],
        PUBLISHED["window_size"], PUBLISHED["chunk_size"],
        PUBLISHED["num_pred_heads"], PUBLISHED["rms_norm_eps"],
        PUBLISHED["rope_theta"], PUBLISHED["max_position_embeddings"],
        PUBLISHED["tie_word_embeddings"], PUBLISHED["norm_add_unit_offset"],
        PUBLISHED["fp32_skip_add"])
    dry = body["check"]["dry_run_hf_config"]
    small = config.get_config(body["dry_run_model_args"][1])
    assert (small.hidden_size, small.num_heads, small.num_kv_heads,
            small.intermediate_size, small.eva_window, small.eva_chunk,
            small.vocab_size, small.pred_heads, small.head_dim) == (
        dry["hidden_size"], dry["num_attention_heads"],
        dry["num_key_value_heads"], dry["intermediate_size"],
        dry["window_size"], dry["chunk_size"], dry["vocab_size"],
        dry["num_pred_heads"], 128)


@pytest.mark.parametrize("sessions, position, rows", [
    (8.0, 1000.0, 1001.0),                    # inside the first window
    (8.0, 2048.0, 1024.5 + 128 - 64),         # (W + 1) / 2 + p / C - W / 2C
    (6.4, 9000.0, 1024.5 + 562.5 - 64),
    (8.0, 15149.0, 1024.5 + 15149 / 16 - 64)])
def test_tick_cost_charges_the_rows_a_tick_reads(body, ref, sessions,
                                                 position, rows):
    hf = body["hf_config"]
    assert ref.layer_params(hf) == LAYER
    assert ref.mean_rows_read(hf, position) == pytest.approx(rows)
    cost = ref.tick_cost(hf, layers=16, sessions=sessions, kv_rows=position,
                         weight_bytes=2)
    assert cost["weight_bytes"] == 16 * LAYER * 2             # 6.48 GB
    assert cost["weight_bytes"] == pytest.approx(6.477e9, rel=1e-3)
    assert cost["head_bytes"] == 320 * 4096 * 2               # head 0 alone
    assert cost["kv_rows_read"] == pytest.approx(rows)
    assert cost["kv_bytes"] == pytest.approx(
        sessions * rows * ROW_BYTES * 16)
    assert cost["bytes"] == (cost["weight_bytes"] + cost["head_bytes"]
                             + cost["kv_bytes"])
    assert cost["flops"] == pytest.approx(
        2.0 * sessions * (16 * LAYER + 320 * 4096)
        + 4.0 * sessions * rows * 32 * 128 * 16)
    # the stock count charges every position sent: 16384 rows a slot would
    # be 34.4 GB of a 16.9 GB chip
    stock = roofline.tick_cost(hf, layers=16, sessions=sessions,
                               kv_rows=position, weight_bytes=2)
    assert stock["kv_bytes"] == sessions * position * ROW_BYTES * 16
    if position > 2048:
        assert cost["kv_bytes"] < 0.6 * stock["kv_bytes"]
    least, bound = roofline.roofline_s(cost, "TPU v5 lite")
    assert bound == "memory" and least >= 6.477e9 / 819e9


def test_rows_read_is_the_window_s_and_a_summary_a_chunk(body, ref):
    hf = body["hf_config"]
    assert [ref.rows_read(hf, p) for p in (0, 2047, 2048, 2049, 4095, 4096,
                                           14000)] == [
        1, 2048, 1 + 128, 2 + 128, 2048 + 128, 1 + 256,
        14000 % 2048 + 1 + 6 * 128]
    # the issue's arithmetic: a tick streams about 8 x (1800 + 600) rows
    assert 8 * 16 * ROW_BYTES * (1800 + 600) == pytest.approx(5.0e9, rel=0.01)


def counters(*, exact, sums, chunks, written, held, sent, stack=6174015488,
             fills=10):
    text = (f"server_attn_rows_read_total {exact}\n"
            f"server_attn_rows_span_total {4 * exact}\n"
            f"server_attn_summary_rows_read_total {sums}\n"
            f"server_kv_chunks_summarised_total {chunks}\n"
            f"server_kv_positions_written_total {written}\n"
            f"server_state_rows_held_total {held}\n"
            f"server_positions_held_total {sent}\n"
            f"server_kv_stack_bytes {stack}\n"
            f"server_batch_fill_sessions_sum {7.0 * fills}\n"
            f"server_batch_fill_sessions_count {fills}\n")
    return {"p": readers.parse_prometheus(text)}


def fixture_ctx(man, body):
    return {
        "counters_before": counters(exact=1000, sums=100, chunks=10,
                                    written=160, held=500, sent=2000,
                                    fills=0),
        "counters_after": counters(exact=1000 + 15000, sums=100 + 5000,
                                   chunks=10 + 625, written=160 + 10000,
                                   held=500 + 2400, sent=2000 + 9600),
        "records": [{"sent": 1.0, "due": None, "error": None,
                     "prompt_len": 9000, "deliveries": [[2.0, 16],
                                                        [3.0, 16]]}],
        "w0": 0.0, "w1": 10.0, "traffic": man.traffic(TRAFFIC),
        "config": body, "reference_file": man.reference_file(body),
        "hf": body["hf_config"], "device": {"kind": "TPU v5 lite"},
        "trace": {"programs": {"jit_burst_tick(1)": {"whole": 3,
                                                     "mean_s": 0.32}},
                  "ops": {}}}


def test_the_three_readers_on_a_fixture(man, body):
    ctx = fixture_ctx(man, body)
    assert readers.read_metric(man, "summary_rows_read_share", ctx) == \
        pytest.approx(25.0)
    assert readers.read_metric(man, "state_rows_held_share", ctx) == \
        pytest.approx(25.0)
    assert readers.read_metric(
        man, "chunks_summarised_per_position", ctx) == pytest.approx(0.0625)
    assert readers.read_metric(man, "kv_stack_gb", ctx) == pytest.approx(
        (16 * 8 * 2048 + 16 * 8 * 896) * 32 * 128 * 2 * 2 / 1e9)
    # step_roofline_share goes through the configuration's own tick_cost,
    # which takes the MEASURED share of rows held (a quarter of ~9016
    # positions, 7 sessions): 6.48 GB + 4.1 GB at 819 GB/s over a 20 ms tick
    share = readers.read_metric(man, "step_roofline_share", ctx)
    assert 55.0 < share < 75.0
    assert ctx["notes"]["step_roofline_bound"] == "memory"


@pytest.mark.parametrize("name", ["summary_rows_read_share",
                                  "state_rows_held_share",
                                  "chunks_summarised_per_position"])
def test_a_reader_finds_nothing_in_a_program_without_the_counters(
        man, body, name):
    """The parent commit has none of the series; a family that keeps a row
    a position never moves the summaries' two. The metric is left out of
    the line and nothing raises."""
    ctx = fixture_ctx(man, body)
    ctx["counters_before"] = ctx["counters_after"] = {
        "p": readers.parse_prometheus("server_burst_tokens_total 5\n"
                                      "server_attn_rows_read_total 9\n")}
    assert readers.read_metric(man, name, ctx) is None
    ctx.pop("counters_after")
    assert readers.read_metric(man, name, ctx) is None
    # ... and the roofline share falls back on the mean of rows read
    ctx = fixture_ctx(man, body)
    for side in ("counters_before", "counters_after"):
        for key in ("server_state_rows_held_total",
                    "server_positions_held_total"):
            ctx[side]["p"].pop(key)
    assert 55.0 < readers.read_metric(man, "step_roofline_share", ctx) < 75.0


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


def test_the_rehearsal_s_control_is_not_correct(tmp_path):
    """The program one precision down (``--quant int8``) through the same
    drive and the same limits: it has to come out as not correct."""
    rc, got = run_check(2 ** 31 + 135, True, str(tmp_path / "cache"))
    assert rc == 1 and got["pass"] is False and got["quant"] == "int8"
    assert got["finite"] and got["burst_tokens"] > 100
    assert (got["logit_rel_rms"] > got["logit_rel_rms_limit"]
            or got["burst_gap"] > got["burst_gap_limit"])


def test_traced_dry_run_of_the_cell(tmp_path):
    """The whole harness on the CPU: the family's rehearsal preset at 2
    layers serves prompts of 255-1750 rows over a 256-row window, the
    check runs 4 layers against the reference across window edges and
    comes out ``correct``, and the cell's own metrics are on the line."""
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
    # [2 layers, 4 slots, 256 | 6 x 16 rows, 8 heads of 128] x bf16 x (K + V)
    slot = traffic.least_slot({
        "prompt_lens": [n // 8 for n in PROMPTS],
        "token_budgets": [n // 8 for n in BUDGETS],
        "pairing": [6, 7, 0, 1, 2, 3, 4, 5], "route": {"burst": 4}})
    assert slot == 1920
    assert metrics["cpu_dry_run.kv_stack_gb"]["value"] == pytest.approx(
        2 * 4 * (256 + (-(-slot // 256) - 1) * 16) * 8 * 128 * 2 * 2 / 1e9)
    # over the server's whole life: chunks pooled a position written, what
    # the sessions hold against a row a position, summaries among the reads
    with open(tmp_path / "out" / "metrics_after.jsonl") as f:
        total = readers.parse_prometheus(json.loads(f.readline())["text"])
    per = (total["server_kv_chunks_summarised_total"]
           / total["server_kv_positions_written_total"])
    assert 0.058 < per <= 0.0625
    assert 0.05 < (total["server_state_rows_held_total"]
                   / total["server_positions_held_total"]) < 0.5
    assert total["server_attn_summary_rows_read_total"] > 0
    for name in ("summary_rows_read_share", "state_rows_held_share",
                 "chunks_summarised_per_position"):
        got = metrics.get("cpu_dry_run." + name)
        assert got is None or got["value"] > 0      # a round in the window
    # device metrics need a device trace: none is printed from a CPU
    assert "cpu_dry_run.step_roofline_share" not in metrics
    check_line = json.loads(
        next(l for l in lines if l.startswith("CHECK "))[6:])
    assert check_line["pass"] and check_line["layers"] == 4
    assert check_line["quant"] == "none"
    assert check_line["sizes"] == {"check": {"layers": 4},
                                   "cell": {"layers": 2}}
    assert check_line["burst_rounds"] == 8
    assert check_line["burst_tokens"] > 100
    run = json.loads(next(l for l in lines if l.startswith("RUN "))[4:])
    assert run["compiles_in_window"] == 0 and run["stopped_early"] == 0
