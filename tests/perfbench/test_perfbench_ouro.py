"""The Ouro-2.6B configuration and its cell: the file holds the published
config key for key with nothing reduced, the reference's count of a tick
reads the weights once a PASS and the cache once a (pass, layer), the two
readers this cell brings read their counters and find nothing in a program
without them, and the whole harness rehearses on the CPU at the same
preset cut to 2 layers (registry, server, load generator, traced window,
the check with all 4 passes)."""

import json
import os
import subprocess
import sys

import pytest

from perfbench.harness import readers, roofline
from perfbench.harness.manifest import Manifest, load_module

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
CELL, CONFIG, TRAFFIC = "ouro-2.6b-reason-sat8", "ouro-2.6b", "reason-sat8"
# The catalog row's ``config`` (ISSUE 34 lists it; the catalog itself is
# compared below where the machine has it).
PUBLISHED = {
    "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 5632, "layer_types": ["full_attention"] * 48,
    "max_position_embeddings": 65536, "max_window_layers": 48,
    "model_type": "ouro", "num_attention_heads": 16,
    "num_hidden_layers": 48, "num_key_value_heads": 16,
    "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1000000,
    "sliding_window": None, "tie_word_embeddings": False,
    "total_ut_steps": 4, "early_exit_threshold": 1,
    "use_sliding_window": False, "vocab_size": 49152}
LAYER = 4 * 2048 * 2048 + 3 * 2048 * 5632        # q, k, v, o; gate, up, down


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
    assert entry["reduced"] == body["reduced"] == []
    cell = man.workload(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, TRAFFIC, 1)
    assert {m["name"] for m in man.metrics_for(CELL, "end_to_end")} == {
        "gap_p75_ms", "setup_s"}
    layer = {m["name"] for m in man.metrics_for(CELL, "per_layer")}
    every = {m["name"] for m in man.data["per_layer"]
             if not m["name"].endswith(".open")}   # the open loop's splits
    assert layer == every - {"int8_kernel_roofline_share"}
    assert {"loop_exit_step_mean", "kv_stack_gb", "client_tokens_per_s",
            "step_roofline_share", "device_ms_per_tick"} <= layer
    for key in ("sandwich_norms", "per_pass_final_norm", "exit_gate",
                "kv_cache", "biases", "tensor_names", "initializer_range"):
        assert key in body["assumed"], key
    chk = body["check"]
    assert chk["layers"] >= 6 and chk["control"] == "int8"
    assert chk["dry_run_hf_config"]["total_ut_steps"] == 4   # never cut
    args = body["deployment"]["servers"][0]["args"]
    assert body["deployment"]["model_args"] == ["--model", "ouro-2.6b"]
    assert args[args.index("--max_session_len") + 1] == "512"
    # what a 512-row slot holds after the longest prompt of the check
    rows = 200 + chk["decode_steps"] + 16 * chk["burst_rounds"] + 1
    assert rows <= 512


@pytest.mark.parametrize("key", sorted(PUBLISHED))
def test_a_published_key_is_in_the_file_at_its_value(body, key):
    """Letter for letter, a null stays null; the file holds the keys twice
    (its "layout" says why): at the top level for the driver's catalog
    check, under hf_config for the harness."""
    hf = body["hf_config"]
    assert key in hf and key in body
    assert body[key] == hf[key] == PUBLISHED[key]
    assert type(body[key]) is type(hf[key]) is type(PUBLISHED[key])


def test_the_file_holds_the_catalog_row(body):
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog of architectures on this machine")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Ouro-2.6B")
    assert row["config"] == PUBLISHED
    assert body["source"] == row["source_url"]
    for held in (body, body["hf_config"]):
        assert {k for k, v in row["config"].items()
                if held.get(k, "absent") != v} == set()
    assert set(body["hf_config"]) == set(row["config"])


def test_the_traffic_is_the_issue_s(man):
    t = man.traffic(TRAFFIC)
    assert (t["kind"], t["sessions"], t["think_s"]) == ("closed", 8, 0)
    assert t["route"] == {"kind": "full_span", "burst": 16}
    assert sorted(t["prompt_lens"]) == [24, 48, 64, 96, 120, 136, 160, 200]
    assert sorted(t["token_budgets"]) == [96, 128, 160, 192, 192, 224, 256,
                                          288]
    assert max(t["prompt_lens"]) + max(t["token_budgets"]) == 488 <= 512
    assert t["sampling"] == {"temperature": 0.8, "top_p": 0.95, "top_k": 0,
                             "repetition_penalty": 1.0}
    assert (t["ramp_finished_requests"], t["request_timeout_s"],
            t["trace_seconds"]) == (8, 120, 6)


def test_the_program_s_preset_is_the_file_s(body):
    import importlib

    cfg = importlib.import_module(
        "global_capstone_design_distributed_inference_of_llms_over_the_"
        "internet_tpu.models.config").get_config("ouro-2.6b")
    hf = body["hf_config"]
    assert (cfg.hidden_size, cfg.num_layers, cfg.num_heads,
            cfg.num_kv_heads, cfg.head_dim, cfg.intermediate_size,
            cfg.vocab_size, cfg.loop_steps, cfg.exit_threshold,
            cfg.norm_eps, cfg.rope_theta, cfg.max_position_embeddings,
            cfg.tie_word_embeddings) == (
        hf["hidden_size"], hf["num_hidden_layers"],
        hf["num_attention_heads"], hf["num_key_value_heads"],
        hf["head_dim"], hf["intermediate_size"], hf["vocab_size"],
        hf["total_ut_steps"], hf["early_exit_threshold"],
        hf["rms_norm_eps"], hf["rope_theta"],
        hf["max_position_embeddings"], hf["tie_word_embeddings"])


@pytest.mark.parametrize("sessions,rows", [(1.0, 100.0), (4.6, 250.0),
                                           (8.0, 488.0)])
def test_tick_cost_counts_weights_a_pass_and_cache_a_pass_and_layer(
        body, ref, sessions, rows):
    hf = body["hf_config"]
    assert ref.layer_params(hf) == LAYER
    cost = ref.tick_cost(hf, layers=48, sessions=sessions, kv_rows=rows,
                         weight_bytes=2)
    assert cost["weight_bytes"] == 4 * 48 * LAYER * 2         # 19.7 GB
    assert cost["head_bytes"] == 49152 * 2048 * 2             # once
    # K and V, 192 cached layers, 16 heads of 128, 2 bytes: 1.573 MB a row
    assert cost["kv_bytes"] == sessions * rows * 2 * 192 * 16 * 128 * 2
    assert cost["kv_bytes"] / (sessions * rows) == pytest.approx(1.573e6,
                                                                 rel=1e-3)
    assert cost["bytes"] == (cost["weight_bytes"] + cost["head_bytes"]
                             + cost["kv_bytes"])
    assert cost["flops"] == (
        2.0 * sessions * (4 * 48 * LAYER + 49152 * 2048)
        + 4.0 * sessions * rows * 16 * 128 * 192)
    # the stock count reads the weights once: a quarter, plus the head
    stock = roofline.tick_cost(hf, layers=48, sessions=sessions,
                               kv_rows=rows, weight_bytes=2)
    assert cost["weight_bytes"] == 4 * stock["weight_bytes"]
    assert cost["kv_bytes"] == 4 * stock["kv_bytes"]
    least, bound = roofline.roofline_s(cost, "TPU v5 lite")
    assert bound == "memory" and least >= 19.7e9 / 819e9


def counters(steps, tokens, stack, fills=10):
    text = (f"server_loop_exit_steps_total {steps}\n"
            f"server_burst_tokens_total {tokens}\n"
            f"server_kv_stack_bytes {stack}\n"
            f"server_batch_fill_sessions_sum {4.6 * fills}\n"
            f"server_batch_fill_sessions_count {fills}\n")
    return {"p": readers.parse_prometheus(text)}


def fixture_ctx(man, body):
    return {
        "counters_before": counters(400, 100, 6442450944, fills=0),
        "counters_after": counters(400 + 4 * 730, 100 + 730, 6442450944),
        "records": [{"sent": 1.0, "due": None, "error": None,
                     "prompt_len": 100, "deliveries": [[2.0, 16], [3.0, 16]]}],
        "w0": 0.0, "w1": 10.0, "traffic": man.traffic(TRAFFIC),
        "config": body, "reference_file": man.reference_file(body),
        "hf": body["hf_config"], "device": {"kind": "TPU v5 lite"},
        "trace": {"programs": {"jit_burst_tick(1)": {"whole": 3,
                                                     "mean_s": 0.6}},
                  "ops": {}}}


def test_the_two_readers_on_a_fixture(man, body):
    ctx = fixture_ctx(man, body)
    assert readers.read_metric(man, "loop_exit_step_mean", ctx) == 4.0
    assert readers.read_metric(man, "kv_stack_gb", ctx) == pytest.approx(
        192 * 8 * 512 * 16 * 128 * 2 * 2 / 1e9)
    # step_roofline_share goes through the configuration's own tick_cost:
    # four weight streams are 24 ms of a 37.5 ms tick
    share = readers.read_metric(man, "step_roofline_share", ctx)
    assert 64.0 < share < 100.0
    assert ctx["notes"]["step_roofline_bound"] == "memory"


@pytest.mark.parametrize("name", ["loop_exit_step_mean", "kv_stack_gb"])
def test_a_reader_finds_nothing_in_a_program_without_the_counters(
        man, body, name):
    """The parent commit has neither series; a one-pass program of this
    commit never moves the counter. The metric is left out of the line and
    nothing raises."""
    ctx = fixture_ctx(man, body)
    ctx["counters_before"] = ctx["counters_after"] = {
        "p": readers.parse_prometheus("server_burst_tokens_total 5\n")}
    assert readers.read_metric(man, name, ctx) is None
    ctx["counters_before"] = {"p": readers.parse_prometheus(
        "server_loop_exit_steps_total 0\nserver_burst_tokens_total 5\n")}
    ctx["counters_after"] = {"p": readers.parse_prometheus(
        "server_loop_exit_steps_total 0\nserver_burst_tokens_total 900\n")}
    assert readers.read_metric(man, "loop_exit_step_mean", ctx) is None
    ctx.pop("counters_after")
    assert readers.read_metric(man, name, ctx) is None


def test_traced_dry_run_of_the_cell(tmp_path):
    """The whole harness on the CPU: the cell's preset at 2 layers serves,
    the check runs 6 layers x 4 passes at published widths against the
    reference and comes out ``correct``, and the cell's own metrics are on
    the line."""
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
    # over the window where a round ended inside it (on a loaded machine a
    # CPU round can outlast the 4 s), and over the server's whole life
    mean = metrics.get("cpu_dry_run.loop_exit_step_mean")
    assert mean is None or mean["value"] == 4.0
    with open(tmp_path / "out" / "metrics_after.jsonl") as f:
        total = readers.parse_prometheus(json.loads(f.readline())["text"])
    assert (total["server_loop_exit_steps_total"]
            == 4 * total["server_burst_tokens_total"] > 0)
    # [4 passes x 2 layers, 4 slots, 128 rows, 16, 128] x bf16 x (K + V)
    assert metrics["cpu_dry_run.kv_stack_gb"]["value"] == pytest.approx(
        8 * 4 * 128 * 16 * 128 * 2 * 2 / 1e9)
    # device metrics need a device trace: none is printed from a CPU
    assert "cpu_dry_run.step_roofline_share" not in metrics
    check = json.loads(next(l for l in lines if l.startswith("CHECK "))[6:])
    assert check["pass"] and check["layers"] == 6 and check["quant"] == "none"
    assert check["sizes"] == {"check": {"layers": 6}, "cell": {"layers": 2}}
    assert check["burst_rounds"] == 8 and check["burst_tokens"] > 100
    run = json.loads(next(l for l in lines if l.startswith("RUN "))[4:])
    assert run["compiles_in_window"] == 0 and run["stopped_early"] == 0
