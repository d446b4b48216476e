"""The byte and operation counts against hand-worked values; the trace
reduction against a small recorded set of events."""

import json
import os

import pytest

from perfbench.harness import roofline, trace

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def hf(name):
    with open(os.path.join(ROOT, "perfbench", "configs", name + ".json")) as f:
        return json.load(f)["hf_config"]


def test_gpt2_xl_tick_by_hand():
    cfg = hf("gpt2-xl")
    assert roofline.matmul_sites(cfg) == [
        ("wqkv", 1600, 4800), ("wo", 1600, 1600), ("wi", 1600, 6400),
        ("wo_mlp", 6400, 1600)]
    # one layer: 1600*4800 + 1600*1600 + 2*1600*6400 = 30_720_000 weights
    cost = roofline.tick_cost(cfg, layers=48, sessions=8, kv_rows=300,
                              weight_bytes=2)
    weights = 48 * 30_720_000 * 2                      # 2_949_120_000 B
    head = 50257 * 1600 * 2                            # 160_822_400 B
    kv = 8 * 300 * 2 * 48 * 25 * 64 * 2                # 737_280_000 B
    assert cost["weight_bytes"] == weights
    assert cost["head_bytes"] == head
    assert cost["kv_bytes"] == kv
    assert cost["bytes"] == weights + head + kv
    flops = 2 * 8 * (48 * 30_720_000 + 50257 * 1600) \
        + 4 * 8 * 300 * 25 * 64 * 48
    assert cost["flops"] == flops
    least, bound = roofline.roofline_s(cost, "TPU v5 lite")
    assert bound == "memory"
    assert least == pytest.approx(3_847_222_400 / 819e9)   # 4.70 ms


@pytest.mark.parametrize("site,k,n,nbytes", [
    # int8 weight + 4 B scale per column + x[16,k] and y[16,n] in bf16
    ("wqkv", 3584, 4608, 3584 * 4608 + 4 * 4608 + 32 * 3584 + 32 * 4608),
    ("wo", 3584, 3584, 3584 * 3584 + 4 * 3584 + 32 * 3584 + 32 * 3584),
    ("wgu", 3584, 37888, 3584 * 37888 + 4 * 37888 + 32 * 3584 + 32 * 37888),
    ("wd", 18944, 3584, 18944 * 3584 + 4 * 3584 + 32 * 18944 + 32 * 3584),
])
def test_qwen2_7b_int8_sites_by_hand(site, k, n, nbytes):
    sites = {s: (kk, nn) for s, kk, nn in
             roofline.matmul_sites(hf("qwen2-7b-int8"))}
    assert sites[site] == (k, n)
    cost = roofline.int8_site_cost(16, k, n)
    assert cost["bytes"] == nbytes
    assert cost["flops"] == 2 * 16 * k * n


def test_qwen2_tick_counts_int8_weights_and_scales():
    cfg = hf("qwen2-7b-int8")
    cost = roofline.tick_cost(cfg, layers=8, sessions=16, kv_rows=400,
                              weight_bytes=1)
    per_layer = 3584 * 4608 + 3584 * 3584 + 3584 * 37888 + 18944 * 3584
    scales = 4 * (4608 + 3584 + 37888 + 3584)
    assert cost["weight_bytes"] == 8 * (per_layer + scales)
    assert cost["head_bytes"] == 152064 * 3584 * 2
    assert cost["kv_bytes"] == 16 * 400 * 2 * 8 * 4 * 128 * 2


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        roofline.peaks("TPU v9")
    assert roofline.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def test_trace_reduction_on_recorded_events():
    with open(os.path.join(os.path.dirname(__file__), "fixtures",
                           "trace_events.json")) as f:
        fx = json.load(f)
    ops = {dev: [tuple(e) for e in evs] for dev, evs in fx["device"].items()}
    host = [tuple(e) for e in fx["host"]]
    mods = {dev: [tuple(e) for e in evs]
            for dev, evs in fx["modules"].items()}
    got = trace.summarize(ops, host, fx["window_s"], mods)
    want = fx["want"]
    for name, st in want["programs"].items():
        for key, val in st.items():
            assert got["programs"][name][key] == pytest.approx(val)
    assert got["programs"]["jit_fn(11)"]["mean_s"] is None   # cut by the edge
    assert got["busy_s"] == pytest.approx(want["busy_s"])
    assert got["window_s"] == fx["window_s"]
    for name, secs in want["op_seconds"].items():
        assert got["ops"][name]["seconds"] == pytest.approx(secs)
    assert got["breakdown"]["device_ops"][0][0] == want["top_op"]
    gaps = dict(got["breakdown"]["idle_gaps"])
    for label, secs in want["gaps"].items():
        assert gaps[label] == pytest.approx(secs)
    idle = 100 * (1 - got["busy_s"] / got["window_s"])
    assert idle == pytest.approx(want["idle_share"])
    # the stretch the trace recorded, which device_idle_share divides by
    rec, first = got["recorded"], sorted(ops.items())[0][1]
    assert rec["device_events"] == len(first)
    assert rec["first_op_s"] == min(s for _, s, _ in first)
    assert rec["last_op_s"] - rec["first_op_s"] == pytest.approx(
        got["extent_s"])
    assert rec["host_events"] == len(host) and got["extent_s"] <= fx["window_s"]


def test_union_and_self_times():
    assert trace.union_seconds([(0, 1), (0.5, 2), (3, 4)]) == 3
    assert trace.idle_gaps([(1, 2), (3, 4)], 0, 5) == [(0, 1), (2, 3), (4, 5)]
    st = dict(trace.self_times([("while", 0.0, 10.0), ("a", 1.0, 2.0),
                                ("b", 3.0, 4.0), ("c", 3.5, 1.0)]))
    assert st == {"while": 4.0, "a": 2.0, "b": 3.0, "c": 1.0}
