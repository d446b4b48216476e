"""A cell's slot length is what its configuration's server is started with,
and the check, the rehearsal and the slot test read it there: no row count
is written into the harness. Shown on a throw-away cell whose sessions pass
1024 rows (``fixtures/family``: ``qwen2-mini-long`` serving 1280-row slots
under the mix ``long3``), brought into a copy of the benchmark as files
and entries alone. And the stock reference's attention, computed in blocks
of query rows, is the unblocked one."""

import copy
import functools
import importlib
import json
import os
import shutil
import subprocess
import sys

import pytest

from perfbench.harness import check, reference, traffic
from perfbench.harness.manifest import Manifest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
FAMILY = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures",
                      "family", "perfbench")
LONG_CONFIG = "perfbench/configs/qwen2-mini-long.json"
CELLS = [w["name"] for w in Manifest(ROOT).data["workloads"]]


def fixture(rel):
    with open(os.path.join(FAMILY, rel)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def bench_run():
    return importlib.import_module("perfbench.run")


@pytest.fixture
def long_root(tmp_path):
    """A copy of the benchmark with the long cell added: three new files
    (the configuration, its mix, nothing else) and three new entries."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "perfbench"), tmp_path / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    for rel in (LONG_CONFIG, "perfbench/traffic/long3.json"):
        assert not os.path.exists(tmp_path / rel)
        shutil.copy(os.path.join(FAMILY, "..", rel), tmp_path / rel)
    body = fixture("configs/qwen2-mini-long.json")
    path = tmp_path / "BENCHMARK.json"
    d = json.loads(path.read_text())
    d["configs"].append({"name": body["name"], "source": body["source"],
                         "file": LONG_CONFIG, "reduced": body["reduced"],
                         "why": "throw-away"})
    d["workloads"].append({"name": "qwen2-mini-long3", "chips": 1,
                           "config": body["name"], "traffic": "long3",
                           "why": "throw-away cell whose sessions pass 1024 "
                                  "rows"})
    next(m for m in d["end_to_end"] if m["name"] == "gap_p75_ms")[
        "workloads"].append("qwen2-mini-long3")
    path.write_text(json.dumps(d))
    return str(tmp_path)


def fits(man, cell):
    """What `test_prompt_plus_budget_fits_the_slot` asserts of a cell."""
    w = man.workload(cell)
    return traffic.slot_rows(man.traffic(w["traffic"])) <= check.slot_len(
        man.config(w["config"]))


def test_a_mix_past_1024_rows_is_held_to_its_own_cells_slot(long_root):
    man = Manifest(long_root)
    man.validate()
    mix = man.traffic("long3")
    # the longest STATED pair, (1150, 8): not max + max, which none sends
    assert traffic.slot_rows(mix) == 1157
    assert max(mix["prompt_lens"]) + max(mix["token_budgets"]) == 1350
    assert check.slot_len(man.config("qwen2-mini-long")) == 1280
    assert fits(man, "qwen2-mini-long3")
    assert all(fits(man, cell) for cell in CELLS)
    # a copy whose longest pair passes the slot: (1150, 200)
    with open(os.path.join(long_root, "perfbench/traffic/long3.json"),
              "w") as f:
        json.dump(dict(mix, pairing=[0, 1, 2]), f)
    assert traffic.slot_rows(man.traffic("long3")) == 1349
    assert not fits(man, "qwen2-mini-long3")


@pytest.mark.parametrize("pairs,burst,rows", [
    ([(10, 5)], 0, 14),             # P rows, then one a token fed back
    ([(10, 5), (6, 12)], 4, 17),
    ([(100, 2)], 16, 117),          # the warm-up: shortest prompt, burst + 2
    ([(100, 2), (20, 3)], 16, 101)])
def test_what_a_mix_needs_of_a_slot(pairs, burst, rows):
    spec = {"prompt_lens": [p for p, _ in pairs],
            "token_budgets": [b for _, b in pairs],
            "pairing": list(range(len(pairs))), "route": {"burst": burst}}
    assert traffic.slot_rows(spec) == rows
    assert traffic.least_slot(spec) == -(-rows // 128) * 128
    assert traffic.least_slot(spec) - rows < traffic.SLOT_BLOCK


def slot_of(argv):
    return argv[argv.index("--max_session_len") + 1]


@pytest.mark.parametrize("cell", CELLS)
def test_the_rehearsal_of_a_real_cell_keeps_its_argv(cell, bench_run):
    """Byte for byte what the constant gave: a slot of 128 rows."""
    man = Manifest(ROOT)
    w = man.workload(cell)
    config = man.config(w["config"])
    dry = bench_run.dry_traffic(man.traffic(w["traffic"]))
    srv = config["deployment"]["servers"][0]
    argv = bench_run.server_argv(srv, config["dry_run_model_args"],
                                 "127.0.0.1:1", 7, False, True, dry)
    want = list(srv["args"])
    for flag, val in (("--max_session_len", "128"),
                      ("--slots", str(dry["sessions"])), ("--burst", "4")):
        want[want.index(flag) + 1] = val
    assert argv == bench_run.procs.python_argv(
        "perfbench.harness.serve_shim", *want, "--registry_addr",
        "127.0.0.1:1", "--seed", "7", *config["dry_run_model_args"])
    # and outside the rehearsal the served slot stands
    served = bench_run.server_argv(srv, [], "127.0.0.1:1", 7, False, False,
                                   man.traffic(w["traffic"]))
    assert int(slot_of(served)) == check.slot_len(config)


def test_the_rehearsal_s_slot_follows_the_rehearsal_s_traffic(bench_run):
    config = fixture("configs/qwen2-mini-long.json")
    dry = bench_run.dry_traffic(fixture("traffic/long3.json"))
    assert traffic.pairs_of(dry) == [(4, 25), (75, 3), (143, 3)]
    argv = bench_run.server_argv(config["deployment"]["servers"][0], [],
                                 "127.0.0.1:1", 7, False, True, dry)
    # 145 rows do not fit 128: the least multiple of the block that holds
    # them, whatever the configuration serves
    assert traffic.slot_rows(dry) == 145 and slot_of(argv) == "256"
    assert argv[argv.index("--slots") + 1] == "3"


def test_the_check_s_engine_is_as_long_as_the_served_slot():
    """``build`` sizes the engine by the served ``--max_session_len``, not
    by what the drive needs, and says so on the line's ``sizes``."""
    config = fixture("configs/qwen2-mini-long.json")
    mix = fixture("traffic/long3.json")
    b = check.build(config, mix, 11, control=False, dry=False)
    assert b["lens"] == [24, 600, 1150]
    assert b["eng"].max_len == 1280 and b["eng"].slots == 3
    assert b["sizes"]["check"]["max_session_len"] == 1280 == \
        b["sizes"]["cell"]["max_session_len"]


def test_a_check_that_needs_more_rows_than_the_cell_serves_is_refused(
        monkeypatch):
    config = fixture("configs/qwen2-mini-long.json")
    mix = fixture("traffic/long3.json")
    short = copy.deepcopy(config)
    args = short["deployment"]["servers"][0]["args"]
    args[args.index("--max_session_len") + 1] = "1152"
    # before any weight or engine is made
    monkeypatch.setattr(check, "reference_of", lambda config: None)
    monkeypatch.setattr(check, "program_config", lambda args: 1 / 0)
    with pytest.raises(ValueError, match="config qwen2-mini-long: the check "
                       "needs 1165 rows, the cell serves 1152"):
        check.build(short, mix, 11, control=False, dry=False)
    at = args.index("--max_session_len")
    del args[at:at + 2]
    with pytest.raises(ValueError, match="states no --max_session_len"):
        check.slot_len(short)


@pytest.mark.parametrize("control", [False, True])
def test_the_check_of_a_cell_past_1024_rows(long_root, control):
    """The whole check of the long cell on the CPU: prompts of 24, 600 and
    1150 rows into 1280-row slots, reference passes of 1165 rows (two
    blocks of the blocked attention). The program passes and its control
    does not (sound 0.0149-0.0173, control 0.0301-0.0327 over four
    seeds)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=long_root + os.pathsep + ROOT)
    env.pop("XLA_FLAGS", None)
    res = subprocess.run(
        [sys.executable, "-m", "perfbench.harness.check",
         "--config", os.path.join(long_root, LONG_CONFIG),
         "--traffic", os.path.join(long_root, "perfbench/traffic/long3.json"),
         "--seeds", "11", *(["--control"] if control else [])],
        cwd=long_root, env=env, capture_output=True, text=True, timeout=600)
    rows = [json.loads(l[6:]) for l in res.stdout.splitlines()
            if l.startswith("CHECK ")]
    assert rows, res.stderr[-2000:]
    row = rows[-1]
    assert (res.returncode == 0) is (not control) is row["pass"]
    assert row["prompt_lens"] == [24, 600, 1150] and row["finite"]
    assert row["logit_rows"] == 9 and row["burst_tokens"] == 36
    assert row["sizes"]["check"]["max_session_len"] == 1280
    assert (row["logit_rel_rms"] < 0.02) is (not control)
    assert (row["logit_rel_rms"] > 0.027) is control


def unblocked_attention(q, k, v):
    """`reference._causal_attention` as it stood: one [H, T, T] pass."""
    import jax
    import jax.numpy as jnp

    t, h, dh = q.shape
    rep = h // k.shape[1]
    k = jnp.repeat(k, rep, axis=1)
    v = jnp.repeat(v, rep, axis=1)
    scores = jnp.einsum("thd,shd->hts", q, k) / jnp.sqrt(jnp.float32(dh))
    mask = jnp.tril(jnp.ones((t, t), bool))
    scores = jnp.where(mask[None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("hts,shd->thd", probs, v).reshape(t, h * dh)


GPT2 = {"model_type": "gpt2", "n_embd": 64, "n_head": 4, "n_layer": 2,
        "n_positions": 256, "vocab_size": 211, "layer_norm_epsilon": 1e-5}
LLAMA = {"model_type": "qwen2", "hidden_size": 64, "intermediate_size": 160,
         "num_attention_heads": 4, "num_key_value_heads": 2,
         "num_hidden_layers": 2, "vocab_size": 211, "rms_norm_eps": 1e-6,
         "rope_theta": 10000.0, "tie_word_embeddings": False}


@pytest.mark.parametrize("hf", [GPT2, LLAMA],
                         ids=lambda hf: hf["model_type"])
def test_blocked_attention_is_the_unblocked_one(hf, monkeypatch):
    """``forward`` over T = 203 rows, no multiple of the block: blocks of
    64 rows against one [H, T, T] pass, float32 at ``highest``, to 1e-6
    relative; a pass of at most ONE block is the old pass to the bit."""
    import jax.numpy as jnp
    import numpy as np

    weights = reference.make_weights(hf, 2, 2 ** 31 + 5, jnp.float32)
    ids = jnp.asarray(np.random.default_rng(3).integers(0, 211, 203),
                      jnp.int32)
    one_block = np.asarray(reference.forward(hf, 2, weights, ids))
    monkeypatch.setattr(reference, "_causal_attention", functools.partial(
        reference._causal_attention, block=64))
    in_blocks = np.asarray(reference.forward(hf, 2, weights, ids))
    monkeypatch.setattr(reference, "_causal_attention", unblocked_attention)
    plain = np.asarray(reference.forward(hf, 2, weights, ids))
    assert plain.shape == (203, 211) and np.isfinite(plain).all()
    assert np.array_equal(one_block, plain)
    scale = np.abs(plain).max()
    assert np.abs(in_blocks - plain).max() <= 1e-6 * scale
