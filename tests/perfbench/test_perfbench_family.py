"""A new family goes into the benchmark by files alone: its configuration
names its own module (plain reference, seeded checkpoint, cost count), the
check builds the program's configuration through the program's parser and
may run at what the cell lists as reduced. Shown on a throw-away root:
a copy of the benchmark plus ``fixtures/family`` (a qwen2-family
configuration at the dry-run preset, its module, and a second module that
is wrong in one term)."""

import dataclasses
import importlib
import json
import os
import shutil
import subprocess
import sys

import pytest

from perfbench.harness import check, readers, roofline
from perfbench.harness.manifest import (Manifest, ManifestError,
                                        defined_names, load_module)

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
FAMILY = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures",
                      "family")
PLAIN = "perfbench/references/qwen2_plain.py"
NO_BIAS = "perfbench/references/qwen2_no_bias.py"


def snapshot(root):
    out = {}
    for base, _, files in os.walk(root):
        for name in files:
            path = os.path.join(base, name)
            with open(path, "rb") as f:
                out[path] = f.read()
    return out


def add_config(root, name, edit=None):
    """One more configuration + cell in the root: a new file and two new
    entries. ``edit`` changes the fixture's body before it is written."""
    with open(os.path.join(FAMILY, "perfbench/configs/qwen2-mini.json")) as f:
        body = json.load(f)
    body["name"] = name
    body["source"] += "#" + name
    if edit:
        edit(body)
    rel = f"perfbench/configs/{name}.json"
    with open(os.path.join(root, rel), "w") as f:
        json.dump(body, f)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        d = json.load(f)
    d["configs"].append({"name": name, "source": body["source"], "file": rel,
                         "reduced": body["reduced"], "why": "throw-away"})
    d["workloads"].append({"name": name + "-mini8", "config": name,
                           "traffic": "mini8", "chips": 1,
                           "why": "throw-away cell of a family brought as "
                                  "files"})
    # a closed loop: decided by the 75th percentile, as the closed cells
    # are (PR 34 listed its cell in the metrics' ``workloads`` likewise)
    next(m for m in d["end_to_end"] if m["name"] == "gap_p75_ms")[
        "workloads"].append(name + "-mini8")
    with open(path, "w") as f:
        json.dump(d, f)
    return rel


@pytest.fixture
def family_root(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "perfbench"), tmp_path / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = snapshot(tmp_path / "perfbench")
    shutil.copytree(os.path.join(FAMILY, "perfbench"), tmp_path / "perfbench",
                    dirs_exist_ok=True,
                    ignore=shutil.ignore_patterns("configs"))
    yield str(tmp_path)
    for path, body in before.items():      # nothing that existed changed
        with open(path, "rb") as f:
            assert f.read() == body, path


def check_process(root, config_rel, *extra):
    """The check of one seed in the root: the process and its CHECK rows."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=root + os.pathsep + ROOT)
    env.pop("XLA_FLAGS", None)
    res = subprocess.run(
        [sys.executable, "-m", "perfbench.harness.check",
         "--config", os.path.join(root, config_rel),
         "--traffic", os.path.join(root, "perfbench/traffic/mini8.json"),
         "--seeds", "11", *extra],
        cwd=root, env=env, capture_output=True, text=True, timeout=600)
    return res, [json.loads(l[6:]) for l in res.stdout.splitlines()
                 if l.startswith("CHECK ")]


def run_check(root, config_rel, *extra):
    res, rows = check_process(root, config_rel, *extra)
    assert rows, res.stderr[-2000:]
    return res.returncode, rows[-1]


@pytest.mark.parametrize("module,control,passes", [
    (PLAIN, False, True), (NO_BIAS, False, False),
    (PLAIN, True, False), (NO_BIAS, True, False)])
def test_the_module_a_configuration_names_decides(family_root, module,
                                                  control, passes):
    rel = add_config(family_root, "qwen2-mini",
                     lambda body: body.update(reference=module))
    Manifest(family_root).validate()
    rc, row = run_check(family_root, rel, *(["--control"] if control else []))
    assert (rc == 0) is passes and row["pass"] is passes and row["finite"]
    assert row["quant"] == ("int8" if control else "none")
    assert row["logit_rows"] == 9 and row["burst_tokens"] == 36
    if module == PLAIN:
        # the control stands clear of the sound reading (0.0058-0.0070 and
        # 0.0167-0.0201 over four seeds), the limit between them
        assert (row["logit_rel_rms"] < 0.008) is passes
        assert (row["logit_rel_rms"] > 0.015) is not passes
    else:       # biases left out: not a rounding, another model
        assert row["logit_rel_rms"] > 0.5 and row["burst_gap"] > 0.1
    # the line says how far the comparison stood from the cell's own size
    args = ["--model", "qwen2-0.5b", "--num_layers"]
    # ... and that its slots were as long as the served ones
    assert row["sizes"] == {
        "check": {"num_hidden_layers": 2, "layers": 2,
                  "max_session_len": 64, "model_args": args + ["2"]},
        "cell": {"num_hidden_layers": 4, "layers": 4,
                 "max_session_len": 64, "model_args": args + ["4"]}}


def half_a_module(body, root):
    rel = "perfbench/references/half.py"
    with open(os.path.join(root, rel), "w") as f:
        f.write("def make_weights(hf, layers, seed, dtype):\n    return {}\n")
    body["reference"] = rel


# what is broken -> (edit(body, root) of the fixture's file, what the error says)
REFUSED = {
    "reference outside paths": (
        lambda body, root: body.update(reference="scripts/qwen2_plain.py"),
        "under paths"),
    "reference leads out of paths": (
        lambda body, root: body.update(
            reference="perfbench/../tests/conftest.py"), "under paths"),
    "reference file is missing": (
        lambda body, root: body.update(
            reference="perfbench/references/absent.py"),
        "absent.py' is missing"),
    "reference lacks a function": (half_a_module, "does not define forward"),
    "drive outside paths": (
        lambda body, root: body["check"].update(drive="scripts/steps4.py"),
        "check.drive 'scripts/steps4.py' is no Python file under paths"),
    "drive file is missing": (
        lambda body, root: body["check"].update(
            drive="perfbench/drives/absent.py"), "absent.py' is missing"),
    "drive file defines no drive": (
        lambda body, root: body["check"].update(drive=PLAIN),
        "check.drive .* does not define drive"),
    "reduced_to names a key that is not reduced": (
        lambda body, root: body["check"]["reduced_to"].update(vocab_size=1024),
        "not in the configuration's reduced"),
    "reduced_to names a width": (
        lambda body, root: body["check"]["reduced_to"].update(hidden_size=64),
        "names a width"),
    "check.layers under the stated period": (
        lambda body, root: body.update(layer_period=3),
        "under one whole period"),
    "check.model_args is no list": (
        lambda body, root: body["check"].update(model_args="--num_layers 2"),
        "no list of strings"),
}


@pytest.mark.parametrize("what", sorted(REFUSED))
def test_a_broken_configuration_file_is_refused(family_root, what):
    edit, says = REFUSED[what]
    add_config(family_root, "broken", lambda body: edit(body, family_root))
    with pytest.raises(ManifestError, match="config broken: .*" + says):
        Manifest(family_root).validate()


def test_names_are_read_without_running_the_file(family_root):
    plain = os.path.join(family_root, PLAIN)
    assert {"make_weights", "forward", "tick_cost"} <= defined_names(plain)
    # an assignment binds a name as a definition does
    assert {"make_weights", "forward"} <= defined_names(
        os.path.join(family_root, NO_BIAS))
    assert "tick_cost" not in defined_names(os.path.join(family_root, NO_BIAS))
    with pytest.raises(ManifestError):
        defined_names(os.path.join(family_root, "perfbench/absent.py"))


@pytest.mark.parametrize("config,key", [
    ("gpt2-xl", "deployment"), ("qwen2-7b-int8", "deployment"),
    ("gpt2-xl", "dry_run"), ("qwen2-7b-int8", "dry_run")])
def test_the_programs_parser_gives_the_configuration_get_config_gave(
        config, key):
    """What the check built before (the preset, depth replaced) and what
    it builds now (the server's own way) are the same ModelConfig."""
    models = importlib.import_module(check.PKG + ".models")
    body = Manifest(ROOT).config(config)
    args = (body["deployment"]["model_args"] if key == "deployment"
            else body["dry_run_model_args"])
    layers = body["check"]["layers"]
    preset = args[args.index("--model") + 1]
    before = dataclasses.replace(models.get_config(preset), num_layers=layers)
    built = check.program_config(args)
    assert dataclasses.replace(built, num_layers=layers) == before
    want = (int(args[args.index("--num_layers") + 1])
            if "--num_layers" in args else models.get_config(preset).num_layers)
    assert built.num_layers == want
    assert "reference" not in body and check.reference_of(body).__name__ \
        == "perfbench.harness.reference"


@pytest.mark.parametrize("own", [True, False])
def test_step_roofline_takes_the_configurations_count_when_it_has_one(
        family_root, own):
    rel = add_config(family_root, "qwen2-mini", lambda body: body.update(
        reference=PLAIN if own else NO_BIAS))    # NO_BIAS gives no tick_cost
    man = Manifest(family_root)
    cfg = man.config("qwen2-mini")
    assert rel == man.config_entry("qwen2-mini")["file"]
    text = ("server_batch_fill_sessions_sum 30\n"
            "server_batch_fill_sessions_count 10\n")
    recs = [{"sent": 1.0, "due": None, "error": None, "prompt_len": 20,
             "deliveries": [[2.0, 1], [3.0, 4]]}]
    ctx = {"counters_before": {}, "counters_after": {
               "p": readers.parse_prometheus(text)},
           "records": recs, "w0": 0.0, "w1": 10.0,
           "traffic": {"route": {"burst": 4}}, "config": cfg,
           "reference_file": man.reference_file(cfg),
           "hf": cfg["hf_config"], "device": {"kind": "TPU v5 lite"},
           "trace": {"programs": {"jit_fn(1)": {"whole": 3, "mean_s": 0.02}}}}
    share = readers.read_metric(man, "step_roofline_share", ctx)
    hf, rows = cfg["hf_config"], readers.stats.ctx_rows_in_use(recs, 0.0, 10.0)
    kw = dict(layers=4, sessions=3.0, kv_rows=rows, weight_bytes=2)
    stock = roofline.tick_cost(hf, **kw)
    if own:
        cost = load_module(os.path.join(family_root, PLAIN)).tick_cost(
            hf, ctx={}, **kw)
        assert cost["bytes"] == stock["bytes"] + 4 * (896 + 2 * 128) * 2
        assert ctx["notes"]["tick_cost"] == "qwen2_plain"   # it got the ctx
    else:
        cost = stock
        assert "tick_cost" not in ctx.get("notes", {})
    least, _ = roofline.roofline_s(cost, "TPU v5 lite")
    assert share == pytest.approx(100.0 * least / 0.005, rel=1e-12)
    assert 0 < share < 100
