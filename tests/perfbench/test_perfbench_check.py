"""The comparison that decides ``correct``, at a size a test run can hold
(the configurations' dry-run presets, two layers, on the CPU): the
program's engine passes, and each configuration's CONTROL — the program's
own next quantisation down — comes out as not correct."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELLS = [("gpt2-xl", "chat-sat8"), ("qwen2-7b-int8", "decode16")]


def check(config, traffic, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    env.pop("XLA_FLAGS", None)
    res = subprocess.run(
        [sys.executable, "-m", "perfbench.harness.check",
         "--config", os.path.join(ROOT, "perfbench/configs", config + ".json"),
         "--traffic", os.path.join(ROOT, "perfbench/traffic",
                                   traffic + ".json"),
         "--seeds", "11", "--dry-run-cpu", *extra],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    rows = [json.loads(l[6:]) for l in res.stdout.splitlines()
            if l.startswith("CHECK ")]
    assert rows, res.stderr[-2000:]
    return res.returncode, rows[-1]


@pytest.mark.parametrize("config,traffic", CELLS)
def test_the_program_passes_and_its_control_fails(config, traffic):
    rc, sound = check(config, traffic)
    assert rc == 0 and sound["pass"] and sound["finite"]
    assert sound["burst_tokens"] > 0 and sound["logit_rows"] >= 9
    rc, control = check(config, traffic, "--control")
    assert rc != 0 and not control["pass"]
    assert control["quant"] == control["control"] != sound["quant"]
    # the limit lies between the two readings, with room on both sides
    limit = sound["logit_rel_rms_limit"]
    assert sound["logit_rel_rms"] * 1.25 <= limit \
        <= control["logit_rel_rms"] / 1.25
    # burst_gap is a mean over the burst's tokens and the rehearsal emits a
    # quarter of them (4-tick bursts): the sound reading stays under the
    # limit and the control's stands well clear of the sound one
    assert sound["burst_gap"] * 1.25 <= sound["burst_gap_limit"]
    assert control["burst_gap"] > 3 * sound["burst_gap"]
