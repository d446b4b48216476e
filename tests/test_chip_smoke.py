"""One process per chip, and no fallback that hides the device (PR 21).

What the chip check rests on, pinned on the CPU: host-side roles never
open a JAX backend, launchers hand each child its platform explicitly, the
compile cache can be placed from outside, the quantized kernels' tile
picker never plans past its own VMEM budget, a session served whole by a
full-span peer computes nothing in the client, and ``chip_smoke.py`` fails
where JAX finds no accelerator. ``--cpu-dry-run`` (every phase's code path
at a tiny preset) is what a builder runs before spending chip time.
"""

import json
import os
import subprocess
import sys

import jax
import pytest

import global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.ops.int8_kernel as IK
import global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.ops.nf4_kernel as NK
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.utils import (
    platform as plat,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MAIN = ("global_capstone_design_distributed_inference_of_llms_over_the"
        "_internet_tpu.main")
# A platform JAX does not know: ANY backend initialisation raises.
NO_BACKEND = dict(os.environ, JAX_PLATFORMS="no_such_platform")


def _run(argv, env, timeout=120):
    return subprocess.run(argv, cwd=REPO, env=env, timeout=timeout,
                          capture_output=True, text=True)


def test_host_side_roles_open_no_backend():
    """Importing main, --mode registry and --mode status must work where
    initialising ANY JAX backend raises: they share a machine with the
    role that owns the chip."""
    out = _run([sys.executable, "-c", f"import {MAIN}"], NO_BACKEND)
    assert out.returncode == 0, out.stderr
    reg = subprocess.Popen(
        [sys.executable, "-m", MAIN, "--mode", "registry",
         "--registry_port", "31481"], cwd=REPO, env=NO_BACKEND,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
    try:
        line = reg.stdout.readline()
        assert line.startswith("REGISTRY_ADDR="), line + reg.stdout.read()
        out = _run([sys.executable, "-m", MAIN, "--mode", "status",
                    "--registry_addr", "127.0.0.1:31481"], NO_BACKEND)
        # The verdict on an empty swarm — it asked the registry and got an
        # answer, which is all this test needs of it.
        assert "no live servers" in out.stdout, out.stdout + out.stderr
        assert "Unable to initialize backend" not in out.stdout + out.stderr
        assert reg.poll() is None
    finally:
        reg.kill()
        reg.wait(timeout=30)


def test_launcher_envs_state_the_platform():
    base = {"JAX_PLATFORMS": "tpu,cpu", "HOME": "/h"}
    assert plat.host_env(base)["JAX_PLATFORMS"] == "cpu"
    owner = plat.chip_env(base)
    assert owner["JAX_PLATFORMS"] == "tpu"
    assert "TPU_VISIBLE_CHIPS" not in owner
    pinned = plat.chip_env(base, 2)
    assert (pinned["JAX_PLATFORMS"], pinned["TPU_VISIBLE_CHIPS"],
            pinned["TPU_CHIPS_PER_PROCESS_BOUNDS"],
            pinned["TPU_PROCESS_BOUNDS"]) == ("tpu", "2", "1,1,1", "1,1,1")
    # No platform named: a chip owner still gets the TPU, never a default
    # to the CPU ...
    assert plat.chip_env({})["JAX_PLATFORMS"] == "tpu"
    # ... and a caller that chose the CPU keeps it, unpinned.
    assert plat.chip_env({"JAX_PLATFORMS": "cpu"}, 3) == {
        "JAX_PLATFORMS": "cpu"}
    assert base == {"JAX_PLATFORMS": "tpu,cpu", "HOME": "/h"}


def test_compile_cache_can_be_placed_from_outside(monkeypatch, tmp_path):
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert plat.compile_cache_dir() == str(tmp_path)
        # Placed from outside: the program sets nothing in code.
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        want = os.path.join(REPO, ".jax_cache")
        assert plat.compile_cache_dir() == want
        assert jax.config.jax_compilation_cache_dir == want
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_the_backend_starts_with_pallas_imported_meanwhile(monkeypatch):
    """`start_backend`: a chip owner's backend start-up hides the import of
    Pallas (a thread of its own, still importing while the backend starts,
    JOINED before the call returns); a process that chose the CPU does
    neither."""
    import importlib
    import threading

    imported, seen, real = [], [], jax.devices
    importing, started = threading.Event(), threading.Event()

    def import_module(name):
        imported.append((name, threading.current_thread().name))
        importing.set()
        assert started.wait(10)

    def devices():
        assert importing.wait(10)       # the import is under way ...
        seen.append(len(imported))
        started.set()                   # ... and ends after the backend
        return real()

    monkeypatch.setattr(importlib, "import_module", import_module)
    monkeypatch.setattr(jax, "devices", devices)
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    plat.start_backend()
    assert imported == [] and seen == []
    monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
    plat.start_backend()
    assert imported == [("jax.experimental.pallas.tpu", "import-pallas")]
    assert seen == [1]
    assert not [t for t in threading.enumerate() if t.name == "import-pallas"]


@pytest.mark.parametrize("mod", [IK, NK], ids=["int8", "nf4"])
def test_tiles_never_exceed_their_own_budget(mod):
    """Every tile the picker returns fits its own estimate and divides the
    shape; where nothing fits it says so (None -> the XLA path) instead of
    falling back to a tile it never checked. Includes qwen2-7b's fused
    sites — wd at K = 18944 is the one a full-K stripe cannot hold."""
    shapes = [(4608, 3584), (3584, 3584), (37888, 3584), (3584, 18944),
              (4096, 4096), (11008, 4096), (4096, 11008), (128, 128),
              (512, 32768), (2304, 768)]
    picked_none = False
    for n, k in shapes:
        for m in (8, 16, 128, 1024, 8192, 65536):
            for x_bytes in (2, 4):
                got = mod._tiles(n, k, m, x_bytes)
                if got is None:
                    picked_none = True
                    continue
                tn, tk = got
                stripe = tk if mod is IK else 2 * tk   # nf4: packed rows
                assert n % tn == 0 and k % stripe == 0 and tn % 128 == 0
                assert mod._vmem_bytes(m, tk, tn, x_bytes) <= mod.VMEM_BUDGET
    assert picked_none     # a huge m really is refused, not squeezed in
    # The site the old full-K picker overran: several K stripes now.
    tn, tk = IK._tiles(3584, 18944, 16, 2)
    assert tk < 18944


def test_chip_smoke_fails_where_jax_finds_no_accelerator(tmp_path):
    out = _run([sys.executable, "chip_smoke.py", "--out", str(tmp_path)],
               dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode != 0, out.stdout
    assert "JAX found no accelerator" in out.stdout
    assert '"ok"' not in out.stdout


def _assert_dry_run_passed(out):
    assert out.returncode == 0, out.stdout[-4000:] + out.stderr[-2000:]
    last = json.loads(out.stdout.strip().splitlines()[-1])
    assert last["ok"] is True and last["dry_run"] is True
    assert last["device"]["platform"] == "cpu"


@pytest.mark.slow
def test_chip_smoke_cpu_dry_run(tmp_path):
    """Every one-chip phase's code path end to end (tiny preset, Pallas
    kernels interpreted): served burst == repeat == per-step ids, engine
    logits vs the float32 reference, quantized serving + kernel report."""
    out = _run([sys.executable, "chip_smoke.py", "--cpu-dry-run", "--out",
                str(tmp_path)], dict(os.environ), timeout=1200)
    _assert_dry_run_passed(out)
    for phase in ("serve", "numeric", "kernels"):
        assert f"PHASE {phase}: PASS" in out.stdout
    assert "burst ids == per-step ids" in out.stdout


@pytest.mark.slow
def test_chip_smoke_cpu_dry_run_four_chips(tmp_path):
    """The four-chip phases on four virtual devices / four CPU processes:
    fused 4-stage ids == oracle ids, swarm ids == served ids."""
    out = _run([sys.executable, "chip_smoke.py", "--cpu-dry-run", "--chips",
                "4", "--out", str(tmp_path)], dict(os.environ), timeout=1200)
    _assert_dry_run_passed(out)
    assert "PHASE four-chips: PASS" in out.stdout
