"""Host-platform plumbing shared by the CLI, tests, the driver dry-run, and
tools: which device a process runs on, where its compile cache lives, and
the donation rule for engine jits.

One process owns a chip. ``serve``/``fused``/``oracle``/``local`` (and a
client that runs a classic stage 0) initialise the accelerator; launchers
(``chip_smoke.py``, ``scripts/run_swarm.py``) stay off JAX and start
host-side roles with ``JAX_PLATFORMS=cpu`` and chip owners with
``JAX_PLATFORMS=tpu``, so JAX raises instead of sliding to the CPU when the
chip is taken.
"""

from __future__ import annotations

import os
from typing import Optional

# <checkout>/.jax_cache: the cache path is part of the cache key, so it is
# a fixed place under the checkout — never a temp name, pid or time.
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_COMPILE_CACHE = os.path.join(_CHECKOUT, ".jax_cache")


def force_cpu_devices(n: int) -> None:
    """Point JAX at an n-device virtual CPU host platform. Must run before
    the JAX backend initializes (afterwards it changes nothing)."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n}"
        ).strip()
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    jax.config.update("jax_platforms", "cpu")


def host_env(base) -> dict:
    """Child environment for a HOST-SIDE role (registry, status, a client
    or gateway that computes nothing): explicitly on the CPU, so it can
    never take the chip from the role that owns it."""
    return dict(base, JAX_PLATFORMS="cpu")


def chip_env(base, chip: Optional[int] = None) -> dict:
    """Child environment for a CHIP-OWNING role. A caller that chose the
    CPU (``JAX_PLATFORMS=cpu``: tests, CPU drives) keeps it; otherwise the
    child gets ``JAX_PLATFORMS=tpu`` so JAX raises when the chip is taken
    instead of sliding to the CPU. ``chip=i`` pins the process to chip i
    of the host — the libtpu variables that give each of several
    processes one chip (verified on a 2x2 v5e host: four concurrent
    processes, each sees one ``TPU_0``)."""
    if base.get("JAX_PLATFORMS") == "cpu":
        return dict(base)
    env = dict(base, JAX_PLATFORMS="tpu")
    if chip is not None:
        env.update(TPU_VISIBLE_CHIPS=str(chip),
                   TPU_CHIPS_PER_PROCESS_BOUNDS="1,1,1",
                   TPU_PROCESS_BOUNDS="1,1,1")
    return env


def compile_cache_dir() -> str:
    """Where this process keeps its persistent compile cache.

    With ``JAX_COMPILATION_CACHE_DIR`` set, JAX reads it itself and the
    program sets nothing in code. Otherwise the cache is the fixed
    ``<checkout>/.jax_cache``. Called from ``main.main()`` and
    ``chip_smoke.py``'s children; tests keep the cache off
    (tests/conftest.py)."""
    from .flags import raw_flag

    placed = raw_flag("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax

    jax.config.update("jax_compilation_cache_dir", DEFAULT_COMPILE_CACHE)
    return DEFAULT_COMPILE_CACHE


def start_backend() -> None:
    """Start the accelerator's client, with Pallas imported MEANWHILE.

    A chip-owning process waits seconds for the backend's start-up
    (``jax.devices()``, outside the interpreter) and, where one of its
    programs holds a Pallas kernel (`ops.slot_attention` in every engine
    whose decode ticks read by it, `ops.int8_kernel` / `ops.nf4_kernel` in a
    quantised one), spends 1.3 s importing Pallas the first time it traces
    one: pure Python, and on the v5e's host all of what the kernel added to
    a server's warm-up (PERF.md section 6, PR 52). So the import runs on a
    thread of its own while this thread waits for the backend, and is
    JOINED before this returns: nothing else of the program ever runs
    beside it. A process that chose the CPU (``JAX_PLATFORMS=cpu``: tests,
    CPU drives) starts its backend in milliseconds and would only wait for
    the import: it does neither here, and a kernel module imports Pallas
    when it is first traced, as everywhere."""
    if os.environ.get("JAX_PLATFORMS", "").split(",")[0] == "cpu":
        return
    import importlib
    import threading

    import jax

    beside = threading.Thread(
        target=importlib.import_module,
        args=("jax.experimental.pallas.tpu",), name="import-pallas")
    beside.start()
    try:
        jax.devices()
    finally:
        beside.join()


def device_line() -> str:
    """``platform=… device_kind="…" device_id=… device_count=…`` for a
    serving role's handshake line — the device JAX reports, whichever it
    is. Initialises the backend: chip-owning roles only."""
    import jax

    from .flags import raw_flag

    dev = jax.devices()[0]
    line = (f'platform={dev.platform} device_kind="{dev.device_kind}" '
            f"device_id={dev.id} device_count={len(jax.devices())}")
    # A process pinned to one chip of a host sees it as device 0 whichever
    # chip it is; the pin is what tells four such processes apart.
    pinned = raw_flag("TPU_VISIBLE_CHIPS")
    return f"{line} visible_chips={pinned}" if pinned else line


def engine_donation(*idx: int):
    """Donation indices for ENGINE jits that can be DISPATCHED FROM
    CONCURRENT THREADS (serving adapters hold locks around their own
    calls, but other threads in the process — client-side executors,
    co-hosted servers — dispatch other programs at the same time).

    On the CPU backend donation is DISABLED: measured round 4, the
    long-standing "load-correlated token corruption" flake (rounds 2-4;
    wrong tokens in concurrent-engine tests, a different test each run,
    never reproducible standalone) A/B'd to donation — 8 consecutive
    clean full-file runs with donate_argnums stripped vs a ~2/3 per-run
    failure rate with it, same machine, idle. Donated-buffer reuse under
    concurrent dispatch on the XLA CPU client can hand a still-referenced
    buffer to the donating program; the corrupted reader is whichever
    computation raced it, which is exactly the observed
    any-test-any-run signature. TPU keeps donation — HBM headroom is the
    entire point of donating serving caches — and chip_smoke.py serves
    concurrent sessions with it live and requires repeatable token ids.
    """
    import jax

    return idx if jax.default_backend() != "cpu" else ()
