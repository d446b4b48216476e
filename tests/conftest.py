"""Test harness: force a virtual 8-device CPU mesh before JAX initializes.

Multi-chip sharding paths (pipeline ppermute, TP psum, ring attention) are
exercised on host CPU devices — the reference had no equivalent in-process
test rig at all (SURVEY.md §4: verification was operational/manual).
"""

# Tests run on the CPU whatever the machine holds: the suite is about
# correctness and counts, and the chip belongs to chip_smoke.py.
from global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu.utils.platform import (
    force_cpu_devices,
)

force_cpu_devices(8)

import jax  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

pytest_plugins = ("pytester",)      # tests/test_time_limit.py

# Pin matmuls to full fp32: XLA CPU's DEFAULT GEMM path for m>1 runs a
# reduced-precision (bf16-class) kernel while m=1 GEMV runs full fp32 —
# measured ~5e-2 absolute error on unit-scale 64-dim dots. Token-parity
# tests compare engines that batch differently (e.g. slot-batched decode,
# S>1 GEMM, vs a per-session oracle, T=1 GEMV); under the default precision
# they only agree while argmax gaps exceed that noise, which made
# longer-horizon parity assertions flaky. "highest" makes every engine
# bit-comparable on CPU; runs on the TPU (perfbench/, chip_smoke.py: no
# conftest) keep the native bf16 MXU path.
jax.config.update("jax_default_matmul_precision", "highest")

# Tests keep the persistent compilation cache off (the CLI turns it on,
# utils.platform.compile_cache_dir): parity tests were observed flaking
# run-to-run with divergences far larger than any fp32 noise — consistent
# with a stale executable (compiled before the precision pin above) being
# served for a current trace. Fresh compiles are deterministic; the measured
# suite-time cost was marginal (~10%).
jax.config.update("jax_enable_compilation_cache", False)

# Synchronous CPU dispatch: XLA:CPU's default ASYNC dispatch executes each
# computation on a background thread while the caller proceeds — combined
# with buffer frees (donation, or GC of a previous test's engines) and the
# serving engines' multi-threaded callers, this is the measured corruption
# mechanism behind the rounds-2-4 "load-correlated" token flake (see the
# quarantine note below for the A/B evidence ladder). Synchronous dispatch
# removes the race class wholesale on the test rig; TPU dispatch is
# unaffected (different client).
jax.config.update("jax_cpu_enable_async_dispatch", False)


# Diagnostic switch (flake triage): NO_DONATE=1 strips donate_argnums from
# every jax.jit so buffer donation is off suite-wide — used to discriminate
# whether the in-file batching corruption is a donation/concurrent-dispatch
# interaction. Not for normal runs (donation is a real memory optimization).
import os  # noqa: E402
import sys  # noqa: E402

if os.environ.get("NO_DONATE"):
    _orig_jit = jax.jit

    def _no_donate_jit(*args, **kwargs):
        kwargs.pop("donate_argnums", None)
        kwargs.pop("donate_argnames", None)
        return _orig_jit(*args, **kwargs)

    jax.jit = _no_donate_jit
    print("[conftest] NO_DONATE=1: jax.jit donation stripped suite-wide")


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)


@pytest.fixture(autouse=True, scope="module")
def _forget_shared_programs():
    """tests/engines.py shares an engine's compiled programs among the cases
    of ONE module; the next module starts with none (an executable pins its
    memory maps for as long as it lives: see `pytest_sessionfinish`)."""
    yield
    helper = sys.modules.get("engines")
    if helper is not None:
        helper.forget_programs()


def pytest_sessionfinish(session, exitstatus):
    # Machine-readable parity-rerun accounting (advisor r3): a rerun that
    # "recovers" must not scroll by as a warning only. Every run records the
    # count + nodeids (a stdout line, plus the pytest cache); more than one
    # NON-canary rerun in one process exceeds
    # the environmental-corruption allowance and fails the run for
    # re-triage — repeated recoveries are a bug signal, not weather.
    if _PARITY_RERUNS:
        noncanary = [n for n in _PARITY_RERUNS if _CANARY not in n]
        print(f"\n[conftest] PARITY_RERUN_COUNT={len(noncanary)} "
              f"(+{len(_PARITY_RERUNS) - len(noncanary)} canary) "
              f"nodes={noncanary}")
        try:
            session.config.cache.set("parity/last_reruns", _PARITY_RERUNS)
        except Exception:
            pass
        if len(noncanary) > 1:
            print("[conftest] FAILING the run: more than one non-canary "
                  "parity rerun in one process — re-triage (see the "
                  "quarantine note below)")
            session.exitstatus = 1
    # Memory-map headroom diagnostic: every compiled XLA executable pins
    # mmaps for the life of the process, and a single-process run of the
    # FULL suite deterministically exhausts vm.max_map_count (65530 here)
    # around test ~230 — mmap failures inside XLA then corrupt results or
    # segfault (measured root cause of the round-2 "environmental" flake).
    # The driver's command spreads the files over six worker processes
    # (`-n 6 --dist loadfile`). Print the count so every run records how
    # close it came.
    try:
        with open("/proc/self/maps") as f:
            n = sum(1 for _ in f)
        with open("/proc/sys/vm/max_map_count") as f:
            cap = int(f.read())
        print(f"\n[conftest] process memory maps at exit: {n} / "
              f"vm.max_map_count {cap}"
              + (" — DANGER ZONE, spread this run over more processes "
                 "(-n 6 --dist loadfile)" if n > 0.75 * cap else ""))
    except OSError:
        pass


# ---------------------------------------------------------------------------
# A time limit of its own for every test.
#
# The driver runs the whole suite under one `timeout`: until PR 59 a test
# that waited on a thread, a socket or a subprocess that never came took the
# run with it (rc 124, every later test uncounted) instead of failing alone.
# Every test now runs under an interval timer of its own in the worker's
# main thread: when it fires, the test FAILS with the stack of every thread
# (what it was waiting for), and the run goes on. TIME_LIMIT_S is 3x the
# slowest honest test outside tests/perfbench/ in a whole run of six busy
# workers (62 s and 54 s, the first cases of test_dots3_moe.py and
# test_dots3_attention.py, which compile for their files; PR 59, ROADMAP.md
# "What the driver runs"); `@pytest.mark.time_limit(seconds)` is for the few
# that need more. The benchmark's own tests, which this suite may not edit,
# run whole CPU rehearsals in subprocesses (343 s the longest in the same
# run): `_TIME_LIMITS` marks them by directory. A property of the rig, not
# a knob of the program: no environment variable reads it.
# ---------------------------------------------------------------------------

import faulthandler  # noqa: E402
import signal  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402

TIME_LIMIT_S = 180
_TIME_LIMITS = (("perfbench/", 900),)      # nodeid prefix under tests/


@pytest.fixture(autouse=True)
def _time_limit(request):
    marker = request.node.get_closest_marker("time_limit")
    seconds = marker.args[0] if marker else TIME_LIMIT_S
    if threading.current_thread() is not threading.main_thread():
        yield           # no signal reaches another thread: nothing to arm
        return

    def fire(signum, frame):
        with tempfile.TemporaryFile("w+") as f:
            faulthandler.dump_traceback(f, all_threads=True)
            f.seek(0)
            stacks = f.read()
        pytest.fail(f"time limit: {request.node.nodeid} ran past its "
                    f"{seconds} s (tests/conftest.py TIME_LIMIT_S, "
                    f"@pytest.mark.time_limit). Every thread then:\n"
                    f"{stacks}", pytrace=False)

    handler = signal.signal(signal.SIGALRM, fire)
    outer = signal.setitimer(signal.ITIMER_REAL, seconds)
    try:
        yield
    finally:
        # Disarmed before the next test's fixtures are set up and before
        # the teardown of anything wider than this test. ``outer`` is all
        # zeros but under `pytester` (tests/test_time_limit.py), where it is
        # what the enclosing test had left.
        signal.setitimer(signal.ITIMER_REAL, *outer)
        signal.signal(signal.SIGALRM, handler)


# ---------------------------------------------------------------------------
# Smoke tier: `pytest -m smoke` runs a <2-min correctness core (oracle
# parity, one TCP failover, one elastic re-span, KV arena + LB math) for
# fast iteration; the full ~35-min suite stays the default.
# ---------------------------------------------------------------------------

_SMOKE = (
    # whole fast modules (pure-Python or tiny-jit)
    "test_kv_cache.py",
    "test_load_balancing.py",
    "test_partition.py",
    "test_task_pool.py",
    "test_throughput.py",
    "test_chunked_wire.py",
    # curated representatives of the heavier engines
    "test_runtime_pipeline.py::test_pipeline_greedy_matches_oracle",
    "test_runtime_pipeline.py::test_failover_mid_generation_preserves_tokens",
    "test_net.py::test_tensor_codec_roundtrip",
    "test_net.py::test_registry_service_ttl_and_discovery",
    "test_elastic_server.py::test_rebalance_respans_stacked_servers",
)


def _seconds_by_file():
    """``tests/durations.txt`` (`scripts/test_durations.py`): what each file
    took in the last whole run that was written down."""
    with open(os.path.join(os.path.dirname(__file__), "durations.txt")) as f:
        return {name: float(seconds) for seconds, _, name in map(str.split, f)}


def _under_tests(item):
    """A test's node id from `tests/` on: ``test_net.py::test_x``."""
    return item.nodeid.replace("\\", "/").split("tests/")[-1]


def pytest_collection_modifyitems(config, items):
    # With `--dist loadfile` a worker takes whole files, in collection order,
    # and the run ends when its last file does: a long file that starts late
    # sets the tail (alphabetical order cost 80 s of 930, PR 59). The longest
    # files go first; one the table does not know yet goes before them all.
    # A stale table costs seconds, never a test: the order within a file is
    # kept, and no test may lean on another file's.
    took = _seconds_by_file()
    items.sort(key=lambda item: -took.get(
        _under_tests(item).split("::")[0], float("inf")))
    for item in items:
        rel = _under_tests(item)
        if rel.split("::")[0] in _SMOKE or any(
                rel.startswith(s) for s in _SMOKE if "::" in s):
            item.add_marker(pytest.mark.smoke)
        for prefix, seconds in _TIME_LIMITS:
            if rel.startswith(prefix) and not item.get_closest_marker(
                    "time_limit"):
                item.add_marker(pytest.mark.time_limit(seconds))


# ---------------------------------------------------------------------------
# Parity-flake quarantine with teeth (VERDICT r2 item 6).
#
# Token-parity tests on this box occasionally failed with corrupted
# results — a DIFFERENT deterministic test each time, never reproducible
# in isolation (evidence campaign: commits c82adcf/8a00756; once including
# a segfault inside backend_compile).
# ROOT-CAUSED round 4 (superseding the round-3 map-count story, which
# explained the segfault regime but not recurrences at ~19k/65k maps on an
# idle box): **XLA:CPU ASYNC DISPATCH racing buffer frees under the
# engines' multi-threaded callers** — donation amplifies it (explicit
# early frees), GC of previous tests' engine buffers suffices (which is
# why it only ever fired in-file/in-suite, never standalone). Evidence
# ladder, all on the worst file (test_batching.py, ~3.5 min/run, idle
# box): async+donation ~2/3 runs dirty; async+donation-gated 2/6 dirty;
# async+donation-stripped 0/4; SYNC dispatch 0/5 (and no measurable
# slowdown). Fixes: (1) synchronous CPU dispatch suite-wide (above) kills
# the race class on the test rig; (2) utils.platform.engine_donation
# keeps donation OFF on the CPU backend in every thread-exposed engine
# (production CPU hosts run async) — TPU keeps donation, and as of
# round 5 that is EVIDENCE, not assumption: scripts/donation_probe_tpu.py
# reproduced the threaded-engine shape on the real v5e (donating batched
# engine vs a 115k-dispatch noise thread) and ran 12/12 reps clean,
# where the CPU backend ran ~2/3 dirty. The quarantine below stays as a
# TRIPWIRE: with the fixes in, any parity rerun is a signal, not weather.
# The triage rule, mechanized: a test marked `parity` that fails is RERUN ONCE,
# immediately, in-process. A deterministic logic bug fails both runs and the
# suite stays red; load-induced corruption passes the rerun and the suite
# stays trustworthy, with a loud warning recording that the environment —
# not the engine — corrupted the first attempt.
# ---------------------------------------------------------------------------

import warnings  # noqa: E402

from _pytest.runner import runtestprotocol  # noqa: E402

# Nodeids of parity tests that failed once then recovered on rerun, in
# order. The canary (below) recovers by construction every full-suite run;
# it is excluded from the failure threshold in pytest_sessionfinish.
_PARITY_RERUNS: list = []
_CANARY = "test_parity_quarantine_canary_recovers_on_rerun"


def pytest_runtest_protocol(item, nextitem):
    if item.get_closest_marker("parity") is None:
        return None
    item.ihook.pytest_runtest_logstart(nodeid=item.nodeid,
                                       location=item.location)
    reports = runtestprotocol(item, nextitem=nextitem, log=False)
    if any(r.failed for r in reports):
        # Reset the fixture request before rerunning (what
        # pytest-rerunfailures does): run 1's teardown already finalized
        # every function-scoped fixture, and without this the rerun would
        # receive the stale, torn-down fixture objects.
        if hasattr(item, "_initrequest"):
            item._initrequest()
        rerun = runtestprotocol(item, nextitem=nextitem, log=False)
        if not any(r.failed for r in rerun):
            _PARITY_RERUNS.append(item.nodeid)
            warnings.warn(
                f"PARITY RERUN: {item.nodeid} failed once then passed "
                "clean on immediate rerun — load-induced environmental "
                "corruption (see tests/conftest.py quarantine note), not "
                "an engine bug. If this recurs without concurrent load, "
                "re-triage.")
            reports = rerun
    for rep in reports:
        item.ihook.pytest_runtest_logreport(report=rep)
    item.ihook.pytest_runtest_logfinish(nodeid=item.nodeid,
                                        location=item.location)
    return True
