"""Runs the program's own entry point (``main.main(argv)``, what
``python -m …main`` calls) with the benchmark's measuring hooks in the
same process — the only process that can read a chip's memory and trace
it. The hooks change nothing the program does:

  * a listener on JAX's compile events, so that a program built inside the
    measured window is counted;
  * a thread that reads commands from stdin and answers on stdout with
    ``PERFBENCH <json>`` lines: ``trace_start DIR`` / ``trace_stop`` (a
    profiler trace of just the window, so no trace spans the server's
    life) and ``stats`` (device memory peak, compile events).

Usage: python -m perfbench.harness.serve_shim <main.py arguments>"""

from __future__ import annotations

import json
import sys
import threading
import time

PKG = "global_capstone_design_distributed_inference_of_llms_over_the_internet_tpu"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def _say(obj: dict) -> None:
    sys.stdout.write("PERFBENCH " + json.dumps(obj) + "\n")
    sys.stdout.flush()


def device_stats() -> dict:
    import jax

    devs = jax.local_devices()
    peak, limit = 0, None
    for d in devs:
        st = d.memory_stats() or {}
        peak = max(peak, int(st.get("peak_bytes_in_use", 0) or 0))
        limit = st.get("bytes_limit", limit)
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak,
            "bytes_limit": limit}


def _control(compiles: list) -> None:
    import jax

    tracing = None
    for line in sys.stdin:
        cmd = line.split()
        if not cmd:
            continue
        try:
            if cmd[0] == "trace_start":
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                opts.host_tracer_level = 2
                jax.profiler.start_trace(cmd[1], profiler_options=opts)
                tracing = time.time()
                _say({"cmd": "trace_start", "t": tracing})
            elif cmd[0] == "trace_stop":
                t = time.time()
                jax.profiler.stop_trace()
                _say({"cmd": "trace_stop", "t_start": tracing, "t_stop": t,
                      "write_s": time.time() - t})
                tracing = None
            elif cmd[0] == "stats":
                _say({"cmd": "stats", "device": device_stats(),
                      "compile_times": compiles[-512:]})
            else:
                _say({"cmd": cmd[0], "error": "unknown command"})
        except Exception as exc:  # report, and keep the server serving
            _say({"cmd": cmd[0], "error": f"{type(exc).__name__}: {exc}"})


def main() -> int:
    import importlib

    import jax.monitoring

    compiles: list = []

    def on_event(name, duration, **kw):
        if name == COMPILE_EVENT:
            compiles.append(time.time())

    jax.monitoring.register_event_duration_secs_listener(on_event)
    threading.Thread(target=_control, args=(compiles,), daemon=True,
                     name="perfbench-control").start()
    program = importlib.import_module(PKG + ".main")
    return program.main(sys.argv[1:])


if __name__ == "__main__":
    sys.exit(main())
