"""Device time of the engine's prefill programs, per prefill.

Reads ``ctx["trace"]["programs"]`` (harness/trace.py ``program_stats``: per
``XLA Modules`` event name its runs and their device seconds) and the phase
profiler's count of prefills over the window. The traced stretch is 4-6 s
and a cell starts a prefill every ~2 s, so "the mean over the prefill
programs that ran in the stretch" would have nothing to read in one run of
ten. This divides the prefill programs' device seconds in the stretch by
the prefills EXPECTED in it (the window's rate times the stretch's length):
the same mean in expectation, 0 in a stretch no prefill fell into, and
always a reading. Needs the programs to carry their function's name; where
no engine program does (a CPU rehearsal, a program whose modules are all
``jit_fn``) or the ``prefill`` phase is not there, nothing to read."""

import re

from perfbench.harness.readers import counter_delta


def named_programs(ctx, params):
    """(the traced stretch's programs, the prefills expected in it: prefills
    per second over the window x seconds traced), or None where the engine's
    programs carry no names or the ``prefill`` phase is not there."""
    trace = ctx.get("trace") or {}
    programs = trace.get("programs") or {}
    engine = re.compile(params["engine_pattern"])
    n = counter_delta(ctx, 'server_phase_seconds_count{phase="prefill"}')
    if (not n or not trace.get("window_s")
            or not any(engine.search(name) for name in programs)):
        return None
    return programs, n * trace["window_s"] / (ctx["w1"] - ctx["w0"])


def read(ctx, params):
    found = named_programs(ctx, params)
    if found is None:
        return None
    programs, expected = found
    pattern = re.compile(params["pattern"])
    seconds = sum(p["seconds"] for name, p in programs.items()
                  if pattern.search(name))
    return 1e3 * seconds / expected
