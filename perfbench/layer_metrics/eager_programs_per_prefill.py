"""Device programs outside the engine's own, per prefill.

Reads ``ctx["trace"]["programs"]`` (harness/trace.py ``program_stats``: runs
per ``XLA Modules`` event name) and the phase profiler's count of prefills
over the window. The engine's programs are named after their functions
(``engine_pattern``); everything else that ran is eager work — in these
cells the head and host-side sampler of a first token and the slices and
pads around a prefill, spread over the ~4 rounds a first token waits. So
the runs in the traced stretch are divided by the prefills EXPECTED in it
(the window's rate times the stretch's length), not by the prefill programs
that happened to start in it (none, in one stretch of ten). Where no engine
program carries its name (a CPU rehearsal, modules all ``jit_fn``) or the
``prefill`` phase is not there, nothing to read."""

import re

from perfbench.layer_metrics.prefill_device_ms import named_programs


def read(ctx, params):
    found = named_programs(ctx, params)
    if found is None:
        return None
    programs, expected = found
    engine = re.compile(params["engine_pattern"])
    eager = sum(p["count"] for name, p in programs.items()
                if not engine.search(name))
    return eager / expected
