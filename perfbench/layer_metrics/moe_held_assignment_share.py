"""Routed assignments of the window's burst ticks that fell on an expert
this server holds, over all their assignments, in %: the share of the
routed work that is done here.

Both series are the program's own (``--telemetry``), read through the
metrics verb at the window's two ends. A program without them (the parent
of the PR that brought them), a family that never moves them, or a window
in which no tick ran, gives nothing to read."""

from perfbench.harness.readers import counter_delta


def read(ctx, params):
    part = counter_delta(ctx, params["part"])
    whole = counter_delta(ctx, params["whole"])
    if part is None or not whole:
        return None
    return 100.0 * part / whole
