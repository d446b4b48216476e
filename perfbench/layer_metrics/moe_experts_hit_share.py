"""Held experts that at least one active row of a tick chose, over the held
experts there were (every one is streamed every tick whether hit or not),
in %. Informational: it follows the routing, the tick's bytes do not.

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
