"""Ring rows of one sliding layer that the window's decode ticks read, over
the rows the active slots' windows held, in %.

The engine counts both on the host, a tick
(``server_window_rows_read_total``, ``server_window_rows_span_total``). A
program without the series (the parent of the PR that brought them, a
family without sliding layers), or a window in which no tick ran, gives
nothing to read."""

from perfbench.harness.readers import counter_delta


def read(ctx, params):
    part = counter_delta(ctx, params["part"])
    whole = counter_delta(ctx, params["whole"])
    if part is None or not whole:
        return None
    return 100.0 * part / whole
