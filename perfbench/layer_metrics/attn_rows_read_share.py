"""Share of a cache layer's rows that the window's decode ticks read, in %.

A tick of the batched engine reads every cache layer only up to its longest
active slot, by blocks. The engine counts, on the host at readback, the rows
of ONE layer each tick's bound covered (``server_attn_rows_read_total``)
and the rows a full read would have covered (``server_attn_rows_span_total``:
ticks x slots x ``max_session_len``): the ratio of the two deltas is the
share. A program without the series (the parent of the PR that brought
them), or a window in which no tick ran, gives nothing to read."""

from perfbench.harness.readers import counter_delta


def read(ctx, params):
    read_rows = counter_delta(ctx, params["read"])
    span = counter_delta(ctx, params["span"])
    if read_rows is None or not span:
        return None
    return 100.0 * read_rows / span
