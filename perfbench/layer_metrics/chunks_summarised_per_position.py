"""Chunks pooled into a summary row over the positions written, across the
window.

The engine counts both on the host from the lengths
(``server_kv_chunks_summarised_total``,
``server_kv_positions_written_total``). A program without the series (the
parent of the PR that brought them), one that pooled nothing (every family
that keeps a row a position), or a window that wrote nothing, gives nothing
to read."""

from perfbench.harness.readers import counter_delta


def read(ctx, params):
    chunks = counter_delta(ctx, params["chunks"])
    positions = counter_delta(ctx, params["positions"])
    if not chunks or not positions:
        return None
    return chunks / positions
