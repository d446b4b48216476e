"""Share of the rows a windowed family's decode ticks read of a cache layer
that are summaries, in %.

A tick of the batched engine reads a slot's window stack up to the longest
active phase and its summary stack up to the most earlier windows of an
active slot, by blocks; the engine counts both on the host at readback
(``server_attn_rows_read_total``, ``server_attn_summary_rows_read_total``).
A program without the second series (the parent of the PR that brought
it), one that never moved it (every other family), or a window in which no
tick ran, gives nothing to read."""

from perfbench.harness.readers import counter_delta


def read(ctx, params):
    sums = counter_delta(ctx, params["summaries"])
    exact = counter_delta(ctx, params["exact"])
    if not sums or exact is None:
        return None
    return 100.0 * sums / (sums + exact)
