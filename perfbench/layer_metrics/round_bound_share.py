"""Share of the rounds held open for returning sessions that the bound
closed, in %.

The leader of a batched round waits for the sessions that the last round
has just answered and counts what closed its round in
``server_round_closed_total{by}``: ``joined`` (all of them came), ``bound``
(the wait ran out with one still away), ``window`` (nobody was on the way:
it slept ``window_s``). The share is ``bound`` over ``joined`` + ``bound``,
from the deltas across the window. A program without the counter (the
parent of the PR that brought it), or a window whose rounds all closed by
``window``, gives nothing to read."""

from perfbench.harness.readers import counter_delta


def read(ctx, params):
    def rounds(values):
        deltas = [counter_delta(
            ctx, f'{params["family"]}{{{params["label"]}="{v}"}}')
            for v in values]
        return sum(d for d in deltas if d is not None)

    whole = rounds(params["whole"])
    if not whole:
        return None
    return 100.0 * rounds(params["part"]) / whole
