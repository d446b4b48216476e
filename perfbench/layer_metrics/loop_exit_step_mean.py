"""Passes of a looped stack a delivered token took, over the window.

The burst program of a looped configuration sums, ON THE DEVICE, the pass
each emitted token left the loop at (counted from 1) and the engine adds
that to ``server_loop_exit_steps_total`` at readback, where it also adds
the emitted tokens to ``server_burst_tokens_total``: the ratio of the two
deltas is the mean. A program without the counter (the parent of the PR
that brought it), or one whose stack runs once (the counter stays where it
was), gives nothing to read."""

from perfbench.harness.readers import counter_delta


def read(ctx, params):
    steps = counter_delta(ctx, params["steps"])
    tokens = counter_delta(ctx, params["tokens"])
    if not steps or not tokens:
        return None
    return steps / tokens
