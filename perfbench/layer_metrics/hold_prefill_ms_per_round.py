"""Time prefill programs held the stage engine's lock inside an open round,
per round of the window, in ms.

A prefill that takes the adapter's lock while a round is open (its leader
waits for the sessions on their way back and has let go of the lock) runs
its program inside that round's hold: the round starts that much later, for
every session in it. The adapter observes the time each such prefill held
the lock in ``server_round_hold_prefill_seconds``; the window's sum over
the window's rounds is the part of `round_hold_ms` that is a prefill's. A
window with rounds and no such prefill reads 0 (the histogram is there and
did not move); a program without the series (the parent of the PR that
brought it), or a window without a round, gives nothing to read."""

from perfbench.harness.readers import counter_delta


def read(ctx, params):
    held = counter_delta(ctx, params["family"] + "_sum")
    rounds = counter_delta(ctx, params["rounds"] + "_count")
    if held is None or not rounds:
        return None
    return held / rounds * params["scale"]
