"""The window's decode rounds split by whether their program was enqueued
behind a prompt's (``params["stat"]``): ``share``, those that were over all
of them, in %; ``behind``, their mean wall time, in ms; ``clear``, the mean
wall time of the others, in ms.

With telemetry on the engine keeps the last device result of the programs a
prefill enqueued and asks it, at the next round's dispatch, whether it is
finished; the adapter observes the wall time of a round that found it
unfinished in ``server_round_behind_prefill_seconds`` as well as in
``server_decode_round_seconds``. The clear rounds are the difference of the
two families, count and sum, so the mean of all rounds is share x behind +
(1 - share) x clear. A window with rounds and none behind a prompt reads a
share of 0 and 0 ms behind one (the histogram is there and did not move); a
program without the series (the parent of the PR that brought it), or a
window without a round (``clear``: without a clear one), gives nothing to
read."""

from perfbench.harness.readers import counter_delta


def read(ctx, params):
    behind_n = counter_delta(ctx, params["behind"] + "_count")
    behind_s = counter_delta(ctx, params["behind"] + "_sum")
    rounds_n = counter_delta(ctx, params["rounds"] + "_count")
    rounds_s = counter_delta(ctx, params["rounds"] + "_sum")
    if None in (behind_n, behind_s, rounds_s) or not rounds_n:
        return None
    if params["stat"] == "share":
        return 100.0 * behind_n / rounds_n
    if params["stat"] == "behind":
        return behind_s / behind_n * params["scale"] if behind_n else 0.0
    if rounds_n == behind_n:
        return None
    return (rounds_s - behind_s) / (rounds_n - behind_n) * params["scale"]
