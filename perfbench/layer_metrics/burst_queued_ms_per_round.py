"""Time a burst waited on the device's queue for a prompt's programs
enqueued ahead of it, per round of the window, in ms.

The phase profiler brackets, inside a burst round's phase ``device``, the
stretch from the enqueue's return to the prompt's last device result being
ready as phase ``device_queued`` (observed for every burst round, ~0 where
nothing was ahead). Its seconds over the window's rounds
(``server_decode_round_seconds_count``) is what a mean round holds of that
wait. A program without the phase (the parent of the PR that brought it),
an untraced run, or a window without a round gives nothing to read."""

from perfbench.harness.readers import counter_delta


def read(ctx, params):
    queued = counter_delta(
        ctx, f'server_phase_seconds_sum{{phase="{params["phase"]}"}}')
    rounds = counter_delta(ctx, params["rounds"] + "_count")
    if queued is None or not rounds:
        return None
    return queued / rounds * params["scale"]
