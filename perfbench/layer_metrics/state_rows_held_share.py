"""Cache rows a layer holds for the sessions of the window's decode rounds
over the positions those sessions have sent, in %.

The engine adds both once a round, over the slots in it
(``server_state_rows_held_total``, ``server_positions_held_total``). A
program without the series (the parent of the PR that brought them), or a
window in which no round ran, gives nothing to read."""

from perfbench.harness.readers import counter_delta


def read(ctx, params):
    held = counter_delta(ctx, params["held"])
    positions = counter_delta(ctx, params["positions"])
    if held is None or not positions:
        return None
    return 100.0 * held / positions
