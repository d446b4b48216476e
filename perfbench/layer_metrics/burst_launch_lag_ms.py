"""Time between a burst's enqueue returning and its results being ready in
which the chip was not running the burst program, per round, in ms.

The phase profiler brackets a burst round's dispatch-to-ready stretch as
phase ``device`` (the traced run fences every round with
``block_until_ready``) and, inside it, the enqueue as phase ``dispatch``.
What is left of ``device`` after ``dispatch`` is the time the host waited
for the results; the tick program's mean whole run on the device trace is
the part of that in which the chip ran the burst. The rest is launch lag:
the tail of the arguments' uploads (thirteen separate transfers a round,
enqueued by ``burst_build`` and still in flight when it returns) and the
launch itself. Both phase means are the window's (the two scrapes), the
program's mean is the traced stretch's: the same rounds of one steady
loop, not the same stretch. Nothing to read where either phase or the
tick program is missing (an untraced run, a cell without bursts, a program
without the phases)."""

from perfbench.harness.readers import counter_delta, tick_program


def phase_mean(ctx, phase):
    s = counter_delta(ctx, f'server_phase_seconds_sum{{phase="{phase}"}}')
    n = counter_delta(ctx, f'server_phase_seconds_count{{phase="{phase}"}}')
    return s / n if n and s is not None else None


def read(ctx, params):
    outer = phase_mean(ctx, params["outer"])
    inner = phase_mean(ctx, params["inner"])
    prog = tick_program(ctx)
    if outer is None or inner is None or prog is None:
        return None
    return (outer - inner - prog["mean_s"]) * params["scale"]
