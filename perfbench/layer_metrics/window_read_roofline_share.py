"""A sliding layer's ring read's share of its roofline in the decode ticks,
in %: the bytes of the latent rows the active slots' windows hold, over the
time of the operations that read the ring.

Time: the device seconds, over the stretch the trace recorded, of the
operations whose HLO text names an operand of one of the shapes in
``params["operands"]`` (the ring stack, one layer of it; written with the
first server's ``{slots}``) and does not match ``params["but_not"]`` (an
operation whose RESULT is the stack it updates, a loop, a copy). Least
time: the bytes the configuration's own module counts for ONE tick
(``params["bytes_fn"]``: a latent row for every row the windows hold, a
sliding layer; the rows from the program's counter ``params["rows"]`` over
the window's ticks) times the ticks the trace holds, over the chip's HBM
rate. A trace without such operations (the parent of the PR that brought
them, another family), a module without the count, a program without the
counter, or no trace at all, gives nothing to read."""

import os

from perfbench.harness import roofline
from perfbench.harness.drive import server_arg
from perfbench.harness.manifest import defined_names, load_module
from perfbench.harness.readers import counter_delta, tick_program

_any_of = load_module(os.path.join(
    os.path.dirname(os.path.abspath(__file__)),
    "moe_roofline_share.py"))._any_of


def read(ctx, params):
    tr, own = ctx.get("trace"), ctx.get("reference_file")
    if not tr or not own or params["bytes_fn"] not in defined_names(own):
        return None
    hf = ctx["hf"]
    args = ctx["config"]["deployment"]["servers"][0]["args"]
    keys = {"slots": int(server_arg(args, "--slots"))}
    hit = _any_of(params["operands"], keys)
    skip = _any_of(params.get("but_not", []), keys)
    secs = sum(v["seconds"] for k, v in tr["ops"].items()
               if hit.search(k) and not skip.search(k))
    prog = tick_program(ctx)
    burst = int(ctx["traffic"]["route"].get("burst", 0))
    n = counter_delta(ctx, "server_burst_dispatches_total")
    rows = counter_delta(ctx, params["rows"])
    if not secs or prog is None or burst < 1 or not n or not rows:
        return None
    per_tick = getattr(load_module(own), params["bytes_fn"])(
        hf, int(hf["num_hidden_layers"]), rows / (n * burst))
    ticks = prog["seconds"] / prog["mean_s"] * burst
    least = ticks * per_tick / roofline.peaks(
        ctx["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * least / secs
