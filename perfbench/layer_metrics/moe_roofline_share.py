"""The expert layers' share of their roofline in the decode ticks, in %: the
bytes of the router, every held expert and the shared expert (each read
once a tick whatever the routing) over the time of the operations that read
them.

Time: the device seconds, over the stretch the trace recorded, of the
decode tick's operations whose HLO text names an operand of one of the
shapes in ``params["operands"]`` (and, where ``params["beside"]`` is given,
one of those too: the tick's own row count, which a prefill's operations do
not carry) and does not match ``params["but_not"]`` (an operation whose
RESULT is the stack it updates, a loop, a copy). Shapes are written from
the published config's keys (``{key}``), the configuration's count of held
experts (``{held}``) and expert layers (``{expert_layers}``), its first
server's ``{slots}`` and ``{max_len}``, and the rows a tick selects over
its slots (``{selected}``: slots x the lesser of ``index_topk`` and
``max_len``; a configuration without the key has none). Least time: the
bytes the
configuration's own module counts for ONE tick (``params["bytes_fn"]`` in
the file its configuration names) times the ticks the trace holds (the
tick program's seconds over the mean of its whole runs, x the route's burst
length: a run the trace cut counts as the part of it that is there), over
the chip's HBM rate. A
trace without such operations (the parent of the PR that brought them,
another family), a configuration whose module has no such count, or no
trace at all, gives nothing to read."""

import re

from perfbench.harness import roofline
from perfbench.harness.drive import server_arg
from perfbench.harness.manifest import defined_names, load_module
from perfbench.harness.readers import counter_delta, tick_program


def _any_of(shapes, keys):
    """One pattern for any of ``shapes``; one that starts with ``%`` is the
    start of an operation's own name, not of an operand's."""
    return re.compile("|".join(
        ("^" if s.startswith("%") else "") + re.escape(s.format(**keys))
        for s in shapes) or "$^")


def read(ctx, params):
    tr, own = ctx.get("trace"), ctx.get("reference_file")
    if not tr or not own or params["bytes_fn"] not in defined_names(own):
        return None
    mod = load_module(own)
    hf = ctx["hf"]
    args = ctx["config"]["deployment"]["servers"][0]["args"]
    layers = int(hf["num_hidden_layers"])
    keys = dict({k: v for k, v in hf.items() if isinstance(v, int)},
                held=mod.held_experts(hf)[1],
                expert_layers=layers - int(hf["first_k_dense_replace"]),
                slots=int(server_arg(args, "--slots")),
                max_len=int(server_arg(args, "--max_session_len")))
    keys["selected"] = keys["slots"] * min(hf.get("index_topk", 0),
                                           keys["max_len"])
    hit = _any_of(params["operands"], keys)
    beside = _any_of(params["beside"], keys) if params.get("beside") else None
    skip = _any_of(params.get("but_not", []), keys)
    secs = sum(v["seconds"] for k, v in tr["ops"].items()
               if hit.search(k) and not skip.search(k)
               and (beside is None or beside.search(k)))
    prog = tick_program(ctx)
    burst = int(ctx["traffic"]["route"].get("burst", 0))
    if not secs or prog is None or burst < 1:
        return None
    if params["bytes_fn"] == "sparse_attn_tick_bytes":
        # rows a tick scored and selected: the program's own counters over
        # the window, by the window's ticks
        n = counter_delta(ctx, "server_burst_dispatches_total")
        scored = counter_delta(ctx, "server_index_rows_scored_total")
        taken = counter_delta(ctx, "server_attn_rows_read_total")
        if not n or not scored or taken is None:
            return None
        per_tick = mod.sparse_attn_tick_bytes(
            hf, layers, scored / (n * burst), taken / (n * burst))
    else:
        per_tick = getattr(mod, params["bytes_fn"])(
            hf, layers, ctx["config"]["weight_bytes"])
    ticks = prog["seconds"] / prog["mean_s"] * burst
    least = ticks * per_tick / roofline.peaks(
        ctx["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * least / secs
