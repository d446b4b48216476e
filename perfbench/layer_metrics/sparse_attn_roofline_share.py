"""The learned sparse read's share of its roofline in the decode ticks, in %:
the bytes of the index keys a tick scores and of the latent rows it selects
and reads, over the time of the index scores, the top-k and the gather.

The reader is `moe_roofline_share.py`'s, told by this metric's own
``params`` what to look for and which count to take. Time: the device seconds, over the stretch the trace recorded, of the
decode tick's operations whose HLO text names an operand of one of the
shapes in ``params["operands"]`` (and, where ``params["beside"]`` is given,
one of those too: the tick's own row count, which a prefill's operations do
not carry) and does not match ``params["but_not"]`` (an operation whose
RESULT is the stack it updates, a loop, a copy). Shapes are written from
the published config's keys (``{key}``), the configuration's count of held
experts (``{held}``) and expert layers (``{expert_layers}``) and its first
server's ``{slots}`` and ``{max_len}``. Least time: the bytes the
configuration's own module counts for ONE tick (``params["bytes_fn"]`` in
the file its configuration names) times the ticks the trace holds (the
tick program's seconds over the mean of its whole runs, x the route's burst
length: a run the trace cut counts as the part of it that is there), over
the chip's HBM rate. A
trace without such operations (the parent of the PR that brought them,
another family), a configuration whose module has no such count, or no
trace at all, gives nothing to read."""

import os

from perfbench.harness.manifest import load_module

read = load_module(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "moe_roofline_share.py")).read
