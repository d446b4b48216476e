"""Mean wall time of the window's decode rounds whose program was enqueued
behind a prompt's, in ms: the SAME reader as `round_behind_prefill_share`,
told by this metric's own ``params`` to take those rounds' mean. See
`round_behind_prefill_share.py` for what is read and when there is nothing
to read."""

import os

from perfbench.harness.manifest import load_module

read = load_module(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "round_behind_prefill_share.py")).read
