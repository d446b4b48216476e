"""Latent rows the window's decode ticks SELECTED over the latent rows they
STREAMED to read them, in %: what the selection keeps of what a tick that
reads each slot's own blocks whole (the slot kernel under the selection as
a mask) copies out of the latent stack.

The reader is `index_rows_selected_share.py`'s part over whole, told by
this metric's own ``params`` which two series to take. A program without
the counter (the parent of the PR that brought it), a tick that gathers the
selected rows (the counter stays 0: nothing was streamed), or a window in
which no tick ran, gives nothing to read."""

import os

from perfbench.harness.manifest import load_module

read = load_module(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "index_rows_selected_share.py")).read
