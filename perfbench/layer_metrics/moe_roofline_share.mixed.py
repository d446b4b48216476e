"""`moe_roofline_share` for a family whose layers lie in more than
one stack by kind (dots3-note-prev: two full and six sliding expert layers,
three full layers' latent rows): the SAME reader, told by this metric's own
``params`` which shapes to look for. See `moe_roofline_share.py` for what is
read and when there is nothing to read."""

import os

from perfbench.harness.manifest import load_module

read = load_module(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "moe_roofline_share.py")).read
