"""The same drive with the last session's compared rows left out: the
harness has to refuse it, not score what is left."""

import os

from perfbench.harness.manifest import load_module

_steps4 = load_module(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                   "steps4.py"))
rows_needed = _steps4.rows_needed


def drive(eng, cfg, chk, server_args, lens, rng, dry):
    last = len(lens) - 1
    return [dict(ep, rows=[]) if ep["session"] == last else ep
            for ep in _steps4.drive(eng, cfg, chk, server_args, lens, rng,
                                    dry)]
