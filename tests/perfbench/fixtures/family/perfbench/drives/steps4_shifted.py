"""The same drive with ONE thing wrong: every compared row is reported one
position early. The engine is sound, so the check has to fail the drive."""

import os

from perfbench.harness.manifest import load_module

_steps4 = load_module(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                   "steps4.py"))
rows_needed = _steps4.rows_needed


def drive(*args):
    return [dict(ep, rows=[(pos - 1, got) for pos, got in ep["rows"]])
            for ep in _steps4.drive(*args)]
