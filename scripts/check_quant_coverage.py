#!/usr/bin/env python
"""Thin shim over the graftlint driver (analyzer: ``quant_coverage``).

The check itself lives in scripts/graftlint/legacy.py — one driver, one
finding format, one baseline. This entry point survives so existing
tier-1 wrappers (tests/test_quant_coverage.py) keep working; it exits
non-zero when a quant format in models/quant.py::QUANT_BITS lacks a parity
test or an MoE-path parity test.
"""

import pathlib
import sys

REPO = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))

from scripts.graftlint.__main__ import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(["--analyzer", "quant_coverage"]))
