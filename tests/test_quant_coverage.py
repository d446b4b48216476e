"""Tier-1 wrapper for scripts/check_quant_coverage.py: every quant format
in models/quant.py::QUANT_BITS must have a token-parity test under tests/
and one on the MoE path — a new format cannot ship unverified on either
expert layout."""

import pathlib
import subprocess
import sys

REPO = pathlib.Path(__file__).resolve().parents[1]


def test_every_quant_format_has_parity_tests():
    proc = subprocess.run(
        [sys.executable,
         str(REPO / "scripts" / "check_quant_coverage.py")],
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0, (
        f"quant coverage drift:\n{proc.stdout}{proc.stderr}"
    )
