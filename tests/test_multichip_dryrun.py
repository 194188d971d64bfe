"""Tier-1 multichip gate: the full `dryrun_multichip` parity path runs
on every PR on a virtual CPU mesh — mesh regressions surface here, at no
chip time (`python chip_smoke.py --chips 4` is the run on real chips)."""

from __future__ import annotations

import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_dryrun_multichip_on_virtual_cpu_mesh(capfd):
    """The entry point as the driver calls it: it re-execs itself into a
    child with 8 virtual CPU devices and runs the sharded kernel steps +
    PRODUCT registry/tempodb parity asserts there."""
    sys.path.insert(0, REPO)
    try:
        import __graft_entry__ as g
    finally:
        sys.path.remove(REPO)
    g.dryrun_multichip(8)
    out = capfd.readouterr().out
    assert "dry run on a virtual CPU mesh of 8 devices" in out, out[-1000:]
    assert "dryrun_multichip ok" in out, out[-1000:]
