"""Tier-1 guard for the Mosaic lowering: tools/mosaic_aot_check.py compiles
the flash, paged (the full walk and the windowed walk through a ring, at
the window/full cell's shapes), grouped-matmul and state-recurrence Pallas
kernels for a TPU v5e through the installed libtpu, with no chip attached (ISSUE 21: CPU interpret-mode tests say
nothing of whether Mosaic accepts a kernel). Runs in a subprocess so the
libtpu lock and the TPU_* environment stay out of the test process."""
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_pallas_kernels_compile_for_v5e_without_a_chip():
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "mosaic_aot_check.py")],
        capture_output=True, text=True, timeout=300)
    if r.returncode == 3:
        pytest.skip(f"libtpu gave no v5e topology: {r.stderr[-300:]}")
    cases = [ln for ln in r.stdout.splitlines()
             if ln.startswith(("[OK]", "[FAIL]"))]
    assert r.returncode == 0, "\n".join(cases) + r.stderr[-1500:]
    assert len(cases) == 25 and all(c.startswith("[OK]") for c in cases)
    window = [c for c in cases if "'paged_window': 1" in c]
    assert len(window) == 2 and all("slab=[32, 4, 1056, 128]" in c
                                    for c in window)
