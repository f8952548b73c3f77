"""A run with the timed path broken underneath comes out not correct.

Each test skips only the harness's look for a chip and drives the rest of
a run on the CPU at a small fleet: build, warm-up, window, comparison,
with the faults of ``chipbench/faults.py`` planted in what the window
calls.
"""

from __future__ import annotations

import pytest

from chipbench.faults import FAULTS, wrap_with
from chipbench.run import Spec, run_cell

LANES = {"vld-service-b16k": 256, "vld-service-stale-b16k": 256, "fpd-twin-b16k": 64}


@pytest.fixture(autouse=True)
def _no_repo_cache(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))


@pytest.mark.parametrize("cell", sorted(LANES))
def test_sound_run_is_correct(cell):
    out = run_cell(Spec(cell), 4200000001, 0.5, False, lanes=LANES[cell], device_check=False)
    assert out["correct"], out["checks"]


@pytest.mark.parametrize("fault", FAULTS)
@pytest.mark.parametrize("cell", sorted(LANES))
def test_fault_is_not_correct(cell, fault):
    out = run_cell(Spec(cell), 4200000002, 0.5, False, lanes=LANES[cell], device_check=False,
                   wrap=wrap_with(fault))
    assert not out["correct"], out["checks"]
