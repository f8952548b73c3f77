"""The NEXmark cell (keyed operators, ``drivers/service_keyed.py``) end to
end on the CPU at a small fleet: build, warm-up, window, comparison with
``reference_keyed.py``.  A sound run is correct and its hot partitions set
some floors; each planted fault of ``chipbench/faults.py`` is refused."""

from __future__ import annotations

import pytest

from chipbench.faults import FAULTS, wrap_with
from chipbench.run import Spec, run_cell

CELL, LANES = "nexmark-service-b16k", 256


@pytest.fixture(autouse=True)
def _no_repo_cache(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))


def test_sound_run_is_correct():
    out = run_cell(Spec(CELL), 4200000001, 0.5, False, lanes=LANES, device_check=False)
    assert out["correct"], out["checks"]
    assert out["metrics"]["tick_p50_ms"]["value"] > 0


def test_hot_floor_share_reads_above_zero():
    spec = Spec(CELL)
    # A run reads per-layer metrics only when traced, and a CPU trace has
    # no device ops: read the program's counter from an untraced window.
    spec.metrics = lambda traced: [m for m in spec.bench["per_layer"]
                                   if m["name"] == "hot_floor_share"]
    out = run_cell(spec, 4200000003, 0.5, False, lanes=LANES, device_check=False)
    assert out["correct"], out["checks"]
    assert 0 < out["metrics"]["hot_floor_share"]["value"] < 100


@pytest.mark.parametrize("fault", FAULTS)
def test_fault_is_not_correct(fault):
    out = run_cell(Spec(CELL), 4200000002, 0.5, False, lanes=LANES, device_check=False,
                   wrap=wrap_with(fault))
    assert not out["correct"], out["checks"]
