"""The peaks table: the chip the benchmark runs on is in it, with its
published figures, and any other kind is an error, not a default."""

from __future__ import annotations

import pytest

from chipbench import bench


def test_v5e_peaks():
    p = bench.peaks("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["bf16_flops"] == 197e12
    assert p["hbm_bytes"] == 16e9


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", "TPU v5p", ""])
def test_unknown_kind_is_an_error(kind):
    with pytest.raises(KeyError):
        bench.peaks(kind)

