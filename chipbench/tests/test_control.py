"""The control: the plain reference computed in bfloat16 and put in the
program's place must come out not correct against every cell's limits,
while the program (float32, on the CPU here) comes out correct, on the
same traffic at a small fleet."""

from __future__ import annotations

import pathlib

import pytest

from chipbench import check, reference
from chipbench.control import control_numbers
from chipbench.run import Spec, load_module, span_factory

LANES = {"vld-service-b16k": 512, "vld-service-stale-b16k": 512, "fpd-twin-b16k": 128}


@pytest.fixture(autouse=True)
def _no_repo_cache(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))


@pytest.mark.parametrize("name", sorted(LANES))
def test_control_fails_where_program_passes(name):
    spec = Spec(name)
    cell = load_module(pathlib.Path(spec.driver)).Cell(spec.cfg, spec.traffic, 4300000001,
                                                      LANES[name])
    span = span_factory()
    cell.warm(span)
    if spec.traffic["entry"] == "twin":
        cell.window(span, calls=1)
    else:
        cell.window(span, ticks=int(spec.traffic["check_ticks"]))
    dep = reference.Deployment(spec.cfg)
    ok, shown = check.judge(cell.numbers(dep), spec.limits)
    assert ok, shown
    bad, shown = check.judge(control_numbers(spec, cell, dep), spec.limits)
    assert not bad, shown
