"""Golden-trace regression: the committed VLD / FPD control-loop decision
traces — and the proactive forecast/MPC trace on the flash-crowd VLD —
must replay bit-for-bit on the decision surface (ISSUE 4 + §15).

The fixtures live in ``tests/golden/*.json``; regenerate after an
*intentional* decision-path change with::

    PYTHONPATH=src python tests/golden/regen.py

and commit the diff with the change (DESIGN.md §13).  Actions and
allocations are exact; scalar metrics compare with a small tolerance so a
benign float reordering doesn't fail the suite.
"""

import importlib.util
import json
import pathlib

import pytest

from repro.streaming.scenarios import control_trace

GOLDEN = pathlib.Path(__file__).resolve().parent / "golden"

# The fixture list (scenario + proactive cfg per name) lives in regen.py
# so the drift guard and this replay can never disagree about what a
# fixture is.
_spec = importlib.util.spec_from_file_location("golden_regen", GOLDEN / "regen.py")
_regen = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_regen)

ENTRIES = {
    name: (scenario, proactive)
    for name, scenario, proactive, _tick in _regen.entries()
}


def _replay(name):
    path = GOLDEN / f"{name}_control_trace.json"
    want = json.loads(path.read_text())
    scenario, proactive = ENTRIES[name]
    got = control_trace(
        [scenario], tick_interval=want["tick_interval"], proactive=proactive
    )
    return want["scenarios"][name], got["scenarios"][name]


@pytest.mark.parametrize("name", ["vld", "fpd", "vld_proactive", "vld_fused",
                                  "soak"])
def test_golden_trace_replays(name):
    want, got = _replay(name)
    assert got["actions"] == want["actions"], (
        f"{name} control-loop action sequence drifted; if intentional, "
        "regenerate with: PYTHONPATH=src python tests/golden/regen.py"
    )
    assert got["allocations"] == want["allocations"], (
        f"{name} per-tick allocations drifted; if intentional, regenerate "
        "with: PYTHONPATH=src python tests/golden/regen.py"
    )
    assert got["provisioned_total"] == want["provisioned_total"]
    assert got["optimal_total"] == want["optimal_total"]
    assert got["trajectory"] == want["trajectory"], (
        f"{name} per-tick trajectory (k/miss/mpc_used) drifted; if "
        "intentional, regenerate the goldens"
    )
    for metric in ("drop_rate", "mean_sojourn", "deadline_miss_rate"):
        assert got[metric] == pytest.approx(want[metric], rel=1e-6, abs=1e-9), metric


def test_golden_trace_replays_through_fused_jit_loop():
    """The jit-eligible golden fixture replays bit-for-bit through the
    fused jax loop (Pallas dispatch in interpret mode).  The fixture
    itself is twin-generated, so this pins twin == jit on the decision
    surface."""
    path = GOLDEN / "vld_fused_control_trace.json"
    want = json.loads(path.read_text())["scenarios"]["vld_fused"]
    scenario, proactive = ENTRIES["vld_fused"]
    got = control_trace(
        [scenario], tick_interval=10.0, proactive=proactive,
        backend="jax", interpret=True,
    )["scenarios"]["vld_fused"]
    assert got["actions"] == want["actions"], (
        "jit replay drifted from the committed golden decision sequence"
    )
    assert got["allocations"] == want["allocations"]
    assert got["provisioned_total"] == want["provisioned_total"]
    for key in ("k_total", "miss", "warm"):
        assert got["trajectory"][key] == want["trajectory"][key], key
    for metric in ("drop_rate", "mean_sojourn", "deadline_miss_rate"):
        assert got[metric] == pytest.approx(want[metric], rel=1e-6, abs=1e-9), metric


def test_golden_traces_are_nontrivial():
    """The fixtures must actually exercise the control loop: elastic
    scale-out/in and the §11 overloaded path both appear."""
    for name in ("vld", "fpd"):
        want = json.loads((GOLDEN / f"{name}_control_trace.json").read_text())
        actions = set(want["scenarios"][name]["actions"])
        assert "overloaded" in actions, name
        assert {"scale_in", "scale_out"} & actions, name
        totals = [
            sum(a.values()) for a in want["scenarios"][name]["allocations"]
        ]
        assert len(set(totals)) > 1, f"{name} allocation never changed"


def test_golden_proactive_trace_is_nontrivial():
    """The proactive fixture must prove the forecast/MPC plane actually
    drove decisions: committed MPC plans appear alongside the per-tick
    mpc_used/confident trajectory."""
    want = json.loads((GOLDEN / "vld_proactive_control_trace.json").read_text())
    assert want["proactive"] is True
    scen = want["scenarios"]["vld_proactive"]
    assert "proactive" in scen["actions"]
    traj = scen["trajectory"]
    assert sum(traj["mpc_used"]) > 0
    assert sum(traj["confident"]) >= sum(traj["mpc_used"])
