"""The comparison that decides ``correct``: what the timed path produced
against the plain reference (``reference.py``), number by number.

Numbers (each held to its own limit from the cell's file):

* ``decision_margin`` - over the lane-ticks whose action, applied flag
  or E[T] finiteness differ from the reference, the widest relative
  distance of the reference's nearest decision boundary (the two
  triggers, the stability floor, the two gates weighed by ``1 - rho``;
  0 when nothing differs, see ``reference.decide``).  Rounding flips
  only lanes that sit on a boundary; a wrong decide flips lanes that do
  not.
* ``alloc_regret`` - over the lane-ticks that take the same action to a
  different allocation, the widest relative excess of E[T] at the
  program's allocation over E[T] at the reference's, under the
  reference's rates, weighed by ``1 - rho`` of the fullest operator at
  either (or the stability floor's margin, where smaller).  Where the
  marginal gains lie below float32's resolution (a lane far below its
  budget's capacity) Program (4) is flat and any allocation along it is
  as good.
* ``et_gap`` - over the lane-ticks decided alike, the widest relative gap
  of E[T] at the current and at the target allocation where both sides
  are finite, weighed by ``1 - rho`` of the fullest operator at that
  allocation (E[T]'s condition: near saturation a rounding of the rates
  moves it by eps / (1 - rho)).
* ``budget_violations`` - lane-ticks whose next allocation exceeds k_max
  or is negative (exact: limit 0).
* ``sim_gap`` (twin) - widest relative gap of the simulated window's run
  aggregates (offered, served, dropped, external admitted and offered,
  final and peak backlog), in tuples with a floor of one tuple.
"""

from __future__ import annotations

import numpy as np

from chipbench import reference as ref

DECIDE_KEYS = ("code", "k_next", "et_cur", "et_target", "applied")


def _stack(recs, key):
    return np.concatenate([np.asarray(r[key]) for r in recs])


def _rel(p, r):
    return np.abs(p - r) / np.maximum(np.abs(r), 1e-30)


@np.errstate(all="ignore")
def decide_numbers(dep: ref.Deployment, inputs: dict, got: dict) -> dict:
    """``inputs``: stacked ``lam, mu, drop, lam0, k`` rows; ``got``: the
    program's outputs for those rows."""
    code, k_next = np.asarray(got["code"]), np.asarray(got["k_next"])
    want = ref.decide(np, np.float64, dep, inputs["lam"], inputs["mu"], inputs["drop"],
                      inputs["lam0"], inputs["k"], k_other=k_next)
    applied = np.asarray(got["applied"]).astype(bool)
    et = {k: np.asarray(got[k], np.float64) for k in ("et_cur", "et_target")}
    acts = (code != want["code"]) | (applied != want["applied"])
    for key in et:
        acts |= np.isfinite(et[key]) != np.isfinite(want[key])
    moved = ~acts & (k_next != want["k_next"]).any(axis=-1)
    alike = ~acts & ~moved

    # E[T] near saturation moves by eps / (1 - rho) for a rounding eps in
    # the rates: gaps are weighed by 1 - rho of the fullest operator.
    def cond(*allocs):
        rho = np.max([(want["a"] / np.maximum(np.asarray(k), 1)).max(axis=-1) for k in allocs],
                     axis=0)
        return np.clip(1.0 - rho, 0.0, 1.0)

    gap = raw = 0.0
    for key, k in (("et_cur", inputs["k"]), ("et_target", want["k4"])):
        both = np.isfinite(et[key]) & np.isfinite(want[key]) & alike
        rel = _rel(et[key][both], want[key][both])
        gap = max(gap, float(np.max(rel * cond(k)[both], initial=0.0)))
        raw = max(raw, float(np.max(rel, initial=0.0)))
    excess = (want["et_other"] - want["et_target"]) / want["et_target"]
    regret = np.minimum(np.where(np.isfinite(excess), excess * cond(k_next, want["k4"]), np.inf),
                        want["floor_margin"])
    bad_budget = (k_next.sum(axis=-1) > dep.k_max) | (k_next < 0).any(axis=-1)
    return {
        "decision_margin": float(np.max(want["margin"][acts], initial=0.0)),
        "alloc_regret": float(np.max(regret[moved], initial=0.0)),
        "et_gap": gap,
        "budget_violations": int(bad_budget.sum()),
        "_et_gap_unweighed": raw,
        "_differing": int(acts.sum()),
        "_moved": int(moved.sum()),
        "_rows": int(code.shape[0]),
    }


def service_numbers(dep: ref.Deployment, recs: list) -> dict:
    """The decide service: each record is one tick's inputs and outputs."""
    inputs = {k: _stack(recs, k) for k in ("lam", "mu", "drop", "lam0", "k")}
    return decide_numbers(dep, inputs, {k: _stack(recs, k) for k in DECIDE_KEYS})


@np.errstate(all="ignore")
def replay(dep: ref.Deployment, ext, k0, k_hist, *, steps_per_tick: int, dt: float,
           warmup_steps: int, xp=np, dtype=np.float64, decide_own: bool = False):
    """Simulate the horizon with the reference under a given allocation
    sequence (``k_hist[t]`` is the allocation after tick t, in force from
    tick t + 1).  Returns each tick's measurement and the run aggregates.
    With ``decide_own`` the reference decides itself and follows its own
    allocations (the control put in the program's place); its decisions
    are returned as ``outs``."""
    ticks = ext.shape[0] // steps_per_tick
    b, n = k0.shape
    span = steps_per_tick * dt
    state = (xp.zeros((b, n), dtype=dtype), xp.zeros((b, n), dtype=dtype))
    agg = [xp.zeros((b, n), dtype=dtype)] * 3 + [xp.zeros(b, dtype=dtype)] * 2
    q_max = xp.zeros((b, n), dtype=dtype)
    k = np.asarray(k0)
    meas, outs = [], []
    mu_hat = np.broadcast_to(dep.mu, (b, n))
    for t in range(ticks):
        s0 = t * steps_per_tick
        warm = (np.arange(s0, s0 + steps_per_tick) >= warmup_steps).astype(np.float64)
        state, (off, drp, eadm), w, qm = ref.window(
            xp, dtype, dep, state, ext[s0:s0 + steps_per_tick], k, dt, warm)
        agg = [a + x for a, x in zip(agg, w)]
        q_max = xp.maximum(q_max, qm)
        m = {"lam": np.asarray(off / span, np.float64), "mu": mu_hat,
             "drop": np.asarray(drp / span, np.float64),
             "lam0": np.maximum(np.asarray(eadm / span, np.float64), 0.0),
             "k": np.asarray(k)}
        meas.append(m)
        if decide_own:
            d = ref.decide(xp, dtype, dep, off / span, mu_hat, drp / span,
                           xp.maximum(eadm / span, 0), k)
            outs.append({key: np.asarray(d[key]) for key in DECIDE_KEYS})
            k = np.asarray(d["k_next"])
        else:
            k = np.asarray(k_hist[t])
    names = ("offered", "served", "dropped", "ext_admitted", "ext_offered")
    totals = {key: np.asarray(v, np.float64) for key, v in zip(names, agg)}
    totals["q_final"] = np.asarray(state[0], np.float64)
    totals["q_max"] = np.asarray(q_max, np.float64)
    return meas, totals, outs


SIM_KEYS = ("offered", "served", "dropped", "ext_admitted", "ext_offered", "q_final", "q_max")


def twin_numbers(dep: ref.Deployment, ext, k0, out: dict, *, steps_per_tick: int, dt: float,
                 warmup_steps: int) -> dict:
    """The fused loop: ``out`` is one call's host outputs (per-tick stacks
    ``codes, k, et_cur, et_target, applied`` and the run aggregates)."""
    meas, totals, _ = replay(dep, ext, k0, out["k"], steps_per_tick=steps_per_tick, dt=dt,
                             warmup_steps=warmup_steps)
    inputs = {key: np.concatenate([m[key] for m in meas]) for key in meas[0]}
    got = {
        "code": np.concatenate(list(out["codes"])),
        "k_next": np.concatenate(list(out["k"])),
        "et_cur": np.concatenate(list(out["et_cur"])),
        "et_target": np.concatenate(list(out["et_target"])),
        "applied": np.concatenate(list(out["applied"])),
    }
    nums = decide_numbers(dep, inputs, got)
    nums["sim_gap"] = max(
        float(np.max(np.abs(np.asarray(out[key], np.float64) - totals[key])
                     / np.maximum(np.abs(totals[key]), 1.0), initial=0.0))
        for key in SIM_KEYS
    )
    return nums


def judge(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """``(correct, {name: {"value", "limit"}})`` over the cell's limits; a
    number that is missing or not finite fails."""
    shown, ok = {}, True
    for name, limit in limits.items():
        value = numbers.get(name)
        good = value is not None and np.isfinite(value) and value <= limit
        ok &= bool(good)
        shown[name] = {"value": value, "limit": limit}
    return ok, shown
