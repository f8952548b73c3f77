"""Proactive forecast/MPC control vs the reactive trigger (DESIGN.md §15).

The claim: the §11 overload trigger only fires *after* a deadline window
is already degrading — every reactive scale-out pays at least one control
tick of misses while the backlog it reacted to drains.  The forecast/MPC
plane (``repro/forecast``) sizes ahead of predicted rates instead, so on
forecastable load shapes it should dominate the reactive controller on
*both* axes at once: fewer deadline misses/drops AND no more provisioned
processors.  On unforecastable load the confidence gate (MASE/sMAPE)
must close and hand every decision back to the reactive path — predict
only when the predictor has earned it.

Scenarios (all seed-pinned, numpy float64 twin, identical sim randomness
for both controllers until their allocations diverge):

* ``flash``   — the paper's VLD chain under a flash-crowd *ramp*
  (10 -> 30 events/s over 40 s, replay trace): holt double-exponential
  smoothing sees the ramp's trend one window in and extrapolates over
  the MPC horizon, while the reactive controller is always one
  measurement window behind the slope;
* ``diurnal`` — the paper's FPD graph under a day/night sinusoid, four
  periods: the seasonal predictor replays last period's rates and
  pre-provisions every upswing (``min_scored`` = one full season, so the
  gate only opens once the season buffer is real history);
* ``mmpp``    — an adversarial 2-state MMPP (4 <-> 28 events/s, fast
  random switching): unforecastable by construction, so the gate must
  keep the MPC out (``fallback_fraction`` ~ 1).

Gates (asserted, so CI fails loudly on regression):

* flash + diurnal: proactive strictly fewer warm-tick deadline misses,
  drops <= reactive, mean provisioned cost (k_total over warm ticks)
  <= reactive;
* mmpp: fallback fraction >= 0.8;
* numpy-twin vs jit predictor + planner agreement <= 1e-9 under x64.

``--smoke`` shortens the mmpp run; the flash/diurnal gates are cheap and
deterministic, so they run (and are asserted) in both modes.
"""

from __future__ import annotations

import numpy as np

from repro.api.session import ScenarioRunner
from repro.forecast import MPCConfig, PredictorParams
from repro.streaming.scenarios import ArrivalTrace, fpd_scenario, vld_scenario

AGREEMENT_ATOL = 1e-9


# --------------------------------------------------------------------------- #
# Scenario + config builders (calibration notes: the flash deadline/queue
# pair is chosen so the reactive lag misses are real but recoverable, and
# the long post-ramp tail is where the MPC's lean holds pay the cost back)
# --------------------------------------------------------------------------- #
def _flash_scenario():
    t5 = np.arange(0.0, 231.0, 5.0)
    ramp = np.interp(t5, [0, 80, 120, 140, 170, 230], [10, 10, 30, 30, 12, 12])
    return vld_scenario(
        name="flash-ramp",
        traces={"extract": ArrivalTrace(kind="replay", samples=tuple(ramp),
                                        sample_dt=5.0)},
        t_max=1.0, queue_capacity=40, machine_size=1, horizon=230.0,
    )


def _flash_cfg() -> MPCConfig:
    return MPCConfig(
        horizon=3, window=12, min_scored=2, headroom=1.1,
        scale_in_hysteresis=0.7,
        predictor=PredictorParams(kind="holt", alpha=0.6, beta=0.4),
    )


def _diurnal_scenario():
    return fpd_scenario(
        name="diurnal-4p",
        traces={"generate": ArrivalTrace(kind="diurnal", rate=15.0,
                                         amplitude=11.0, period=80.0)},
        horizon=320.0, queue_capacity=300, t_max=1.2,
    )


def _diurnal_cfg() -> MPCConfig:
    # One full season of scored history before the gate opens: a seasonal
    # predictor with a back-filled buffer is a constant predictor.
    return MPCConfig(
        horizon=4, window=32, min_scored=16, smape_gate=0.4,
        predictor=PredictorParams(kind="seasonal", season=16),
    )


def _mmpp_scenario(horizon: float):
    return vld_scenario(
        name="mmpp-adversarial",
        traces={"extract": ArrivalTrace(kind="mmpp", rate=4.0, peak=28.0,
                                        switch01=0.08, switch10=0.08)},
        t_max=1.0, queue_capacity=150, machine_size=1, horizon=horizon,
    )


def _warm_stats(report) -> dict:
    tr = report.trajectory
    warm = np.asarray(tr["warm"], dtype=bool)
    miss = np.asarray(tr["miss"], dtype=bool)
    k = np.asarray(tr["k_total"], dtype=float)
    out = {
        "misses": int((miss & warm).sum()),
        "cost": float(k[warm].mean()),
        "drops": float(report.drop_rate),
    }
    if "mpc_used" in tr:
        out["mpc_frac"] = float(np.asarray(tr["mpc_used"], bool)[warm].mean())
    return out


def _compare(scenario, cfg: MPCConfig, tick: float):
    re = ScenarioRunner([scenario], tick_interval=tick,
                        backend="numpy").run()[0]
    pro = ScenarioRunner([scenario], tick_interval=tick, backend="numpy",
                         proactive=cfg).run()[0]
    return _warm_stats(re), _warm_stats(pro)


def _twin_jit_agreement() -> float:
    """max |numpy twin - jit| over predictor forecasts and the full MPC
    planner outputs on a random batch, under x64."""
    import jax
    import jax.numpy as jnp

    from repro.forecast import forecast_rates, mpc_plan
    from repro.kernels.gain_topr import ops as topr_ops

    with jax.enable_x64(True):
        rng = np.random.default_rng(42)
        b, n, w, hzn, k_hi = 4, 3, 12, 3, 32
        hist = rng.uniform(2.0, 20.0, (b, w, n))
        worst = 0.0
        for kind in ("ewma", "holt", "seasonal"):
            pp = PredictorParams(kind=kind, alpha=0.6, beta=0.4,
                                 season=4 if kind == "seasonal" else 0)
            f_np = forecast_rates(hist, hzn, pp, xp=np)
            f_j = jax.jit(
                lambda h, pp=pp: forecast_rates(h, hzn, pp, xp=jnp)
            )(jnp.asarray(hist))
            worst = max(worst, float(np.max(np.abs(f_np - np.asarray(f_j)))))

        cfg = MPCConfig(horizon=hzn, window=w)
        lam_pred = rng.uniform(2.0, 20.0, (b, hzn, n))
        q0 = rng.uniform(0.0, 5.0, (b, n))
        k_cur = rng.integers(1, 6, (b, n)).astype(np.int64)
        kw = dict(
            mu=rng.uniform(2.0, 8.0, (b, n)),
            group=np.zeros((b, n)),
            alpha=np.zeros((b, n)),
            speed=np.ones((b, n)),
            active=np.ones((b, n), dtype=bool),
            src_mask=(np.arange(n)[None, :] == 0).repeat(b, axis=0),
            cap_queue=np.full((b, n), np.inf),
            t_max=np.full(b, 2.5),
            k_max=np.full(b, 48, dtype=np.int64),
            span=10.0, cfg=cfg, k_hi=k_hi,
        )
        out_np = mpc_plan(lam_pred, q0, k_cur, xp=np, **kw)
        out_j = jax.jit(
            lambda lp, q, k: mpc_plan(lp, q, k, xp=jnp,
                                      topr=topr_ops.gain_topr, **kw)
        )(jnp.asarray(lam_pred), jnp.asarray(q0), jnp.asarray(k_cur))
        for a, bj in zip(out_np, out_j):
            av, bv = np.asarray(a, dtype=float), np.asarray(bj, dtype=float)
            fin = np.isfinite(av) & np.isfinite(bv)
            if not np.array_equal(np.isfinite(av), np.isfinite(bv)):
                return float("inf")
            if fin.any():
                worst = max(worst, float(np.max(np.abs(av[fin] - bv[fin]))))
    return worst


def run(smoke: bool = False) -> list[tuple[str, float, str]]:
    rows: list[tuple[str, float, str]] = []

    def gate(tag, re, pro):
        rows.append((f"{tag}_misses_reactive", float(re["misses"]),
                     "warm-tick deadline misses, reactive trigger"))
        rows.append((f"{tag}_misses_proactive", float(pro["misses"]),
                     "warm-tick deadline misses, forecast/MPC"))
        rows.append((f"{tag}_drops_reactive", re["drops"], "drop rate, reactive"))
        rows.append((f"{tag}_drops_proactive", pro["drops"], "drop rate, proactive"))
        rows.append((f"{tag}_cost_reactive", re["cost"],
                     "mean provisioned processors over warm ticks"))
        rows.append((f"{tag}_cost_proactive", pro["cost"],
                     "mean provisioned processors over warm ticks"))
        rows.append((f"{tag}_mpc_fraction", pro["mpc_frac"],
                     "fraction of warm ticks the MPC plan was committed"))
        assert pro["misses"] < re["misses"], (
            f"{tag}: proactive misses {pro['misses']} not strictly fewer "
            f"than reactive {re['misses']}")
        assert pro["drops"] <= re["drops"], (
            f"{tag}: proactive drops {pro['drops']} > reactive {re['drops']}")
        assert pro["cost"] <= re["cost"], (
            f"{tag}: proactive cost {pro['cost']} > reactive {re['cost']}")
        rows.append((f"{tag}_gate", 1.0,
                     "proactive strictly fewer misses, drops <=, cost <="))

    # --- flash-crowd ramp (holt trend lookahead) ------------------------- #
    re, pro = _compare(_flash_scenario(), _flash_cfg(), tick=10.0)
    gate("flash", re, pro)

    # --- diurnal sinusoid (seasonal predictor) --------------------------- #
    re, pro = _compare(_diurnal_scenario(), _diurnal_cfg(), tick=5.0)
    gate("diurnal", re, pro)

    # --- adversarial MMPP: the confidence gate must close ---------------- #
    mmpp = _mmpp_scenario(horizon=100.0 if smoke else 150.0)
    pro = ScenarioRunner([mmpp], tick_interval=10.0, backend="numpy",
                         proactive=_flash_cfg()).run()[0]
    stats = _warm_stats(pro)
    fallback = 1.0 - stats["mpc_frac"]
    rows.append(("mmpp_fallback_fraction", fallback,
                 "warm ticks decided reactively under the adversarial MMPP "
                 "(confidence gate closed); gate >= 0.8"))
    assert fallback >= 0.8, f"mmpp fallback {fallback} < 0.8"

    # --- numpy twin vs jit agreement ------------------------------------- #
    diff = _twin_jit_agreement()
    rows.append(("twin_jit_max_abs_diff", diff,
                 f"predictors + mpc_plan, x64; gate <= {AGREEMENT_ATOL}"))
    assert diff <= AGREEMENT_ATOL, f"twin/jit diff {diff} > {AGREEMENT_ATOL}"
    return rows


def main() -> None:
    for name, val, note in run():
        print(f"{name},{val},{note}")


if __name__ == "__main__":
    main()
