"""Forecast/MPC subsystem (DESIGN.md §15): predictor properties, the
numpy-twin vs jit agreement contract, confidence-gate semantics, and the
proactive control plane end to end (twin, fused lax.scan, live scheduler).

The agreement gate mirrors the rest of the repo's twin/jit discipline:
every predictor and the whole MPC planner are written once against an
``xp`` array namespace, so the float64 twin and the x64 jit path must
agree to <= 1e-9 on identical inputs.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.api.session import ScenarioRunner
from repro.core.measurer import MeasurementSnapshot
from repro.core.scheduler import DRSScheduler, SchedulerConfig
from repro.forecast import (
    MPCConfig,
    PredictorParams,
    confidence,
    error_init,
    error_update,
    forecast_rates,
    history_init,
    history_push,
    mase,
    mpc_plan,
    smape,
)
from repro.kernels.gain_topr import ops as topr_ops
from repro.streaming.scenarios import ArrivalTrace, vld_scenario

ATOL = 1e-9


# ------------------------------------------------------------------ #
# Predictor properties
# ------------------------------------------------------------------ #
def test_ewma_flat_history_predicts_level():
    hist = np.full((2, 8, 3), 7.5)
    pred = forecast_rates(hist, 4, PredictorParams(kind="ewma", alpha=0.4))
    np.testing.assert_allclose(pred, 7.5, atol=1e-12)
    assert pred.shape == (2, 4, 3)


def test_holt_extrapolates_linear_ramp():
    t = np.arange(30.0)
    hist = (5.0 + 2.0 * t)[None, :, None]  # slope 2 per tick
    pred = forecast_rates(hist, 3, PredictorParams(kind="holt", alpha=0.5, beta=0.3))
    last = hist[0, -1, 0]
    # Holt's trend converges onto the slope of a clean ramp, so the
    # h-step forecast continues it: last + 2*(h+1).
    np.testing.assert_allclose(pred[0, :, 0], last + 2.0 * np.arange(1, 4),
                               rtol=1e-3)


def test_holt_forecasts_clamped_nonnegative():
    t = np.arange(10.0)
    hist = (20.0 - 3.0 * t)[None, :, None]  # heading below zero
    pred = forecast_rates(hist, 6, PredictorParams(kind="holt"))
    assert (pred >= 0.0).all()


def test_seasonal_replays_last_season():
    season = 4
    base = np.array([3.0, 9.0, 6.0, 12.0])
    hist = np.tile(base, 3)[None, :, None]  # 3 full seasons
    pred = forecast_rates(
        hist, 2 * season,
        PredictorParams(kind="seasonal", season=season),
    )
    np.testing.assert_allclose(pred[0, :, 0], np.tile(base, 2), atol=1e-12)


def test_predictor_params_validation():
    with pytest.raises(ValueError):
        PredictorParams(kind="nope")
    with pytest.raises(ValueError):
        PredictorParams(kind="holt", alpha=1.5)
    with pytest.raises(ValueError):
        PredictorParams(kind="seasonal", season=0)


def test_history_push_backfills_first_observation():
    hist = history_init(1, 5, 2)
    n_obs = np.zeros(1)
    y = np.array([[4.0, 6.0]])
    h1 = history_push(hist, y, n_obs)
    # First observation fills the whole window — no phantom 0 -> rate step.
    np.testing.assert_array_equal(h1, np.broadcast_to(y[:, None, :], (1, 5, 2)))
    h2 = history_push(h1, np.array([[8.0, 2.0]]), n_obs + 1.0)
    np.testing.assert_array_equal(h2[0, -1], [8.0, 2.0])
    np.testing.assert_array_equal(h2[0, :-1], h1[0, 1:])


# ------------------------------------------------------------------ #
# Online error tracking + the confidence gate
# ------------------------------------------------------------------ #
def _score_series(preds, ys):
    state = error_init(1, 1)
    for p, y in zip(preds, ys):
        state = error_update(state, np.array([[p]]), np.array([[y]]))
    return state


def test_error_tracking_perfect_predictor_opens_gate():
    ys = [10.0, 12.0, 11.0, 13.0, 12.0, 14.0]
    # prev_pred scored against y: feed y itself one tick early.
    state = error_init(1, 1)
    for i, y in enumerate(ys):
        nxt = ys[i + 1] if i + 1 < len(ys) else y
        state = error_update(state, np.array([[nxt]]), np.array([[y]]))
    assert smape(state)[0, 0] < 1e-6
    conf = confidence(state, np.ones((1, 1), bool),
                      min_scored=3, mase_gate=2.0, smape_gate=0.25)
    assert bool(conf[0])


def test_error_tracking_bad_predictor_closes_gate():
    # Predict 1.0 forever against a series living at ~20: sMAPE ~ 1.8.
    state = _score_series([1.0] * 8, [20.0, 22.0, 18.0, 21.0, 19.0, 23.0, 20.0, 22.0])
    assert smape(state)[0, 0] > 1.0
    conf = confidence(state, np.ones((1, 1), bool),
                      min_scored=3, mase_gate=2.0, smape_gate=0.25)
    assert not bool(conf[0])


def test_confidence_needs_min_scored():
    state = _score_series([5.0, 5.0], [5.0, 5.0])  # only 1 scored comparison
    conf = confidence(state, np.ones((1, 1), bool),
                      min_scored=3, mase_gate=2.0, smape_gate=0.25)
    assert not bool(conf[0])


def test_mase_compares_against_naive_forecast():
    ys = [10.0, 14.0, 10.0, 14.0, 10.0, 14.0]
    state = _score_series([12.0] * 6, ys)  # always-mean predictor
    # Naive (last value) is off by 4 every step; the mean predictor by 2.
    assert mase(state)[0, 0] == pytest.approx(0.5, rel=1e-9)


# ------------------------------------------------------------------ #
# Twin vs jit agreement (the repo's <= 1e-9 x64 contract)
# ------------------------------------------------------------------ #
@pytest.mark.parametrize("kind", ["ewma", "holt", "seasonal"])
def test_forecast_rates_twin_vs_jit(kind):
    rng = np.random.default_rng(3)
    hist = rng.uniform(1.0, 25.0, (5, 12, 4))
    pp = PredictorParams(kind=kind, alpha=0.55, beta=0.35,
                         season=6 if kind == "seasonal" else 0)
    with jax.enable_x64(True):
        want = forecast_rates(hist, 4, pp, xp=np)
        got = jax.jit(lambda h: forecast_rates(h, 4, pp, xp=jnp))(
            jnp.asarray(hist))
        np.testing.assert_allclose(np.asarray(got), want, atol=ATOL, rtol=0)


def test_mpc_plan_twin_vs_jit():
    rng = np.random.default_rng(17)
    b, n, hzn, k_hi = 6, 4, 3, 40
    lam_pred = rng.uniform(1.0, 18.0, (b, hzn, n))
    q0 = rng.uniform(0.0, 8.0, (b, n))
    k_cur = rng.integers(1, 7, (b, n)).astype(np.int64)
    kw = dict(
        mu=rng.uniform(2.0, 9.0, (b, n)),
        group=np.zeros((b, n)),
        alpha=np.zeros((b, n)),
        speed=np.ones((b, n)),
        active=np.ones((b, n), dtype=bool),
        src_mask=(np.arange(n)[None, :] == 0).repeat(b, axis=0),
        cap_queue=np.full((b, n), np.inf),
        t_max=np.where(np.arange(b) % 2 == 0, 3.0, np.inf),
        k_max=np.full(b, 48, dtype=np.int64),
        span=10.0, cfg=MPCConfig(horizon=hzn, window=12), k_hi=k_hi,
    )
    with jax.enable_x64(True):
        want = mpc_plan(lam_pred, q0, k_cur, xp=np, **kw)
        got = jax.jit(
            lambda lp, q, k: mpc_plan(lp, q, k, xp=jnp,
                                      topr=topr_ops.gain_topr, **kw)
        )(jnp.asarray(lam_pred), jnp.asarray(q0), jnp.asarray(k_cur))
    for name, a, bj in zip(("k_plan", "any_ok", "et_hold", "et_plan", "need"),
                           want, got):
        av = np.asarray(a, dtype=float)
        bv = np.asarray(bj, dtype=float)
        np.testing.assert_array_equal(np.isfinite(av), np.isfinite(bv),
                                      err_msg=name)
        fin = np.isfinite(av)
        np.testing.assert_allclose(bv[fin], av[fin], atol=ATOL, rtol=0,
                                   err_msg=name)


# ------------------------------------------------------------------ #
# Proactive control plane end to end
# ------------------------------------------------------------------ #
def _ramp_scenario(**kw):
    t5 = np.arange(0.0, 151.0, 5.0)
    ramp = np.interp(t5, [0, 50, 90, 110, 150], [8, 8, 24, 24, 10])
    defaults = dict(
        traces={"extract": ArrivalTrace(kind="replay", samples=tuple(ramp),
                                        sample_dt=5.0)},
        t_max=1.2, queue_capacity=200, machine_size=1, horizon=150.0,
    )
    defaults.update(kw)
    return vld_scenario(**defaults)


def _cfg():
    return MPCConfig(horizon=3, window=12, min_scored=2,
                     predictor=PredictorParams(kind="holt", alpha=0.6, beta=0.4))


def test_proactive_twin_emits_proactive_actions():
    runner = ScenarioRunner([_ramp_scenario()], tick_interval=10.0,
                            backend="numpy", proactive=_cfg())
    rep = runner.run()[0]
    assert "proactive" in rep.actions
    tr = rep.trajectory
    assert set(tr) >= {"t", "k_total", "miss", "warm", "mpc_used", "confident"}
    assert any(tr["mpc_used"])


def test_reactive_runner_has_no_proactive_actions_but_has_trajectory():
    rep = ScenarioRunner([_ramp_scenario()], tick_interval=10.0,
                         backend="numpy").run()[0]
    assert "proactive" not in rep.actions
    tr = rep.trajectory
    assert tr is not None and "mpc_used" not in tr
    assert len(tr["t"]) == len(tr["k_total"]) == len(tr["miss"])


def test_proactive_fused_matches_twin_under_x64():
    scens = [_ramp_scenario(negotiated=False)]
    cfg = _cfg()
    with jax.enable_x64(True):
        twin = ScenarioRunner(scens, tick_interval=10.0, backend="numpy",
                              proactive=cfg)
        r_twin = twin.run()[0]
        fused = ScenarioRunner(scens, tick_interval=10.0, backend="jax",
                               proactive=cfg)
        assert fused.fused, "static-budget jax runner should take the fused path"
        r_fused = fused.run()[0]
    assert list(r_twin.actions) == list(r_fused.actions)
    assert r_twin.k_final == r_fused.k_final
    assert r_twin.trajectory["k_total"] == r_fused.trajectory["k_total"]
    assert r_twin.trajectory["mpc_used"] == r_fused.trajectory["mpc_used"]


def test_mmpp_confidence_gate_falls_back_to_reactive():
    scen = vld_scenario(
        name="mmpp",
        traces={"extract": ArrivalTrace(kind="mmpp", rate=4.0, peak=28.0,
                                        switch01=0.08, switch10=0.08)},
        t_max=1.0, queue_capacity=150, machine_size=1, horizon=100.0,
    )
    rep = ScenarioRunner([scen], tick_interval=10.0, backend="numpy",
                         proactive=_cfg()).run()[0]
    assert "proactive" not in rep.actions
    assert not any(rep.trajectory["mpc_used"])


def test_scheduler_live_proactive_scales_ahead_of_ramp():
    names = ["extract", "match"]
    routing = np.array([[0.0, 1.0], [0.0, 0.0]])
    sched = DRSScheduler(
        names, routing, np.array([2, 1]),
        SchedulerConfig(k_max=32, t_max=2.0, tick_interval=10.0),
        proactive=MPCConfig(horizon=3, window=8, min_scored=2,
                            predictor=PredictorParams(kind="holt",
                                                      alpha=0.6, beta=0.4)),
    )
    mu = np.array([2.0, 5.0])
    actions = []
    for i in range(8):
        lam0 = 3.0 + 1.5 * i  # steady ramp the holt predictor locks onto
        lam = np.array([lam0, lam0])
        d = sched.tick_from(
            MeasurementSnapshot.from_rates(lam, mu, lam0, 0.6, 10.0 * i),
            10.0 * i,
        )
        actions.append(d.action)
    assert "proactive" in actions
    # The committed allocation must track the ramp upward.
    assert sched.k_current.sum() > 3


@pytest.mark.slow
@pytest.mark.parametrize("horizon", [1, 2, 4, 6])
def test_slow_mpc_horizon_sweep(horizon):
    """Longer lookahead horizons must stay stable (no worse misses than
    the reactive baseline on the forecastable ramp) and keep the twin
    deterministic across repeated runs."""
    scen = _ramp_scenario()
    cfg = MPCConfig(horizon=horizon, window=12, min_scored=2,
                    predictor=PredictorParams(kind="holt", alpha=0.6, beta=0.4))
    re = ScenarioRunner([scen], tick_interval=10.0, backend="numpy").run()[0]
    pro1 = ScenarioRunner([scen], tick_interval=10.0, backend="numpy",
                          proactive=cfg).run()[0]
    pro2 = ScenarioRunner([scen], tick_interval=10.0, backend="numpy",
                          proactive=cfg).run()[0]
    assert list(pro1.actions) == list(pro2.actions)  # deterministic
    warm = np.asarray(pro1.trajectory["warm"], dtype=bool)

    def misses(rep):
        return int((np.asarray(rep.trajectory["miss"], bool) & warm).sum())

    assert misses(pro1) <= misses(re)
