"""The mesh-sharded control plane (DESIGN.md §16).

Two tiers of tests:

* **Single-device** (always run): pad-and-mask semantics — padded
  statics/params/arrays, the masked-lane "none" contract on both the
  jit decide and the numpy twin, the chunked/donated
  :class:`~repro.core.controller.FusedLoop` carry.
* **Multi-device** (skipped unless >= 2 devices are visible): bit-identity
  of the sharded fused loop / decide / planner against the unsharded
  program.  Run locally or in CI with
  ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` — the flag must
  be set *before* jax imports.

Bit-identity contract: decisions and allocations (action codes, k, the
applied mask, integer aggregates) are compared **bitwise**; the E[T]
diagnostics (``et_cur``/``et_target``/``sojourn``) get an rtol because
XLA may reassociate float32 lane reductions differently at different
batch extents (~1 ulp).
"""

from __future__ import annotations

import jax
import numpy as np
import pytest

import repro.core.controller as ctl
from repro.api.session import ScenarioRunner
from repro.core.measurer import MeasurementBatch
from repro.distributed.sharding import fleet_mesh
from repro.streaming.scenarios import pack_scenarios, scenario_matrix

multi_device = pytest.mark.skipif(
    len(jax.devices()) < 2,
    reason="needs >=2 devices: XLA_FLAGS=--xla_force_host_platform_device_count=8",
)

# Bitwise-equal keys (decisions, allocations, integer aggregates) vs
# rtol'd float diagnostics — see module docstring.
EXACT = (
    "codes", "k", "applied", "miss", "warm_windows", "k_final", "q_final",
    "offered", "served", "dropped", "ext_admitted", "ext_offered",
    "q_int", "q_max", "mpc_used", "confident",
)
CLOSE = ("sojourn", "et_cur", "et_target")


def _scens(b, seed=11):
    return [
        s.with_(negotiated=False)
        for s in scenario_matrix(b, seed=seed, horizon=20.0, warmup=5.0, dt=0.05)
    ]


def _mpc_cfg():
    from repro.forecast.mpc import MPCConfig, PredictorParams

    return MPCConfig(horizon=3, window=12, min_scored=2,
                     predictor=PredictorParams(kind="holt", alpha=0.6, beta=0.4))


def _loop(scens, mesh=None, proactive=None, compact=None):
    r = ScenarioRunner(scens, tick_interval=5.0, backend="jax",
                       mesh=mesh, proactive=proactive, compact=compact)
    assert r.fused
    loop, n_ticks = ctl.make_fused_loop(
        r.arrays, r.static, r._params(),
        steps_per_tick=r._steps_per_tick,
        warmup_seconds=scens[0].warmup,
        proactive=r.proactive_cfg, mesh=mesh, compact=compact,
    )
    return r, loop, n_ticks


def _assert_outs_match(ref: dict, got: dict):
    assert set(ref) == set(got)
    for key in ref:
        a, b = np.asarray(ref[key]), np.asarray(got[key])
        if key in EXACT:
            np.testing.assert_array_equal(a, b, err_msg=key)
        else:
            assert key in CLOSE, key
            np.testing.assert_allclose(a, b, rtol=1e-6, err_msg=key)


# --------------------------------------------------------------------------- #
# Pad-and-mask semantics (single device)
# --------------------------------------------------------------------------- #
def test_pad_static_params_build_inert_lanes():
    scens = _scens(3)
    r = ScenarioRunner(scens, tick_interval=5.0, backend="jax")
    st = ctl.pad_static(r.static, 5)
    pr = ctl.pad_params(r._params(), 5)
    assert st.batch == 5 and pr.k_max.shape[0] == 5
    # inert lane contract: no operators, no routing, no budget, closed gates
    assert (st.n_ops[3:] == 0).all()
    assert not st.active[3:].any()
    assert (st.base_routing[3:] == 0).all()
    assert (st.speed[3:] == 1.0).all()
    assert (pr.k_max[3:] == 0).all()
    assert np.isnan(pr.t_max[3:]).all()
    assert np.isinf(pr.min_improvement[3:]).all()
    # idempotent at the same extent, refuses to shrink
    assert ctl.pad_static(st, 5) is st
    with pytest.raises(ValueError):
        ctl.pad_static(st, 4)
    with pytest.raises(ValueError):
        ctl.pad_params(pr, 4)


def test_pack_scenarios_pad_to_inert_arrivals():
    scens = _scens(3)
    base = pack_scenarios(scens)
    padded = pack_scenarios(scens, pad_to=5)
    assert padded.batch == 5
    assert (np.asarray(padded.ext)[:, 3:, :] == 0).all()
    np.testing.assert_array_equal(np.asarray(padded.ext)[:, :3], np.asarray(base.ext))
    assert not padded.active[3:].any()
    with pytest.raises(ValueError):
        pack_scenarios(scens, pad_to=2)


def test_masked_lanes_decide_none_in_jit_and_twin():
    """Satellite contract: a padded lane decides "none" bit-for-bit, with
    an unchanged (all-zero) allocation — in the jit decide AND the twin."""
    scens = _scens(3)
    r = ScenarioRunner(scens, tick_interval=5.0, backend="jax")
    b, n = 5, r.static.n
    st = ctl.pad_static(r.static, b)
    pr = ctl.pad_params(r._params(), b)
    rng = np.random.default_rng(0)
    lam = np.abs(rng.normal(2.0, 0.5, (b, n)))
    mu = np.abs(rng.normal(6.0, 0.5, (b, n))) + 1.0
    drop = np.zeros((b, n))
    lam0 = np.abs(rng.normal(2.0, 0.5, b))
    k = np.where(st.active, 2, 0).astype(np.int64)

    decide = ctl.make_decide_jax(st, pr)
    code, k_next, et_cur, et_target, applied = (
        np.asarray(v) for v in decide(lam, mu, drop, lam0, k)
    )
    none_code = ctl.ACTIONS.index("none")
    np.testing.assert_array_equal(code[3:], none_code)
    np.testing.assert_array_equal(applied[3:], False)
    np.testing.assert_array_equal(k_next[3:], k[3:])  # allocation untouched

    meas = MeasurementBatch(
        lam_hat=lam, mu_hat=mu, lam0_hat=lam0,
        sojourn_hat=np.full(b, 0.5), t=0.0, drop_hat=drop,
    )
    batch = ctl.tick_batch(meas, k.copy(), st, pr)
    for row in batch.rows[3:]:
        assert row.action == "none" and not row.applied
        assert row.reason == "padded lane"


@pytest.mark.parametrize("keyed", [False, True], ids=["pooled", "keyed"])
@pytest.mark.parametrize("compact", [None, True], ids=["dense", "compact"])
def test_make_decide_jax_one_device_mesh_is_bitwise(compact, keyed):
    """The decide wrapped in ``shard_map`` over one device equals the
    unwrapped decide bit for bit, dense and compacted, with and without
    keyed operators, over a cold tick and a perturbed one."""
    import dataclasses

    scens = _scens(4, seed=13)
    r = ScenarioRunner(scens, tick_interval=5.0, backend="jax")
    static, params = r.static, r._params()
    b, n = static.batch, static.n
    if keyed:
        hot = np.full((b, n), np.nan)
        hot[:, 1] = np.where(static.active[:, 1], 0.4, np.nan)
        static = dataclasses.replace(static, hot=hot)
        assert static.keyed
    rng = np.random.default_rng(2)
    lam = np.abs(rng.normal(2.0, 0.6, (b, n)))
    mu = np.abs(rng.normal(6.0, 0.5, (b, n))) + 1.0
    drop = np.zeros((b, n))
    lam0 = np.abs(rng.normal(2.0, 0.5, b))
    k = np.where(static.active, 2, 0).astype(np.int64)
    lam2 = lam.copy()
    lam2[1] *= 1.5

    outs = []
    for mesh in (None, fleet_mesh(1)):
        decide = ctl.make_decide_jax(static, params, mesh=mesh, compact=compact)
        cache = (decide.init_cache(),) if compact else ()
        ticks = []
        for lam_t in (lam, lam2):
            out = decide(lam_t, mu, drop, lam0, k, *cache)
            if compact:
                cache = out[2:]
            ticks.append(jax.tree.leaves(out))
        outs.append(ticks)
    assert len(outs[0][0]) == len(outs[1][0]) >= 5 + keyed
    for ref, got in zip(*outs):
        for a, g in zip(ref, got):
            a, g = np.asarray(a), np.asarray(g)
            assert a.dtype == g.dtype
            np.testing.assert_array_equal(a, g)


def test_fused_loop_padded_lanes_never_influence_real_ones():
    """Run the fused loop at B and at B+2 (two inert pad lanes, no mesh):
    the real lanes' decisions and aggregates must be bitwise unchanged,
    and the pad lanes must decide "none" forever with zero aggregates."""
    scens = _scens(4, seed=7)
    _, loop, _ = _loop(scens)
    ref = {k: np.asarray(v) for k, v in loop(
        ScenarioRunner(scens, tick_interval=5.0, backend="jax").k).items()}

    r = ScenarioRunner(scens, tick_interval=5.0, backend="jax")
    b_pad = len(scens) + 2
    arrays = pack_scenarios(scens, pad_to=b_pad)
    loop_p, _ = ctl.make_fused_loop(
        arrays, ctl.pad_static(r.static, b_pad), ctl.pad_params(r._params(), b_pad),
        steps_per_tick=r._steps_per_tick, warmup_seconds=scens[0].warmup,
    )
    k0 = np.zeros((b_pad, r.static.n), dtype=np.int64)
    k0[: len(scens)] = r.k
    got = {k: np.asarray(v) for k, v in loop_p(k0).items()}

    none_code = ctl.ACTIONS.index("none")

    # slice real lanes per key shape: batch is the last-but-one axis for
    # [T, B, N] / [B, N] arrays and the last axis for [T, B] / [B] ones.
    def real_lanes(v):
        if v.ndim >= 2 and v.shape[-2] == b_pad:
            return v[..., : len(scens), :]
        if v.ndim >= 1 and v.shape[-1] == b_pad:
            return v[..., : len(scens)]
        return v

    def pad_lanes(v):
        if v.ndim >= 2 and v.shape[-2] == b_pad:
            return v[..., len(scens):, :]
        if v.ndim >= 1 and v.shape[-1] == b_pad:
            return v[..., len(scens):]
        return None

    for key in EXACT:
        if key not in ref:
            continue
        np.testing.assert_array_equal(real_lanes(got[key]), ref[key], err_msg=key)
    for key in CLOSE:
        np.testing.assert_allclose(
            real_lanes(got[key]), ref[key], rtol=1e-6, err_msg=key
        )
    np.testing.assert_array_equal(pad_lanes(got["codes"]), none_code)
    np.testing.assert_array_equal(pad_lanes(got["applied"]), False)
    for key in ("k_final", "q_final", "offered", "served", "dropped",
                "q_int", "q_max", "miss"):
        np.testing.assert_array_equal(pad_lanes(got[key]), 0, err_msg=key)


# --------------------------------------------------------------------------- #
# Chunked, donated carry (single device)
# --------------------------------------------------------------------------- #
def test_fused_loop_chunked_resume_bit_identical():
    scens = _scens(4, seed=3)
    r, loop, n_ticks = _loop(scens)
    ref = {k: np.asarray(v) for k, v in loop(r.k).items()}

    r2, loop2, _ = _loop(scens)
    state = loop2.init(r2.k)
    state, out_a = loop2.run(state, 2)
    state, out_b = loop2.run(state)  # remainder of the horizon
    assert int(state.tick) == n_ticks
    for key in ("codes", "k", "sojourn", "et_cur", "et_target", "applied"):
        merged = np.concatenate([np.asarray(out_a[key]), np.asarray(out_b[key])])
        np.testing.assert_array_equal(merged, ref[key], err_msg=key)
    # run aggregates carried in the state: the final chunk's dict has them
    for key in ("k_final", "q_final", "offered", "served", "dropped",
                "ext_admitted", "ext_offered", "q_int", "q_max"):
        np.testing.assert_array_equal(
            np.asarray(out_b[key]), ref[key], err_msg=key
        )
    # miss / warm_windows are per-chunk sums
    np.testing.assert_array_equal(
        np.asarray(out_a["miss"]) + np.asarray(out_b["miss"]), ref["miss"]
    )
    assert int(out_a["warm_windows"]) + int(out_b["warm_windows"]) == int(
        ref["warm_windows"]
    )


def test_fused_loop_run_donates_the_carry():
    scens = _scens(3, seed=5)
    r, loop, _ = _loop(scens)
    state = loop.init(r.k)
    new_state, _ = loop.run(state, 1)
    # donate_argnums=0: the old carry's buffers are consumed by XLA
    assert state.q.is_deleted()
    assert state.k.is_deleted()
    assert not new_state.q.is_deleted()


def test_fused_loop_run_range_validation():
    scens = _scens(2, seed=9)
    r, loop, n_ticks = _loop(scens)
    state = loop.init(r.k)
    with pytest.raises(ValueError):
        loop.run(state, 0)
    with pytest.raises(ValueError):
        loop.run(state, n_ticks + 1)
    state, _ = loop.run(state, n_ticks)
    with pytest.raises(ValueError):
        loop.run(state, 1)  # horizon exhausted


# --------------------------------------------------------------------------- #
# Sharded vs unsharded bit-identity (multi device)
# --------------------------------------------------------------------------- #
@multi_device
def test_sharded_fused_loop_bit_identical_to_unsharded():
    scens = _scens(8, seed=21)
    r, loop, _ = _loop(scens)
    ref = {k: np.asarray(v) for k, v in loop(r.k).items()}
    mesh = fleet_mesh(2)
    rm, loop_m, _ = _loop(scens, mesh=mesh)
    got = {k: np.asarray(v) for k, v in loop_m(rm.k).items()}
    _assert_outs_match(ref, got)


@multi_device
def test_sharded_fused_loop_nondivisible_batch():
    """B = 6 on a 4-device mesh: two lanes of shard padding, decisions
    still bit-identical to the unsharded program."""
    if len(jax.devices()) < 4:
        pytest.skip("needs >= 4 devices")
    scens = _scens(6, seed=17)
    r, loop, _ = _loop(scens)
    ref = {k: np.asarray(v) for k, v in loop(r.k).items()}
    rm, loop_m, _ = _loop(scens, mesh=fleet_mesh(4))
    got = {k: np.asarray(v) for k, v in loop_m(rm.k).items()}
    _assert_outs_match(ref, got)


@multi_device
def test_sharded_proactive_fused_loop_bit_identical():
    scens = _scens(8, seed=29)
    cfg = _mpc_cfg()
    r, loop, _ = _loop(scens, proactive=cfg)
    ref = {k: np.asarray(v) for k, v in loop(r.k).items()}
    rm, loop_m, _ = _loop(scens, mesh=fleet_mesh(2), proactive=cfg)
    got = {k: np.asarray(v) for k, v in loop_m(rm.k).items()}
    _assert_outs_match(ref, got)


@multi_device
def test_sharded_chunked_resume_bit_identical():
    scens = _scens(8, seed=31)
    r, loop, n_ticks = _loop(scens)
    ref = {k: np.asarray(v) for k, v in loop(r.k).items()}
    rm, loop_m, _ = _loop(scens, mesh=fleet_mesh(2))
    state = loop_m.init(rm.k)
    state, out_a = loop_m.run(state, 1)
    state, out_b = loop_m.run(state)
    merged = np.concatenate([np.asarray(out_a["codes"]), np.asarray(out_b["codes"])])
    np.testing.assert_array_equal(merged, ref["codes"])
    np.testing.assert_array_equal(np.asarray(out_b["k_final"]), ref["k_final"])


@multi_device
def test_sharded_compacted_fused_loop_bit_identical_to_dense_unsharded():
    """§18 per-shard compaction under shard_map: each device compacts its
    own lanes (no cross-device gather), and the whole loop still matches
    the dense unsharded program — decisions bitwise, E[T] to rtol."""
    scens = _scens(8, seed=21)
    r, loop, _ = _loop(scens)
    ref = {k: np.asarray(v) for k, v in loop(r.k).items()}
    rm, loop_m, _ = _loop(scens, mesh=fleet_mesh(2), compact=True)
    got = {k: np.asarray(v) for k, v in loop_m(rm.k).items()}
    assert got.pop("repriced").shape == ref["codes"].shape
    _assert_outs_match(ref, got)


@multi_device
def test_sharded_compacted_nondivisible_batch():
    """B = 6 on a 4-device mesh with compaction on: the shard-padding
    lanes ride the trigger scan as permanently-quiet lanes."""
    if len(jax.devices()) < 4:
        pytest.skip("needs >= 4 devices")
    scens = _scens(6, seed=17)
    r, loop, _ = _loop(scens)
    ref = {k: np.asarray(v) for k, v in loop(r.k).items()}
    rm, loop_m, _ = _loop(scens, mesh=fleet_mesh(4), compact=True)
    got = {k: np.asarray(v) for k, v in loop_m(rm.k).items()}
    got.pop("repriced")
    _assert_outs_match(ref, got)


@multi_device
def test_make_decide_jax_mesh_compact_parity():
    """The standalone mesh compacted decide: bit-identical decisions to
    the dense unsharded decide across cold / quiet / perturbed ticks,
    with per-shard trigger counts summing to the expected totals."""
    scens = _scens(8, seed=13)
    r = ScenarioRunner(scens, tick_interval=5.0, backend="jax")
    b, n = len(scens), r.static.n
    rng = np.random.default_rng(2)
    lam = np.abs(rng.normal(2.0, 0.6, (b, n)))
    mu = np.abs(rng.normal(6.0, 0.5, (b, n))) + 1.0
    drop = np.zeros((b, n))
    lam0 = np.abs(rng.normal(2.0, 0.5, b))
    k = np.where(r.static.active, 2, 0).astype(np.int64)

    dense = ctl.make_decide_jax(r.static, r._params())
    comp = ctl.make_decide_jax(
        r.static, r._params(), mesh=fleet_mesh(2), compact=True
    )
    cache = comp.init_cache()

    def check(lam_t):
        want = dense(lam_t, mu, drop, lam0, k)
        nonlocal cache
        got, repriced, cache = comp(lam_t, mu, drop, lam0, k, cache)
        for name, a, b_ in zip(
            ("code", "k_next", "et_cur", "et_target", "applied"), want, got
        ):
            a, b_ = np.asarray(a), np.asarray(b_)
            if name in ("et_cur", "et_target"):
                np.testing.assert_allclose(a, b_, rtol=1e-6, err_msg=name)
            else:
                np.testing.assert_array_equal(a, b_, err_msg=name)
        return int(np.asarray(repriced)[:b].sum())

    assert check(lam) == b  # cold
    assert check(lam) == 0  # quiet
    lam2 = lam.copy()
    lam2[3] *= 1.5
    assert check(lam2) == 1  # exactly the perturbed lane, on its shard
    assert check(lam2) == 0


@multi_device
def test_make_decide_jax_mesh_parity():
    scens = _scens(8, seed=13)
    r = ScenarioRunner(scens, tick_interval=5.0, backend="jax")
    b, n = len(scens), r.static.n
    rng = np.random.default_rng(2)
    lam = np.abs(rng.normal(2.0, 0.6, (b, n)))
    mu = np.abs(rng.normal(6.0, 0.5, (b, n))) + 1.0
    drop = np.zeros((b, n))
    lam0 = np.abs(rng.normal(2.0, 0.5, b))
    k = np.where(r.static.active, 2, 0).astype(np.int64)

    ref = ctl.make_decide_jax(r.static, r._params())(lam, mu, drop, lam0, k)
    got = ctl.make_decide_jax(r.static, r._params(), mesh=fleet_mesh(2))(
        lam, mu, drop, lam0, k
    )
    for name, a, b_ in zip(("code", "k_next", "et_cur", "et_target", "applied"),
                           ref, got):
        a, b_ = np.asarray(a), np.asarray(b_)
        if name in ("et_cur", "et_target"):
            np.testing.assert_allclose(a, b_, rtol=1e-6, err_msg=name)
        else:
            np.testing.assert_array_equal(a, b_, err_msg=name)


@multi_device
def test_scenario_runner_mesh_reports_match():
    scens = _scens(6, seed=23)
    base = ScenarioRunner(scens, tick_interval=5.0, backend="jax").run()
    mesh = ScenarioRunner(
        _scens(6, seed=23), tick_interval=5.0, backend="jax",
        mesh=fleet_mesh(len(jax.devices())),
    ).run()
    for rb, rm in zip(base, mesh):
        assert list(rb.actions) == list(rm.actions)
        assert rb.k_final == rm.k_final
        assert rb.trajectory["k_total"] == rm.trajectory["k_total"]
        assert rb.trajectory["miss"] == rm.trajectory["miss"]


def test_controller_mesh_must_be_1d():
    devs = np.asarray(jax.devices()[:1]).reshape(1, 1)
    from jax.sharding import Mesh

    with pytest.raises(ValueError):
        ctl._mesh_axis(Mesh(devs, ("a", "b")))
