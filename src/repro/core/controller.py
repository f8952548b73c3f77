"""Batched closed-loop controller — the measure -> model -> rebalance loop
as pure functions over stacked arrays (DESIGN.md §14).

PR 3 batched the analytic tables and PR 4 batched the simulator, but the
*decision* path — overload detection, offered-load clamping, Programs
(4)/(6), hysteresis, the improvement and cost/benefit gates — was still
scalar Python living inside :class:`~repro.core.scheduler.DRSScheduler`,
executed once per scenario per tick.  This module extracts that math into
a stateless controller that operates on ``[B, N]`` snapshot stacks:

* **float64 numpy twin** — :func:`tick_batch` / :func:`decide_single` are
  a verbatim port of the scheduler's decision flow.  The measurement
  plane (overload masks, throughput-capped propagation, offered-load
  clamping) is vectorized across the batch; the per-scenario allocator
  and negotiator calls replay the exact scalar float ops (the same
  table-driven Programs (4)/(6) of core/allocator.py), so a B=1 tick is
  **bit-identical** to the pre-extraction scheduler.  ``DRSScheduler``
  is now a thin stateful shell over these functions.
* **jit jax path** — :func:`make_decide_jax` compiles the whole decide
  (batched Jackson solve, a lanes-minor Gaussian elimination in
  ``solve_traffic_batch_jax``, batched offered-load clamping, one
  table pass through ``kernels/erlang_c``,
  Program-4 allocation as a masked top-R selection through
  ``kernels/gain_topr``, vectorized improvement + cost gates) into ONE
  program over the ``[B, N]`` fleet; :func:`make_fused_loop` fuses it
  with the batch simulator's window step in a single ``lax.scan`` so a
  full simulate -> measure -> decide -> apply tick sequence is one XLA
  computation (no Python between ticks).

What stays in Python (the batch boundaries): per-scenario
:class:`~repro.core.negotiator.Negotiator` leases (``ensure`` is a
side-effecting pool mutation), custom
:class:`~repro.core.rebalance.RebalanceCostModel` subclasses /
:class:`~repro.core.rebalance.ExecutableCache` lookups, and the engine
``apply_allocation`` call.  The fused path therefore supports statically
budgeted scenarios end-to-end; negotiated scenarios run the same batched
twin with the lease hooks invoked between ticks.

Machine-class heterogeneity (paper §III-A) is wired through ``speed``:
a per-operator machine-class speed factor scales the effective service
rate ``mu_eff = mu_hat * speed`` everywhere the model consumes it —
equivalent to the uniform-speed case of
:func:`~repro.core.heterogeneous.assign_heterogeneous` (mean-speed
M/M/k), which tests/test_heterogeneous.py asserts.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, replace
from typing import Any, Callable, NamedTuple, Sequence

import numpy as np

from .allocator import (
    AllocationResult,
    InsufficientResourcesError,
    assign_processors,
    assign_processors_table,
    min_processors,
    min_processors_table,
)
from . import stages
from .jackson import OperatorSpec, Topology, UnstableTopologyError
from .measurer import MeasurementBatch
from .rebalance import RebalanceCostModel, RebalancePlan

__all__ = [
    "ACTIONS",
    "ALLOCATORS",
    "ControllerStatic",
    "ControllerParams",
    "ControllerState",
    "CompactionConfig",
    "DecideCache",
    "TwinCompactionState",
    "init_decide_cache",
    "FusedLoop",
    "RowDecision",
    "BatchDecision",
    "overloaded_mask_batch",
    "capped_mask_batch",
    "clamp_row",
    "decide_single",
    "tick_batch",
    "pad_static",
    "pad_params",
    "make_decide_jax",
    "make_fused_loop",
]

# Action vocabulary (codes shared by the numpy twin and the jit path).
# "proactive" (appended last so earlier codes stay stable) marks an
# allocation committed by the forecast/MPC planner ahead of any trigger.
ACTIONS = (
    "none",
    "rebalance",
    "scale_out",
    "scale_in",
    "infeasible",
    "overloaded",
    "rebalance_hint",
    "proactive",
)
_CODE = {name: i for i, name in enumerate(ACTIONS)}

# Program (4)/(6) solver pairs, keyed like SchedulerConfig.allocator.
ALLOCATORS = {
    "table": (assign_processors_table, min_processors_table),
    "heap": (assign_processors, min_processors),
}

# An operator shedding more than this fraction of its capacity is
# overloaded even if the smoothed arrival rate dips below capacity
# (EWMA lag under bursty arrivals) — DRSScheduler.DROP_TRIGGER_FRACTION.
DROP_TRIGGER_FRACTION = 0.01


# --------------------------------------------------------------------------- #
# Static structure + per-scenario parameters
# --------------------------------------------------------------------------- #
@dataclass(frozen=True)
class ControllerStatic:
    """Declared per-scenario structure, padded to the batch-wide N_max.

    ``names`` keeps each scenario's operator names (reason strings +
    Topology reconstruction); array lanes beyond ``n_ops[b]`` are inert
    padding (no routing, no arrivals, ``active`` False).  ``hot`` holds
    each keyed operator's hot-key share (DESIGN.md §20), NaN elsewhere;
    ``None`` means no operator is keyed.
    """

    base_routing: np.ndarray  # [B, N, N] declared multiplicities
    group: np.ndarray  # [B, N] bool: chip-gang scaling
    alpha: np.ndarray  # [B, N] group efficiency rolloff
    active: np.ndarray  # [B, N] bool: real operator lanes
    speed: np.ndarray  # [B, N] machine-class speed factors (1 = reference)
    n_ops: np.ndarray  # [B] operators per scenario
    names: tuple  # per-scenario tuple of operator names
    hot: np.ndarray | None = None  # [B, N] keyed hot-key share, NaN = not keyed

    @property
    def batch(self) -> int:
        return self.base_routing.shape[0]

    @property
    def n(self) -> int:
        return self.base_routing.shape[1]

    @property
    def keyed(self) -> bool:
        """Whether any operator of any lane is keyed."""
        return self.hot is not None and bool((~np.isnan(self.hot)).any())

    @classmethod
    def from_graphs(cls, graphs: Sequence, *, speed=None) -> "ControllerStatic":
        """Stack B AppGraphs (padded) into one static bundle."""
        b = len(graphs)
        n = max(g.n for g in graphs)
        routing = np.zeros((b, n, n))
        group = np.zeros((b, n), dtype=bool)
        alpha = np.zeros((b, n))
        active = np.zeros((b, n), dtype=bool)
        spd = np.ones((b, n))
        hot = np.full((b, n), np.nan)
        n_ops = np.zeros(b, dtype=np.int64)
        names = []
        for bi, g in enumerate(graphs):
            ni = g.n
            routing[bi, :ni, :ni] = g.routing_matrix()
            scaling, ga = g.scaling_lists()
            group[bi, :ni] = [s == "group" for s in scaling]
            alpha[bi, :ni] = ga
            hot[bi, :ni] = g.hot_shares()
            active[bi, :ni] = True
            n_ops[bi] = ni
            names.append(tuple(g.names))
            if speed is not None and speed[bi] is not None:
                spd[bi, :ni] = speed[bi]
        return cls(routing, group, alpha, active, spd, n_ops, tuple(names), hot)


@dataclass(frozen=True)
class ControllerParams:
    """Per-scenario decision parameters (SchedulerConfig, stacked).

    ``t_max`` uses NaN for "no real-time constraint"; ``k_max`` is the
    budget *resolved at tick entry* (the static config value, or the
    negotiator's current lease — the caller re-reads it each tick).
    """

    t_max: np.ndarray  # [B] float (NaN = Program 4 only)
    k_max: np.ndarray  # [B] int64 resolved budget
    headroom: np.ndarray  # [B]
    scale_in_hysteresis: np.ndarray  # [B]
    min_improvement: np.ndarray  # [B]
    horizon_seconds: np.ndarray  # [B]
    allocator: tuple  # [B] "table" | "heap"
    # Accepted for callers that still pass it; only False is valid.
    fused_decide: bool = False

    def __post_init__(self):
        if self.fused_decide:
            raise ValueError(
                "fused_decide=True: the one-pass decide was removed; every "
                "decide runs the two-pass dataflow (leave fused_decide False)"
            )

    @classmethod
    def stack(cls, configs: Sequence, k_max: Sequence[int]) -> "ControllerParams":
        """From B SchedulerConfig-likes + resolved per-scenario budgets."""
        return cls(
            t_max=np.array(
                [np.nan if c.t_max is None else float(c.t_max) for c in configs]
            ),
            k_max=np.asarray(k_max, dtype=np.int64),
            headroom=np.array([c.headroom for c in configs]),
            scale_in_hysteresis=np.array([c.scale_in_hysteresis for c in configs]),
            min_improvement=np.array([c.min_improvement for c in configs]),
            horizon_seconds=np.array([c.horizon_seconds for c in configs]),
            allocator=tuple(c.allocator for c in configs),
        )


# --------------------------------------------------------------------------- #
# Batch-axis padding (device-mesh sharding needs B % device count == 0)
# --------------------------------------------------------------------------- #
def pad_static(static: ControllerStatic, b_total: int) -> ControllerStatic:
    """Append ``b_total - B`` inert scenario lanes: no operators
    (``n_ops = 0``), ``active`` all-False, zero routing/alpha, unit speed.
    Padded lanes provably decide ``"none"`` with an unchanged allocation
    (tests/test_mesh_control.py asserts this bit-for-bit) so they never
    influence real decisions — the masked-lane contract DESIGN.md §16."""
    b, n = static.batch, static.n
    if b_total < b:
        raise ValueError(f"b_total {b_total} < batch {b}")
    if b_total == b:
        return static
    pad = b_total - b
    return ControllerStatic(
        base_routing=np.concatenate(
            [static.base_routing, np.zeros((pad, n, n))], axis=0
        ),
        group=np.concatenate([static.group, np.zeros((pad, n), dtype=bool)]),
        alpha=np.concatenate([static.alpha, np.zeros((pad, n))]),
        active=np.concatenate([static.active, np.zeros((pad, n), dtype=bool)]),
        speed=np.concatenate([static.speed, np.ones((pad, n))]),
        n_ops=np.concatenate([static.n_ops, np.zeros(pad, dtype=np.int64)]),
        names=static.names + ((),) * pad,
        hot=None if static.hot is None
        else np.concatenate([static.hot, np.full((pad, n), np.nan)]),
    )


def pad_params(params: ControllerParams, b_total: int) -> ControllerParams:
    """Decision parameters for inert padded lanes: no constraint
    (``t_max = NaN``), zero budget, and an infinite improvement gate —
    every gate in the decide is provably closed on a padded lane."""
    b = params.k_max.shape[0]
    if b_total < b:
        raise ValueError(f"b_total {b_total} < batch {b}")
    if b_total == b:
        return params
    pad = b_total - b
    return ControllerParams(
        t_max=np.concatenate([params.t_max, np.full(pad, np.nan)]),
        k_max=np.concatenate([params.k_max, np.zeros(pad, dtype=np.int64)]),
        headroom=np.concatenate([params.headroom, np.ones(pad)]),
        scale_in_hysteresis=np.concatenate(
            [params.scale_in_hysteresis, np.zeros(pad)]
        ),
        min_improvement=np.concatenate([params.min_improvement, np.full(pad, np.inf)]),
        horizon_seconds=np.concatenate([params.horizon_seconds, np.zeros(pad)]),
        allocator=params.allocator + ("table",) * pad,
    )


def _mesh_axis(mesh) -> tuple[str, int]:
    """The (axis name, device count) of a 1-D controller mesh."""
    if len(mesh.axis_names) != 1:
        raise ValueError(
            f"controller mesh must be 1-D (batch axis only); got axes "
            f"{mesh.axis_names}"
        )
    return mesh.axis_names[0], int(mesh.size)


def _padded_batch(b: int, n_shards: int) -> int:
    """B rounded up to a multiple of the shard count."""
    return -(-b // n_shards) * n_shards


# --------------------------------------------------------------------------- #
# Trigger-gated lane compaction (DESIGN.md §18)
# --------------------------------------------------------------------------- #
@dataclass(frozen=True)
class CompactionConfig:
    """Knobs for the sparse (trigger-gated) decide.

    ``b_active_cap`` is the static bucket ladder: ascending compacted
    widths, the largest of which must be the (per-shard) batch extent so
    a fully-triggered tick falls back to the dense decide.  ``None``
    derives it with :func:`repro.distributed.sharding.bucket_ladder`.

    The compaction is **exact, not approximate**: the decide is a pure
    function of ``(statics, lam_hat, mu_hat, drop_hat, lam0_hat, k)``,
    so a lane whose inputs are bitwise unchanged since it was last
    priced replays its cached outputs — which are, by purity, exactly
    what repricing would produce.  The trigger scan therefore marks a
    lane active when (a) it has no cached entry, (b) any decide input
    changed bitwise (NaN-tolerant: NaN == NaN for this purpose, since
    every consumer of a NaN measurement branches identically on it), or
    (c) the §11 overload mask fires — (c) is subsumed by (b) in steady
    state but is kept as a belt-and-braces guard so a hot lane can never
    ride the fast path.
    """

    b_active_cap: tuple[int, ...] | None = None


class DecideCache(NamedTuple):
    """Per-lane memo for the jit decide: the inputs it was last priced
    with and the outputs it produced (the dense "none"-row fast path
    replays these).  Every leaf is ``[B, ...]``-leading so a device mesh
    shards the whole cache with the same one-axis rule as the statics.

    The cache is deliberately NOT part of :class:`ControllerState`: a
    cold cache only makes the next tick price every lane (same outputs,
    more work), so checkpoints stay layout-independent — a restore into
    a loop with a different mesh/ladder shape resumes bit-identically
    (DESIGN.md §18).
    """

    ok: Any  # [B] bool: lane has a priced entry
    lam: Any  # [B, N] cached lam_hat
    mu: Any  # [B, N] cached mu_hat
    drop: Any  # [B, N] cached drop_hat
    lam0: Any  # [B] cached lam0_hat
    k: Any  # [B, N] int32 cached entry allocation
    code: Any  # [B] int32 cached action code
    k_next: Any  # [B, N] int32 cached post-decide allocation
    et_cur: Any  # [B] cached E[T] at entry allocation
    et_target: Any  # [B] cached E[T] at proposed allocation
    applied: Any  # [B] bool cached applied flag
    hot_floor: Any = None  # [B] int32 cached counter (keyed graphs only)


def init_decide_cache(b: int, n: int, *, dtype=None, keyed: bool = False) -> DecideCache:
    """Cold (all-lanes-invalid) cache — the first tick prices densely.
    ``keyed`` adds the slot of the keyed decide's ``hot_floor`` output."""
    import jax.numpy as jnp

    dtype = jnp.zeros((), dtype=dtype).dtype  # canonical under the x64 flag
    return DecideCache(
        ok=jnp.zeros(b, dtype=bool),
        lam=jnp.zeros((b, n), dtype=dtype),
        mu=jnp.zeros((b, n), dtype=dtype),
        drop=jnp.zeros((b, n), dtype=dtype),
        lam0=jnp.zeros(b, dtype=dtype),
        k=jnp.zeros((b, n), dtype=jnp.int32),
        code=jnp.zeros(b, dtype=jnp.int32),
        k_next=jnp.zeros((b, n), dtype=jnp.int32),
        et_cur=jnp.zeros(b, dtype=dtype),
        et_target=jnp.zeros(b, dtype=dtype),
        applied=jnp.zeros(b, dtype=bool),
        hot_floor=jnp.zeros(b, dtype=jnp.int32) if keyed else None,
    )


def _resolve_ladder(compact, b: int) -> tuple[int, ...]:
    """The static bucket ladder for a (per-shard) batch extent ``b``."""
    from ..distributed.sharding import bucket_ladder

    cfg = compact if isinstance(compact, CompactionConfig) else CompactionConfig()
    if cfg.b_active_cap is None:
        return bucket_ladder(b)
    ladder = tuple(sorted({min(int(w), b) for w in cfg.b_active_cap} | {b}))
    if ladder[0] < 1:
        raise ValueError(f"bucket ladder widths must be >= 1: {cfg.b_active_cap}")
    return ladder


def _bucketed(ladder, b, mask, run_at_width, templates):
    """Gather -> compute -> scatter over the masked lanes at the smallest
    static ladder width that holds them (MoE-style capacity dispatch).

    ``run_at_width(gather_idx)`` receives ``[w]`` clipped lane indices
    and returns a tuple matching ``templates``; lanes outside the mask
    keep their template values.  Unused gather rows (the ``fill_value``
    tail, clipped into range) compute garbage that the drop-mode scatter
    discards — safe because every op in the decide is per-lane.
    """
    import jax
    import jax.numpy as jnp

    idx = jnp.nonzero(mask, size=b, fill_value=b)[0]
    sel = jnp.searchsorted(
        jnp.asarray(ladder, dtype=jnp.int32),
        mask.sum(dtype=jnp.int32),
        side="left",
    )

    def branch(w):
        def go(_):
            outs = run_at_width(jnp.clip(idx[:w], 0, b - 1))
            return tuple(
                t.at[idx[:w]].set(o, mode="drop")
                for t, o in zip(templates, outs)
            )

        return go

    return jax.lax.switch(sel, [branch(w) for w in ladder], 0)


def _capacity_jax(st, mu_eff, k_floor):
    """Per-operator capacity at ``k_floor`` (``k`` floored at 1): k
    replicas, a gang at ``eff(k)``, or a keyed operator's hot partition
    (DESIGN.md §20; traced only when ``st`` holds keyed operators)."""
    import jax.numpy as jnp

    eff = 1.0 / (1.0 + st["alpha"] * (k_floor - 1.0))
    capacity = jnp.where(st["group"], mu_eff * k_floor * eff, mu_eff * k_floor)
    if "hot" in st:
        capacity = _keyed_capacity_jax(st, capacity, mu_eff, k_floor)
    return capacity


def _keyed_capacity_jax(st, capacity, mu_eff, k_floor):
    """``capacity`` with each keyed operator's replaced by its hot
    partition's, ``mu / (h + (1 - h)/k)`` (``k_floor`` >= 1)."""
    import jax.numpy as jnp

    hot = st["hot"]
    return jnp.where(st["keyed"], mu_eff / (hot + (1.0 - hot) / k_floor), capacity)


def _make_compact_decide(core, b: int, ladder: tuple[int, ...]):
    """Wrap a dense decide core with the trigger scan + bucketed dispatch.

    ``decide(st, lam_hat, mu_hat, drop_hat, lam0_hat, k_current, cache)
    -> ((code, k_next, et_cur, et_target, applied[, hot_floor]), repriced,
    cache')`` is bitwise identical to ``core(...)`` on every output: active lanes
    are gathered, priced at the compacted width, and scattered back;
    quiet lanes replay their cached row, which purity guarantees equals
    a fresh repricing (see :class:`CompactionConfig`).
    """
    import jax.numpy as jnp

    def _neq(a, c):
        # Bitwise-change test with NaN == NaN (a persistently-NaN
        # measurement must not keep a lane hot forever).
        return (a != c) & ~(jnp.isnan(a) & jnp.isnan(c))

    def decide(st, lam_hat, mu_hat, drop_hat, lam0_hat, k_current, cache):
        # The whole body is the compact stage; the core priced inside a
        # ladder branch keeps its own (innermost) stages.
        with stages.scope("compact"):
            k_in = k_current.astype(jnp.int32)
            # --- trigger scan: O(B*N), no table/solve/top-R work ----------- #
            mu_eff = mu_hat * st["speed"]
            k_floor = jnp.maximum(k_in, 1).astype(lam_hat.dtype)
            capacity = _capacity_jax(st, mu_eff, k_floor)
            valid = jnp.isfinite(lam_hat) & jnp.isfinite(mu_eff) & (mu_eff > 0)
            drops = jnp.nan_to_num(drop_hat, nan=0.0)
            hot = (
                valid & st["active"] & (
                    (lam_hat >= capacity * (1.0 - 1e-9))
                    | (drops > DROP_TRIGGER_FRACTION * capacity)
                )
            ).any(axis=-1)
            changed = (
                _neq(lam_hat, cache.lam).any(axis=-1)
                | _neq(mu_hat, cache.mu).any(axis=-1)
                | _neq(drop_hat, cache.drop).any(axis=-1)
                | _neq(lam0_hat, cache.lam0)
                | (k_in != cache.k).any(axis=-1)
            )
            repriced = ~cache.ok | changed | hot

            # --- compacted decide + cached-row fast path ------------------- #
            def price(g):
                st_g = {key: val[g] for key, val in st.items()}
                return core(
                    st_g, lam_hat[g], mu_hat[g], drop_hat[g], lam0_hat[g], k_in[g]
                )

            keyed = () if cache.hot_floor is None else (cache.hot_floor,)
            outs = _bucketed(
                ladder, b, repriced, price,
                (cache.code, cache.k_next, cache.et_cur, cache.et_target,
                 cache.applied) + keyed,
            )
            new_cache = DecideCache(
                jnp.ones_like(cache.ok), lam_hat, mu_hat, drop_hat, lam0_hat, k_in,
                *outs,
            )
        return outs, repriced, new_cache

    return decide


@dataclass
class TwinCompactionState:
    """Per-lane memo for the numpy twin's reactive decide (mutable,
    caller-owned; pass it to every :func:`tick_batch` of one run).

    Lanes with a negotiator ``ensure`` hook or a custom cost model are
    never memoized (their decide is side-effecting / stateful); for the
    rest, a bitwise-unchanged input tuple replays the cached
    :class:`RowDecision` — the same purity argument as the jit cache.
    Valid only for a fixed ``(static, params-other-than-k_max)``;
    ``k_max`` is compared per tick because negotiator leases move it.
    """

    valid: np.ndarray  # [B] bool
    lam: np.ndarray  # [B, N]
    mu: np.ndarray  # [B, N]
    drop: np.ndarray  # [B, N]
    lam0: np.ndarray  # [B]
    k: np.ndarray  # [B, N] int64
    k_max: np.ndarray  # [B] int64
    rows: list  # [B] RowDecision | None
    errors: list  # [B] Exception | None
    replayed: np.ndarray  # [B] bool: last tick's fast-path lanes (diagnostic)

    @classmethod
    def create(cls, b: int, n: int) -> "TwinCompactionState":
        return cls(
            valid=np.zeros(b, dtype=bool),
            lam=np.full((b, n), np.nan),
            mu=np.full((b, n), np.nan),
            drop=np.full((b, n), np.nan),
            lam0=np.full(b, np.nan),
            k=np.zeros((b, n), dtype=np.int64),
            k_max=np.zeros(b, dtype=np.int64),
            rows=[None] * b,
            errors=[None] * b,
            replayed=np.zeros(b, dtype=bool),
        )

    def hit(self, bi, lam, mu, drop, lam0, k, k_max) -> bool:
        return bool(
            self.valid[bi]
            and self.k_max[bi] == k_max
            and np.array_equal(self.k[bi, : len(k)], k)
            and np.array_equal(self.lam[bi, : len(lam)], lam, equal_nan=True)
            and np.array_equal(self.mu[bi, : len(mu)], mu, equal_nan=True)
            and np.array_equal(self.drop[bi, : len(drop)], drop, equal_nan=True)
            and (
                np.isnan(self.lam0[bi]) and np.isnan(lam0)
                or self.lam0[bi] == lam0
            )
        )

    def remember(self, bi, lam, mu, drop, lam0, k, k_max, row, error) -> None:
        self.valid[bi] = True
        self.lam[bi, : len(lam)] = lam
        self.mu[bi, : len(mu)] = mu
        self.drop[bi, : len(drop)] = drop
        self.lam0[bi] = lam0
        self.k[bi, : len(k)] = k
        self.k_max[bi] = k_max
        self.rows[bi] = row
        self.errors[bi] = error


# --------------------------------------------------------------------------- #
# Vectorized measurement plane
# --------------------------------------------------------------------------- #
def _source_mask(static: ControllerStatic) -> np.ndarray:
    """[B, N] bool: declared external-arrival entry points (no in-edges;
    a scenario with none falls back to operator 0 — the scalar rule)."""
    in_deg = static.base_routing.sum(axis=1)
    src = (in_deg == 0) & static.active
    for bi in range(static.batch):
        if not src[bi].any():
            src[bi, 0] = True
    return src


def effective_capacity(k, mu_eff, group, alpha, hot=None) -> np.ndarray:
    """Per-operator service capacity at allocation ``k`` with the group
    efficiency curve applied (k floored at 1, mirroring the scalar
    ``overloaded_mask``); a keyed operator's (``hot`` finite, DESIGN.md
    §20) is where its hot partition saturates, ``mu / (h + (1 - h)/k)``."""
    k_eff = np.maximum(np.asarray(k, dtype=np.int64), 1)
    with np.errstate(divide="ignore", invalid="ignore"):
        eff = 1.0 / (1.0 + alpha * (k_eff - 1))
    cap = np.where(group, mu_eff * k_eff * eff, mu_eff * k_eff)
    if hot is not None:
        with np.errstate(invalid="ignore"):
            keyed_cap = mu_eff / (hot + (1.0 - hot) / k_eff)
        cap = np.where(np.isnan(hot), cap, keyed_cap)
    return cap


def overloaded_mask_batch(lam_hat, mu_eff, drop, k, group, alpha, hot=None) -> np.ndarray:
    """[B, N] bool: measured offered load >= capacity, or sustained
    shedding — the vectorized twin of ``DRSScheduler.overloaded_mask``
    (same comparisons, so bit-identical decisions at any batch size)."""
    lam_hat = np.asarray(lam_hat, dtype=np.float64)
    mu_eff = np.asarray(mu_eff, dtype=np.float64)
    drops = np.nan_to_num(np.asarray(drop, dtype=np.float64), nan=0.0)
    capacity = effective_capacity(k, mu_eff, group, alpha, hot)
    valid = np.isfinite(lam_hat) & np.isfinite(mu_eff) & (mu_eff > 0)
    with np.errstate(invalid="ignore"):
        hot = (lam_hat >= capacity * (1.0 - 1e-9)) | (
            drops > DROP_TRIGGER_FRACTION * capacity
        )
    return valid & hot


def capped_mask_batch(overloaded, base_routing, active=None) -> np.ndarray:
    """[B, N] bool: operators whose *measured arrival rate* is throughput-
    capped — transitively downstream of a saturated operator (vectorized
    ``DRSScheduler._capped_mask`` fixed point)."""
    overloaded = np.atleast_2d(np.asarray(overloaded, dtype=bool))
    routing = np.asarray(base_routing, dtype=np.float64)
    if routing.ndim == 2:
        routing = routing[None]
    adj = routing > 0  # [B, N, N]
    n = adj.shape[-1]
    out_capped = overloaded.copy()
    in_capped = np.zeros_like(overloaded)
    for _ in range(n):
        new_in = (adj & out_capped[:, :, None]).any(axis=1)
        new_out = overloaded | new_in
        if (new_in == in_capped).all() and (new_out == out_capped).all():
            break
        in_capped, out_capped = new_in, new_out
    if active is not None:
        in_capped = in_capped & np.asarray(active, dtype=bool)
    return in_capped


# --------------------------------------------------------------------------- #
# Offered-load clamping (the topology_from math) — scalar row port
# --------------------------------------------------------------------------- #
def clamp_row(
    names: Sequence[str],
    base_routing: np.ndarray,
    lam_hat: np.ndarray,
    mu_hat: np.ndarray,
    lam0_hat: float,
    overloaded: np.ndarray,
    capped: np.ndarray,
    scaling: Sequence[str],
    group_alpha: Sequence[float],
    speed: np.ndarray | None = None,
    hot_share: Sequence[float] | None = None,
) -> Topology:
    """Rebuild one scenario's model from measurements (DESIGN.md §4/§11).

    This is the pure-function extraction of ``DRSScheduler.topology_from``
    — identical float ops, so the rebuilt Topology is bit-identical to the
    pre-extraction scheduler's.  ``speed`` applies machine-class factors
    to the effective per-processor service rates (1.0 = reference class);
    ``hot_share`` gives keyed operators their hot-key shares (NaN
    elsewhere; DESIGN.md §20).
    """
    n = len(names)
    hot = bool(np.asarray(overloaded).any())
    lam_hat = np.array(lam_hat, dtype=np.float64)
    lam0 = np.zeros(n)
    in_deg = base_routing.sum(axis=0)
    sources = np.nonzero(in_deg == 0)[0]
    if len(sources) == 0:
        sources = np.array([0])
    if hot:
        for s in sources:
            lam0[s] = lam_hat[s] if math.isfinite(lam_hat[s]) else 0.0
    else:
        src_lam = lam_hat[sources]
        total_src = max(src_lam.sum(), 1e-12)
        for s, l in zip(sources, src_lam):
            lam0[s] = lam0_hat * (l / total_src) if math.isfinite(lam0_hat) else l
    routing = base_routing.copy()
    for j in range(n):
        declared_in = routing[:, j]
        if declared_in.sum() == 0:
            continue
        if capped[j]:
            continue  # measured lam_hat[j] is capacity, not offered load
        inflow = float(np.dot(declared_in, lam_hat))
        if inflow > 1e-12 and math.isfinite(lam_hat[j]) and lam_hat[j] > 0:
            routing[:, j] *= lam_hat[j] / inflow
    ops = [
        OperatorSpec(
            name=names[i],
            mu=float(mu_hat[i]) if speed is None else float(mu_hat[i] * speed[i]),
            scaling=scaling[i],
            group_alpha=group_alpha[i],
            hot_share=0.0 if hot_share is None or np.isnan(hot_share[i])
            else float(hot_share[i]),
        )
        for i in range(n)
    ]
    return Topology(ops, lam0, routing)


# --------------------------------------------------------------------------- #
# The decision flow — scalar row port + batched driver
# --------------------------------------------------------------------------- #
@dataclass(frozen=True)
class RowDecision:
    """One scenario's tick outcome (pure data; no scheduler state)."""

    action: str
    k_next: np.ndarray  # allocation in force after the tick
    k_target: np.ndarray | None  # proposed allocation (None on hard failure)
    k_max: int  # budget after any lease change
    et_cur: float
    et_target: float | None
    need_total: int | None  # Program-(6)-sized demand (overload / scaling)
    plan: RebalancePlan | None
    reason: str
    applied: bool  # k_next != entry k (an allocation change to execute)

    @property
    def code(self) -> int:
        return _CODE[self.action]


@dataclass
class BatchDecision:
    """Stacked tick outcomes for a B-scenario batch."""

    rows: list  # [B] RowDecision
    errors: list  # [B] Exception | None (model/allocator hard failures)

    @property
    def actions(self) -> list[str]:
        return [r.action for r in self.rows]

    def k_next(self, n: int) -> np.ndarray:
        out = np.zeros((len(self.rows), n), dtype=np.int64)
        for bi, r in enumerate(self.rows):
            out[bi, : len(r.k_next)] = r.k_next
        return out


def _default_cost_plan(
    cost_model: RebalanceCostModel,
    top: Topology,
    k_old: np.ndarray,
    k_new: np.ndarray,
    cache,
    stage_names,
) -> RebalancePlan:
    return cost_model.plan(top, k_old, k_new, cache=cache, stage_names=stage_names)


def decide_single(
    top: Topology,
    k_current: np.ndarray,
    k_max: int,
    *,
    t_max: float | None,
    headroom: float,
    scale_in_hysteresis: float,
    min_improvement: float,
    horizon_seconds: float,
    allocator: str = "table",
    overloaded: np.ndarray | None = None,
    lam_hat: np.ndarray | None = None,
    mu_hat: np.ndarray | None = None,
    drop: np.ndarray | None = None,
    ensure: Callable[[int], int] | None = None,
    cost_model: RebalanceCostModel | None = None,
    cache=None,
    stage_names: Sequence[str] | None = None,
    stragglers: tuple = (),
    names: Sequence[str] | None = None,
) -> RowDecision:
    """One scenario's decide — the float64 numpy twin of the old
    ``DRSScheduler.decide`` body (same branch order, same float ops, same
    allocator calls, so the outcome is bit-identical).

    ``ensure`` is the per-scenario negotiator lease hook (target -> new
    k_max); ``None`` disables the scale-out/scale-in branches exactly
    like a scheduler without a negotiator.  Model/allocator hard failures
    (``UnstableTopologyError`` and uncaught ``InsufficientResourcesError``)
    propagate to the caller, as they did from ``decide``.
    """
    assign_fn, min_proc_fn = ALLOCATORS[allocator]
    names = list(names) if names is not None else [op.name for op in top.operators]
    n = len(names)
    cost_model = cost_model or RebalanceCostModel()
    k_current = np.asarray(k_current, dtype=np.int64)
    et_cur = top.expected_sojourn(k_current)  # may raise UnstableTopologyError

    if overloaded is None:
        if lam_hat is None or mu_hat is None:
            overloaded = np.zeros(n, dtype=bool)
        else:
            group = np.array([op.scaling == "group" for op in top.operators])
            alpha = np.array([op.group_alpha for op in top.operators])
            hot = np.array([op.hot_share if op.scaling == "keyed" else np.nan
                            for op in top.operators])
            overloaded = overloaded_mask_batch(
                lam_hat[None], mu_hat[None], None if drop is None else drop[None],
                k_current[None], group[None], alpha[None], hot[None],
            )[0]

    # --- Overload: defined unstable-snapshot path (no gates) ------------ #
    if overloaded.any():
        hot_names = [names[i] for i in np.nonzero(overloaded)[0]]
        try:
            if t_max is not None:
                need_total = math.ceil(min_proc_fn(top, t_max).total * headroom)
            else:
                need_total = math.ceil(
                    int(top.min_feasible_allocation().sum()) * headroom
                )
        except (InsufficientResourcesError, UnstableTopologyError):
            need_total = k_max + 1
        if need_total > k_max and ensure is not None:
            k_max = max(k_max, ensure(need_total))
        try:
            best = assign_fn(top, k_max)
        except (InsufficientResourcesError, UnstableTopologyError) as e:
            return RowDecision(
                "overloaded", k_current.copy(), None, k_max, et_cur, None,
                need_total, None,
                f"overloaded at {hot_names}; offered load infeasible "
                f"within k_max={k_max}: {e}",
                applied=False,
            )
        return RowDecision(
            "overloaded", best.k.copy(), best.k, k_max, et_cur,
            best.expected_sojourn, need_total, None,
            f"measured rho >= 1 at {hot_names}; offered-load model "
            f"needs {need_total}, reallocated within k_max={k_max}",
            applied=True,
        )

    # --- Program (6): how many processors do we actually need? ---------- #
    need: AllocationResult | None = None
    if t_max is not None:
        try:
            need = min_proc_fn(top, t_max)
        except InsufficientResourcesError:
            need = None

    if t_max is not None:
        needed_total = (
            math.ceil(need.total * headroom) if need is not None else k_max + 1
        )
        # Scale out: T_max unreachable within the current lease.
        if needed_total > k_max and ensure is not None:
            new_k_max = ensure(needed_total)
            if new_k_max > k_max:
                k_max = new_k_max
                best = assign_fn(top, k_max)
                return RowDecision(
                    "scale_out", best.k.copy(), best.k, k_max, et_cur,
                    best.expected_sojourn, needed_total, None,
                    f"Program(6) needs {needed_total} > leased; "
                    f"negotiated k_max={k_max}",
                    applied=True,
                )
        # Scale in: we need much less than we lease (with hysteresis).
        if (
            need is not None
            and ensure is not None
            and math.ceil(need.total * headroom) < scale_in_hysteresis * k_max
        ):
            target_total = math.ceil(need.total * headroom)
            new_k_max = ensure(target_total)
            if new_k_max < k_max:
                best = assign_fn(top, new_k_max)
                return RowDecision(
                    "scale_in", best.k.copy(), best.k, new_k_max, et_cur,
                    best.expected_sojourn, target_total, None,
                    f"Program(6) needs {need.total} (headroom "
                    f"{target_total}) << leased {k_max}; released to {new_k_max}",
                    applied=True,
                )

    # --- Program (4): best placement within k_max ----------------------- #
    try:
        best = assign_fn(top, k_max)
    except InsufficientResourcesError as e:
        return RowDecision(
            "infeasible", k_current.copy(), None, k_max, et_cur, None,
            None if need is None else need.total, None, str(e), applied=False,
        )

    improvement = (
        (et_cur - best.expected_sojourn) / et_cur
        if math.isfinite(et_cur) and et_cur > 0
        else float("inf")
    )
    if np.array_equal(best.k, k_current) or improvement < min_improvement:
        return _none_or_hint_row(
            k_current, best, k_max, et_cur, stragglers,
            reason=f"improvement {improvement:.1%} < {min_improvement:.0%}",
        )

    plan = _default_cost_plan(cost_model, top, k_current, best.k, cache, stage_names)
    if not plan.worthwhile(horizon_seconds, top.lam0_total) and math.isfinite(et_cur):
        return _none_or_hint_row(
            k_current, best, k_max, et_cur, stragglers, plan=plan,
            reason="rebalance cost exceeds benefit over horizon",
        )
    return RowDecision(
        "rebalance", best.k.copy(), best.k, k_max, et_cur,
        best.expected_sojourn, None, plan, "", applied=True,
    )


def _none_or_hint_row(
    k_current, best, k_max, et_cur, stragglers, *, plan=None, reason=""
) -> RowDecision:
    action = "none"
    if stragglers:
        action = "rebalance_hint"
        named = ", ".join(f"{op}[{inst}]" for op, inst in stragglers)
        reason = (reason + "; " if reason else "") + f"stragglers flagged: {named}"
    return RowDecision(
        action, np.asarray(k_current, dtype=np.int64).copy(), best.k, k_max,
        et_cur, best.expected_sojourn, None, plan, reason, applied=False,
    )


def tick_batch(
    meas: MeasurementBatch,
    k_current: np.ndarray,
    static: ControllerStatic,
    params: ControllerParams,
    *,
    ensure: Sequence[Callable[[int], int] | None] | None = None,
    cost_models: Sequence[RebalanceCostModel | None] | None = None,
    raise_errors: bool = False,
    proactive=None,
    q_backlog: np.ndarray | None = None,
    compact_state: TwinCompactionState | None = None,
) -> BatchDecision:
    """One control tick for the whole batch (the float64 numpy twin).

    Vectorized across ``[B, N]``: snapshot completeness, the overload
    trigger, and the throughput-capped propagation.  Per scenario (the
    parts whose float sequencing carries the bit-exactness guarantee, and
    the stateful hooks): offered-load clamping, the Jackson solve, the
    Program-(4)/(6) table allocations, and the negotiator/cost calls.
    Model hard failures become per-row ``errors`` entries with an
    ``"infeasible"`` row (the ScenarioRunner semantics) unless
    ``raise_errors`` (the scalar-scheduler semantics).

    ``proactive`` (a :class:`~repro.forecast.mpc.ProactiveController`)
    switches on the forecast/MPC plane (DESIGN.md §15): the predictor
    state advances on every complete tick, and scenarios whose forecast
    passes the confidence gate — and are NOT currently overloaded (the
    §11 trigger always wins) — commit the MPC plan instead of the
    reactive decide.  ``q_backlog [B, N]`` seeds the planner's rollout
    with the actual queue backlog (0 when the caller has no probe).

    ``compact_state`` (a caller-owned :class:`TwinCompactionState`)
    switches on the twin-side trigger-gated fast path (DESIGN.md §18):
    lanes whose decide inputs are bitwise unchanged — and that are not
    hot, have no negotiator hook / custom cost model, and are not MPC
    overrides — replay their cached :class:`RowDecision` instead of
    re-running clamp + solve + Programs (4)/(6); with ``proactive`` the
    planner prices only the MPC-eligible lanes.  Decisions are bitwise
    identical either way (the memo key is the full input tuple of a pure
    decide); the ``need`` diagnostic defaults to 0 on unpriced lanes.
    """
    b, n = static.batch, static.n
    if proactive is not None and static.keyed:
        raise ValueError(
            "the MPC planner has no keyed-operator model (DESIGN.md §20); "
            "decide keyed graphs reactively"
        )
    k_current = np.asarray(k_current, dtype=np.int64)
    mu_eff = meas.mu_hat * static.speed
    overloaded = overloaded_mask_batch(
        meas.lam_hat, mu_eff, meas.drop_hat, k_current, static.group, static.alpha,
        static.hot,
    ) & static.active
    hot = overloaded.any(axis=1)
    capped = np.zeros((b, n), dtype=bool)
    if hot.any():
        capped = capped_mask_batch(overloaded, static.base_routing, static.active)
    complete = meas.complete(static.active)

    use = np.zeros(b, dtype=bool)
    k_plan = et_hold = et_plan = need_mpc = None
    if proactive is not None:
        from ..forecast.mpc import forecast_step, mpc_plan, mpc_plan_compact

        t_arr = np.nan_to_num(params.t_max, nan=np.inf)
        k_hi = int(max(params.k_max.max(), k_current.max(), 1))
        q0 = (
            np.zeros((b, n)) if q_backlog is None
            else np.asarray(q_backlog, dtype=np.float64)
        )
        proactive.state, lam_pred, conf = forecast_step(
            proactive.state, meas.lam_hat, static.active, proactive.cfg
        )
        plan_kw = dict(
            mu=np.asarray(meas.mu_hat, dtype=np.float64),
            group=static.group, alpha=static.alpha, speed=static.speed,
            active=static.active, src_mask=_source_mask(static),
            cap_queue=proactive.cap_queue, t_max=t_arr,
            span=proactive.span, cfg=proactive.cfg, k_hi=k_hi,
        )
        # A plan can only be committed where the confidence gate is open,
        # the snapshot is complete, the §11 trigger is quiet, and T_max is
        # real — so under compaction the planner prices exactly that set
        # (``use`` below is a subset of it, hence unchanged bitwise).
        eligible = conf & complete & ~hot & np.isfinite(t_arr)

        def _plan(k_max_arr):
            if compact_state is None:
                return mpc_plan(lam_pred, q0, k_current, k_max=k_max_arr, **plan_kw)
            return mpc_plan_compact(
                eligible, lam_pred, q0, k_current, k_max=k_max_arr, **plan_kw
            )

        k_maxes = params.k_max.astype(np.int64).copy()
        k_plan, any_ok, et_hold, et_plan, need_mpc = _plan(k_maxes)
        use = conf & any_ok & complete & ~hot & np.isfinite(t_arr)
        # Negotiator leases: grow toward the Program-6-at-peak demand,
        # release (with hysteresis) when it shrinks; one re-plan pass if
        # any lease moved (the twin-side analogue of scale_out/scale_in).
        if ensure is not None:
            hyst = proactive.cfg.scale_in_hysteresis
            moved = False
            for bi in range(b):
                hook = ensure[bi]
                if hook is None or not use[bi]:
                    continue
                tgt, lease = int(need_mpc[bi]), int(k_maxes[bi])
                if tgt > lease or tgt < hyst * lease:
                    new_lease = int(hook(max(tgt, 1)))
                    if new_lease != lease:
                        k_maxes[bi] = new_lease
                        moved = True
            if moved:
                k_plan, any_ok, et_hold, et_plan, need_mpc = _plan(k_maxes)
                use = conf & any_ok & complete & ~hot & np.isfinite(t_arr)
        proactive.mpc_used = use.copy()
        proactive.confident = conf.copy()
        proactive.need = np.asarray(need_mpc).copy()

    rows: list[RowDecision] = []
    errors: list = [None] * b
    if compact_state is not None:
        compact_state.replayed[:] = False
    for bi in range(b):
        ni = int(static.n_ops[bi])
        k_row = k_current[bi, :ni]
        k_max = int(params.k_max[bi])
        if ni == 0:
            # Padded batch lane (pad_static / pack_scenarios pad_to=): no
            # operators, nothing to decide — the masked-lane contract says
            # it is always "none" with an unchanged (empty) allocation.
            rows.append(RowDecision(
                "none", k_row.copy(), None, k_max, float("nan"), None, None,
                None, "padded lane", applied=False,
            ))
            continue
        if use[bi]:
            k_new = np.asarray(k_plan[bi, :ni], dtype=np.int64)
            changed = bool((k_new != k_row).any())
            rows.append(RowDecision(
                "proactive" if changed else "none",
                k_new.copy() if changed else k_row.copy(),
                k_new, int(k_maxes[bi]), float(et_hold[bi]), float(et_plan[bi]),
                int(need_mpc[bi]), None,
                "MPC plan committed ahead of trigger" if changed
                else "proactive hold",
                applied=changed,
            ))
            continue
        if not complete[bi]:
            rows.append(RowDecision(
                "none", k_row.copy(), None, k_max, float("nan"), None, None,
                None, "insufficient measurements", applied=False,
            ))
            continue
        # Trigger-gated fast path (§18): replay the cached row when every
        # decide input is bitwise unchanged.  Hot lanes always reprice
        # (mirrors the jit trigger); hooked / custom-cost lanes and
        # raise_errors callers never memoize.
        memo = (
            compact_state is not None
            and not raise_errors
            and (ensure is None or ensure[bi] is None)
            and (cost_models is None or cost_models[bi] is None)
        )
        lam_row = np.asarray(meas.lam_hat[bi, :ni], dtype=np.float64)
        mu_row = np.asarray(meas.mu_hat[bi, :ni], dtype=np.float64)
        drop_row = np.asarray(meas.drop_hat[bi, :ni], dtype=np.float64)
        lam0_sc = float(meas.lam0_hat[bi])
        if (
            memo
            and not overloaded[bi, :ni].any()
            and compact_state.hit(
                bi, lam_row, mu_row, drop_row, lam0_sc, k_row, k_max
            )
        ):
            cached = compact_state.rows[bi]
            rows.append(replace(cached, k_next=cached.k_next.copy()))
            errors[bi] = compact_state.errors[bi]
            compact_state.replayed[bi] = True
            continue
        names = static.names[bi]
        hot_row = None if static.hot is None else static.hot[bi, :ni]
        scaling = [
            "group" if static.group[bi, i]
            else "keyed" if hot_row is not None and not np.isnan(hot_row[i])
            else "replica"
            for i in range(ni)
        ]
        t_max = params.t_max[bi]
        try:
            top = clamp_row(
                names,
                static.base_routing[bi, :ni, :ni],
                meas.lam_hat[bi, :ni],
                meas.mu_hat[bi, :ni],
                float(meas.lam0_hat[bi]),
                overloaded[bi, :ni],
                capped[bi, :ni],
                scaling,
                static.alpha[bi, :ni],
                speed=None if np.all(static.speed[bi, :ni] == 1.0)
                else static.speed[bi, :ni],
                hot_share=hot_row,
            )
            row = decide_single(
                top,
                k_row,
                k_max,
                t_max=None if math.isnan(t_max) else float(t_max),
                headroom=float(params.headroom[bi]),
                scale_in_hysteresis=float(params.scale_in_hysteresis[bi]),
                min_improvement=float(params.min_improvement[bi]),
                horizon_seconds=float(params.horizon_seconds[bi]),
                allocator=params.allocator[bi],
                overloaded=overloaded[bi, :ni],
                ensure=None if ensure is None else ensure[bi],
                cost_model=None if cost_models is None else cost_models[bi],
                names=names,
            )
        except (InsufficientResourcesError, UnstableTopologyError) as e:
            if raise_errors:
                raise
            errors[bi] = e
            row = RowDecision(
                "infeasible", k_row.copy(), None, k_max, float("inf"), None,
                None, None, str(e), applied=False,
            )
        rows.append(row)
        if memo and not overloaded[bi, :ni].any():
            compact_state.remember(
                bi, lam_row, mu_row, drop_row, lam0_sc, k_row.copy(), k_max,
                row, errors[bi],
            )
    return BatchDecision(rows, errors)


# --------------------------------------------------------------------------- #
# jit path: the whole decide (and the fused simulate->decide loop) in JAX
# --------------------------------------------------------------------------- #
def _topr_ops():
    """The ``kernels/gain_topr`` dispatch module, imported lazily ONCE.

    Every decide path (reactive core, proactive MPC closure, fleet
    planner) shares this accessor instead of repeating the lazy-import
    block — importing here keeps ``import repro.core.controller`` free
    of a hard jax dependency (numpy-twin-only callers never pay it).
    """
    from ..kernels.gain_topr import ops as topr_ops

    return topr_ops


def _decide_statics(static: ControllerStatic, params: ControllerParams) -> dict:
    """The decide's per-lane array inputs as one ``[B, ...]``-leading dict.

    Every entry has the batch axis leading, so a device mesh shards the
    whole bundle with one rule (``P(axis, None, ...)``) — this is what
    lets the decide run under ``shard_map`` with the statics passed as
    explicit (sharded) arguments instead of replicated closure constants.
    Keyed graphs (DESIGN.md §20) add ``keyed`` and ``hot``; others carry
    neither, so their programs are unchanged.
    """
    st = {
        "routing0": np.asarray(static.base_routing, dtype=np.float64),
        "group": np.asarray(static.group, dtype=bool),
        "alpha": np.asarray(static.alpha, dtype=np.float64),
        "active": np.asarray(static.active, dtype=bool),
        "speed": np.asarray(static.speed, dtype=np.float64),
        "src": _source_mask(static),
        "k_max": np.asarray(params.k_max, dtype=np.int64),
        "min_improvement": np.asarray(params.min_improvement, dtype=np.float64),
        "horizon": np.asarray(params.horizon_seconds, dtype=np.float64),
    }
    if static.keyed:
        st["keyed"] = ~np.isnan(static.hot)
        st["hot"] = np.where(st["keyed"], static.hot, 0.0)
    return st


def _masked_candidates(G, k_start, active):
    """Program (4)'s candidate gains: ``G [..., N, K]`` with the entries
    below each operator's minimal feasible ``k_start``, of inactive
    operators and non-finite ones set to 0.

    ``gain_topr`` depends on a row's positive gains, not on where they
    sit in the row, so this selects exactly what the row shifted to start
    at ``k_start`` would, without a gather along k (element-serial on the
    TPU): one select that XLA fuses.
    """
    import jax.numpy as jnp

    k_col = jnp.arange(G.shape[-1], dtype=k_start.dtype)
    keep = (k_col >= k_start[..., None]) & active[..., None] & jnp.isfinite(G)
    return jnp.where(keep, G, 0.0)


def _column_select(T, k_vec):
    """``T[..., k_vec]`` along the last axis (``k_vec`` clipped into it)
    as a one-hot select summed over k: exact, since one entry is picked
    and the rest are 0, and ``where`` (not a multiply) keeps an ``inf``
    in another column from making a NaN."""
    import jax.numpy as jnp

    k_sel = jnp.clip(k_vec, 0, T.shape[-1] - 1)[..., None]
    hit = jnp.arange(T.shape[-1], dtype=k_sel.dtype) == k_sel
    return jnp.where(hit, T, 0.0).sum(axis=-1)


def _make_decide_core(
    n: int,
    k_hi: int,
    pause: float,
    interpret: bool,
    force_kernel: bool,
):
    """The decide body as a pure function of (statics dict, measurements).

    ``core(st, lam_hat, mu_hat, drop_hat, lam0_hat, k_current)`` operates
    on whatever batch extent its inputs carry — the full ``B`` under plain
    jit, or one device's ``B/D`` shard under ``shard_map`` (every op is
    per-lane, so shard results are bit-identical to the unsharded run).
    """
    import jax
    import jax.numpy as jnp

    topr_ops = _topr_ops()
    from .batched import sojourn_table_jax, solve_traffic_batch_jax

    def decide(st, lam_hat, mu_hat, drop_hat, lam0_hat, k_current):
        routing0 = st["routing0"]
        adj = routing0 > 0
        group = st["group"]
        alpha = st["alpha"]
        active = st["active"]
        speed = st["speed"]
        src_mask = st["src"]
        k_max = st["k_max"]
        min_improvement = st["min_improvement"]
        horizon = st["horizon"]
        keyed = st.get("keyed")  # None: no keyed operator, nothing traced
        b = lam_hat.shape[0]
        dtype = lam_hat.dtype
        # --- overload trigger + capped propagation (§11) --------------- #
        with stages.scope("trigger"):
            mu_eff = mu_hat * speed
            k_cur = k_current.astype(jnp.int32)
            k_floor = jnp.maximum(k_cur, 1).astype(dtype)
            capacity = _capacity_jax(st, mu_eff, k_floor)
            valid = jnp.isfinite(lam_hat) & jnp.isfinite(mu_eff) & (mu_eff > 0)
            drops = jnp.nan_to_num(drop_hat, nan=0.0)
            overloaded = valid & active & (
                (lam_hat >= capacity * (1.0 - 1e-9))
                | (drops > DROP_TRIGGER_FRACTION * capacity)
            )
            hot = overloaded.any(axis=-1)

            def _prop(_, out_c):
                return overloaded | (adj & out_c[:, :, None]).any(axis=1)

            out_c = jax.lax.fori_loop(0, n, _prop, overloaded)
            capped = (adj & out_c[:, :, None]).any(axis=1) & active

        # --- offered-load clamping (topology_from) ---------------------- #
        with stages.scope("solve"):
            lam_src = jnp.where(src_mask & jnp.isfinite(lam_hat), lam_hat, 0.0)
            total_src = jnp.maximum(lam_src.sum(axis=-1), 1e-12)
            lam0_cold = jnp.where(
                jnp.isfinite(lam0_hat)[:, None],
                lam0_hat[:, None] * (lam_src / total_src[:, None]),
                lam_src,
            )
            lam0 = jnp.where(src_mask, jnp.where(hot[:, None], lam_src, lam0_cold), 0.0)
            colsum = routing0.sum(axis=1)
            inflow = jnp.einsum("bij,bi->bj", routing0, jnp.where(active, lam_hat, 0.0))
            rescale = jnp.where(
                (colsum > 0) & ~capped & (inflow > 1e-12)
                & jnp.isfinite(lam_hat) & (lam_hat > 0),
                lam_hat / jnp.maximum(inflow, 1e-300),
                1.0,
            )
            routing = routing0.astype(dtype) * rescale[:, None, :]
            lam = solve_traffic_batch_jax(lam0, routing)
            lam = jnp.where(active, lam, 0.0)
            solve_bad = (~jnp.isfinite(lam) | (lam < 0)).any(axis=-1)
            lam = jnp.where(jnp.isfinite(lam) & (lam >= 0), lam, 0.0)
            lam0_total = lam0.sum(axis=-1)

        # --- one table pass: E[T_i](k) and Algorithm-1 gains ------------ #
        with stages.scope("table"):
            T = sojourn_table_jax(
                lam.reshape(-1), mu_eff.reshape(-1), k_hi=k_hi,
                group=group.reshape(-1), alpha=alpha.reshape(-1),
                min_k=jnp.ones(b * n, dtype=jnp.int32),
                hot=jnp.where(keyed, st["hot"], jnp.nan).reshape(-1)
                if keyed is not None else None,
                interpret=interpret, force_kernel=force_kernel,
            ).reshape(b, n, k_hi + 1)
            G = lam[..., None] * (T[..., :-1] - T[..., 1:])
            G = jnp.where(jnp.isfinite(T[..., :-1]), G, jnp.inf)

            # Minimal feasible allocation = first finite table column.
            finite = jnp.isfinite(T)
            has_finite = finite.any(axis=-1)
            first = jnp.argmax(finite, axis=-1).astype(jnp.int32)
            k_start = jnp.where(active, jnp.where(has_finite, first, k_hi + 1), 0)
            floor_total = k_start.sum(axis=-1)
            infeasible = solve_bad | (floor_total > k_max)
            if keyed is not None:
                # Keyed operators whose floor the hot partition set:
                # above the pooled M/M/k floor floor(lam/mu) + 1.
                pooled = jnp.floor(lam / mu_eff) + 1.0
                hot_floor = (
                    keyed & active & (k_start.astype(dtype) > pooled)
                ).sum(axis=-1, dtype=jnp.int32)

        # --- Program (4): masked top-R over the gain table -------------- #
        with stages.scope("candidates"):
            budget = jnp.clip(k_max - floor_total, 0, None).astype(jnp.int32)
            cand = _masked_candidates(G, k_start, active)
        with stages.scope("topr"):
            take = topr_ops.gain_topr(
                cand, budget, interpret=interpret, force_kernel=force_kernel
            )
            k4 = k_start + take

        with stages.scope("price"):
            def _et_of(per_op):
                contrib = jnp.where(lam > 0, lam * per_op, 0.0)
                return contrib.sum(axis=-1) / jnp.maximum(lam0_total, 1e-300)

            t_cur_op = _column_select(T, k_cur)
            t4_op = _column_select(T, k4)
            et_cur = _et_of(t_cur_op)
            et4 = _et_of(t4_op)

        # --- gates (vectorized improvement + cost/benefit) -------------- #
        with stages.scope("gates"):
            unchanged = jnp.where(active, k4 == k_cur, True).all(axis=-1)
            improvement = jnp.where(
                jnp.isfinite(et_cur) & (et_cur > 0),
                (et_cur - et4) / et_cur,
                jnp.inf,
            )
            visit = lam / jnp.maximum(lam0_total, 1e-300)[:, None]
            cap4 = k4.astype(dtype) * mu_eff
            if keyed is not None:
                cap4 = _keyed_capacity_jax(
                    st, cap4, mu_eff, jnp.maximum(k4, 1).astype(dtype)
                )
            cap_new = jnp.where(
                active,
                cap4 / jnp.maximum(visit, 1e-12),
                jnp.inf,
            ).min(axis=-1)
            slack = jnp.maximum(cap_new - lam0_total, 1e-9)
            drain = lam0_total * pause / slack
            benefit = jnp.where(jnp.isfinite(et_cur), et_cur - et4, jnp.inf)
            worthwhile = benefit * lam0_total * horizon > (
                (pause + drain) * jnp.maximum(lam0_total, 1.0)
            )
            rebalance = (
                ~unchanged
                & (improvement >= min_improvement)
                & (worthwhile | ~jnp.isfinite(et_cur))
            )

            # --- action selection (precedence mirrors the twin) ------------- #
            complete = (
                jnp.where(active, jnp.isfinite(lam_hat) & jnp.isfinite(mu_hat), True)
                .all(axis=-1)
                & jnp.isfinite(lam0_hat)
            )
            feasible4 = ~infeasible
            code = jnp.where(
                rebalance, _CODE["rebalance"], _CODE["none"]
            )
            code = jnp.where(
                infeasible & ~hot | (solve_bad & hot), _CODE["infeasible"], code
            )
            code = jnp.where(hot & ~solve_bad, _CODE["overloaded"], code)
            code = jnp.where(~complete, _CODE["none"], code)
            apply_mask = complete & ~solve_bad & feasible4 & (
                (hot) | rebalance
            )
            k_next = jnp.where(apply_mask[:, None], k4, k_cur)
            et_target = jnp.where(feasible4, et4, jnp.inf)
        if keyed is not None:
            return code, k_next, et_cur, et_target, apply_mask, hot_floor
        return code, k_next, et_cur, et_target, apply_mask

    return decide


def make_decide_jax(
    static: ControllerStatic,
    params: ControllerParams,
    *,
    k_hi: int | None = None,
    pause_seconds: float | None = None,
    interpret: bool = False,
    force_kernel: bool = False,
    mesh=None,
    compact=None,
):
    """Compile the batched decide into one jit program.

    Returns ``decide(lam_hat, mu_hat, drop_hat, lam0_hat, k_current) ->
    (action_code [B], k_next [B, N], et_cur [B], et_target [B],
    applied [B])`` — the
    complete non-negotiated decision flow: overload masks, offered-load
    clamping, batched Jackson solve, one Erlang table pass
    (``kernels/erlang_c``), Program-4 top-R selection
    (``kernels/gain_topr``), and the vectorized improvement + cost gates.
    Negotiator-owned branches (scale_out / scale_in) need the Python
    lease hook and are deliberately absent: ``params.k_max`` is the
    static per-scenario budget.  Dtype follows JAX's active precision.

    ``mesh`` (a 1-D :class:`jax.sharding.Mesh`) shards the batch axis
    across devices with ``shard_map`` (DESIGN.md §16): every statics
    array and measurement input is partitioned on its leading ``B`` dim,
    each device decides its own lane shard, and — because every op in
    the flow is per-lane — the sharded outputs are bit-identical to the
    unsharded ones.  ``B`` need not divide the device count: lanes are
    padded with inert scenarios (:func:`pad_static`, which provably
    decide ``"none"``) and outputs are sliced back to the real ``B``.

    Semantics mirror the numpy twin with two documented deviations
    (DESIGN.md §14): a singular/unstable traffic solve is detected from
    non-finite or negative solved rates (no eigvalue check inside jit),
    and Program (6) sizing is skipped (it only feeds negotiator leases).

    ``compact`` (``True`` or a :class:`CompactionConfig`) returns the
    trigger-gated sparse decide instead (DESIGN.md §18): signature
    ``decide(lam_hat, mu_hat, drop_hat, lam0_hat, k_current, cache) ->
    ((code, k_next, et_cur, et_target, applied), repriced [B] bool,
    cache')`` with ``decide.init_cache()`` producing the cold cache.
    Outputs are bitwise identical to the dense decide on every tick;
    only the work placement changes.  Under a mesh the compaction runs
    per shard inside ``shard_map`` (no cross-device gather) and the
    cache keeps the padded extent.

    A static graph with a keyed operator (DESIGN.md §20) prices it by its
    hot partition and adds a sixth output, ``hot_floor [B]`` int32: the
    keyed operators per lane whose least stable allocation lies above the
    pooled M/M/k floor ``floor(lam/mu) + 1``.  Graphs without one trace
    none of it.
    """
    import jax
    import jax.numpy as jnp

    b, n = static.batch, static.n
    k_hi = int(k_hi if k_hi is not None else max(int(params.k_max.max()), 1))
    pause = float(
        RebalanceCostModel().pause_cache_miss if pause_seconds is None
        else pause_seconds
    )
    keyed = static.keyed
    body = _make_decide_core(n, k_hi, pause, interpret, force_kernel)

    # The one composition: core, compacted when asked, run per lane shard
    # under a mesh.  B is padded to a multiple of the device count.
    axis, n_shards = (None, 1) if mesh is None else _mesh_axis(mesh)
    b_pad = _padded_batch(b, n_shards)
    pad = b_pad - b
    st_np = _decide_statics(pad_static(static, b_pad), pad_params(params, b_pad))
    st = {k: jnp.asarray(v) for k, v in st_np.items()}
    if compact:
        # Under a mesh each device compacts its own lane shard: no
        # cross-device gather, at the cost of load imbalance (see
        # bucket_ladder).
        b_shard = b_pad // n_shards
        body = _make_compact_decide(body, b_shard, _resolve_ladder(compact, b_shard))
    if mesh is not None:
        from jax.sharding import PartitionSpec as P

        row, lane = P(axis, None), P(axis)
        st_specs = {
            k: P(axis, *((None,) * (v.ndim - 1))) for k, v in st_np.items()
        }
        in_specs = (st_specs, row, row, row, lane, row)
        out_specs = (lane, row, lane, lane, lane) + ((lane,) if keyed else ())
        if compact:
            cache_specs = DecideCache(
                ok=lane, lam=row, mu=row, drop=row, lam0=lane, k=row,
                code=lane, k_next=row, et_cur=lane, et_target=lane,
                applied=lane, hot_floor=lane if keyed else None,
            )
            in_specs += (cache_specs,)
            out_specs = (out_specs, lane, cache_specs)
        body = jax.shard_map(
            body, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
            check_vma=False,
        )

    def decide(lam_hat, mu_hat, drop_hat, lam0_hat, k_current, *cache):
        if not pad:
            return body(st, lam_hat, mu_hat, drop_hat, lam0_hat, k_current, *cache)
        # Inert lanes in, real lanes out; a compact cache keeps the padded
        # extent (it is a shard_map operand).
        dtype = lam_hat.dtype
        lam_hat = jnp.concatenate([lam_hat, jnp.zeros((pad, n), dtype)])
        mu_hat = jnp.concatenate([mu_hat, jnp.ones((pad, n), dtype)])
        drop_hat = jnp.concatenate([drop_hat, jnp.zeros((pad, n), dtype)])
        lam0_hat = jnp.concatenate([lam0_hat, jnp.zeros(pad, dtype)])
        k_current = jnp.concatenate(
            [k_current, jnp.zeros((pad, n), k_current.dtype)]
        )
        res = body(st, lam_hat, mu_hat, drop_hat, lam0_hat, k_current, *cache)
        if compact:
            out, repriced, cache = res
            return tuple(o[:b] for o in out), repriced[:b], cache
        return tuple(o[:b] for o in res)

    decide = stages.recorded(jax.jit(decide))
    if compact:
        decide.init_cache = lambda dtype=None: init_decide_cache(
            b_pad, n, dtype=dtype, keyed=keyed
        )
    return decide


class ControllerState(NamedTuple):
    """The fused loop's scan carry as one donated pytree (DESIGN.md §16).

    ``tick`` (int32 scalar) is the index of the *next* control window,
    which makes the state resumable: :meth:`FusedLoop.run` advances any
    number of ticks from it, and a checkpoint -> restore -> resume
    sequence is bit-identical to a straight-through run
    (tests/test_checkpoint.py).  Under a device mesh the batch extent is
    the padded ``B`` (a multiple of the device count); ``fstate`` is the
    flat ForecastState tuple when the loop is proactive, else ``()``.
    ``acc`` holds the post-warmup run aggregates in BatchQueueSim order:
    (offered, served, dropped, ext_admitted, ext_offered, q_int, q_max).
    """

    q: Any  # [B, N] queue backlog
    served_prev: Any  # [B, N] last-step completions (the routing delay line)
    k: Any  # [B, N] int32 allocation in force
    acc: tuple  # post-warmup aggregates (7-tuple, see above)
    tick: Any  # int32 scalar: next control-window index
    fstate: tuple = ()  # flat ForecastState when proactive


class FusedLoop:
    """One compiled measure -> model -> rebalance program over the horizon.

    ``loop(k0)`` runs the whole horizon and returns the legacy output
    dict (the pre-refactor ``run(k0)`` surface).  The chunked surface —
    ``state = loop.init(k0)`` then ``state, out = loop.run(state,
    ticks)`` — exposes the same program with the carry as an explicit
    :class:`ControllerState`.  The state argument is **donated** to XLA
    on every ``run`` call (``donate_argnums=0``), so long-horizon loops
    update their ``[B, N]`` buffers in place instead of reallocating;
    the caller must keep using the returned state, never the one it
    passed in.  Compiled executables are cached per chunk length.
    """

    def __init__(self, n_ticks: int, init_fn, build_fn):
        self.n_ticks = n_ticks
        self._init_fn = init_fn
        self._build = build_fn
        self._compiled: dict = {}

    def init(self, k0) -> ControllerState:
        """Fresh tick-0 state (k0 is [B, N]; auto-padded under a mesh)."""
        return self._init_fn(k0)

    def run(self, state: ControllerState, ticks: int | None = None):
        """Advance ``ticks`` windows (default: to the end of the horizon).

        Returns ``(new_state, out)`` where ``out`` is the output dict for
        the chunk just run (per-tick stacks cover only this chunk; the
        run aggregates come from ``new_state.acc`` and therefore cover
        everything since tick 0).
        """
        done = int(state.tick)
        if ticks is None:
            ticks = self.n_ticks - done
        ticks = int(ticks)
        if not 0 < ticks <= self.n_ticks - done:
            raise ValueError(
                f"cannot run {ticks} ticks from tick {done} "
                f"(horizon {self.n_ticks})"
            )
        fn = self._compiled.get(ticks)
        if fn is None:
            fn = self._compiled[ticks] = self._build(ticks)
        return fn(state)

    def __call__(self, k0) -> dict:
        _, out = self.run(self.init(k0), self.n_ticks)
        return out


def make_fused_loop(
    arrays,
    static: ControllerStatic,
    params: ControllerParams,
    *,
    steps_per_tick: int,
    k_hi: int | None = None,
    warmup_seconds: float | None = None,
    interpret: bool = False,
    force_kernel: bool = False,
    proactive=None,
    mesh=None,
    compact=None,
):
    """Fuse simulate -> measure -> decide -> apply into ONE jit program.

    ``arrays`` is the :class:`~repro.streaming.batchsim.BatchArrays`
    bundle; the returned :class:`FusedLoop` lax.scans the horizon: each
    scan step advances one control window through the batch simulator's
    step function (``streaming.batchsim.window_step_fn`` — the same
    bounded-queue kernel path the standalone sim uses), derives the
    window's synthetic measurement (§13 Little's-law surface), runs the
    compiled decide, and applies the allocation — no Python between
    ticks.  ``loop(k0)`` yields per-tick stacked decisions plus the
    post-warmup whole-run aggregates (the BatchSimResult surface);
    ``loop.init`` / ``loop.run`` expose the donated, resumable
    :class:`ControllerState` carry.

    ``proactive`` (a :class:`~repro.forecast.mpc.MPCConfig`) extends the
    scan carry with the forecast state (DESIGN.md §15): each tick also
    advances the rate predictors, runs the MPC planner from the live
    queue backlog, and — where the confidence gate is open, no operator
    is overloaded, and some candidate meets T_max — commits the plan over
    the reactive decide.  The whole predict -> simulate -> price ->
    commit step stays inside the one ``lax.scan`` (outputs gain
    ``mpc_used`` / ``confident`` per tick).

    ``mesh`` (a 1-D :class:`jax.sharding.Mesh`, e.g. from
    :func:`repro.distributed.sharding.fleet_mesh`) shards the batch axis
    of the WHOLE loop across devices with ``shard_map`` (DESIGN.md §16):
    arrivals, statics, the carry, and the per-tick outputs are
    partitioned on ``B``, and each device scans its own lane shard —
    every op in the tick is per-lane, so the sharded loop is
    bit-identical to the unsharded one (tests/test_mesh_control.py).
    ``B`` is auto-padded to a multiple of the device count with inert
    lanes (:func:`pad_static` / ``BatchArrays.pad_batch``) and all
    outputs are sliced back to the real ``B``; only the carried
    ``ControllerState`` keeps the padded extent.

    ``compact`` (``True`` or a :class:`CompactionConfig`) splits every
    tick into the cheap O(B*N) trigger scan and the bucketed compacted
    decide (DESIGN.md §18): lanes whose decide inputs are bitwise
    unchanged since their last pricing replay the cached row; triggered
    lanes are gathered to the smallest static ladder width and priced
    there.  With ``proactive`` the MPC planner likewise prices only the
    commit-eligible lanes.  Outputs are bitwise identical to the dense
    loop; the per-tick output dict gains a ``"repriced" [ticks, B]``
    work-placement diagnostic (NOT part of the decision surface — chunk
    boundaries reset the cache, so a resumed run's ``repriced`` differs
    from a straight-through run's even though every decision matches).
    The memo cache rides only the in-chunk ``lax.scan`` carry, never
    :class:`ControllerState`: checkpoints stay layout-independent and a
    restore re-prices every lane once (same outputs, more work).  Under
    a mesh each device compacts its own shard inside ``shard_map`` —
    no cross-device gather (see
    :func:`repro.distributed.sharding.bucket_ladder` for the imbalance
    tradeoff).

    Negotiated scenarios cannot ride in here (leases are Python): callers
    keep those on the numpy twin path.
    """
    import jax
    import jax.numpy as jnp
    from jax import lax

    from ..streaming.batchsim import composed_wait as _composed_wait
    from ..streaming.batchsim import refuse_keyed, window_step_fn

    if static.keyed:
        refuse_keyed([
            name for names, row in zip(static.names, static.hot)
            for name, h in zip(names, row) if not np.isnan(h)
        ])
    b_real, n = static.batch, static.n
    dt = float(arrays.dt)
    steps = arrays.steps
    n_ticks = steps // steps_per_tick
    k_hi_res = int(k_hi if k_hi is not None else max(int(params.k_max.max()), 1))
    if compact:
        compact_cfg = (
            compact if isinstance(compact, CompactionConfig) else CompactionConfig()
        )
    else:
        compact_cfg = None

    if mesh is not None:
        axis, n_shards = _mesh_axis(mesh)
        b_pad = _padded_batch(b_real, n_shards)
        static = pad_static(static, b_pad)
        params = pad_params(params, b_pad)
        arrays = arrays.pad_batch(b_pad)
    b = static.batch

    decide_core = _make_decide_core(
        n, k_hi_res, float(RebalanceCostModel().pause_cache_miss),
        interpret, force_kernel,
    )
    window = window_step_fn(interpret=interpret, force_kernel=force_kernel)
    # Every [B, ...]-leading array rides in one of two dicts so the mesh
    # path can pass them as explicit sharded operands (one P(axis, ...)
    # rule per leaf).  They are arguments of the compiled run, never
    # closure constants, which XLA would embed in the program: at fleet
    # extent the arrivals alone are hundreds of MB.
    t_max_np = np.nan_to_num(params.t_max, nan=np.inf)
    st_np = _decide_statics(static, params)
    sim_np = {
        "mu": arrays.mu,  # reference-class priors
        "group": arrays.group,
        "alpha": arrays.alpha,
        "cap_queue": arrays.cap_queue,
        "routing": arrays.routing,
        "speed": static.speed,
        "t_max": t_max_np,
        # §17 Allen-Cunneen inputs for the stationary-wait term of the
        # window measurement (ones = the M/M/k prior when unset).
        "ca2": np.ones((arrays.batch, arrays.n)) if arrays.ca2 is None else arrays.ca2,
        "cs2": np.ones((arrays.batch, arrays.n)) if arrays.cs2 is None else arrays.cs2,
    }
    # Pre-sliced per-tick arrival chunks + warmup masks.
    ext_np = arrays.ext[: n_ticks * steps_per_tick].reshape(
        n_ticks, steps_per_tick, b, n
    )
    warm_np = (
        (np.arange(n_ticks * steps_per_tick) >= arrays.warmup_steps)
        .astype(np.float64)
        .reshape(n_ticks, steps_per_tick)
    )
    if mesh is None:
        place = jnp.asarray
    else:
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        def _lane_spec(v):
            nd = getattr(v, "ndim", 0)
            return P(axis, *((None,) * (nd - 1))) if nd >= 1 else P()

        st_specs = {k_: _lane_spec(v) for k_, v in st_np.items()}
        sim_specs = {k_: _lane_spec(v) for k_, v in sim_np.items()}
        data_specs = (P(None, None, axis, None), P(None, None))

        def place(x, spec=None):
            # Each device receives only its own lane shard.
            spec = _lane_spec(x) if spec is None else spec
            return jax.device_put(x, NamedSharding(mesh, spec))

    st = {k_: place(v) for k_, v in st_np.items()}
    sim = {k_: place(v) for k_, v in sim_np.items()}
    if mesh is None:
        ext_r, warm_r = place(ext_np), place(warm_np)
    else:
        ext_r, warm_r = place(ext_np, data_specs[0]), place(warm_np, data_specs[1])
    data = (st, sim, ext_r, warm_r)
    # A window counts as warm when it *starts* past the warmup boundary,
    # compared in seconds like the twin runner (t0 >= warmup), not in
    # rounded steps — the run-accumulator gating above stays step-based
    # to match BatchQueueSim exactly.
    warmup_s = (
        arrays.warmup_steps * dt if warmup_seconds is None else float(warmup_seconds)
    )
    tick_warm_r = jnp.asarray(
        (np.arange(n_ticks) * steps_per_tick * dt >= warmup_s).astype(np.float64)
    )
    span = steps_per_tick * dt
    t_max_real = jnp.asarray(t_max_np[:b_real])

    if proactive is not None:
        from ..forecast.mpc import forecast_init_state, forecast_step, mpc_plan

        topr_ops = _topr_ops()
        fstate0 = forecast_init_state(b, n, proactive, xp=jnp, dtype=sim["mu"].dtype)

        def topr(c, bud):
            return topr_ops.gain_topr(
                c, bud, interpret=interpret, force_kernel=force_kernel
            )

    def capacity_of(sim_d, k):
        mu_d, alpha_d = sim_d["mu"], sim_d["alpha"]
        kf = jnp.maximum(k.astype(mu_d.dtype), 0.0)
        eff = 1.0 / (1.0 + alpha_d * (kf - 1.0))
        spd = mu_d * sim_d["speed"]
        return jnp.where(sim_d["group"], spd * kf * eff, spd * kf)

    def chunk(ticks, st_d, sim_d, ext_d, warm_d, state):
        """Advance ``ticks`` windows from ``state`` — one lax.scan over
        tick indices (gathered from the pre-sliced arrival chunks, so a
        resumed chunk reads exactly the windows a straight-through run
        would).  Runs on whatever batch extent its operands carry: the
        full ``B`` under plain jit, or one device's shard under
        ``shard_map``."""
        mu = sim_d["mu"]
        mu_eff = sim_d["mu"] * sim_d["speed"]
        active = st_d["active"]
        t_max = sim_d["t_max"]
        alpha = sim_d["alpha"]
        group = sim_d["group"]

        bb = active.shape[0]  # this chunk's batch extent (shard under mesh)
        if compact_cfg is not None:
            decide_c = _make_compact_decide(
                decide_core, bb, _resolve_ladder(compact_cfg, bb)
            )
            mpc_ladder = _resolve_ladder(compact_cfg, bb)

        def tick_fn(carry, t_idx):
            if compact_cfg is not None:
                carry, dcache = carry[:-1], carry[-1]
            if proactive is not None:
                q, served_prev, k, acc, fstate = carry
            else:
                q, served_prev, k, acc = carry
            ext_chunk = lax.dynamic_index_in_dim(ext_d, t_idx, 0, keepdims=False)
            warm_chunk = lax.dynamic_index_in_dim(warm_d, t_idx, 0, keepdims=False)
            with stages.scope("window"):
                cap_serve_dt = capacity_of(sim_d, k) * dt
                out = window(
                    q, served_prev, ext_chunk, warm_chunk, cap_serve_dt,
                    sim_d["cap_queue"], sim_d["routing"],
                )
            (q1, served_prev1, offered, served_sum, dropped, ext_adm, ext_off,
             q_int, q_max, w_offered, w_served, w_dropped, w_ext_adm, w_ext_off,
             w_q_int) = out
            # Window measurement (ungated): the §13 synthetic snapshot.
            with stages.scope("measure"):
                lam_hat = offered / span
                drop_hat = dropped / span
                admitted = jnp.maximum(lam_hat - drop_hat, 0.0)
                q_mean = q_int / steps_per_tick
                # §17 composed wait — the same helper (and op order) as the
                # numpy twin's window measurement, so twin == jit holds on
                # the measured-sojourn surface too.
                wait = _composed_wait(
                    q_mean, admitted, dt, span, k, mu, group, alpha,
                    sim_d["speed"], sim_d["ca2"], sim_d["cs2"], xp=jnp,
                )
                cap = capacity_of(sim_d, k)
                svc = jnp.where(
                    group,
                    jnp.where(cap > 0, 1.0 / cap, jnp.inf),
                    1.0 / mu_eff,
                )
                lam0 = jnp.maximum(ext_adm / span, 0.0)
                contrib = jnp.where(admitted > 0, admitted * (wait + svc), 0.0)
                sojourn = jnp.where(
                    lam0 > 0, contrib.sum(axis=-1) / jnp.maximum(lam0, 1e-300), jnp.nan
                )
            if compact_cfg is not None:
                dout, repriced, dcache = decide_c(
                    st_d, lam_hat, mu, drop_hat, lam0, k, dcache
                )
                code, k_next, et_cur, et_target, applied = dout
            else:
                code, k_next, et_cur, et_target, applied = decide_core(
                    st_d, lam_hat, mu, drop_hat, lam0, k
                )
            if proactive is not None:
                # Forecast plane: advance the predictors on this window's
                # measured rates, plan over the horizon from the live
                # backlog, and commit where the gate is open and the §11
                # trigger is quiet (the trigger always outranks the plan).
                fstate, lam_pred, conf = forecast_step(
                    fstate, lam_hat, active, proactive, xp=jnp
                )
                # Inline recompute of the trigger + completeness (decide
                # owns them internally; same formulas as the twin's
                # gating).  Computed BEFORE the planner so the compacted
                # path can restrict pricing to the commit-eligible lanes.
                k_floor = jnp.maximum(k.astype(jnp.int32), 1).astype(lam_hat.dtype)
                eff_t = 1.0 / (1.0 + alpha * (k_floor - 1.0))
                capacity = jnp.where(
                    group, mu_eff * k_floor * eff_t, mu_eff * k_floor
                )
                valid = jnp.isfinite(lam_hat) & jnp.isfinite(mu_eff) & (mu_eff > 0)
                drops_t = jnp.nan_to_num(drop_hat, nan=0.0)
                hot = (
                    valid & active & (
                        (lam_hat >= capacity * (1.0 - 1e-9))
                        | (drops_t > DROP_TRIGGER_FRACTION * capacity)
                    )
                ).any(axis=-1)
                complete = (
                    jnp.where(active, jnp.isfinite(lam_hat) & jnp.isfinite(mu), True)
                    .all(axis=-1)
                    & jnp.isfinite(lam0)
                )
                plan_kw = dict(
                    span=span, cfg=proactive, k_hi=k_hi_res, xp=jnp, topr=topr,
                )
                if compact_cfg is not None:
                    # A plan can only be committed where use (below) is
                    # open, and use is a subset of this eligibility mask
                    # — so pricing only these lanes is exact (mpc_plan
                    # is per-lane throughout).  any_ok defaults False
                    # (reactive fallback) on unpriced lanes; their
                    # k_plan / E[T] slots are never read.
                    eligible = conf & complete & ~hot & jnp.isfinite(t_max)

                    def price_mpc(g):
                        kp, ok, eh, ep, _ = mpc_plan(
                            lam_pred[g], q1[g], k[g], mu=mu[g],
                            group=st_d["group"][g], alpha=alpha[g],
                            speed=sim_d["speed"][g], active=active[g],
                            src_mask=st_d["src"][g],
                            cap_queue=sim_d["cap_queue"][g], t_max=t_max[g],
                            k_max=st_d["k_max"][g], **plan_kw,
                        )
                        return kp, ok, eh, ep

                    inf_l = jnp.full(bb, jnp.inf, dtype=lam_hat.dtype)
                    k_plan, any_ok, et_hold, et_plan = _bucketed(
                        mpc_ladder, bb, eligible, price_mpc,
                        (jnp.where(active, k, 0), jnp.zeros(bb, dtype=bool),
                         inf_l, inf_l),
                    )
                else:
                    k_plan, any_ok, et_hold, et_plan, _need = mpc_plan(
                        lam_pred, q1, k, mu=mu, group=st_d["group"],
                        alpha=alpha, speed=sim_d["speed"], active=active,
                        src_mask=st_d["src"], cap_queue=sim_d["cap_queue"],
                        t_max=t_max, k_max=st_d["k_max"], **plan_kw,
                    )
                use = conf & any_ok & complete & ~hot & jnp.isfinite(t_max)
                changed = use & (
                    (k_plan.astype(jnp.int32) != k) & active
                ).any(axis=-1)
                k_next = jnp.where(
                    use[:, None],
                    jnp.where(active, k_plan.astype(jnp.int32), k),
                    k_next,
                )
                code = jnp.where(
                    use,
                    jnp.where(changed, _CODE["proactive"], _CODE["none"]),
                    code,
                )
                applied = jnp.where(use, changed, applied)
                et_cur = jnp.where(use, et_hold, et_cur)
                et_target = jnp.where(use, et_plan, et_target)
            with stages.scope("measure"):
                new_acc = tuple(
                    a + w for a, w in zip(
                        acc[:6],
                        (w_offered, w_served, w_dropped, w_ext_adm, w_ext_off,
                         w_q_int),
                    )
                ) + (jnp.maximum(acc[6], q_max),)
                ys = (code, k_next, sojourn, et_cur, et_target, applied)
            if proactive is not None:
                ys = ys + (use, conf)
            new_carry = (q1, served_prev1, k_next, new_acc)
            if proactive is not None:
                new_carry = new_carry + (fstate,)
            if compact_cfg is not None:
                ys = ys + (repriced,)
                new_carry = new_carry + (dcache,)
            return new_carry, ys

        carry0 = (state.q, state.served_prev, state.k, state.acc)
        if proactive is not None:
            carry0 = carry0 + (state.fstate,)
        if compact_cfg is not None:
            # The memo cache starts COLD every chunk (it is not part of
            # ControllerState): the chunk's first tick prices every lane,
            # which purity makes output-invisible — this is what keeps
            # checkpoints layout-independent (§18).
            carry0 = carry0 + (init_decide_cache(bb, n, dtype=mu.dtype),)
        xs = state.tick + jnp.arange(ticks, dtype=state.tick.dtype)
        final, ys = lax.scan(tick_fn, carry0, xs)
        new_state = ControllerState(
            q=final[0], served_prev=final[1], k=final[2], acc=final[3],
            tick=state.tick + ticks,
            fstate=final[4] if proactive is not None else (),
        )
        return new_state, ys

    def init_fn(k0) -> ControllerState:
        k0 = np.asarray(k0)
        if k0.shape[0] < b:  # mesh padding: inert lanes hold 0 processors
            k0 = np.concatenate(
                [k0, np.zeros((b - k0.shape[0], n), dtype=k0.dtype)]
            )
        # Each leaf gets its OWN buffer: the run step donates the whole
        # state, and XLA rejects the same buffer donated twice.  Under a
        # mesh each leaf is placed lane-sharded, like the loop's data.
        def zeros(*shape):
            return place(np.zeros(shape))

        acc0 = (zeros(b, n), zeros(b, n), zeros(b, n), zeros(b), zeros(b),
                zeros(b, n), zeros(b, n))
        fstate = ()
        if proactive is not None:
            fstate = tuple(place(np.asarray(x)) for x in fstate0)  # copies
        return ControllerState(
            q=zeros(b, n), served_prev=zeros(b, n),
            k=place(k0.astype(np.int32)),
            acc=acc0, tick=place(np.asarray(0, dtype=np.int32)),
            fstate=fstate,
        )

    if mesh is not None:
        state_specs = jax.tree.map(
            _lane_spec, init_fn(np.zeros((b_real, n), dtype=np.int64))
        )
        ys_lane, ys_row = P(None, axis), P(None, axis, None)
        ys_specs = (ys_lane, ys_row, ys_lane, ys_lane, ys_lane, ys_lane)
        if proactive is not None:
            ys_specs = ys_specs + (ys_lane, ys_lane)
        if compact_cfg is not None:
            ys_specs = ys_specs + (ys_lane,)

    def build(ticks: int):
        def stepped(data_, state):
            return chunk(ticks, *data_, state)

        if mesh is not None:
            stepped = jax.shard_map(
                stepped,
                mesh=mesh,
                in_specs=((st_specs, sim_specs) + data_specs, state_specs),
                out_specs=(state_specs, ys_specs),
                check_vma=False,
            )

        def run(data_, state):
            tick0 = state.tick
            new_state, ys = stepped(data_, state)
            per_tick = tuple(y[:, :b_real] for y in ys)
            codes, k_hist, sojourns, et_cur, et_target, applied = per_tick[:6]
            # Warm flags + miss counting stay OUTSIDE shard_map: they are
            # per-tick scalars / cross-chunk reductions, not per-lane work.
            warm_flags = lax.dynamic_slice_in_dim(tick_warm_r, tick0, ticks)
            miss = (
                (sojourns > t_max_real[None, :]) & (warm_flags[:, None] > 0)
            ).sum(axis=0)
            acc = new_state.acc
            out = {
                "codes": codes, "k": k_hist, "sojourn": sojourns,
                "et_cur": et_cur, "et_target": et_target, "applied": applied,
                "miss": miss, "warm_windows": (warm_flags > 0).sum(),
                "k_final": new_state.k[:b_real], "q_final": new_state.q[:b_real],
                "offered": acc[0][:b_real], "served": acc[1][:b_real],
                "dropped": acc[2][:b_real],
                "ext_admitted": acc[3][:b_real], "ext_offered": acc[4][:b_real],
                "q_int": acc[5][:b_real], "q_max": acc[6][:b_real],
            }
            if proactive is not None:
                out["mpc_used"] = per_tick[6]
                out["confident"] = per_tick[7]
            if compact_cfg is not None:
                out["repriced"] = per_tick[-1]
            return new_state, out

        jitted = stages.recorded(jax.jit(run, donate_argnums=1))
        return lambda state: jitted(data, state)

    return FusedLoop(n_ticks, init_fn, build), n_ticks
