"""Expected-energy planning: checkpoint intervals and failure-time risk.

The paper studies single failure instants; at fleet scale the operator needs
*expectations* over failure-time distributions.  This module extends the
paper's model (all in vectorized JAX, reusing the Algorithm-1 engine):

* ``expected_savings`` — E[saving] and the wait-action distribution over a
  failure-time grid (failure uniform in the checkpoint interval — the
  classical renewal assumption);
* ``optimal_checkpoint_interval`` — a Young/Daly-style first-order optimum
  extended with the *energy* objective: checkpoints cost energy
  (T_ckpt·P_ckpt) and re-execution costs energy (E[t_fail−t_ckpt]·P_comp),
  while longer re-execution also *increases* survivors' harvestable waits
  (the paper's effect).  The optimum trades checkpoint energy against
  re-execution energy *net of* the strategy savings — checkpointing less
  often is optimal in energy terms than in time terms whenever the paper's
  strategies recover a large fraction of the wait energy.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import energy_model as em
from repro.core import strategies
from repro.core.characterization import MachineProfile

__all__ = [
    "ExpectedSavings",
    "CheckpointPlan",
    "advance_checkpoint_sawtooth",
    "balanced_span",
    "timer_checkpoint_count",
    "floor_mod",
    "checkpoint_plan",
    "move_ahead_plan",
    "expected_savings",
    "optimal_checkpoint_interval",
]


def _ns(*arrays):
    """numpy/jnp namespace dispatch: jnp iff any input is a jax array (incl.
    tracers), so the same closed forms serve the float64 event-simulator path
    and the jitted sweep engine."""
    return jnp if any(isinstance(a, jax.Array) for a in arrays) else np


# ---------------------------------------------------------------------------
# analytic phase geometry (shared by simulator.py and sweep.py)
# ---------------------------------------------------------------------------

def _floor_quotient(q, period):
    """``(floor(q / period), q - j * period)`` for ``q >= 0``.  A division
    that is not correctly rounded (the TPU's) can land the quotient one off
    near an integer; the remainder then leaves ``[0, period)`` and the
    quotient is stepped back."""
    xp = _ns(q, period)
    j = xp.floor(q / period)
    r = q - j * period
    low, high = r < 0.0, r >= period
    j = xp.where(low, j - 1.0, xp.where(high, j + 1.0, j))
    return j, xp.where(low | high, q - j * period, r)


def floor_mod(x, y):
    """``x mod y`` (floor convention, ``y > 0``) as exact as ``jnp.mod`` on
    an IEEE backend, also where the backend's division and remainder are
    approximate (Mosaic on TPU).  The product ``q * y`` is split exactly
    (Dekker; no fused multiply-add needed), so ``x - q * y`` rounds once."""
    xp = _ns(x, y)
    x, y = xp.asarray(x), xp.asarray(y)
    split = 4097.0 if x.dtype == xp.float32 else 134217729.0   # 2^s + 1
    q = xp.floor(x / y)

    def halves(a):
        c = split * a
        hi = c - (c - a)
        return hi, a - hi

    p = q * y
    q_hi, q_lo = halves(q)
    y_hi, y_lo = halves(y)
    e = ((q_hi * y_hi - p) + q_hi * y_lo + q_lo * y_hi) + q_lo * y_lo
    r = (x - p) - e
    return xp.where(r < 0.0, r + y, xp.where(r >= y, r - y, r))

def advance_checkpoint_sawtooth(age0, delta, interval, dur):
    """Advance a timer-checkpoint sawtooth by ``delta`` wall seconds.

    Pre-failure execution model (paper §4.1): the node executes at fa and a
    transparent timer checkpoint of duration ``dur`` fires whenever the wall
    age since the last checkpoint end reaches ``interval``.  Closed form — no
    event stepping — and broadcastable over any batch shape.

    Failure instants landing strictly inside a checkpoint are snapped forward
    to that checkpoint's end (age 0): the simulator state ``(exec_rem,
    ckpt_age)`` cannot represent a half-written checkpoint, and an FT runtime
    quiesces control decisions during a checkpoint anyway.  ``delta_eff``
    reports the possibly-snapped instant.

    Returns ``(age, work, n_fired, delta_eff)``:
      age       wall seconds since the last checkpoint end at ``delta_eff``
      work      fa-seconds of execution completed in ``[0, delta_eff]``
      n_fired   checkpoints completed in ``[0, delta_eff]``
      delta_eff the evaluated failure instant (``>= delta``, ``< delta + dur``)
    """
    xp = _ns(age0, delta, interval, dur)
    age0, delta = xp.asarray(age0), xp.asarray(delta)
    first = interval - age0                 # wall time of the first timer fire
    period = interval + dur
    fired = delta >= first
    q = xp.maximum(delta - first, 0.0)
    # j: index of the last fire <= delta; r: time since that fire began
    j, r = _floor_quotient(q, period)
    mid = fired & (r < dur)                 # failure lands inside a checkpoint
    n_fired = xp.where(fired, j + 1.0, 0.0)
    age = xp.where(fired, xp.where(mid, 0.0, r - dur), age0 + delta)
    delta_eff = xp.where(mid, first + j * period + dur, delta)
    work = delta_eff - n_fired * dur
    return age, work, n_fired, delta_eff


def balanced_span(age0, span, interval, dur):
    """Split a balanced-execution span into (work, checkpoint) wall time.

    A node executing at fa with timer checkpoints (age ``age0`` at the span
    start) spends ``span`` wall seconds either working or checkpointing —
    there are no waits in balanced execution, so the two partition the span
    exactly.  Unlike ``advance_checkpoint_sawtooth`` this does *not* snap
    mid-checkpoint endpoints forward: a span ending inside a checkpoint
    counts the partial checkpoint time, so the returned pair always sums to
    ``span``.  The renewal engines integrate inter-failure and end-of-run
    spans with it:  ``energy = work * p_comp[0] + ckpt * p_ckpt[0]``.

    Returns ``(work, ckpt_time)``; broadcasts over any batch shape.
    """
    xp = _ns(age0, span, interval, dur)
    age0, span = xp.asarray(age0), xp.asarray(span)
    first = interval - age0                  # wall time of the first timer fire
    period = interval + dur
    q = xp.maximum(span - first, 0.0)
    # j: completed fires before the span end; r: time since the last began
    j, r = _floor_quotient(q, period)
    ckpt = xp.where(span > first, j * dur + xp.minimum(r, dur), 0.0)
    return span - ckpt, ckpt


@dataclasses.dataclass(frozen=True)
class CheckpointPlan:
    """Decision-time checkpoint forecast for the intervention interval.

    ``n_timer``/``n_ckpt`` carry a trailing ladder axis (..., F); the rest
    share the node batch shape.  ``n_ckpt = n_timer + planned move-ahead``.
    """

    n_timer: Any           # timer checkpoints during the (stretched) compute phase
    n_ckpt: Any            # + the planned move-ahead checkpoint
    plan_move: Any         # bool: move-ahead checkpoint planned at block time
    age_at_block_fa: Any   # checkpoint age when blocking (fa timeline)
    wait_at_block_fa: Any  # wait duration at block (fa timeline)


def timer_checkpoint_count(exec_rem, age, beta, interval, eps: float = 1e-9):
    """Closed-form count of timer checkpoints firing during a (stretched)
    compute phase:  ``max(0, ceil((exec_rem*beta + age - interval)/interval
    - eps))`` — the checkpoint-duration terms cancel (see
    ``checkpoint_plan``).  ``beta`` may be the (F,) ladder (broadcast
    against ``exec_rem[..., None]``) or one scalar level; the single
    definition keeps ``checkpoint_plan`` and the device renewal engine's
    per-level fold bit-identical.
    """
    xp = _ns(exec_rem, age, beta)
    return xp.maximum(
        0.0, xp.ceil((exec_rem * beta + age - interval) / interval - eps))


def checkpoint_plan(
    exec_rem,
    age,
    t_failed,
    *,
    interval,
    dur,
    beta,
    gamma,
    move_ahead,
    move_frac,
    eps: float = 1e-9,
):
    """Closed-form checkpoint plan, identical to the event engine's timers.

    Per (node, ladder level): timer ``k`` fires at wall ``(interval - age) +
    k*(interval + dur*gamma_l)`` and pushes the block time by ``dur*gamma_l``;
    the count of fires before the block admits the closed form

        n_timer = max(0, ceil((exec_rem*beta_l + age - interval)/interval))

    (the checkpoint-duration terms cancel).  The move-ahead is FT policy
    decided once on the un-stretched fa timeline — paper §4.1: checkpoint
    before blocking if the last checkpoint is older than ``move_frac *
    interval`` and the wait is long enough to fit it.

    Inputs broadcast over any node batch shape; ``beta``/``gamma`` are the
    (F,) ladder arrays.  Works on numpy float64 (event simulator) and traced
    jnp float32 (sweep engine) alike.
    """
    xp = _ns(exec_rem, age, t_failed, beta)
    exec_rem, age, t_failed = (xp.asarray(a) for a in (exec_rem, age, t_failed))
    n_timer = timer_checkpoint_count(
        exec_rem[..., None], age[..., None], beta, interval, eps)
    plan_move, age_at_block_fa, wait_at_block_fa = move_ahead_plan(
        exec_rem, age, t_failed, n_timer[..., 0], interval=interval, dur=dur,
        move_ahead=move_ahead, move_frac=move_frac)
    n_ckpt = n_timer + xp.where(plan_move, 1.0, 0.0)[..., None]
    return CheckpointPlan(
        n_timer=n_timer,
        n_ckpt=n_ckpt,
        plan_move=plan_move,
        age_at_block_fa=age_at_block_fa,
        wait_at_block_fa=wait_at_block_fa,
    )


def move_ahead_plan(exec_rem, age, t_failed, n0, *, interval, dur,
                    move_ahead, move_frac):
    """The move-ahead half of ``checkpoint_plan`` given the fa-level timer
    count ``n0`` (``timer_checkpoint_count`` at ``beta[0]``).  Split out so
    the Pallas renewal kernel, which holds no trailing ladder axis, runs the
    same operations.  Returns ``(plan_move, age_at_block_fa,
    wait_at_block_fa)``."""
    xp = _ns(exec_rem, age, t_failed, n0)
    wait_at_block_fa = t_failed - (exec_rem + n0 * dur)
    # age at block: if a timer fired during the compute phase the age restarts
    # from its end.
    last_timer_end = xp.where(
        n0 > 0,
        (interval - age) + (n0 - 1.0) * (interval + dur) + dur,
        -age,
    )
    age_at_block_fa = exec_rem + n0 * dur - last_timer_end
    plan_move = (
        xp.asarray(move_ahead, bool)
        & (age_at_block_fa > move_frac * interval)
        & (wait_at_block_fa > dur)
    )
    return plan_move, age_at_block_fa, wait_at_block_fa


@dataclasses.dataclass(frozen=True)
class ExpectedSavings:
    mean_saving_j: float
    mean_saving_pct: float
    p_sleep: float
    p_min_freq: float
    p_comp_change: float
    grid: int


def expected_savings(
    profile: MachineProfile,
    *,
    ckpt_interval_s: float,
    t_down_s: float,
    t_restart_s: float,
    comp_to_block_s: float,
    t_ckpt_s: float = 120.0,
    wait_mode: int = 0,
    grid: int = 512,
) -> ExpectedSavings:
    """E[saving] for one survivor when the failure instant is uniform over
    the failed node's checkpoint interval (re-execution ~ U[0, interval])."""
    reexec = jnp.linspace(0.0, ckpt_interval_s, grid)
    t_failed = t_down_s + t_restart_s + reexec + comp_to_block_s
    d = strategies.evaluate_strategies_profile(
        profile,
        jnp.full((grid,), comp_to_block_s),
        t_failed,
        jnp.zeros((grid,)),
        t_ckpt_s,
        jnp.full((grid,), wait_mode, jnp.int32),
    )
    actions = np.asarray(d.wait_action)
    return ExpectedSavings(
        mean_saving_j=float(jnp.mean(d.saving)),
        mean_saving_pct=float(jnp.mean(d.saving_pct)),
        p_sleep=float(np.mean(actions == em.WaitAction.SLEEP)),
        p_min_freq=float(np.mean(actions == em.WaitAction.MIN_FREQ)),
        p_comp_change=float(np.mean(np.asarray(d.comp_changed))),
        grid=grid,
    )


def _expected_savings_grid(
    profile: MachineProfile,
    intervals: np.ndarray,
    *,
    t_down_s: float,
    t_restart_s: float,
    comp_to_block_s: float,
    t_ckpt_s: float,
    wait_mode: int,
    grid: int,
) -> list:
    """``expected_savings`` for a whole interval batch in ONE jitted
    dispatch: the (interval, failure-phase) grid is (I, G) and Algorithm 1
    broadcasts over it exactly as it does over the sweep engine's batches.
    Returns one ``ExpectedSavings`` per interval (same reductions as the
    scalar path, per row)."""
    ivals = jnp.asarray(intervals, jnp.float32)[:, None]          # (I, 1)
    frac = jnp.linspace(0.0, 1.0, grid)[None, :]                  # (1, G)
    reexec = ivals * frac                                         # (I, G)
    t_failed = t_down_s + t_restart_s + reexec + comp_to_block_s
    d = strategies.evaluate_strategies_profile(
        profile,
        jnp.full(reexec.shape, comp_to_block_s),
        t_failed,
        jnp.zeros(reexec.shape),
        t_ckpt_s,
        jnp.full(reexec.shape, wait_mode, jnp.int32),
    )
    saving = np.asarray(d.saving, np.float64)
    saving_pct = np.asarray(d.saving_pct, np.float64)
    actions = np.asarray(d.wait_action)
    comp_changed = np.asarray(d.comp_changed)
    return [
        ExpectedSavings(
            mean_saving_j=float(saving[i].mean()),
            mean_saving_pct=float(saving_pct[i].mean()),
            p_sleep=float(np.mean(actions[i] == em.WaitAction.SLEEP)),
            p_min_freq=float(np.mean(actions[i] == em.WaitAction.MIN_FREQ)),
            p_comp_change=float(np.mean(comp_changed[i])),
            grid=grid,
        )
        for i in range(len(intervals))
    ]


def optimal_checkpoint_interval(
    profile: MachineProfile,
    *,
    mtbf_s: float,
    t_ckpt_s: float = 120.0,
    t_down_s: float = 60.0,
    t_restart_s: float = 60.0,
    comp_to_block_s: float = 300.0,
    n_survivors: int = 3,
    wait_mode: int = 0,
    intervals: Optional[np.ndarray] = None,
):
    """Sweep the checkpoint interval for minimum expected energy overhead
    per unit of useful work — the closed-form *sanity oracle* for the
    whole-run optimizer.

    Per interval T (cluster failure rate 1/mtbf, failure uniform within T),
    both terms price the whole (n_survivors + 1)-node cluster:
      checkpoint power overhead:  (n+1) · (T_ckpt/T) · P_ckpt    [J/s always]
      failure overhead rate:      (1/mtbf) · E[failure energy]   [J/s]
        where E[failure energy] = re-execution on the failed node
        (E[reexec]=T/2 at P_comp) + survivors' wait energy MINUS the paper's
        strategy savings (expected_savings above).
    (The original derivation priced checkpoints for ONE node against
    failure costs for the whole cluster, which biased the optimum ~2x
    short of the renewal engine's; cross-checking against
    ``core.optimize`` exposed the inconsistency.)

    The whole (interval x failure-phase) grid is evaluated in ONE jitted
    Algorithm-1 dispatch (``_expected_savings_grid``) — the former
    per-interval Python loop paid 17 dispatches for identical numbers.

    Returns (best_interval_s, table) where table rows are dicts per interval
    — including the *no-strategy* optimum for comparison, which lands close
    to Young's sqrt(2·T_ckpt·mtbf) while the energy-aware optimum shifts
    longer (savings discount the failure cost).

    Scope note (docs/optimize.md): this is a single-failure, fixed-workload
    first-order model.  The renewal engine's optimizer
    (``core.optimize.optimize_policy``) prices what this model cannot —
    post-recovery resync checkpoints, rendezvous structure, non-Poisson
    failure processes — and is the deployment answer; this heuristic is
    kept as the transparent oracle it is cross-checked against
    (tests/test_planning.py pins the two optima to within one grid step on
    the paper's Table-4 profile in the regime where their assumptions
    coincide).
    """
    pt = profile.power_table
    p_comp = float(pt.p_comp[0])
    p_ckpt = float(pt.p_ckpt[0])
    if intervals is None:
        young = np.sqrt(2.0 * t_ckpt_s * mtbf_s)
        intervals = young * np.geomspace(0.25, 4.0, 17)
    intervals = np.asarray(intervals, np.float64)

    expectations = _expected_savings_grid(
        profile, intervals, t_down_s=t_down_s, t_restart_s=t_restart_s,
        comp_to_block_s=comp_to_block_s, t_ckpt_s=t_ckpt_s,
        wait_mode=wait_mode, grid=512)
    rows = []
    for T, exp in zip(intervals, expectations):
        # every node in the cluster checkpoints, so the steady-state
        # checkpoint overhead is per-cluster — as the failure terms are
        ckpt_rate = (n_survivors + 1) * (t_ckpt_s / T) * p_ckpt
        # failed node re-executes E[T/2] at full power
        reexec_e = (T / 2.0) * p_comp
        # survivors' no-intervention wait energy (reference) and savings
        mean_wait = t_down_s + t_restart_s + T / 2.0
        survivors_ref = n_survivors * mean_wait * p_comp
        survivors_saved = n_survivors * exp.mean_saving_j
        fail_rate_no_strategy = (reexec_e + survivors_ref) / mtbf_s
        fail_rate_strategy = (reexec_e + survivors_ref - survivors_saved) / mtbf_s
        rows.append({
            "interval_s": float(T),
            "overhead_w_no_strategy": ckpt_rate + fail_rate_no_strategy,
            "overhead_w_with_strategy": ckpt_rate + fail_rate_strategy,
            "mean_saving_pct": exp.mean_saving_pct,
            "p_sleep": exp.p_sleep,
        })
    best = min(rows, key=lambda r: r["overhead_w_with_strategy"])
    return best["interval_s"], rows
