"""The paper's six experimental scenarios (§4.3, Table 4) as configs.

Scenario inputs are reverse-derived from the published phase durations (the
paper does not publish the raw simulator inputs).  The derivation uses the
rendezvous identity validated against every Table-4 row:

    T_failed_i = T_recover + exec_to_rendezvous_i
    wait_i     = T_failed_i - comp_phase_i(f)

with T_recover = T_down + T_restart + T_reexec (eq. 15).  See
tests/test_scenarios.py for the row-by-row checks.

Scenario 3 note: the paper modifies the ladder by "decreas[ing] the dissipated
power by 2 W and increas[ing] the slowdown by one tenth".  Applying
beta(2.1 GHz) = 1.2 -> 1.3 makes energy/work at 2.1 GHz *worse* than at fa
(1.3 x 146 > 1.0 x 166), so Algorithm 1 would keep fa, contradicting the
paper's own reported selection of 2.1 GHz, while beta = 1.1 reproduces both
the selection and the published comp-phase duration (11.02 min =
8.02 x 1.1 + 2 x 1.1).  We therefore read "by one tenth" as moving the
slowdown one tenth toward 1 and document the discrepancy (EXPERIMENTS.md).
"""
from __future__ import annotations

import dataclasses

import numpy as np

from repro.core import energy_model as em
from repro.core import planning
from repro.core.characterization import (
    MachineProfile,
    PowerTable,
    paper_machine_profile,
)
# the sampling-side failure-state view: per-node failure-clock ages the
# renewal sampler conditions on.  Implemented next to the sampler it must
# mirror (core/failures.py); re-exported here with the other failure-state
# views (FailureState, the sawtooth ages) it is the twin of.
from repro.core.failures import failure_clock_ages
from repro.core.simulator import NodeStart, ScenarioConfig

__all__ = [
    "paper_scenarios",
    "scenario",
    "sparse_rendezvous_scenario",
    "apply_policy",
    "FailureState",
    "failure_state_at",
    "failure_clock_ages",
    "shift_failure",
    "post_recovery_anchor",
    "next_rendezvous",
    "post_recovery_config",
]


def _scenario3_profile() -> MachineProfile:
    base = paper_machine_profile()
    pt = base.power_table
    table = PowerTable(
        freq_ghz=pt.freq_ghz,
        p_comp=np.array([166.0, 146.0, 137.0, 124.0]),   # -2 W off non-max levels
        beta=np.array([1.0, 1.1, 1.4, 2.0]),             # slowdown moved 0.1 toward 1
        p_ckpt=pt.p_ckpt,
        gamma=pt.gamma,
    )
    return dataclasses.replace(base, power_table=table)


def paper_scenarios() -> dict:
    """name -> ScenarioConfig for the paper's six scenarios."""
    short = dict(t_down=60.0, t_restart=60.0, t_reexec=110.0)       # T_recover 230 s
    long = dict(t_down=60.0, t_restart=60.0, t_reexec=1920.0)       # T_recover 2040 s
    tiny = dict(t_down=60.0, t_restart=39.8, t_reexec=60.0)         # T_recover 159.8 s

    s1 = ScenarioConfig(
        name="scenario1_short_reexec",
        survivors=(
            NodeStart(exec_to_rendezvous=972.0, ckpt_age=600.0),
            NodeStart(exec_to_rendezvous=103.8, ckpt_age=60.0),
            NodeStart(exec_to_rendezvous=193.8, ckpt_age=60.0),
        ),
        ckpt_interval=1800.0,
        **short,
    )
    s2 = ScenarioConfig(
        name="scenario2_long_reexec",
        survivors=(
            NodeStart(exec_to_rendezvous=481.2, ckpt_age=1500.0),
            NodeStart(exec_to_rendezvous=511.2, ckpt_age=1500.0),
            NodeStart(exec_to_rendezvous=541.2, ckpt_age=1500.0),
        ),
        ckpt_interval=3600.0,
        move_ahead_frac=0.5,
        **long,
    )
    s3 = dataclasses.replace(s2, name="scenario3_freq_behaviour_change",
                             profile=_scenario3_profile())
    s4 = ScenarioConfig(
        name="scenario4_short_active_waits",
        survivors=(
            NodeStart(exec_to_rendezvous=141.0, ckpt_age=60.0),
            NodeStart(exec_to_rendezvous=166.0, ckpt_age=60.0),
            NodeStart(exec_to_rendezvous=191.0, ckpt_age=60.0),
        ),
        ckpt_interval=3600.0,
        **tiny,
    )
    s5 = dataclasses.replace(s4, name="scenario5_short_idle_waits",
                             wait_mode=em.WaitMode.IDLE)
    s6 = dataclasses.replace(s2, name="scenario6_no_move_ahead", move_ahead=False)
    return {c.name: c for c in (s1, s2, s3, s4, s5, s6)}


def scenario(index: int) -> ScenarioConfig:
    """Scenario by paper number (1-6)."""
    return list(paper_scenarios().values())[index - 1]


def sparse_rendezvous_scenario(period_s: float = 14400.0,
                               name: str = "long_period") -> ScenarioConfig:
    """Scenario 4's machine on a sparser-rendezvous application — the
    canonical policy-optimization workload (docs/optimize.md §workload
    pinning).

    On the paper's own scenarios (3600 s rendezvous period) the checkpoint-
    interval optimum pins to the workload structure: per-failure resync
    checkpoints cap the loss and the optimum parks just under the period,
    insensitive to MTBF or failure process.  Spreading the rendezvous to
    ``period_s`` (default 4 h, survivors evenly phased at 1/4, 2/4, 3/4 of
    it) restores the classical overhead-vs-re-execution tradeoff the
    optimizer exists to price.  tests/test_optimize.py, examples/
    optimize_policy.py, and benchmarks/optimize_policy.py all use this one
    definition.
    """
    base = paper_scenarios()["scenario4_short_active_waits"]
    return dataclasses.replace(
        base, name=name,
        survivors=tuple(
            NodeStart(exec_to_rendezvous=period_s * f, rendezvous_period=period_s,
                      ckpt_age=60.0)
            for f in (0.25, 0.5, 0.75)))


def apply_policy(
    cfg: ScenarioConfig,
    *,
    ckpt_interval: float = None,
    mu1: float = None,
    mu2: float = None,
    wait_mode=None,
    move_ahead_frac: float = None,
    move_ahead: bool = None,
) -> ScenarioConfig:
    """A copy of ``cfg`` with operator-tunable knobs replaced.

    The knobs are exactly the policy axes ``core.optimize`` searches over
    (checkpoint timer interval, sleep-gate margins, wait mode, move-ahead
    fraction); ``None`` keeps the scenario's own value.  The paper evaluates
    fixed configurations — this is the hook that turns a ``ScenarioConfig``
    into one *point* of a policy grid, and what the optimizer's
    cross-validation tests use to rebuild a single policy as a standalone
    config.  The returned config goes through the usual validation on use
    (e.g. ``sweep.sweep_inputs`` rejects intervals shorter than the starting
    checkpoint ages).
    """
    updates = {}
    if ckpt_interval is not None:
        updates["ckpt_interval"] = float(ckpt_interval)
    if mu1 is not None:
        updates["mu1"] = float(mu1)
    if mu2 is not None:
        updates["mu2"] = float(mu2)
    if wait_mode is not None:
        updates["wait_mode"] = em.WaitMode(int(wait_mode))
    if move_ahead_frac is not None:
        updates["move_ahead_frac"] = float(move_ahead_frac)
    if move_ahead is not None:
        updates["move_ahead"] = bool(move_ahead)
    return dataclasses.replace(cfg, **updates)


# ---------------------------------------------------------------------------
# analytic failure-instant shifting (substrate of core/sweep.py)
# ---------------------------------------------------------------------------

def _check_ages(age0: np.ndarray, t_reexec: float, interval: float) -> None:
    """The checkpoint sawtooth assumes no node starts with an *overdue*
    timer (age > interval): the closed form would place the overdue
    checkpoint in the past and return negative work.  Such configs are
    ill-posed for the event simulator too (its timer would fire at a
    negative timestamp)."""
    if np.any(age0 > interval) or t_reexec > interval:
        raise ValueError(
            "ckpt_age / t_reexec exceed ckpt_interval: a node cannot be "
            f"older than one timer period (ages {age0.tolist()}, "
            f"t_reexec {t_reexec}, interval {interval})"
        )

@dataclasses.dataclass(frozen=True)
class FailureState:
    """Per-node pre-failure state when the failure lands ``delta`` wall
    seconds after a scenario's reference instant.  All arrays are float64,
    shape (N,) over survivors unless noted."""

    delta: float               # requested shift (wall seconds)
    exec_rem: np.ndarray       # fa-seconds of work to each survivor's next rendezvous
    ckpt_age: np.ndarray       # wall seconds since each survivor's last checkpoint end
    delta_eff: np.ndarray      # per-node snapped instant (see advance_checkpoint_sawtooth)
    t_reexec: float            # failed node's lost work = re-execution time at fa
    t_recover: float           # T_down + T_restart + t_reexec  (eq. 15)
    delta_eff_failed: float    # the failed node's own snapped instant


def failure_state_at(cfg: ScenarioConfig, delta: float) -> FailureState:
    """Advance a scenario's pre-failure timeline by ``delta`` wall seconds.

    A ``ScenarioConfig`` is a snapshot of the system at one failure instant
    (the paper simulates exactly that instant).  Before the failure every
    process executes at fa with timer checkpoints every ``ckpt_interval``
    (paper §4.1) and rendezvous every ``rendezvous_period`` fa-seconds of
    work, completing instantly while all peers are alive (balanced app — the
    paper's waits arise only from the failure).  Both sawtooths admit closed
    forms, so the state at any later failure instant is analytic:

      * survivor ``i``:  ``ckpt_age`` advances/wraps on the checkpoint
        sawtooth; ``exec_rem`` decreases by the work done and wraps on the
        rendezvous period (remaining work in ``(0, period]``);
      * the failed node: its lost work ``t_reexec`` follows the same sawtooth
        (at fa, work since the last checkpoint equals the wall age).

    Per-node failure instants snap forward past in-progress checkpoints
    (``delta_eff``), keeping every state representable as a ``NodeStart``.
    """
    if delta < 0:
        raise ValueError("delta must be >= 0")
    exec0 = np.array([s.exec_to_rendezvous for s in cfg.survivors], np.float64)
    period = np.array([s.rendezvous_period for s in cfg.survivors], np.float64)
    age0 = np.array([s.ckpt_age for s in cfg.survivors], np.float64)
    _check_ages(age0, cfg.t_reexec, cfg.ckpt_interval)
    age, work, _, delta_eff = planning.advance_checkpoint_sawtooth(
        age0, np.float64(delta), cfg.ckpt_interval, cfg.ckpt_duration
    )
    rem = np.mod(exec0 - work, period)
    exec_rem = np.where(rem == 0.0, period, rem)
    # failed node: age == lost work at fa between checkpoints
    reexec, _, _, delta_eff_failed = planning.advance_checkpoint_sawtooth(
        np.float64(cfg.t_reexec), np.float64(delta),
        cfg.ckpt_interval, cfg.ckpt_duration,
    )
    t_reexec = float(reexec)
    return FailureState(
        delta=float(delta),
        exec_rem=exec_rem,
        ckpt_age=age,
        delta_eff=np.asarray(delta_eff, np.float64),
        t_reexec=t_reexec,
        t_recover=cfg.t_down + cfg.t_restart + t_reexec,
        delta_eff_failed=float(delta_eff_failed),
    )


def shift_failure(cfg: ScenarioConfig, delta: float) -> ScenarioConfig:
    """A ``ScenarioConfig`` whose failure lands ``delta`` seconds later.

    The returned config feeds the event simulator directly, which is how
    ``tests/test_sweep.py`` cross-validates the analytic sweep engine
    pointwise.  Chained survivors (``peer != 0``) are rejected when the shift
    breaks the progress ordering the chain requires.
    """
    st = failure_state_at(cfg, delta)
    for i, sv in enumerate(cfg.survivors):
        if sv.peer != 0 and st.exec_rem[i] <= st.exec_rem[sv.peer - 1]:
            raise ValueError(
                f"shift {delta}: chained survivor {i + 1} wrapped past its peer"
            )
    survivors = tuple(
        dataclasses.replace(
            sv,
            exec_to_rendezvous=float(st.exec_rem[i]),
            ckpt_age=float(st.ckpt_age[i]),
        )
        for i, sv in enumerate(cfg.survivors)
    )
    return dataclasses.replace(
        cfg,
        name=f"{cfg.name}@+{delta:g}s",
        survivors=survivors,
        t_reexec=st.t_reexec,
    )


def post_recovery_anchor(exec_rem, period, p_star=None):
    """Array form of the renewal re-anchor: next rendezvous after ``P*``.

    Given each survivor's remaining work ``exec_rem`` at the failure instant
    (trailing axis over survivors) and the per-survivor rendezvous
    ``period``, returns the re-anchored ``exec_to_rendezvous`` — the first
    multiple of each period strictly past the epoch's shared progress point
    ``P* = max exec_rem``, in ``(0, period]``.  This is the single closed
    form behind ``post_recovery_config`` (scalar, host), the host renewal
    recursion (``sweep.renewal_compose``), and the device renewal scan
    (``sweep.renewal_compose_device``): numpy float64 and traced jnp inputs
    both work (``planning._ns`` dispatch).

    ``p_star`` overrides the shared progress point (batch shape of
    ``exec_rem`` minus the survivor axis).  Correlated multi-node epochs
    use it: when a shock fells several nodes, the resync point is the max
    over the *non-felled* survivors only (``sweep`` threads it through),
    while felled survivors re-execute to that same point — their next
    rendezvous still follows this closed form.  ``None`` keeps the
    single-failure default ``max exec_rem``.
    """
    xp = planning._ns(exec_rem, period)
    exec_rem, period = xp.asarray(exec_rem), xp.asarray(period)
    if p_star is None:
        p_star = xp.max(exec_rem, axis=-1, keepdims=True)
    else:
        p_star = xp.asarray(p_star)[..., None]
    return next_rendezvous(p_star, exec_rem, period)


def next_rendezvous(p_star, exec_rem, period):
    """The closed form under ``post_recovery_anchor`` with ``p_star``
    already broadcastable against ``exec_rem`` and ``period`` — any layout
    of the survivor axis (the Pallas renewal kernel keeps it leading)."""
    xp = planning._ns(p_star, exec_rem, period)
    gap = planning.floor_mod(p_star - exec_rem, period)
    return xp.where(gap == 0.0, period, period - gap)


def post_recovery_config(cfg: ScenarioConfig, p_star=None) -> ScenarioConfig:
    """Re-anchor a scenario at the renewal point after its failure is handled.

    ``cfg`` is the system state at a failure instant (the original snapshot
    or a ``shift_failure`` output).  The epoch it starts plays out as in the
    paper — down / restart / re-execute on the failed node, per-survivor
    intervention windows — and closes at the renewal point ``T_E = T_recover
    + max_i exec_rem_i``, when the last rendezvous completes.  Two FT-runtime
    policies (documented in docs/sweep.md) make the post-epoch state exact
    and balanced:

      * post-rendezvous, survivors revert to fa and timer checkpoints are
        suppressed for the epoch's short trailing span, so at ``T_E`` every
        node — including the recovered one — sits at the same progress point
        ``P* = max_i exec_rem_i`` (the rendezvous identity: survivor ``i``
        completes at ``T_recover + exec_rem_i`` and then executes at fa for
        ``T_E - t_failed_i = P* - exec_rem_i`` seconds);
      * at ``T_E`` the runtime takes a *coordinated re-synchronization
        checkpoint* (standard practice after a recovery: a second failure
        must not replay the first), so every checkpoint age — and the failed
        node's lost-work sawtooth — restarts from zero.

    The returned config is the balanced snapshot right after that
    checkpoint: ages 0, lost work 0, and each survivor's next rendezvous at
    the first multiple of its period past ``P*`` (in ``(0, period]``).
    Chained blocking topologies are rejected — the renewal identity above
    assumes direct blockers (``peer == 0``), which all Table-4 scenarios are.

    ``p_star`` overrides the resync progress point for correlated
    multi-node epochs (see ``post_recovery_anchor``); felled survivors'
    ``exec_to_rendezvous`` still re-anchor through the same closed form.
    """
    if any(sv.peer != 0 for sv in cfg.survivors):
        raise ValueError(
            f"{cfg.name}: renewal re-anchoring requires direct blockers "
            "(peer == 0); chained topologies do not resynchronize at T_E"
        )
    exec_rem = np.array([s.exec_to_rendezvous for s in cfg.survivors], np.float64)
    period = np.array([s.rendezvous_period for s in cfg.survivors], np.float64)
    exec_next = post_recovery_anchor(
        exec_rem, period,
        p_star=None if p_star is None else np.float64(p_star))
    survivors = tuple(
        dataclasses.replace(
            sv,
            exec_to_rendezvous=float(exec_next[i]),
            ckpt_age=0.0,
            level=0,
        )
        for i, sv in enumerate(cfg.survivors)
    )
    return dataclasses.replace(
        cfg,
        name=f"{cfg.name}|renewed",
        survivors=survivors,
        t_reexec=0.0,
    )
