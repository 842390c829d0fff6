"""Batched Monte-Carlo failure-sweep engine.

The paper evaluates each scenario at a *single* failure instant (§4, Table 4);
its conclusion calls for analyzing "the behavior of an application under
different configurations and failure time".  This module is that path: one
jitted JAX program evaluates Algorithm 1 over a dense grid of

    failure_time x scenario x wait_mode x mu-band x ladder level

by deriving every survivor's pre-failure state *analytically* from a
``ScenarioConfig`` at each failure instant — no Python event stepping:

  * ``planning.advance_checkpoint_sawtooth`` gives each node's checkpoint age
    and completed work at any shifted instant in closed form;
  * the rendezvous phase wraps on each survivor's period;
  * the failed node's lost work (= re-execution time at fa) follows the same
    sawtooth, so ``T_failed`` (eq. 14/15) is analytic per instant;
  * ``planning.checkpoint_plan`` forecasts per-(node, level) checkpoint
    counts and the move-ahead exactly as the event engine executes them;
  * ``strategies.evaluate_strategies`` (Algorithm 1) runs once over the whole
    grid — everything broadcasts, as promised in strategies.py.

``tests/test_sweep.py`` cross-validates the analytic per-point savings
against the event simulator on every Table-4 scenario; the two paths share
the closed-form plan, so agreement is a real check of the energy accounting,
not a tautology.

On top of the dense grid sit Monte-Carlo sampling over failure times
(``monte_carlo``: expected annual savings per strategy under a fixed PRNG
key — exponential-MTBF arrivals by default, any ``core.failures``
process via ``process=``) and summary statistics (``summarize``:
mean/p5/p95 saving, sleep-gate occupancy, infeasibility rate).

The renewal layer (``renewal_failure_gaps`` / ``renewal_compose`` /
``renewal_monte_carlo``) extends the single-failure view to *whole runs*
with repeated failures: per-node failure sequences over an application
makespan (exponential by default; Weibull / log-normal / gamma /
trace-driven via ``core.failures``, whose non-memoryless processes sample
age-conditioned **conditional residuals** under the quiesce policy —
docs/failures.md), each failure handled as a paper epoch, state
re-anchored after every recovery (``scenarios.post_recovery_config``), and
whole-run energy composed from the closed-form sawtooth + one jitted
Algorithm-1 dispatch across every (run, epoch, survivor) point.
Cross-validated pointwise against ``simulator.simulate_run`` in
tests/test_renewal.py; semantics in docs/sweep.md.

The renewal composition comes in three engines (``engine=``):

  * ``"host"`` (``renewal_compose``, and ``renewal_monte_carlo`` only) —
    the float64 *host oracle*: a Python loop over failure epochs (numpy
    geometry) plus one jitted Algorithm-1 dispatch.  Slow but
    transparent; the cross-validation anchor.
  * ``"scan"`` (the default of every device entry) — the same recursion
    as a ``jax.lax.scan`` over epochs whose carry is the re-anchored
    state, ``vmap``ped over runs and over stacked lanes (scenarios or
    policies, one lane core: ``_renewal_lanes``), fused with the
    Algorithm-1 dispatch, the balanced-span energy, the trailing-span
    accounting, and in the Monte-Carlo entries the on-device gap sampling
    (one sampler: ``_sample_histories``) into **one jitted program** — no
    per-epoch host round-trips, no per-lane re-dispatch.  Geometry is
    traced under ``jax.enable_x64`` so wall-clock times stay float64-exact
    against the oracle while the Algorithm-1 energy math stays float32,
    exactly as on the host path.  ``tests/test_renewal_device.py`` pins
    the two paths together at <= 1e-4 relative (observed ~1e-9) on
    whole-run energies.
  * ``"pallas"`` — the float32 Kahan-ledger kernel
    (``kernels.renewal_scan``) behind the same sampler, stats only.

A study (``renewal_monte_carlo_scenarios``, and ``renewal_monte_carlo`` of
one scenario) reduces over runs inside its program; the per-run entries
(``renewal_monte_carlo_device``, ``renewal_monte_carlo_policies``) share
one staged dispatch (``_renewal_mc_runs``).

Semantics notes (also in docs/sweep.md):
  * failure instants landing inside a node's checkpoint snap forward to the
    checkpoint's end (per node) — see ``advance_checkpoint_sawtooth``;
  * pre-failure rendezvous complete instantly (balanced application — the
    paper's waits arise only from the failure);
  * chained survivors (``peer != 0``) are evaluated with ``T_failed`` =
    peer completion + progress delta; instants where the shift breaks the
    chain's progress ordering are flagged in ``chain_ok`` and their savings
    are not meaningful.
"""
from __future__ import annotations

import contextlib
import dataclasses
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import energy_model as em
from repro.core import failures
from repro.core import planning
from repro.core import strategies
from repro.core import topology as node_topology
from repro.core.scenarios import post_recovery_anchor
from repro.core.simulator import ScenarioConfig

__all__ = [
    "SweepInputs",
    "SweepResult",
    "SweepSummary",
    "MonteCarloSummary",
    "RenewalResult",
    "RenewalDeviceResult",
    "RenewalDeviceStats",
    "RenewalMonteCarloSummary",
    "sweep_inputs",
    "sweep_failure_times",
    "sweep_scenarios",
    "summarize",
    "exponential_failure_offsets",
    "failure_offsets",
    "monte_carlo",
    "renewal_failure_gaps",
    "renewal_compose",
    "renewal_compose_device",
    "renewal_compose_policies",
    "renewal_monte_carlo_device",
    "renewal_monte_carlo",
    "renewal_monte_carlo_scenarios",
    "renewal_monte_carlo_policies",
]

SECONDS_PER_YEAR = 365.25 * 24 * 3600.0


# ---------------------------------------------------------------------------
# inputs: a ScenarioConfig flattened to arrays (vmap-able across scenarios)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class SweepInputs:
    """Device-array view of a ``ScenarioConfig`` for the sweep engine.

    All fields are jnp scalars / arrays (pytree leaves) except ``peer``,
    which is static structure (the blocking topology).  Scenario batches are
    built by stacking pytrees — every scenario in a batch must share the
    survivor count, ladder size, and blocking topology.
    """

    exec_rem0: jax.Array    # (N,) fa-seconds to each survivor's next rendezvous
    period: jax.Array       # (N,) rendezvous period (fa-seconds of work)
    age0: jax.Array         # (N,) wall seconds since last checkpoint end
    reexec0: jax.Array      # ()  failed node's lost work at the reference instant
    t_down: jax.Array       # ()
    t_restart: jax.Array    # ()
    interval: jax.Array     # ()  checkpoint timer interval (wall s)
    dur: jax.Array          # ()  checkpoint duration at fa (wall s)
    move_ahead: jax.Array   # ()  bool
    move_frac: jax.Array    # ()
    wait_mode: jax.Array    # ()  em.WaitMode
    mu1: jax.Array          # ()  sleep-gate margin (eq. 8)
    mu2: jax.Array          # ()
    p_idle_wait: jax.Array  # ()
    ladder: em.LadderArrays
    sleep: em.SleepArrays
    peer: tuple             # static: (N,) blocking topology, 0 = failed process


jax.tree_util.register_dataclass(
    SweepInputs,
    data_fields=[
        "exec_rem0", "period", "age0", "reexec0", "t_down", "t_restart",
        "interval", "dur", "move_ahead", "move_frac", "wait_mode", "mu1",
        "mu2", "p_idle_wait", "ladder", "sleep",
    ],
    meta_fields=["peer"],
)


def sweep_inputs(cfg: ScenarioConfig, dtype=jnp.float32) -> SweepInputs:
    """Flatten a ``ScenarioConfig`` into sweep-engine arrays.

    ``dtype`` is float32 for the single-failure sweep; the device renewal
    engine builds float64 inputs (under ``jax.enable_x64``) so
    the scan geometry matches the host float64 oracle, down-casting to
    float32 only at the Algorithm-1 dispatch.
    """
    ages = [s.ckpt_age for s in cfg.survivors]
    if max(ages, default=0.0) > cfg.ckpt_interval or cfg.t_reexec > cfg.ckpt_interval:
        # the sawtooth closed form assumes no node starts with an overdue
        # timer (the event simulator would fire it at a negative timestamp)
        raise ValueError(
            f"{cfg.name}: ckpt_age/t_reexec exceed ckpt_interval "
            f"(ages {ages}, t_reexec {cfg.t_reexec}, interval {cfg.ckpt_interval})"
        )
    fx = lambda x: jnp.asarray(x, dtype)
    return SweepInputs(
        exec_rem0=fx([s.exec_to_rendezvous for s in cfg.survivors]),
        period=fx([s.rendezvous_period for s in cfg.survivors]),
        age0=fx([s.ckpt_age for s in cfg.survivors]),
        reexec0=fx(cfg.t_reexec),
        t_down=fx(cfg.t_down),
        t_restart=fx(cfg.t_restart),
        interval=fx(cfg.ckpt_interval),
        dur=fx(cfg.ckpt_duration),
        move_ahead=jnp.asarray(cfg.move_ahead),
        move_frac=fx(cfg.move_ahead_frac),
        wait_mode=jnp.asarray(int(cfg.wait_mode), jnp.int32),
        mu1=fx(cfg.mu1),
        mu2=fx(cfg.mu2),
        p_idle_wait=fx(cfg.profile.p_idle_wait),
        ladder=em.LadderArrays.from_table(cfg.profile.power_table, dtype),
        sleep=em.SleepArrays.from_spec(cfg.profile.sleep, dtype),
        peer=tuple(s.peer for s in cfg.survivors),
    )


# ---------------------------------------------------------------------------
# the grid evaluation (one jitted program)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class SweepResult:
    """Per-grid-point decisions + geometry.

    Leading batch shape is ``(T, N)`` for a plain failure-time sweep —
    ``(M, T, N)`` with a mu-band, ``(S, T, N)`` for stacked scenarios
    (``decision`` fields only; geometry stays mu-independent at ``(T, N)``).
    """

    decision: strategies.Decision
    exec_rem: jax.Array     # (T, N) work to rendezvous at the failure instant
    ckpt_age: jax.Array     # (T, N)
    delta_eff: jax.Array    # (T, N) per-node snapped failure instant
    t_reexec: jax.Array     # (T,)
    t_failed: jax.Array     # (T, N) eq. 14
    n_ckpt: jax.Array       # (T, N, F) planned checkpoints per ladder level
    plan_move: jax.Array    # (T, N) move-ahead planned
    chain_ok: jax.Array     # (T, N) chained-rendezvous ordering holds


jax.tree_util.register_dataclass(
    SweepResult,
    data_fields=[
        "decision", "exec_rem", "ckpt_age", "delta_eff", "t_reexec",
        "t_failed", "n_ckpt", "plan_move", "chain_ok",
    ],
    meta_fields=[],
)


def _sweep_core(inp: SweepInputs, offsets: jax.Array, mu1: jax.Array) -> SweepResult:
    """Evaluate Algorithm 1 at every failure offset.  Shapes: offsets (T,),
    mu1 () or (M, 1, 1, 1) for a mu-band."""
    delta = offsets[:, None]                                     # (T, 1)
    age, work, _, delta_eff = planning.advance_checkpoint_sawtooth(
        inp.age0, delta, inp.interval, inp.dur)                  # (T, N)
    rem = jnp.mod(inp.exec_rem0 - work, inp.period)
    exec_rem = jnp.where(rem == 0.0, inp.period, rem)            # (0, period]
    t_reexec, _, _, _ = planning.advance_checkpoint_sawtooth(
        inp.reexec0, offsets, inp.interval, inp.dur)             # (T,)
    t_recover = inp.t_down + inp.t_restart + t_reexec            # eq. 15

    # rendezvous-completion times in chain (topological) order: direct
    # blockers wait for the recovering process (eq. 14); chained blockers
    # wait for their peer to resume and reach the shared progress point.
    cols, ok = [], []
    for i, p in enumerate(inp.peer):
        if p == 0:
            cols.append(t_recover + exec_rem[:, i])
            ok.append(jnp.ones_like(exec_rem[:, i], bool))
        else:
            cols.append(cols[p - 1] + (exec_rem[:, i] - exec_rem[:, p - 1]))
            ok.append(exec_rem[:, i] > exec_rem[:, p - 1])
    t_failed = jnp.stack(cols, axis=-1)                          # (T, N)
    chain_ok = jnp.stack(ok, axis=-1)

    plan = planning.checkpoint_plan(
        exec_rem, age, t_failed,
        interval=inp.interval, dur=inp.dur,
        beta=inp.ladder.beta, gamma=inp.ladder.gamma,
        move_ahead=inp.move_ahead, move_frac=inp.move_frac,
    )
    decision = strategies.evaluate_strategies(
        exec_rem, t_failed, plan.n_ckpt, inp.dur, inp.ladder, inp.sleep,
        inp.wait_mode, inp.p_idle_wait, mu1=mu1, mu2=inp.mu2,
        per_level_n_ckpt=True,
    )
    return SweepResult(
        decision=decision,
        exec_rem=exec_rem,
        ckpt_age=age,
        delta_eff=delta_eff,
        t_reexec=t_reexec,
        t_failed=t_failed,
        n_ckpt=plan.n_ckpt,
        plan_move=plan.plan_move,
        chain_ok=chain_ok,
    )


_sweep_jit = jax.jit(_sweep_core)
# scenario-stacked variants: per-scenario mu (mapped) vs shared mu-band
_sweep_scenarios_mu_mapped = jax.jit(jax.vmap(_sweep_core, in_axes=(0, None, 0)))
_sweep_scenarios_mu_shared = jax.jit(jax.vmap(_sweep_core, in_axes=(0, None, None)))


def _mu_band(mu1) -> jax.Array:
    """() passthrough or (M,) -> (M, 1, 1, 1) so the gate broadcasts against
    the (T, N, F) wait grid, yielding (M, T, N) decisions."""
    mu1 = jnp.asarray(mu1, jnp.float32)
    return mu1 if mu1.ndim == 0 else mu1[:, None, None, None]


def sweep_failure_times(
    cfg: ScenarioConfig,
    offsets,
    mu1: Optional[object] = None,
) -> SweepResult:
    """Dense failure-time sweep of one scenario — a single jitted call.

    ``offsets`` are wall seconds after the scenario's reference failure
    instant (shape (T,)).  ``mu1=None`` uses the scenario's own sleep-gate
    margin; an (M,) array sweeps the mu-band, giving decisions of shape
    ``(M, T, N)``.
    """
    inp = sweep_inputs(cfg)
    mu1 = inp.mu1 if mu1 is None else _mu_band(mu1)
    return _sweep_jit(inp, jnp.asarray(offsets, jnp.float32), mu1)


def sweep_scenarios(
    cfgs: Sequence[ScenarioConfig],
    offsets,
    mu1: Optional[object] = None,
) -> SweepResult:
    """Stacked sweep over scenarios: one jitted dispatch for the whole
    (scenario x failure_time x node x ladder) grid.

    All scenarios must share survivor count, ladder size, and blocking
    topology (the Table-4 six do).  Result arrays carry a leading scenario
    axis.  Per-scenario wait modes, mu margins, ladders, and profiles ride
    along in the stacked inputs — wait-mode and mu-band axes of the paper
    grid are covered by stacking scenario variants.
    """
    inputs = [sweep_inputs(c) for c in cfgs]
    peers = {i.peer for i in inputs}
    if len(peers) != 1:
        raise ValueError(f"scenarios have mixed blocking topologies: {peers}")
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *inputs)
    offsets = jnp.asarray(offsets, jnp.float32)
    if mu1 is None:
        return _sweep_scenarios_mu_mapped(stacked, offsets, stacked.mu1)
    return _sweep_scenarios_mu_shared(stacked, offsets, _mu_band(mu1))


# ---------------------------------------------------------------------------
# summary statistics
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class SweepSummary:
    """Distributional view of one scenario's sweep (floats, host-side)."""

    points: int                 # grid points (T * N)
    mean_saving_j: float        # per-node saving, eq. (1)
    p5_saving_j: float
    p95_saving_j: float
    mean_saving_pct: float
    sleep_occupancy: float      # fraction of points the sleep gate admitted
    min_freq_rate: float
    comp_change_rate: float
    infeasible_rate: float      # no ladder level feasible -> no intervention
    mean_wait_s: float
    chain_violation_rate: float  # chained-rendezvous ordering broken (see chain_ok)


def summarize(res: SweepResult) -> SweepSummary:
    """Reduce a sweep (any batch shape) to summary statistics.

    Points where a chained survivor wrapped past its peer (``chain_ok``
    False) carry meaningless savings (module docstring); they are *excluded*
    from every statistic and reported only through
    ``chain_violation_rate``.  ``points`` counts the full grid; all other
    fields are over the chain-valid subset (NaN when nothing is valid).
    """
    d = res.decision
    saving = np.asarray(d.saving, np.float64)
    # decision arrays may carry extra leading batch dims (e.g. a mu-band)
    # that the geometry — and mu-independent fields like feasible_any — do
    # not: broadcast both the validity mask and every picked field up.
    ok = np.broadcast_to(np.asarray(res.chain_ok, bool), saving.shape)
    valid = ok.reshape(-1)
    pick = lambda a: np.broadcast_to(np.asarray(a), ok.shape).reshape(-1)[valid]
    saving = saving.reshape(-1)[valid]
    actions = pick(d.wait_action)
    if saving.size == 0:
        nan = float("nan")
        return SweepSummary(
            points=int(ok.size), mean_saving_j=nan, p5_saving_j=nan,
            p95_saving_j=nan, mean_saving_pct=nan, sleep_occupancy=nan,
            min_freq_rate=nan, comp_change_rate=nan, infeasible_rate=nan,
            mean_wait_s=nan,
            chain_violation_rate=float(np.mean(~np.asarray(res.chain_ok))),
        )
    return SweepSummary(
        points=int(ok.size),
        mean_saving_j=float(saving.mean()),
        p5_saving_j=float(np.percentile(saving, 5)),
        p95_saving_j=float(np.percentile(saving, 95)),
        mean_saving_pct=float(pick(d.saving_pct).mean()),
        sleep_occupancy=float(np.mean(actions == em.WaitAction.SLEEP)),
        min_freq_rate=float(np.mean(actions == em.WaitAction.MIN_FREQ)),
        comp_change_rate=float(np.mean(pick(d.comp_changed))),
        infeasible_rate=float(np.mean(~pick(d.feasible_any))),
        mean_wait_s=float(pick(d.wait_time).mean()),
        chain_violation_rate=float(np.mean(~np.asarray(res.chain_ok))),
    )


# ---------------------------------------------------------------------------
# Monte-Carlo over exponential failure times
# ---------------------------------------------------------------------------

def exponential_failure_offsets(
    key: jax.Array,
    n_samples: int,
    mtbf_s: float,
    wrap_s: float,
) -> np.ndarray:
    """Failure offsets for a Poisson failure process with the given MTBF.

    Inter-failure gaps are exponential draws from ``key`` (deterministic);
    absolute arrival times accumulate in float64 and fold into ``[0,
    wrap_s)`` — the sweep geometry is evaluated at the folded offset, so the
    phase of each failure relative to the checkpoint/rendezvous sawtooths is
    what the exponential process implies, while float32 stays accurate.
    """
    return failure_offsets(key, n_samples, failures.Exponential(mtbf_s),
                           wrap_s)


def failure_offsets(
    key: jax.Array,
    n_samples: int,
    process: failures.FailureProcess,
    wrap_s: float,
) -> np.ndarray:
    """Failure offsets for a renewal arrival process with the given
    inter-failure gap distribution — ``exponential_failure_offsets``
    generalized to any ``FailureProcess``.

    Gaps are unconditional float32 draws from the process (one cluster-level
    arrival stream, one node failing per event as in the paper); absolute
    arrival times accumulate in float64 and fold into ``[0, wrap_s)``
    exactly as on the exponential path.  Requires scalar process parameters
    (the per-node axis is a renewal-engine concept — see
    ``renewal_failure_gaps``).
    """
    if np.size(process.mean_s()) != 1:
        raise ValueError(
            "failure_offsets samples one cluster-level arrival stream; "
            "per-node heterogeneous parameters belong to the renewal "
            "engines (renewal_failure_gaps / renewal_monte_carlo)")
    gaps = np.asarray(process.sample(key, (n_samples,)), np.float64)
    arrivals = np.cumsum(gaps)
    return np.mod(arrivals, float(wrap_s)).astype(np.float32)


@dataclasses.dataclass(frozen=True)
class MonteCarloSummary:
    """Expected-value view of a scenario under a failure distribution."""

    n_samples: int
    mtbf_s: float
    failures_per_year: float
    # per-failure totals over all survivors (J)
    mean_saving_j: float
    p5_saving_j: float
    p95_saving_j: float
    mean_saving_pct: float
    # action occupancy over (sample, node) points
    sleep_occupancy: float
    min_freq_rate: float
    comp_change_rate: float
    infeasible_rate: float
    # expected annual savings (J/year), total and per strategy family
    annual_saving_j: float
    annual_saving_by_strategy: dict


def monte_carlo(
    cfg: ScenarioConfig,
    key: jax.Array,
    n_samples: int = 4096,
    mtbf_s: float = 30 * 24 * 3600.0,
    wrap_s: Optional[float] = None,
    mu1: Optional[object] = None,
    process: Optional[failures.FailureProcess] = None,
) -> MonteCarloSummary:
    """Monte-Carlo expectation of the paper's strategies under sampled
    failure times (one node failing per event, as in the paper).

    Each sampled failure is evaluated with the full analytic engine in the
    same single jitted dispatch as the dense sweep.  Results are
    deterministic for a fixed ``key`` (regression-tested).  Annual savings
    scale the per-failure mean by the expected failure count; the
    ``by_strategy`` split attributes each point's saving to the selected
    action family (sleep / min-freq wait / compute-frequency change — points
    combining a frequency change with a wait action count toward the wait
    action, matching Table 4's labeling).

    ``process=None`` keeps the paper's exponential arrivals at ``mtbf_s``
    (bit-identical to the pre-process sampler); any other
    ``failures.FailureProcess`` drives the arrival stream through
    ``failure_offsets`` and the reported ``mtbf_s`` / annual scaling use the
    process's mean gap.
    """
    if wrap_s is None:
        wrap_s = 64.0 * (cfg.ckpt_interval + cfg.ckpt_duration)
    if process is None:
        offsets = exponential_failure_offsets(key, n_samples, mtbf_s, wrap_s)
    else:
        offsets = failure_offsets(key, n_samples, process, wrap_s)
        mtbf_s = float(np.mean(process.mean_s()))
    res = sweep_failure_times(cfg, offsets, mu1=mu1)
    if not bool(np.all(np.asarray(res.chain_ok))):
        # savings at chain-broken instants are meaningless (module docstring);
        # refuse to average them into expectations — mirror shift_failure.
        rate = float(np.mean(~np.asarray(res.chain_ok)))
        raise ValueError(
            f"{cfg.name}: {rate:.1%} of sampled failure instants break the "
            "chained-rendezvous ordering; Monte-Carlo expectations are not "
            "defined for this blocking topology"
        )
    d = res.decision
    saving = np.asarray(d.saving, np.float64)           # (T, N)
    eni = np.asarray(d.energy_reference, np.float64)
    actions = np.asarray(d.wait_action)
    comp_changed = np.asarray(d.comp_changed)
    per_failure = saving.sum(axis=-1)                   # (T,)
    failures_per_year = SECONDS_PER_YEAR / float(mtbf_s)
    mean_saving = float(per_failure.mean())

    masks = {
        "sleep": actions == em.WaitAction.SLEEP,
        "min_freq": actions == em.WaitAction.MIN_FREQ,
        "comp_change_only": (actions == em.WaitAction.NONE) & comp_changed,
    }
    by_strategy = {
        name: float((saving * mask).sum(axis=-1).mean() * failures_per_year)
        for name, mask in masks.items()
    }
    return MonteCarloSummary(
        n_samples=n_samples,
        mtbf_s=float(mtbf_s),
        failures_per_year=failures_per_year,
        mean_saving_j=mean_saving,
        p5_saving_j=float(np.percentile(per_failure, 5)),
        p95_saving_j=float(np.percentile(per_failure, 95)),
        mean_saving_pct=float(100.0 * per_failure.sum() / max(eni.sum(), 1e-9)),
        sleep_occupancy=float(np.mean(masks["sleep"])),
        min_freq_rate=float(np.mean(masks["min_freq"])),
        comp_change_rate=float(np.mean(comp_changed)),
        infeasible_rate=float(np.mean(~np.asarray(d.feasible_any))),
        annual_saving_j=mean_saving * failures_per_year,
        annual_saving_by_strategy=by_strategy,
    )


# ---------------------------------------------------------------------------
# renewal process: whole-run energy across repeated failures
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class RenewalResult:
    """Per-epoch decisions + whole-run energy for a batch of renewal runs.

    ``decision`` fields are jax arrays of shape (R, K, N) — runs x failure
    epochs x survivors; the geometry and energy fields are host float64.
    Epochs past a run's last failure (``valid`` False) hold placeholder
    values and are excluded from every total.
    """

    decision: strategies.Decision
    valid: np.ndarray        # (R, K) bool: epoch k occurred in run r
    gaps: np.ndarray         # (R, K) balanced-execution gaps as evaluated
    t_fail: np.ndarray       # (R, K) absolute (snapped) failure instants
    exec_rem: np.ndarray     # (R, K, N) survivor work-to-rendezvous at failure
    t_failed: np.ndarray     # (R, K, N) eq. 14 per epoch
    t_renewal: np.ndarray    # (R, K) epoch duration T_E
    n_ckpt: np.ndarray       # (R, K, N, F) planned checkpoints per ladder level
    failed_node: np.ndarray  # (R, K) which node failed (labeling only)
    n_failures: np.ndarray   # (R,)
    truncated: np.ndarray    # (R,) bool: exhausted max_failures before makespan
    end_time: np.ndarray     # (R,) wall end of the run (>= makespan)
    balanced_energy: np.ndarray  # (R,) inter-failure + resync-ckpt + tail (J)
    epoch_ref: np.ndarray    # (R, K, N) per-survivor epoch energy, reference
    epoch_int: np.ndarray    # (R, K, N) per-survivor epoch energy, intervened
    epoch_failed: np.ndarray  # (R, K) failed-node epoch energy (both runs)
    energy_ref: np.ndarray   # (R,) whole-run reference energy
    energy_int: np.ndarray   # (R,) whole-run intervened energy
    saving: np.ndarray       # (R,) energy_ref - energy_int


def renewal_failure_gaps(
    key: jax.Array,
    n_runs: int,
    n_nodes: int,
    max_failures: int,
    mtbf_s: Optional[float] = None,
    process: Optional[failures.FailureProcess] = None,
    topology=None,
):
    """Per-node failure sequences, reduced to renewal-epoch gaps.

    Each of the ``n_nodes`` nodes fails as an independent renewal process of
    inter-failure gaps drawn from ``process`` (default: the paper's
    exponential at the per-node ``mtbf_s``; per-node heterogeneous
    parameters broadcast along the node axis).  Under the quiesce policy (a
    failure arriving while an epoch is open defers to the renewal point) the
    exponential's memorylessness makes the deferred process equivalent to
    redrawing every node's time-to-failure at each renewal anchor — so the
    epoch gap is the minimum of ``n_nodes`` fresh draws and the failing node
    is the argmin.  Non-exponential processes are *not* memoryless: the
    sampler tracks per-node failure-clock ages and draws each node's
    **conditional residual** (age-conditioned inverse CDF,
    ``failures.sample_renewal_gaps``) instead, with the exponential kept as
    the closed-form special case.  Returns ``(gaps, failed_node)`` of shape
    ``(n_runs, max_failures)``, float64/int64.

    The unit draws and the inverse-CDF transforms both happen in float32
    before the float64 cast: ``jax.random`` emits identical float32 bits
    with and without x64 enabled, so the host oracle and the device engine
    (``renewal_monte_carlo_device``, which samples inside its jitted
    program) see *bit-identical* failure histories for the same key.

    A ``core.topology.Topology`` switches to the correlated shock sampler
    and the return becomes the *triple* ``(gaps, failed_node, failed_mask)``
    — ``failed_mask`` ((n_runs, max_failures, n_nodes) bool) marks every
    node felled per epoch (a shock fells several at once) and
    ``failed_node`` is the primary; map the mask to survivor slots with
    ``topology.survivor_slot_mask`` before feeding ``renewal_compose``'s
    ``felled``.  Same bit-identity contract as the iid path.
    """
    if topology is not None:
        gaps, fmask, primary = node_topology.correlated_renewal_gaps(
            topology, failures.as_process(process, mtbf_s), key, n_runs,
            n_nodes, max_failures)
        return gaps, primary, fmask
    if process is not None and not isinstance(process, failures.Exponential):
        return failures.renewal_gaps(
            failures.as_process(process, mtbf_s), key, n_runs, n_nodes,
            max_failures)
    if process is not None:
        mtbf_s = process.mtbf_s
    if mtbf_s is None:
        raise ValueError("provide mtbf_s or a FailureProcess")
    draws = np.asarray(failures.Exponential(mtbf_s).sample(
        key, (n_runs, max_failures, n_nodes)), np.float64)
    return draws.min(axis=-1), draws.argmin(axis=-1)


def renewal_compose(cfg: ScenarioConfig, gaps, makespan_s: float,
                    failed_node=None, felled=None) -> RenewalResult:
    """Compose whole-run multi-failure energy analytically.

    ``gaps`` (R, K) or (K,) are balanced-execution wall seconds between each
    renewal anchor and the next failure; ``makespan_s`` is the application's
    failure-free length, so epoch ``k`` of run ``r`` occurs only while the
    balanced time consumed so far plus ``gaps[r, k]`` stays within it
    (recovery epochs extend the wall end instead of eating the makespan).
    The per-epoch state is
    the closed-form sawtooth advanced from the previous renewal anchor
    (ages and the lost-work sawtooth restart at zero after each epoch's
    coordinated re-synchronization checkpoint — ``scenarios.
    post_recovery_config`` semantics), the geometry recursion runs in host
    float64, and Algorithm 1 evaluates every (run, epoch, survivor) point in
    a single jitted dispatch.  Cross-validated pointwise against
    ``simulator.simulate_run`` in tests/test_renewal.py.

    Occurrence / truncation semantics (shared verbatim with the device
    path, regression-tested in tests/test_renewal_device.py):

      * epoch ``k`` *occurs* in run ``r`` iff the run is still alive and
        ``bal_elapsed + gaps[r, k] <= makespan_s`` — a gap landing exactly
        on the makespan boundary still occurs (mirroring ``simulate_run``'s
        ``>``-break);
      * the first non-occurring epoch kills the run (everything after it is
        dropped, ``valid`` False, outputs hold placeholder values);
      * ``n_failures`` counts occurring epochs; ``truncated`` flags runs
        that consumed *all* ``max_failures`` sampled gaps while balanced
        time still remained (``alive & (bal_elapsed < makespan_s)``) — more
        failures would have been drawn.  A run killed by an overlong gap is
        never truncated.

    This is the float64 host oracle; ``renewal_compose_device`` is the
    fused scan over epochs x runs x scenarios that replaces it on the hot
    path.

    ``felled`` ((R, K, N) bool over survivor slots, or None) marks slots
    additionally felled per epoch — the correlated-shock extension
    (``core.topology``; build it with ``topology.survivor_slot_mask`` from
    the sampler's physical-node mask).  Felled slots join the primary's
    recovery (max lost work governs the re-execution, the resync point is
    the furthest *non-felled* survivor, each felled node pays the
    failed-node closed form) and are excluded from the survivor window
    energies; all formulas reduce exactly to the single-failure path for
    an all-False mask.
    """
    _check_renewal_config(cfg)
    ages0 = np.array([s.ckpt_age for s in cfg.survivors], np.float64)

    gaps = np.atleast_2d(np.asarray(gaps, np.float64))            # (R, K)
    n_runs, max_failures = gaps.shape
    n = len(cfg.survivors)
    if felled is None:
        felled = np.zeros((n_runs, max_failures, n), bool)
    felled = np.broadcast_to(np.asarray(felled, bool),
                             (n_runs, max_failures, n))
    pt = cfg.profile.power_table
    p_comp0, p_ckpt0 = float(pt.p_comp[0]), float(pt.p_ckpt[0])
    beta0, gamma0 = float(pt.beta[0]), float(pt.gamma[0])
    dur_fa = cfg.ckpt_duration * gamma0
    n_nodes = n + 1
    interval, dur = cfg.ckpt_interval, cfg.ckpt_duration
    period = np.array([s.rendezvous_period for s in cfg.survivors], np.float64)
    if failed_node is None:
        failed_node = np.zeros((n_runs, max_failures), np.int64)
    failed_node = np.broadcast_to(
        np.asarray(failed_node, np.int64), (n_runs, max_failures))

    # --- host float64 geometry recursion (decision-independent) ------------
    exec_anchor = np.broadcast_to(
        np.array([s.exec_to_rendezvous for s in cfg.survivors], np.float64),
        (n_runs, n)).copy()
    ages = np.broadcast_to(ages0, (n_runs, n)).copy()
    reexec_age = np.full(n_runs, float(cfg.t_reexec))
    t_anchor = np.zeros(n_runs)      # wall clock (balanced + epochs + resyncs)
    bal_elapsed = np.zeros(n_runs)   # balanced time consumed (vs the makespan)
    alive = np.ones(n_runs, bool)
    balanced = np.zeros(n_runs)

    valid = np.zeros((n_runs, max_failures), bool)
    t_fail = np.zeros((n_runs, max_failures))
    exec_rem_k = np.zeros((n_runs, max_failures, n))
    t_failed_k = np.zeros((n_runs, max_failures, n))
    t_renewal_k = np.zeros((n_runs, max_failures))
    n_ckpt_k = np.zeros((n_runs, max_failures, n, len(pt.beta)))
    epoch_failed = np.zeros((n_runs, max_failures))
    ct_ref_k = np.zeros((n_runs, max_failures, n))  # comp duration at fa

    for k in range(max_failures):
        delta = gaps[:, k]
        occurs = alive & (bal_elapsed + delta <= makespan_s)
        if not occurs.any():
            alive &= occurs
            continue
        age_f, work, _, d_eff = planning.advance_checkpoint_sawtooth(
            ages, delta[:, None], interval, dur)                 # (R, N)
        rem = np.mod(exec_anchor - work, period)
        exec_rem = np.where(rem == 0.0, period, rem)
        reexec_f, _, _, d_eff_fail = planning.advance_checkpoint_sawtooth(
            reexec_age, delta, interval, dur)                    # (R,)
        m_k = felled[:, k]                                       # (R, N)
        # felled survivors' lost work joins the re-execution race; the
        # resync point is the furthest non-felled survivor (both reduce
        # exactly to the old formulas for an all-False mask)
        reexec_f = np.maximum(
            reexec_f, np.max(np.where(m_k, age_f, -np.inf), axis=-1))
        t_recover = cfg.t_down + cfg.t_restart + reexec_f
        t_failed = t_recover[:, None] + exec_rem

        # balanced span energy up to each node's (snapped) failure instant
        w_s, ck_s = planning.balanced_span(ages, d_eff, interval, dur)
        w_f, ck_f = planning.balanced_span(reexec_age, d_eff_fail, interval, dur)
        e_bal = (w_s * p_comp0 + ck_s * p_ckpt0).sum(axis=-1) \
            + w_f * p_comp0 + ck_f * p_ckpt0
        balanced += np.where(occurs, e_bal + n_nodes * dur_fa * p_ckpt0, 0.0)

        plan = planning.checkpoint_plan(
            exec_rem, age_f, t_failed,
            interval=interval, dur=dur, beta=pt.beta, gamma=pt.gamma,
            move_ahead=cfg.move_ahead, move_frac=cfg.move_ahead_frac)
        p_star = np.maximum(
            np.max(np.where(m_k, -np.inf, exec_rem), axis=-1), 0.0)
        t_e = t_recover + p_star
        # failed node over [failure, T_E]: down (0 W) + restart at P_ckpt +
        # re-execution and post-recovery serving at P_comp; every felled
        # slot pays the same closed form (identical in both runs)
        epoch_failed[:, k] = np.where(
            occurs,
            (1.0 + m_k.sum(axis=-1))
            * (cfg.t_restart * p_ckpt0 + (reexec_f + p_star) * p_comp0), 0.0)

        valid[:, k] = occurs
        t_fail[:, k] = np.where(occurs, t_anchor + d_eff_fail, 0.0)
        exec_rem_k[:, k] = exec_rem
        t_failed_k[:, k] = t_failed
        t_renewal_k[:, k] = np.where(occurs, t_e, 0.0)
        n_ckpt_k[:, k] = np.asarray(plan.n_ckpt)
        ct_ref_k[:, k] = exec_rem * beta0 + np.asarray(plan.n_ckpt)[..., 0] * dur * gamma0

        # re-anchor: coordinated resync checkpoint -> ages 0, progress P*
        exec_next = post_recovery_anchor(exec_rem, period, p_star=p_star)
        exec_anchor = np.where(occurs[:, None], exec_next, exec_anchor)
        ages = np.where(occurs[:, None], 0.0, ages)
        reexec_age = np.where(occurs, 0.0, reexec_age)
        bal_elapsed = np.where(occurs, bal_elapsed + d_eff_fail, bal_elapsed)
        t_anchor = np.where(occurs, t_fail[:, k] + t_e + dur_fa, t_anchor)
        alive &= occurs

    # balanced tail: the rest of the failure-free work (mid-checkpoint snaps
    # can nudge bal_elapsed slightly past the makespan; clamp)
    span = np.maximum(makespan_s - bal_elapsed, 0.0)
    w_s, ck_s = planning.balanced_span(ages, span[:, None], interval, dur)
    w_f, ck_f = planning.balanced_span(reexec_age, span, interval, dur)
    balanced += (w_s * p_comp0 + ck_s * p_ckpt0).sum(axis=-1) \
        + w_f * p_comp0 + ck_f * p_ckpt0

    # --- one jitted Algorithm-1 dispatch over every (run, epoch, node) -----
    inp = sweep_inputs(cfg)
    decision = strategies.evaluate_strategies(
        jnp.asarray(exec_rem_k, jnp.float32),
        jnp.asarray(t_failed_k, jnp.float32),
        jnp.asarray(n_ckpt_k, jnp.float32),
        inp.dur, inp.ladder, inp.sleep, inp.wait_mode, inp.p_idle_wait,
        mu1=inp.mu1, mu2=inp.mu2, per_level_n_ckpt=True,
    )

    # per-survivor epoch energy = window energy + trailing fa span to T_E
    # (the trailing end is max(t_failed, comp duration): an overrunning
    # reference comp phase — the sweep engine's "infeasible pockets" — eats
    # into the trailing span exactly as the event timeline does)
    eni = np.asarray(decision.energy_reference, np.float64)
    ei = np.asarray(decision.energy_intervened, np.float64)
    ct_sel = np.asarray(decision.comp_time, np.float64)
    t_e3 = t_renewal_k[:, :, None]
    trail_ref = np.maximum(t_e3 - np.maximum(t_failed_k, ct_ref_k), 0.0) * p_comp0
    trail_int = np.maximum(t_e3 - np.maximum(t_failed_k, ct_sel), 0.0) * p_comp0
    # felled slots are accounted through epoch_failed's closed form, not
    # the survivor window energies
    v3 = valid[:, :, None] & ~felled
    epoch_ref = np.where(v3, eni + trail_ref, 0.0)
    epoch_int = np.where(v3, ei + trail_int, 0.0)

    energy_ref = balanced + epoch_ref.sum(axis=(1, 2)) + epoch_failed.sum(axis=1)
    energy_int = balanced + epoch_int.sum(axis=(1, 2)) + epoch_failed.sum(axis=1)
    return RenewalResult(
        decision=decision,
        valid=valid,
        gaps=gaps,
        t_fail=t_fail,
        exec_rem=exec_rem_k,
        t_failed=t_failed_k,
        t_renewal=t_renewal_k,
        n_ckpt=n_ckpt_k,
        failed_node=np.where(valid, failed_node, -1),
        n_failures=valid.sum(axis=1),
        truncated=alive & (bal_elapsed < makespan_s),
        end_time=t_anchor + span,
        balanced_energy=balanced,
        epoch_ref=epoch_ref,
        epoch_int=epoch_int,
        epoch_failed=epoch_failed,
        energy_ref=energy_ref,
        energy_int=energy_int,
        saving=energy_ref - energy_int,
    )


# ---------------------------------------------------------------------------
# device-resident renewal engine: one jitted scan over epochs x runs x scenarios
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class RenewalDeviceResult:
    """Device-resident analog of ``RenewalResult``, batched over scenarios.

    All fields are jax arrays with leading ``(S, R)`` axes — stacked
    scenarios x runs; ``decision`` fields are ``(S, R, K, N)`` float32
    (identical math to the host dispatch), geometry and energy fields are
    float64.  ``gaps`` is ``(R, K)``, shared across scenarios: the same
    failure histories hit every stacked scenario, exactly as when the host
    oracle is called per scenario with one PRNG key.  Epochs with ``valid``
    False hold placeholder values and are excluded from every total.
    """

    decision: strategies.Decision
    valid: jax.Array          # (S, R, K) bool
    gaps: jax.Array           # (R, K) balanced-execution gaps as evaluated
    t_fail: jax.Array         # (S, R, K) absolute (snapped) failure instants
    exec_rem: jax.Array       # (S, R, K, N)
    t_failed: jax.Array       # (S, R, K, N) eq. 14 per epoch
    t_renewal: jax.Array      # (S, R, K) epoch duration T_E
    failed_node: jax.Array    # (S, R, K) which node failed (labeling only)
    n_failures: jax.Array     # (S, R)
    truncated: jax.Array      # (S, R) bool (same semantics as the host path)
    end_time: jax.Array       # (S, R)
    balanced_energy: jax.Array  # (S, R)
    epoch_ref: jax.Array      # (S, R, K, N)
    epoch_int: jax.Array      # (S, R, K, N)
    epoch_failed: jax.Array   # (S, R, K)
    energy_ref: jax.Array     # (S, R)
    energy_int: jax.Array     # (S, R)
    saving: jax.Array         # (S, R)


jax.tree_util.register_dataclass(
    RenewalDeviceResult,
    data_fields=[
        "decision", "valid", "gaps", "t_fail", "exec_rem", "t_failed",
        "t_renewal", "failed_node", "n_failures", "truncated", "end_time",
        "balanced_energy", "epoch_ref", "epoch_int", "epoch_failed",
        "energy_ref", "energy_int", "saving",
    ],
    meta_fields=[],
)


@dataclasses.dataclass(frozen=True)
class RenewalDeviceStats:
    """Hot-path output of the device renewal engine: whole-run quantities
    plus integer action counts, nothing per-epoch.

    At production batch sizes the per-epoch diagnostic arrays of
    ``RenewalDeviceResult`` dominate wall time (they are pure output
    traffic); this lean view leaves them on the device floor.  The counts
    divide by ``n_points`` on the host, so the derived occupancy rates are
    *exactly* the float64 oracle's ``np.mean`` over the same valid points.
    """

    n_failures: jax.Array     # (S, R) int32
    truncated: jax.Array      # (S, R) bool
    end_time: jax.Array       # (S, R)
    balanced_energy: jax.Array  # (S, R)
    energy_ref: jax.Array     # (S, R)
    energy_int: jax.Array     # (S, R)
    saving: jax.Array         # (S, R)
    n_points: jax.Array       # (S, R) valid (epoch, survivor) points per run
    n_sleep: jax.Array        # (S, R) int32 counts over valid points
    n_min_freq: jax.Array     # (S, R)
    n_comp_changed: jax.Array  # (S, R)
    n_infeasible: jax.Array   # (S, R)
    failed_counts: jax.Array  # (S, n_nodes) failures attributed per node


jax.tree_util.register_dataclass(
    RenewalDeviceStats,
    data_fields=[
        "n_failures", "truncated", "end_time", "balanced_energy",
        "energy_ref", "energy_int", "saving", "n_points", "n_sleep",
        "n_min_freq", "n_comp_changed", "n_infeasible", "failed_counts",
    ],
    meta_fields=[],
)


def _felled_race(m, age_surv, age_fail, exec_rem):
    """Re-execution length and resync point ``P*`` of an epoch.  Felled
    survivors' lost work joins the re-execution race and ``P*`` is the
    furthest *non-felled* survivor; ``m=None`` is the single-failure epoch
    (the failed node's age, the furthest survivor) — the same values an
    all-False mask gives, without the mask.  ``age_surv=None`` says every
    survivor's age is the failed node's (the run-wide sawtooth of
    ``_renewal_scan``), so the race is that age whatever the mask."""
    if m is None:
        return age_fail, jnp.maximum(jnp.max(exec_rem, axis=-1), 0.0)
    reexec = age_fail if age_surv is None else jnp.maximum(
        age_fail, jnp.max(jnp.where(m, age_surv, -jnp.inf), axis=-1))
    p_star = jnp.maximum(
        jnp.max(jnp.where(m, -jnp.inf, exec_rem), axis=-1), 0.0)
    return reexec, p_star


def _ordered_sum(x, axis=None):
    """``jnp.sum`` over ``axis`` (or all axes) as a fixed pairwise tree of
    elementwise adds.  XLA orders a reduction's adds by the layout it picks
    for the program, so one lane of a vmapped program and the same lane
    computed alone can differ in the last bits (the TPU's float64 does).
    Elementwise adds are never reassociated, so this order is the same in
    every program that computes the lane: per-cluster fleet rows equal
    standalone calls bit for bit on every backend."""
    x = jnp.asarray(x)
    axes = range(x.ndim) if axis is None else [axis % x.ndim]
    x = jnp.moveaxis(x, list(axes), list(range(-len(axes), 0)))
    x = x.reshape(x.shape[:x.ndim - len(axes)] + (-1,))
    width = 1 << (x.shape[-1] - 1).bit_length()
    x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, width - x.shape[-1])])
    while x.shape[-1] > 1:
        half = x.shape[-1] // 2
        x = x[..., :half] + x[..., half:]
    return x[..., 0]


def _ordered_sum_of_copies(x, count: int):
    """``_ordered_sum`` over a last axis of ``count`` copies of ``x``, bit
    for bit, without the axis.  The tree's padded row holds the copies and
    then zeros, so each level holds few distinct entries: each distinct
    pair is added once, O(log count) adds in all."""
    width = 1 << (count - 1).bit_length()
    row = [x] * count + [jnp.zeros_like(x)] * (width - count)
    while len(row) > 1:
        half, sums = len(row) // 2, {}
        for a, b in zip(row[:half], row[half:]):
            if (id(a), id(b)) not in sums:
                sums[id(a), id(b)] = a + b
        row = [sums[id(a), id(b)] for a, b in zip(row[:half], row[half:])]
    return row[0]


# Elements of a (lane, run, epoch, survivor) array that a stats-mode fold
# may hold at once.  At 8-12 float64 arrays live in the fold this bounds its
# temporaries to a few GB.  Both Table-4 studies (4.7M elements) fold their
# stacked epochs whole; a whole machine's study (201M) folds inside the
# epoch scan (``_survivor_tile``).
_SURVIVOR_TILE_ELEMENTS = 1 << 25


def _survivor_tile(n_lanes: int, n_runs: int, n_epochs: int,
                   n_survivors: int) -> Optional[int]:
    """How a stats-mode fold takes the survivor axis: None where the whole
    ``n_lanes x n_runs x n_epochs x n_survivors`` grid fits
    ``_SURVIVOR_TILE_ELEMENTS`` (one fold over the stacked epochs), else
    the survivors of one tile of the fold inside the epoch scan: all of
    them where lanes x runs x survivors fit, else as many as fit, in whole
    multiples of 128 (the TPU's lane width) or, below that, of 8."""
    lane_runs = n_lanes * n_runs
    if lane_runs * n_epochs * n_survivors <= _SURVIVOR_TILE_ELEMENTS:
        return None
    tile = max(1, _SURVIVOR_TILE_ELEMENTS // lane_runs)
    if tile >= n_survivors:
        return n_survivors
    for align in (128, 8):
        if tile >= align:
            return tile // align * align
    return tile


def _renewal_scan(inp: SweepInputs, gaps: jax.Array, makespan_s,
                  stats: bool = False, felled=None,
                  survivor_tile: Optional[int] = None):
    """Whole-run renewal recursion for ONE scenario x ONE run as a
    ``lax.scan`` over failure epochs.

    The carry is the re-anchored state ``(ages, exec_anchor, bal_elapsed,
    t_anchor, alive)``; each step advances the checkpoint/rendezvous
    sawtooths to the failure instant and re-anchors — the exact recursion
    of ``renewal_compose``, but traced once and compiled.  Epoch 0 runs
    before the scan and advances every node's own age; the scan carries
    one age a run for the later epochs, whose nodes the resync checkpoint
    starts all at 0 (docs/sweep.md, "The resync invariant").  The
    checkpoint plan, Algorithm-1 dispatch, and trailing-span accounting
    run *after* the scan over the stacked per-epoch states (still the same
    jitted program), where XLA vectorizes them across the whole grid.
    Must be traced under ``enable_x64`` with float64 inputs: wall-clock
    anchors grow to the makespan and would lose ~0.5 s to float32 over
    month-long runs, while Algorithm 1 is dispatched on float32 casts of
    the float64 geometry — the very same values the host oracle feeds it.
    ``_renewal_lanes`` vmaps this over runs and stacked lanes.

    ``stats=True`` is the hot-path mode: per-epoch diagnostic arrays are
    never materialized; only whole-run energies and integer action counts
    leave the program (the arrays dominate wall time at small batch sizes
    — they are pure output traffic, the decisions are computed either
    way).

    ``felled`` (optional, (K, N) bool over survivor *slots*) marks slots
    additionally felled in each epoch — the correlated-shock extension
    (``core.topology``).  Felled slots join the primary's recovery: the
    epoch's re-execution is the max lost work over all felled nodes, the
    resync point ``P*`` is the max ``exec_rem`` over the *non-felled*
    survivors, each felled node's epoch energy is the failed-node closed
    form, and felled slots are excluded from decisions/energies/counts.
    ``None`` (or an all-False mask — the formulas reduce through exact
    neutral elements) is the single-failure path, bit-identical to the
    pre-correlation engine.

    ``survivor_tile`` (stats mode only; ``_survivor_tile`` chooses it from
    the shapes) moves the fold into the scan's step: each epoch folds its
    survivors in tiles of that many, and the scan stacks per-epoch scalars
    only (occurrence, the re-execution race, ``P*``, the balanced-span
    energy, the tiles' summed energies and action counts).  No (epoch,
    survivor) array is then held.  The sums over survivors and epochs
    change order, so energies agree with the stacked fold to float64
    round-off; every count is the same.
    """
    n = inp.period.shape[0]
    n_nodes = n + 1
    per_epoch = stats and survivor_tile is not None
    f8 = lambda x: jnp.asarray(x, jnp.float64)
    f4 = lambda x: jnp.asarray(x, jnp.float32)
    interval, dur = f8(inp.interval), f8(inp.dur)
    period = f8(inp.period)
    beta, gamma = f8(inp.ladder.beta), f8(inp.ladder.gamma)
    p_comp0, p_ckpt0 = f8(inp.ladder.p_comp[0]), f8(inp.ladder.p_ckpt[0])
    beta0, gamma0 = beta[0], gamma[0]
    dur_fa = dur * gamma0
    t_restart = f8(inp.t_restart)
    t_dr = f8(inp.t_down) + t_restart
    makespan = f8(makespan_s)
    # Algorithm 1 runs in float32 exactly as on the host path
    ladder32 = jax.tree.map(lambda a: a.astype(jnp.float32), inp.ladder)
    sleep32 = jax.tree.map(lambda a: a.astype(jnp.float32), inp.sleep)

    # The scan body carries ONLY the re-anchor recursion — the part with a
    # true epoch-to-epoch dependency.  Everything with a ladder axis
    # (checkpoint plan, Algorithm 1) or that is pure per-epoch arithmetic
    # (window energies, trailing spans) is evaluated AFTER the scan over the
    # stacked (K, ...) epoch states, where XLA vectorizes it across the
    # whole epochs x runs x scenarios grid instead of re-issuing it inside
    # a 32-step sequential loop — unless that grid outgrows the fold's
    # element budget, when the step folds its own epoch (``survivor_tile``).
    # None on the single-failure path: an all-False mask is a compile-time
    # constant that XLA folds through every reduction over it, which made
    # the TPU compile of the fleet core 141 s against 25 s without it, and
    # of the scan engine 47 s against 17 s (tests/test_tpu_compile.py, an
    # ahead-of-time compile for a described v5e; results are the same, an
    # all-False mask reducing exactly)
    m_all = None if felled is None else jnp.asarray(felled, bool)

    def step(carry, xs):
        # The sawtooth state ``ages`` stacks the survivors' checkpoint ages
        # with the failed node's lost-work age (the same sawtooth governs
        # both), (N+1,), so one closed-form advance serves all N+1 nodes.
        # Shaped () it is the one age all N+1 nodes share (the run-wide
        # state after the first epoch, below), advanced once.
        delta, m = xs
        ages, exec_anchor, bal_elapsed, t_anchor, alive = carry
        node_wide = jnp.ndim(ages) > 0
        occurs = alive & (bal_elapsed + delta <= makespan)
        age_all, work, _, d_eff_all = planning.advance_checkpoint_sawtooth(
            ages, delta, interval, dur)                    # (N+1,) or ()
        if node_wide:            # the survivors, then the failed node
            age_surv, age_fail = age_all[:-1], age_all[-1]
            work_surv, d_eff_fail = work[:-1], d_eff_all[-1]
        else:                    # one value for all N+1 nodes
            age_surv = age_fail = age_all
            work_surv, d_eff_fail = work, d_eff_all
        rem = jnp.mod(exec_anchor - work_surv, period)
        exec_rem = jnp.where(rem == 0.0, period, rem)
        reexec, p_star = _felled_race(
            m, age_surv if node_wide else None, age_fail, exec_rem)
        t_e = t_dr + reexec + p_star                             # epoch span T_E

        # balanced span energy up to each node's (snapped) failure instant.
        # At the snapped instant the span's checkpoint share is exactly the
        # fired checkpoints, so ``work``/``d_eff - work`` from the sawtooth
        # *is* the ``balanced_span`` decomposition (both are exact multiples
        # of ``dur`` — tests pin the identity) without recomputing it.  Over
        # one age, the tree of N+1 equal terms without the axis.
        bal = work * p_comp0 + (d_eff_all - work) * p_ckpt0
        e_bal = (_ordered_sum(bal, axis=-1) if node_wide
                 else _ordered_sum_of_copies(bal, n_nodes))

        # re-anchor: coordinated resync checkpoint -> ages 0, progress P*
        new_carry = (
            jnp.where(occurs, 0.0, ages),
            jnp.where(occurs,
                      post_recovery_anchor(exec_rem, period, p_star=p_star),
                      exec_anchor),
            jnp.where(occurs, bal_elapsed + d_eff_fail, bal_elapsed),
            jnp.where(occurs, t_anchor + d_eff_fail + t_e + dur_fa, t_anchor),
            alive & occurs,
        )
        ys = (occurs, reexec, p_star, e_bal)
        if per_epoch:
            with jax.named_scope("survivor_tile"):
                sums = fold_tiles(occurs, age_surv, exec_rem, t_dr + reexec,
                                  t_e, m)
            return new_carry, ys + (sums,)
        return new_carry, ys + (age_surv, exec_rem) + (
            () if stats else (jnp.where(occurs, t_anchor + d_eff_fail, 0.0),))

    def survivor_fold(exec_rem_k, age_f, t_failed_k, t_e, valid, m):
        """The checkpoint plan, Algorithm 1 and the survivors' epoch
        energies: over the stacked epochs (K, N), or over one epoch's tile
        of survivors."""
        # per-level checkpoint plan as F separate node-batch columns: the fa
        # column comes from the shared checkpoint_plan (it also decides the
        # move-ahead), the others from the same closed form — no (..., F)
        # float64 array ever materializes.
        plan0 = planning.checkpoint_plan(
            exec_rem_k, age_f, t_failed_k,
            interval=interval, dur=dur, beta=beta[:1], gamma=gamma[:1],
            move_ahead=inp.move_ahead, move_frac=f8(inp.move_frac))
        move = jnp.where(plan0.plan_move, 1.0, 0.0)
        n_cols = [plan0.n_ckpt[..., 0]] + [
            planning.timer_checkpoint_count(
                exec_rem_k, age_f, beta[f], interval)
            + move
            for f in range(1, beta.shape[0])
        ]
        decision = strategies.evaluate_strategies_fold(
            f4(exec_rem_k), f4(t_failed_k), n_cols, f4(dur),
            ladder32, sleep32, inp.wait_mode, f4(inp.p_idle_wait),
            mu1=f4(inp.mu1), mu2=f4(inp.mu2))

        # per-survivor epoch energy = window energy + trailing fa span to T_E
        ct_ref = exec_rem_k * beta0 + n_cols[0] * dur * gamma0
        t_e2 = t_e[..., None]
        trail_ref = jnp.maximum(
            t_e2 - jnp.maximum(t_failed_k, ct_ref), 0.0) * p_comp0
        trail_int = jnp.maximum(
            t_e2 - jnp.maximum(t_failed_k, f8(decision.comp_time)),
            0.0) * p_comp0
        # felled slots are accounted through epoch_failed's closed form, not
        # the survivor window energies (their Algorithm-1 point is
        # meaningless)
        v2 = (jnp.broadcast_to(valid[..., None], exec_rem_k.shape)
              if m is None else valid[..., None] & ~m)
        epoch_ref = jnp.where(
            v2, f8(decision.energy_reference) + trail_ref, 0.0)
        epoch_int = jnp.where(
            v2, f8(decision.energy_intervened) + trail_int, 0.0)
        return decision, v2, epoch_ref, epoch_int

    def action_counts(decision, v2):
        # integer action counts over valid (epoch, survivor) points — the
        # summary rates divide by the point count on the host, so they
        # match the oracle's np.mean over the same points exactly.
        i32 = lambda m: jnp.sum((v2 & m).astype(jnp.int32))
        return dict(
            n_points=jnp.sum(v2.astype(jnp.int32)),
            n_sleep=i32(decision.wait_action == em.WaitAction.SLEEP),
            n_min_freq=i32(decision.wait_action == em.WaitAction.MIN_FREQ),
            n_comp_changed=i32(decision.comp_changed),
            n_infeasible=i32(~decision.feasible_any),
        )

    def fold_tiles(occurs, age, exec_rem, t_recover, t_e, m):
        """One epoch's survivor fold, ``survivor_tile`` survivors at a time
        (the last tile takes the rest): the epoch's summed energies and
        action counts."""
        sums = None
        for lo in range(0, n, survivor_tile):
            tile = slice(lo, lo + survivor_tile)
            decision, v2, epoch_ref, epoch_int = survivor_fold(
                exec_rem[tile], age[tile] if jnp.ndim(age) else age,
                t_recover + exec_rem[tile], t_e,
                occurs, None if m is None else m[tile])
            part = dict(epoch_ref=_ordered_sum(epoch_ref),
                        epoch_int=_ordered_sum(epoch_int),
                        **action_counts(decision, v2))
            sums = part if sums is None else jax.tree.map(jnp.add, sums,
                                                          part)
        return sums

    def stack(first, rest):
        """Epoch 0's value over the later epochs' stacked values, a run-wide
        value broadcast over the survivors: a pad and a select, which fuse
        into what reads them (a concatenation is copied whole)."""
        rest = rest.reshape(rest.shape + (1,) * (first.ndim + 1 - rest.ndim))
        rest = jnp.pad(rest, [(1, 0)] + [(0, 0)] * first.ndim)
        is_first = jnp.arange(rest.shape[0]).reshape(
            rest.shape[:1] + (1,) * first.ndim) == 0
        return jnp.where(is_first, first[None], rest)

    init = (jnp.concatenate([f8(inp.age0), f8(inp.reexec0)[None]]),
            f8(inp.exec_rem0), f8(0.0), f8(0.0), jnp.asarray(True))
    gaps = f8(gaps)
    m_rest = None if m_all is None else m_all[1:]
    with jax.named_scope("renewal_scan"):
        # Epoch 0 advances each node's own age (the configuration's age0
        # and reexec0).  Every occurring epoch ends in the resync
        # checkpoint, which sets all N+1 ages to 0, and the first epoch that
        # does not occur ends the run: from epoch 1 on, every epoch that
        # counts starts with all nodes at one age, so the later epochs carry
        # the sawtooth as that one age a run.  The carry after epoch 0 is
        # what the balanced tail starts from (age0 and reexec0 in a run
        # with no failure, else zeros; later epochs leave it so).
        carry, ys0 = step(init, (gaps[0], None if m_all is None else m_all[0]))
        ages_all = carry[0]
        carry, ys = jax.lax.scan(step, (ages_all[-1],) + carry[1:],
                                 (gaps[1:], m_rest))
        ys = jax.tree.map(stack, ys0, ys)
    with jax.named_scope("renewal_fold"):
        _, exec_anchor, bal_elapsed, t_anchor, alive = carry
        valid, reexec_f, p_star, e_bal = ys[:4]                      # (K,)
        t_recover = t_dr + reexec_f
        t_e = t_recover + p_star
        if per_epoch:
            epoch_sums = ys[4]
        else:
            # per-epoch accounting, vectorized over the stacked epochs
            (age_f, exec_rem_k), t_fail = ys[4:6], (None if stats else ys[6])
            t_failed_k = t_recover[..., None] + exec_rem_k           # (K, N)
        # the balanced spans plus the resync checkpoint closing each epoch
        balanced = _ordered_sum(jnp.where(
            valid, e_bal + n_nodes * dur_fa * p_ckpt0, 0.0))

        # failed node over [failure, T_E]: down (0 W) + restart at P_ckpt +
        # re-execution and post-recovery serving at P_comp.  Every felled slot
        # plays the same closed-form role (identical in reference and
        # intervened runs, so the saving is untouched); the factor is 1 for the
        # single-failure path.
        n_felled = 1.0 if m_all is None else 1.0 + jnp.sum(m_all, axis=-1)
        epoch_failed = jnp.where(
            valid,
            n_felled * (t_restart * p_ckpt0 + (reexec_f + p_star) * p_comp0),
            0.0)

        if per_epoch:
            sums = {k: _ordered_sum(v) if v.dtype == jnp.float64
                    else jnp.sum(v) for k, v in epoch_sums.items()}
        else:
            decision, v2, epoch_ref, epoch_int = survivor_fold(
                exec_rem_k, age_f, t_failed_k, t_e, valid, m_all)

        # balanced tail: the rest of the failure-free work (mid-checkpoint
        # snaps can nudge bal_elapsed slightly past the makespan; clamp)
        span = jnp.maximum(makespan - bal_elapsed, 0.0)
        w_t, ck_t = planning.balanced_span(ages_all, span, interval, dur)
        balanced = balanced + _ordered_sum(w_t * p_comp0 + ck_t * p_ckpt0)

        e_failed = _ordered_sum(epoch_failed)
        if per_epoch:
            energy_ref = balanced + sums.pop("epoch_ref") + e_failed
            energy_int = balanced + sums.pop("epoch_int") + e_failed
        else:
            energy_ref = balanced + _ordered_sum(epoch_ref) + e_failed
            energy_int = balanced + _ordered_sum(epoch_int) + e_failed
        common = dict(
            valid=valid,
            n_failures=jnp.sum(valid.astype(jnp.int32)),
            truncated=alive & (bal_elapsed < makespan),
            end_time=t_anchor + span,
            balanced_energy=balanced,
            energy_ref=energy_ref,
            energy_int=energy_int,
            saving=energy_ref - energy_int,
        )
        if per_epoch:
            return dict(common, **sums)
        if stats:
            return dict(common, **action_counts(decision, v2))
        return dict(
            common,
            decision=decision,
            t_fail=t_fail,
            exec_rem=exec_rem_k,
            t_failed=t_failed_k,
            t_renewal=jnp.where(valid, t_e, 0.0),
            epoch_ref=epoch_ref,
            epoch_int=epoch_int,
            epoch_failed=epoch_failed,
        )


def _renewal_lanes(inp: SweepInputs, gaps: jax.Array, makespan_s,
                   stats: bool = False, felled=None,
                   survivor_tile: Optional[int] = None):
    """vmap the per-run scan over runs (gaps axis 0) and stacked lanes
    (inputs axis 0), scenarios or the policies of one scenario
    (``core.optimize.policy_inputs``): one XLA program.  ``makespan_s`` is
    one number for every lane or (L,), one a lane (per-policy wall
    makespans, ``core.optimize.wall_makespan``).  ``gaps`` and ``felled``
    ((R, K, N) survivor-slot mask or None) ride the run axis, shared by
    every lane (common random numbers: a lane equals the same lane
    composed alone, tests/test_optimize.py).  In stats mode the shapes
    choose how the fold takes the survivor axis (``_survivor_tile``),
    unless ``survivor_tile`` says."""
    if stats and survivor_tile is None:
        survivor_tile = _survivor_tile(inp.period.shape[0], *gaps.shape,
                                       inp.period.shape[-1])
    scan = lambda i, g, m, f: _renewal_scan(
        i, g, m, stats=stats, felled=f, survivor_tile=survivor_tile)
    over_runs = jax.vmap(scan, in_axes=(None, 0, None, 0))
    per_lane = 0 if jnp.ndim(makespan_s) else None
    return jax.vmap(over_runs, in_axes=(0, None, per_lane, None))(
        inp, gaps, makespan_s, felled)


def _sample_histories(process, key: jax.Array, n_runs: int,
                      max_failures: int, n_nodes: int, topology=None):
    """The device sampler of every Monte-Carlo core: the float32 gaps
    (R, K), the failed node, and with a ``core.topology.Topology`` the
    correlated shock scan's felled survivor slots (R, K, N) and physical
    nodes (R, K, n_nodes), else None.  Histories are bit-identical to
    ``renewal_failure_gaps``'s at the same key, with or without x64."""
    if topology is None:
        gaps32, failed = failures.sample_renewal_gaps(
            process, key, n_runs, max_failures, n_nodes)
        return gaps32, failed, None, None
    gaps32, fmask, failed = node_topology.sample_correlated_renewal_gaps(
        topology, process, key, n_runs, max_failures, n_nodes)
    return (gaps32, failed, node_topology.survivor_slot_mask(fmask, failed),
            fmask)


def _attach_failed_counts(out: dict, failed: jax.Array, n_nodes: int,
                          fmask=None) -> dict:
    """stats-mode epilogue of the Monte-Carlo cores: per-node failure
    counts over valid epochs (``out['valid']``: (L, R, K)), reduced over
    runs.  With a correlated sampler's physical-node ``fmask``
    ((R, K, n_nodes)) every felled node counts, not just the primary."""
    with jax.named_scope("renewal_fold"):
        valid = out.pop("valid")
        if fmask is None:
            hit = valid[..., None] & (
                failed[None, ..., None]
                == jnp.arange(n_nodes)[None, None, None])
        else:
            hit = valid[..., None] & fmask[None]
        out["failed_counts"] = jnp.sum(hit.astype(jnp.int32), axis=(1, 2))
        return out


def _renewal_mc_core(inp: SweepInputs, key: jax.Array, makespan_s, process,
                     n_runs: int, max_failures: int, stats: bool = False,
                     topology=None, survivor_tile: Optional[int] = None):
    """Fused Monte-Carlo of stacked lanes, scenarios or policies: ONE
    sampling pass (``_sample_histories``) that never sees the lane axis,
    so the histories cannot depend on the knobs being tuned, then the
    composition (``_renewal_lanes``), one jitted program."""
    n_nodes = inp.period.shape[-1] + 1
    gaps32, failed, felled, fmask = _sample_histories(
        process, key, n_runs, max_failures, n_nodes, topology)
    gaps = gaps32.astype(jnp.float64)
    out = _renewal_lanes(inp, gaps, makespan_s, stats=stats, felled=felled,
                         survivor_tile=survivor_tile)
    if stats:
        out = _attach_failed_counts(out, failed, n_nodes, fmask=fmask)
    return out, gaps, failed


def _fleet_fold(n_policies: int, n_runs: int, n_epochs: int,
                n_survivors: int) -> Tuple[Optional[int], int]:
    """How the fleet core folds one cluster of ``n_policies`` lanes, and
    how many such clusters one dispatch may hold.  The survivor tile is
    the cluster's own (``_survivor_tile`` over its ``P`` lanes, as a
    standalone policy-stacked call on it decides); the clusters are as
    many as keep the dispatch's fold, all clusters' lanes together, within
    ``_SURVIVOR_TILE_ELEMENTS``, and at least one."""
    tile = _survivor_tile(n_policies, n_runs, n_epochs, n_survivors)
    held = n_epochs * n_survivors if tile is None else tile
    return tile, max(1, _SURVIVOR_TILE_ELEMENTS // (n_policies * n_runs
                                                    * held))


def _renewal_fleet_mc_core(inp: SweepInputs, key: jax.Array, makespan_s,
                           process, n_runs: int, max_failures: int):
    """``_renewal_mc_core`` in stats mode vmapped over clusters: ``inp``
    carries leading ``(C, P)`` axes (``core.optimize.fleet_policy_inputs``),
    ``makespan_s`` is ``(C, P)`` and ``process`` a same-family stack with
    leading ``(C,)`` leaves (``failures.stack_processes``).  Each cluster
    samples its OWN histories at the SAME key, exactly as a standalone
    policy-stacked call on it would (its shapes choose its survivor
    tiles, ``_fleet_fold``), so per-cluster rows are bit-identical to
    standalone calls and batch padding is inert (tests/test_fleet.py pins
    both).  The fold of ``C`` clusters holds ``C`` times one cluster's:
    the caller keeps ``C`` within ``_fleet_fold``'s count (docs/fleet.md,
    "The dispatch bound")."""
    _, n_policies, n_survivors = inp.period.shape
    tile, _ = _fleet_fold(n_policies, n_runs, max_failures, n_survivors)

    def one_cluster(inp_c, makespan_c, proc_c):
        out, _, _ = _renewal_mc_core(inp_c, key, makespan_c, proc_c, n_runs,
                                     max_failures, stats=True,
                                     survivor_tile=tile)
        return out

    return jax.vmap(one_cluster)(inp, makespan_s, process)


_renewal_lanes_jit = jax.jit(
    _renewal_lanes, static_argnames=("stats", "survivor_tile"))
_renewal_mc_jit = jax.jit(
    _renewal_mc_core, static_argnames=("n_runs", "max_failures", "stats"))
_renewal_fleet_mc_jit = jax.jit(
    _renewal_fleet_mc_core, static_argnames=("n_runs", "max_failures"))


# ---------------------------------------------------------------------------
# engine="pallas": float32 geometry + Kahan energy ledger
# (kernels/renewal_scan.py) behind the same Monte-Carlo entry points
# ---------------------------------------------------------------------------

def _pallas_interpret() -> bool:
    """Pallas execution mode for the current backend: the interpreter
    everywhere but TPU.  Interpret mode is traceable, so under ``jax.jit``
    the kernel lowers to ordinary XLA ops — the compiled CPU path CI
    exercises."""
    return jax.default_backend() != "tpu"


def _pack_pallas_inputs(stacked: SweepInputs, makespan_s):
    """Flatten a (scenario- or policy-)stacked ``SweepInputs`` plus the
    per-lane makespan into the Pallas kernel's packed operands
    (``kernels.renewal_scan``): the ``(P, N_PARAMS)`` scalar row, the
    ``(P, 3, N)`` node-state block, and the ``(P, 5, F)`` power ladder.
    Float32 casts of float64-built leaves are bit-exact for every value
    the configs carry (tests/test_precision.py pins this), so the policy
    path and the scenario path feed the kernel identical bits."""
    from repro.kernels import renewal_scan as _rs

    f4 = lambda x: jnp.asarray(x, jnp.float32)
    params = _rs.pack_lane_params(
        interval=stacked.interval, dur=stacked.dur, reexec0=stacked.reexec0,
        t_down=stacked.t_down, t_restart=stacked.t_restart, mu1=stacked.mu1,
        mu2=stacked.mu2, wait_mode=stacked.wait_mode,
        p_idle_wait=stacked.p_idle_wait, move_ahead=stacked.move_ahead,
        move_frac=stacked.move_frac, makespan=f4(makespan_s),
        sleep=jax.tree.map(f4, stacked.sleep))
    nodes = jnp.stack(
        [f4(stacked.age0), f4(stacked.exec_rem0), f4(stacked.period)], axis=1)
    lad = stacked.ladder
    ladder = jnp.stack([f4(lad.freq_ghz), f4(lad.p_comp), f4(lad.beta),
                        f4(lad.p_ckpt), f4(lad.gamma)], axis=1)
    return params, nodes, ladder


def _renewal_pallas_mc_core(stacked: SweepInputs, key: jax.Array, makespan_s,
                            process, n_runs: int, max_failures: int,
                            topology=None, compensated: bool = True):
    """Fused Monte-Carlo through the Pallas kernel: the SAME gap sampler as
    the x64 scan engine (``_sample_histories`` draws identical float32 bits
    with or without x64 — the CRN contract carries over unchanged), then
    the packed f32 composition.  ``makespan_s`` is per lane, so one core
    serves both the scenario stack (scalar broadcast) and the policy stack
    (per-policy wall makespans)."""
    from repro.kernels import renewal_scan as _rs

    n_nodes = stacked.period.shape[-1] + 1
    gaps32, failed, felled, fmask = _sample_histories(
        process, key, n_runs, max_failures, n_nodes, topology)
    params, nodes, ladder = _pack_pallas_inputs(stacked, makespan_s)
    gaps_t = jnp.asarray(gaps32, jnp.float32).T                  # (K, R)
    felled_t = (None if felled is None
                else jnp.transpose(felled, (1, 2, 0)).astype(jnp.float32))
    out = _rs.renewal_scan_pallas(
        params, nodes, ladder, gaps_t, felled_t,
        interpret=_pallas_interpret(), compensated=compensated)
    out["valid"] = jnp.transpose(out["valid"], (0, 2, 1)).astype(bool)
    out["truncated"] = out["truncated"].astype(bool)
    return _attach_failed_counts(out, failed, n_nodes, fmask=fmask)


_renewal_pallas_mc_jit = jax.jit(
    _renewal_pallas_mc_core,
    static_argnames=("n_runs", "max_failures", "compensated"))


# ---------------------------------------------------------------------------
# a study: the fused Monte-Carlo reduced over runs inside its own program
# ---------------------------------------------------------------------------

# integer columns of a study's reduction: these totals over runs, then the
# n_failures histogram over 0..max_failures, then failed_counts per node
_STUDY_TOTALS = ("n_points", "n_sleep", "n_min_freq", "n_comp_changed",
                 "n_infeasible")
# float64 columns of a study's reduction, named as the summary's fields
_STUDY_MOMENTS = ("mean_failures", "truncated_rate", "mean_energy_ref_j",
                  "mean_energy_int_j", "mean_saving_j", "p5_saving_j",
                  "p95_saving_j")
_PERCENTILES = (5.0, 95.0)


def _rank_counts(x, block: int = 256):
    """How many values of its row (last axis) each value of ``x`` is at
    least, by comparing every pair: ``block`` values at a time, so that no
    more than ``block`` x ``n`` comparisons are held at once."""
    cols = jnp.moveaxis(x, -1, 0)
    le = jax.lax.map(
        lambda c: jnp.sum(x <= c[..., None], axis=-1, dtype=jnp.int32),
        cols, batch_size=block)
    return jnp.moveaxis(le, 0, -1)


def _percentiles(x):
    """``np.percentile(x, q, axis=-1)`` at each of ``_PERCENTILES``, as
    numpy's default (``linear``) method computes it: the values at the
    ranks below and above the virtual index ``(n - 1) q / 100`` of the
    sorted row, interpolated as numpy's ``_lerp`` does, and NaN on a row
    holding a NaN.  The ``k``-th smallest value (from 0) is the least value
    with more than ``k`` values at most itself (``_rank_counts``): the
    entry of ``jnp.sort(x)`` at ``k`` exactly, without a sort, whose
    compile for a TPU v5e takes about two minutes at a study's (6, 4096)
    float64 (the counts: under a second)."""
    n = x.shape[-1]
    le = _rank_counts(x)
    kth = lambda k: jnp.min(jnp.where(le > min(k, n - 1), x, jnp.inf),
                            axis=-1)
    has_nan = jnp.any(jnp.isnan(x), axis=-1)
    out = []
    for q in _PERCENTILES:
        vi = (n - 1) * (q / 100)
        lo, g = int(vi), vi - int(vi)
        a, b = kth(lo), kth(lo + 1)
        diff = b - a
        p = b - diff * (1.0 - g) if g >= 0.5 else a + diff * g
        out.append(jnp.where(has_nan, jnp.nan, p))
    return out


def _study_reduce(out: dict, max_failures: int):
    """A study's reduction over runs: the per-run stats of the fused
    Monte-Carlo (``out``: ``RenewalDeviceStats`` fields, (S, R) per
    scenario and run, and ``failed_counts`` (S, n_nodes)) to what
    ``RenewalMonteCarloSummary`` reads, per scenario:

    * ``totals`` (S, 5 + max_failures + 1 + n_nodes) int32: the
      ``_STUDY_TOTALS`` summed over runs, the histogram of ``n_failures``
      over ``0..max_failures``, and ``failed_counts``;
    * ``moments`` (S, 7) float64, the ``_STUDY_MOMENTS``: means over runs
      (energies as ``_ordered_sum`` trees, the same wherever the lane is
      computed, so a campaign lane equals the scenario path bit for bit)
      and the saving's percentiles as ``np.percentile`` gives them.

    Float64 whatever the caller's mode: the float32 Pallas stats are cast
    first, as the host summary cast them."""
    n_runs = out["saving"].shape[-1]
    n_survivors = out["failed_counts"].shape[-1] - 1
    if n_runs * max_failures * max(n_survivors, 1) >= 2 ** 31:
        raise ValueError(
            f"{n_runs} runs x {max_failures} failures x {n_survivors} "
            "survivors overflow a study's int32 decision-point counts")
    count = lambda a: jnp.sum(a, axis=-1, dtype=jnp.int32)
    with jax.named_scope("renewal_fold"), jax.enable_x64(True):
        f8 = lambda a: jnp.asarray(a, jnp.float64)
        mean = lambda a: _ordered_sum(f8(a), axis=-1) / n_runs
        hist = count(out["n_failures"][..., None, :]
                     == jnp.arange(max_failures + 1, dtype=jnp.int32)[:, None])
        totals = jnp.concatenate(
            [jnp.stack([count(out[k]) for k in _STUDY_TOTALS], -1), hist,
             out["failed_counts"].astype(jnp.int32)], -1)
        saving = f8(out["saving"])
        moments = jnp.stack(
            [f8(count(out["n_failures"])) / n_runs,
             f8(count(out["truncated"])) / n_runs,
             mean(out["energy_ref"]), mean(out["energy_int"]), mean(saving),
             *_percentiles(saving)], -1)
    return totals, moments


_study_reduce_jit = jax.jit(_study_reduce, static_argnames=("max_failures",))


def _renewal_study_core(stacked: SweepInputs, key: jax.Array, makespan_s,
                        process, n_runs: int, max_failures: int,
                        topology=None, engine: str = "scan",
                        survivor_tile: Optional[int] = None):
    """One study as one program: the engine's fused Monte-Carlo in stats
    mode (``_renewal_mc_core`` under x64, or ``_renewal_pallas_mc_core`` in
    float32), then ``_study_reduce``.  Only the reduction leaves the
    device, so the compiler drops what no summary reads: the scan's wall
    clock (``end_time``) and the balanced energy.  ``survivor_tile``
    overrides how the scan engine's fold takes the survivor axis
    (``_survivor_tile``), for tests."""
    if engine == "pallas":
        out = _renewal_pallas_mc_core(stacked, key, makespan_s, process,
                                      n_runs, max_failures, topology=topology)
    else:
        out, _, _ = _renewal_mc_core(stacked, key, makespan_s, process,
                                     n_runs, max_failures, stats=True,
                                     topology=topology,
                                     survivor_tile=survivor_tile)
    return _study_reduce(out, max_failures)


_renewal_study_jit = jax.jit(
    _renewal_study_core,
    static_argnames=("n_runs", "max_failures", "engine", "survivor_tile"))


def _compose_lanes(stacked: SweepInputs, gaps, makespan_s, failed_node,
                   felled) -> RenewalDeviceResult:
    """The explicit-history composition of stacked lanes, one jitted
    dispatch (call under x64)."""
    gaps = jnp.atleast_2d(jnp.asarray(np.asarray(gaps, np.float64)))
    if felled is not None:
        felled = jnp.asarray(np.asarray(felled, bool))
    out = _renewal_lanes_jit(stacked, gaps, _makespan_operand(
        makespan_s, "scan"), felled=felled)
    return _wrap_device_result(out, gaps, failed_node)


def renewal_compose_policies(stacked: SweepInputs, gaps, makespan_s,
                             felled=None):
    """Compose explicit failure histories for a policy-stacked scenario.

    ``stacked`` is a policy-stacked float64 ``SweepInputs`` (leading policy
    axis P over the knob leaves — build it with ``core.optimize.
    policy_inputs``), ``makespan_s`` a (P,) per-policy wall makespan, and
    ``gaps`` (R, K) or (K,) histories shared by every policy (CRN).
    ``felled`` ((R, K, N) survivor-slot mask — see ``renewal_compose``) is
    likewise shared across policies.  One jitted dispatch; returns a
    ``RenewalDeviceResult`` whose leading axis is the policy axis.
    """
    with jax.enable_x64():
        return _compose_lanes(stacked, gaps, makespan_s, None, felled)


def renewal_monte_carlo_policies(
    stacked: SweepInputs,
    key: jax.Array,
    *,
    makespan_s,
    n_runs: int = 256,
    max_failures: int = 32,
    mtbf_s: Optional[float] = None,
    process: Optional[failures.FailureProcess] = None,
    stats: bool = True,
    topology=None,
    engine: str = "scan",
):
    """Whole-run Monte-Carlo over a policy grid — one fused dispatch.

    ``renewal_monte_carlo_device`` over a policy-stacked float64
    ``SweepInputs`` (``core.optimize.policy_inputs``) with a per-policy
    ``makespan_s``, (P,): the same program, staging and options
    (``stats``, ``topology``, ``engine``; the kernel takes float32 casts of
    the float64 leaves, which are bit-exact).  Every policy sees the same
    histories (common random numbers), so for a fixed ``key`` each
    policy's per-run energies are bit-identical to a standalone
    ``renewal_monte_carlo_device`` call on that policy's config with that
    policy's makespan (tests/test_optimize.py): the optimizer's
    low-variance comparisons rest on it.  ``stats=True`` is the default
    here (the optimizer's hot path); every field leads with the policy
    axis.

    **Cluster axis (fleet dispatch).**  A ``stacked`` whose knob leaves
    carry TWO leading axes ``(C, P)`` (``core.optimize.
    fleet_policy_inputs``) evaluates C heterogeneous cluster profiles x P
    policies in the same single program: ``makespan_s`` must then be
    ``(C, P)`` and ``process`` a same-family stack with leading ``(C,)``
    parameter leaves (``failures.stack_processes``).  Every cluster lane
    samples its own histories at the SAME key through its own parameters,
    so per-cluster rows are bit-identical to standalone per-cluster calls
    (the fleet CRN contract, tests/test_fleet.py) and answers are
    independent of the batch they shipped in — which is what makes
    request-batch padding inert (docs/fleet.md).  Clusters go out in as
    many dispatches as keep each one's survivor fold within the budget
    (``_fleet_fold``).  The cluster axis is scan-engine, stats-only,
    iid-sampler territory for now (``engine="pallas"``, ``stats=False``,
    and ``topology`` all raise).
    """
    if stacked.interval.ndim == 2:
        proc = failures.as_process(process, mtbf_s)
        if engine != "scan":
            raise ValueError(
                "the cluster axis runs on the scan engine only (the Pallas "
                "kernel's grid is policies x runs; see ROADMAP)")
        if not stats:
            raise ValueError(
                "cluster-stacked dispatch is the stats-only advisory hot "
                "path; use per-cluster calls for per-epoch diagnostics")
        if topology is not None:
            raise ValueError(
                "cluster-stacked dispatch samples iid per cluster; "
                "correlated topologies are a single-cluster feature")
        n_clusters = stacked.interval.shape[0]
        leaves = jax.tree.leaves(proc)
        if not leaves or any(
                np.ndim(l) < 1 or np.shape(l)[0] != n_clusters for l in leaves):
            raise ValueError(
                f"cluster-stacked dispatch needs a process stacked over the "
                f"{n_clusters} cluster lanes (failures.stack_processes)")
        with jax.enable_x64():
            makespan = jnp.asarray(np.asarray(makespan_s, np.float64))
            if makespan.shape != stacked.interval.shape:
                raise ValueError(
                    f"fleet makespan_s must be (C, P) = "
                    f"{stacked.interval.shape}, got {makespan.shape}")
            _, per = _fleet_fold(stacked.interval.shape[1], n_runs,
                                 max_failures, stacked.period.shape[-1])
            part = lambda lo: lambda a: a[lo:lo + per]
            outs = [_renewal_fleet_mc_jit(
                jax.tree.map(part(lo), stacked), key, makespan[lo:lo + per],
                jax.tree.map(part(lo), proc),
                n_runs=n_runs, max_failures=max_failures)
                for lo in range(0, n_clusters, per)]
            return _wrap_device_stats(jax.tree.map(
                lambda *a: jnp.concatenate(a), *outs) if len(outs) > 1
                else outs[0])
    return _renewal_mc_runs(
        stacked, key, makespan_s, process, mtbf_s, n_runs=n_runs,
        max_failures=max_failures, stats=stats, topology=topology,
        engine=engine)


def _check_renewal_config(cfg: ScenarioConfig) -> None:
    """The renewal preconditions shared by host and device paths."""
    if any(sv.peer != 0 for sv in cfg.survivors):
        raise ValueError(
            f"{cfg.name}: renewal composition requires direct blockers (peer == 0)")
    ages0 = np.array([s.ckpt_age for s in cfg.survivors], np.float64)
    if np.any(ages0 > cfg.ckpt_interval) or cfg.t_reexec > cfg.ckpt_interval:
        raise ValueError(
            f"{cfg.name}: ckpt_age/t_reexec exceed ckpt_interval")
    if any(s.level != 0 for s in cfg.survivors):
        raise ValueError(
            f"{cfg.name}: renewal composition starts from a balanced app "
            "(survivor levels must be 0; non-fa starts are single-failure inputs)")


def _cfg_fingerprint(cfg: ScenarioConfig) -> tuple:
    """Hashable content key of everything ``sweep_inputs`` reads from a
    config — the device-input cache below keys on it."""
    pt = cfg.profile.power_table
    sl = cfg.profile.sleep
    return (
        cfg.name, cfg.survivors, cfg.t_down, cfg.t_restart, cfg.t_reexec,
        cfg.ckpt_interval, cfg.ckpt_duration, int(cfg.wait_mode),
        cfg.move_ahead, cfg.move_ahead_frac, cfg.mu1, cfg.mu2,
        cfg.profile.p_idle_wait,
        pt.freq_ghz.tobytes(), pt.p_comp.tobytes(), pt.beta.tobytes(),
        pt.p_ckpt.tobytes(), pt.gamma.tobytes(),
        sl.t_go_sleep, sl.t_wakeup, sl.p_go_sleep, sl.p_wakeup, sl.p_sleep,
    )


_renewal_inputs_cache: dict = {}


def _renewal_device_inputs(cfgs, dtype=jnp.float64):
    """Validate and stack scenarios into ``SweepInputs`` of ``dtype``
    (float64 for the x64 scan engine — call under ``enable_x64`` — float32
    for the Pallas engine).  Accepts one ``ScenarioConfig`` or a sequence;
    always returns the list plus a stacked pytree with a leading scenario
    axis.

    Stacking is memoized on the configs' *content* AND the dtype regime:
    rebuilding the device arrays costs tens of milliseconds of host time
    (dozens of small transfers), which would otherwise dominate the jitted
    dispatch itself on repeated calls — the whole point of the device
    engine.  The regime component is the *effective* dtype ``jnp.asarray``
    yields right now (a float64 request outside ``enable_x64`` builds
    float32 arrays), so toggling x64 around a cached call — or interleaving
    the f32 Pallas engine with the x64 scan — can never serve stale-dtype
    stacked inputs (tests/test_precision.py pins the regression).
    """
    cfg_list = [cfgs] if isinstance(cfgs, ScenarioConfig) else list(cfgs)
    if not cfg_list:
        raise ValueError("no scenarios to compose")
    regime = jnp.asarray(0.0, dtype).dtype.name
    cache_key = (regime,) + tuple(_cfg_fingerprint(c) for c in cfg_list)
    stacked = _renewal_inputs_cache.get(cache_key)
    if stacked is None:
        for cfg in cfg_list:
            _check_renewal_config(cfg)
        inputs = [sweep_inputs(c, dtype) for c in cfg_list]
        shapes = {i.exec_rem0.shape for i in inputs}
        ladders = {i.ladder.freq_ghz.shape for i in inputs}
        if len(shapes) != 1 or len(ladders) != 1:
            raise ValueError(
                f"stacked scenarios must share survivor count and ladder size "
                f"(got {shapes}, {ladders})")
        stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *inputs)
        if len(_renewal_inputs_cache) >= 64:
            _renewal_inputs_cache.clear()
        _renewal_inputs_cache[cache_key] = stacked
    return cfg_list, stacked


def _wrap_device_result(out: dict, gaps: jax.Array,
                        failed_node) -> RenewalDeviceResult:
    valid = out["valid"]
    if failed_node is None:
        failed = jnp.zeros(gaps.shape, jnp.int32)
    else:
        failed = jnp.asarray(failed_node, jnp.int32)
    failed = jnp.where(valid, jnp.broadcast_to(failed, valid.shape), -1)
    return RenewalDeviceResult(gaps=gaps, failed_node=failed, **out)


def _wrap_device_stats(out: dict) -> RenewalDeviceStats:
    return RenewalDeviceStats(**out)


def renewal_compose_device(cfgs, gaps, makespan_s: float,
                           failed_node=None, felled=None) -> RenewalDeviceResult:
    """Compose whole-run multi-failure energy on device for explicit
    failure histories.

    The device analog of ``renewal_compose``: ``cfgs`` is one
    ``ScenarioConfig`` or a sequence sharing survivor count and ladder size
    (the Table-4 six); ``gaps`` is (R, K) or (K,) balanced-execution wall
    seconds, shared across scenarios.  ``felled`` ((R, K, N) survivor-slot
    mask or None) is the correlated multi-node extension — semantics as
    ``renewal_compose``.  One jitted scan-over-epochs program evaluates
    every (scenario, run, epoch, survivor) point; semantics — occurrence,
    truncation, re-anchoring, energy accounting — match the host float64
    oracle at ~1e-9 relative (tests/test_renewal_device.py).
    """
    with jax.enable_x64():
        _, stacked = _renewal_device_inputs(cfgs)
        return _compose_lanes(stacked, gaps, makespan_s, failed_node, felled)


def renewal_monte_carlo_device(
    cfgs,
    key: jax.Array,
    *,
    n_runs: int = 256,
    makespan_s: float = 30 * 24 * 3600.0,
    mtbf_s: float = 14 * 24 * 3600.0,
    max_failures: int = 64,
    stats: bool = False,
    process: Optional[failures.FailureProcess] = None,
    topology=None,
    engine: str = "scan",
):
    """Whole-run Monte-Carlo with gap sampling fused into the device program.

    Per-node failure sequences (``renewal_failure_gaps`` semantics and
    bit-identical histories for the same key — exponential by default,
    any ``failures.FailureProcess`` via ``process``; ``topology``, a
    ``core.topology.Topology``, swaps in the correlated shock scan) are
    drawn *inside* the jitted program, then composed by the same scan as
    ``renewal_compose_device``: one dispatch.

    ``stats=False`` returns the full ``RenewalDeviceResult`` (per-epoch
    decisions and energies — the cross-validation view); ``stats=True``
    the lean ``RenewalDeviceStats`` (whole-run energies + integer action
    counts), the hot path.  ``engine="pallas"`` dispatches the float32
    Kahan-ledger kernel (``kernels.renewal_scan``), stats only, on the
    same histories (<= 1e-4 relative to the float64 oracle,
    tests/test_renewal_pallas.py).

    This is the per-run view, for callers that read runs one by one (the
    policy grid, ``FleetAdvisor``, the FT controller read ``end_time``).
    A study's summaries come from ``renewal_monte_carlo_scenarios``, whose
    program reduces over runs on the device.
    """
    return _renewal_mc_runs(
        cfgs, key, makespan_s, process, mtbf_s, n_runs=n_runs,
        max_failures=max_failures, stats=stats, topology=topology,
        engine=engine)


def _makespan_operand(makespan_s, engine: str):
    """The makespan as the engine's program takes it: float32 for the
    kernel; for the scan a Python float where it is one number for every
    lane, else the lanes' float64 array (call under x64)."""
    if engine == "pallas":
        return jnp.asarray(np.asarray(makespan_s, np.float32))
    if np.ndim(makespan_s) == 0:
        return float(makespan_s)
    return jnp.asarray(np.asarray(makespan_s, np.float64))


@contextlib.contextmanager
def _staged(lanes, process, mtbf_s, engine: str):
    """Stage a Monte-Carlo dispatch under the ``sweep.stage`` span: yields
    the stacked lanes in the engine's dtype and the failure process.
    Configs are validated and stacked in it (``_renewal_device_inputs``); a
    float64 policy stack (``core.optimize.policy_inputs``) is cast to
    float32 for the kernel, bit-exact for every value the configs carry
    (tests/test_precision.py).  The scan engine's x64 mode opens while
    staging and holds until the block ends, so the dispatch inside it runs
    in x64 and one span covers the staging alone."""
    with contextlib.ExitStack() as x64:
        with jax.profiler.TraceAnnotation("sweep.stage"):
            proc = failures.as_process(process, mtbf_s)
            if engine not in ("scan", "pallas"):
                raise ValueError(
                    f"unknown engine {engine!r} (use 'scan' or 'pallas')")
            if engine == "scan":
                x64.enter_context(jax.enable_x64())
            if not isinstance(lanes, SweepInputs):
                _, lanes = _renewal_device_inputs(
                    lanes, jnp.float32 if engine == "pallas" else jnp.float64)
            elif engine == "pallas":
                lanes = jax.tree.map(
                    lambda a: a.astype(jnp.float32)
                    if jnp.issubdtype(a.dtype, jnp.floating) else a, lanes)
        yield lanes, proc


def _renewal_mc_runs(lanes, key, makespan_s, process, mtbf_s, *,
                     n_runs: int, max_failures: int, stats: bool, topology,
                     engine: str):
    """The per-run Monte-Carlo of stacked lanes, scenario configs or a
    policy stack, as one staged dispatch of ``engine``'s program
    (``_renewal_mc_jit`` or ``_renewal_pallas_mc_jit``): a
    ``RenewalDeviceStats`` in stats mode, else a ``RenewalDeviceResult``."""
    if engine == "pallas" and not stats:
        raise ValueError(
            "engine='pallas' is the stats-only hot path; use the scan "
            "engine for per-epoch RenewalDeviceResult diagnostics")
    with _staged(lanes, process, mtbf_s, engine) as (stacked, proc):
        with jax.profiler.TraceAnnotation("sweep.dispatch"):
            makespan = _makespan_operand(makespan_s, engine)
            if engine == "pallas":
                return _wrap_device_stats(_renewal_pallas_mc_jit(
                    stacked, key, makespan, proc, n_runs=n_runs,
                    max_failures=max_failures, topology=topology))
            out, gaps, failed = _renewal_mc_jit(
                stacked, key, makespan, proc, n_runs=n_runs,
                max_failures=max_failures, stats=stats, topology=topology)
        if stats:
            return _wrap_device_stats(out)
        return _wrap_device_result(out, gaps, failed)


def _renewal_study_device(cfgs, key, *, n_runs: int, makespan_s: float,
                          mtbf_s: float, max_failures: int, process,
                          topology, engine: str):
    """Stage and dispatch one study's program (``_renewal_study_core``);
    returns its reduction over runs, ``(totals, moments)``, on the
    device."""
    with _staged(cfgs, process, mtbf_s, engine) as (stacked, proc):
        n_lanes, n = stacked.period.shape
        tile = (None if engine == "pallas"
                else _survivor_tile(n_lanes, n_runs, max_failures, n))
        # a run's fold in one piece, or per epoch in tiles of survivors
        tiles = 1 if tile is None else max_failures * -(-n // tile)
        # the scan advances the sawtooth per node in epoch 0 and per run
        # after it (``_renewal_scan``); the kernel, per node in every epoch
        node_epochs = max_failures if engine == "pallas" else 1
        with jax.profiler.TraceAnnotation(
                "sweep.dispatch", survivors=n, survivor_tile=tile or n,
                tiles=tiles, node_epochs=node_epochs,
                run_epochs=max_failures - node_epochs):
            return _renewal_study_jit(
                stacked, key, _makespan_operand(makespan_s, engine), proc,
                n_runs=n_runs, max_failures=max_failures, topology=topology,
                engine=engine)


@dataclasses.dataclass(frozen=True)
class RenewalMonteCarloSummary:
    """Whole-run expectation view of a scenario under repeated failures."""

    n_runs: int
    makespan_s: float
    mtbf_s: float               # per-node MTBF
    max_failures: int
    # failure-count distribution over runs
    mean_failures: float
    failure_count_hist: dict    # n_failures -> fraction of runs
    per_node_failures: tuple    # mean failures per node over the makespan
    truncated_rate: float       # runs that hit max_failures before makespan
    # whole-run energies (J)
    mean_energy_ref_j: float
    mean_energy_int_j: float
    mean_saving_j: float
    p5_saving_j: float
    p95_saving_j: float
    mean_saving_pct: float      # 100 * E[saving] / E[reference energy]
    # action occupancy over valid (run, epoch, node) points
    sleep_occupancy: float
    min_freq_rate: float
    comp_change_rate: float
    infeasible_rate: float
    # expected savings scaled to a year of operation
    annual_saving_j: float


def _assemble_summary(
    *,
    per_node,
    n_runs: int,
    makespan_s: float,
    mtbf_s: float,
    max_failures: int,
    **fields,
) -> RenewalMonteCarloSummary:
    """The single ``RenewalMonteCarloSummary`` construction behind every
    engine, from one scenario's reduction over runs: the ``_STUDY_MOMENTS``,
    ``failure_count_hist`` and the four action rates in ``fields``, and
    the mean failures per node.  The derived formulas (saving pct, annual
    scaling) exist once, so the engines' summaries can only differ where
    their reductions do.  The host oracle reduces with numpy
    (``_run_moments``), a study on the device (``_study_reduce``, read by
    ``_study_summary``)."""
    mean_ref, mean_saving = fields["mean_energy_ref_j"], fields["mean_saving_j"]
    return RenewalMonteCarloSummary(
        n_runs=n_runs,
        makespan_s=float(makespan_s),
        mtbf_s=float(mtbf_s),
        max_failures=max_failures,
        per_node_failures=tuple(per_node),
        mean_saving_pct=float(100.0 * mean_saving / max(mean_ref, 1e-9)),
        annual_saving_j=mean_saving * SECONDS_PER_YEAR / float(makespan_s),
        **fields,
    )


def _run_moments(n_failures, truncated, energy_ref, energy_int,
                 saving) -> dict:
    """numpy's reduction over one scenario's runs, the host oracle's: the
    ``_STUDY_MOMENTS`` and the failure-count histogram."""
    counts = np.asarray(n_failures)
    saving = np.asarray(saving, np.float64)
    return dict(
        mean_failures=float(counts.mean()),
        failure_count_hist={
            int(c): float(np.mean(counts == c)) for c in np.unique(counts)},
        truncated_rate=float(np.mean(np.asarray(truncated, bool))),
        mean_energy_ref_j=float(np.asarray(energy_ref, np.float64).mean()),
        mean_energy_int_j=float(np.asarray(energy_int, np.float64).mean()),
        mean_saving_j=float(saving.mean()),
        p5_saving_j=float(np.percentile(saving, 5)),
        p95_saving_j=float(np.percentile(saving, 95)),
    )


def _renewal_summary(
    *,
    valid,
    failed_node,
    truncated,
    energy_ref,
    energy_int,
    saving,
    wait_action,
    comp_changed,
    feasible_any,
    n_survivors: int,
    n_runs: int,
    makespan_s: float,
    mtbf_s: float,
    max_failures: int,
    felled=None,
    fmask=None,
) -> RenewalMonteCarloSummary:
    """Reduce one scenario's (R, K[, N]) host-oracle arrays to expectations
    (rates as means over valid decision points; assembly shared with the
    device path via ``_assemble_summary``).  ``felled`` (survivor-slot
    mask) excludes felled slots from the action-occupancy points; ``fmask``
    (physical-node mask) attributes every felled node in ``per_node`` —
    both mirror what the device path's integer counts do."""
    valid = np.asarray(valid, bool)
    failed_node = np.asarray(failed_node)
    if fmask is None:
        per_node = tuple(
            float(np.mean(np.sum((failed_node == m) & valid, axis=1)))
            for m in range(n_survivors + 1))
    else:
        fmask = np.asarray(fmask, bool)
        per_node = tuple(
            float(np.mean(np.sum(fmask[:, :, m] & valid, axis=1)))
            for m in range(n_survivors + 1))
    v = valid[:, :, None] & np.ones(n_survivors, bool)
    if felled is not None:
        v = v & ~np.asarray(felled, bool)
    actions = np.asarray(wait_action)[v.nonzero()] if v.any() else np.array([])
    pick = lambda a: np.asarray(a)[v.nonzero()]
    return _assemble_summary(
        **_run_moments(valid.sum(axis=1), truncated, energy_ref, energy_int,
                       saving),
        per_node=per_node,
        sleep_occupancy=float(np.mean(actions == em.WaitAction.SLEEP))
        if actions.size else 0.0,
        min_freq_rate=float(np.mean(actions == em.WaitAction.MIN_FREQ))
        if actions.size else 0.0,
        comp_change_rate=float(np.mean(pick(comp_changed)))
        if actions.size else 0.0,
        infeasible_rate=float(np.mean(~np.asarray(pick(feasible_any), bool)))
        if actions.size else 0.0,
        n_runs=n_runs, makespan_s=makespan_s, mtbf_s=mtbf_s,
        max_failures=max_failures,
    )


def _study_summary(
    totals, moments, *,
    n_runs: int, makespan_s: float, mtbf_s: float, max_failures: int,
) -> RenewalMonteCarloSummary:
    """One scenario's summary from its row of a study's reduction
    (``_study_reduce``): the rates are integer ratios over the valid
    decision points and the histogram the non-zero bins over ``n_runs``,
    exactly the oracle's ``np.mean`` over the same points and runs;
    assembly shared with the host path via ``_assemble_summary``."""
    totals = np.asarray(totals).tolist()
    k = len(_STUDY_TOTALS)
    total = dict(zip(_STUDY_TOTALS, totals[:k]))
    hist = totals[k:k + max_failures + 1]
    n_pts = total["n_points"]
    rate = lambda name: total[name] / n_pts if n_pts else 0.0
    return _assemble_summary(
        **dict(zip(_STUDY_MOMENTS, np.asarray(moments, np.float64).tolist())),
        failure_count_hist={c: h / n_runs for c, h in enumerate(hist) if h},
        per_node=[c / n_runs for c in totals[k + max_failures + 1:]],
        sleep_occupancy=rate("n_sleep"),
        min_freq_rate=rate("n_min_freq"),
        comp_change_rate=rate("n_comp_changed"),
        infeasible_rate=rate("n_infeasible"),
        n_runs=n_runs, makespan_s=makespan_s, mtbf_s=mtbf_s,
        max_failures=max_failures,
    )


def renewal_monte_carlo(
    cfg: ScenarioConfig,
    key: jax.Array,
    n_runs: int = 256,
    makespan_s: float = 30 * 24 * 3600.0,
    mtbf_s: float = 14 * 24 * 3600.0,
    max_failures: int = 64,
    engine: str = "scan",
    process: Optional[failures.FailureProcess] = None,
    topology=None,
) -> RenewalMonteCarloSummary:
    """Monte-Carlo whole-run energy under per-node failure processes.

    Samples ``n_runs`` failure histories (``renewal_failure_gaps``
    semantics: independent renewal failures per node — exponential at
    ``mtbf_s`` by default, any ``failures.FailureProcess`` via ``process``
    — with the quiesce policy for arrivals during an open epoch), composes
    each run, and reduces to whole-run expectations.  Deterministic for a
    fixed ``key``.  ``makespan_s`` is the application's balanced-execution
    wall length; recovery epochs extend the wall end beyond it.  With a
    ``process`` the summary's ``mtbf_s`` reports the process's mean gap
    (averaged over heterogeneous nodes).

    ``engine="scan"`` (default) and ``engine="pallas"`` run the study of
    ``renewal_monte_carlo_scenarios`` on this one scenario: the x64 scan
    engine or the float32 Kahan-ledger kernel (``kernels.renewal_scan`` —
    see docs/sweep.md "Precision strategy").  ``engine="host"`` runs the
    float64 oracle (``renewal_compose``) — same histories, same summary
    fields, pinned together by tests/test_renewal_device.py and
    tests/test_renewal_pallas.py.  For several scenarios at once call
    ``renewal_monte_carlo_scenarios`` (one device dispatch).

    ``topology`` (a ``core.topology.Topology`` over the scenario's node
    count) swaps in the correlated shock sampler on either engine — shock
    epochs fell several nodes at once; the bit-identity contract between
    the engines carries over to the correlated histories.
    """
    if engine not in ("scan", "pallas", "host"):
        raise ValueError(
            f"unknown engine {engine!r} (use 'scan', 'pallas' or 'host')")
    if engine != "host":
        return renewal_monte_carlo_scenarios(
            [cfg], key, n_runs=n_runs, makespan_s=makespan_s, mtbf_s=mtbf_s,
            max_failures=max_failures, process=process, topology=topology,
            engine=engine)[cfg.name]
    if process is not None:
        mtbf_s = float(np.mean(failures.as_process(process).mean_s()))
    kw = dict(n_runs=n_runs, makespan_s=makespan_s, mtbf_s=mtbf_s,
              max_failures=max_failures)
    n_nodes = len(cfg.survivors) + 1
    if topology is None:
        gaps, failed = renewal_failure_gaps(
            key, n_runs, n_nodes, max_failures, mtbf_s, process=process)
        felled = fmask = None
    else:
        gaps, failed, fmask = renewal_failure_gaps(
            key, n_runs, n_nodes, max_failures, mtbf_s, process=process,
            topology=topology)
        felled = np.asarray(node_topology.survivor_slot_mask(fmask, failed))
        fmask = np.asarray(fmask)
    res = renewal_compose(cfg, gaps, makespan_s, failed_node=failed,
                          felled=felled)
    return _renewal_summary(
        felled=felled,
        fmask=fmask,
        valid=res.valid,
        failed_node=res.failed_node,
        truncated=res.truncated,
        energy_ref=res.energy_ref,
        energy_int=res.energy_int,
        saving=res.saving,
        wait_action=np.asarray(res.decision.wait_action),
        comp_changed=np.asarray(res.decision.comp_changed),
        feasible_any=np.asarray(res.decision.feasible_any),
        n_survivors=len(cfg.survivors),
        **kw,
    )


def renewal_monte_carlo_scenarios(
    cfgs: Sequence[ScenarioConfig],
    key: jax.Array,
    n_runs: int = 256,
    makespan_s: float = 30 * 24 * 3600.0,
    mtbf_s: float = 14 * 24 * 3600.0,
    max_failures: int = 64,
    process: Optional[failures.FailureProcess] = None,
    topology=None,
    engine: str = "scan",
) -> dict:
    """name -> ``RenewalMonteCarloSummary`` for stacked scenarios from ONE
    fused device dispatch (sampling + scan + Algorithm 1 + the reduction
    over runs, ``_renewal_study_core``).

    Every scenario sees the same sampled failure histories — exactly what
    calling ``renewal_monte_carlo`` per scenario with the same key (and
    ``process``, and ``topology`` for the correlated family) yields, minus
    S-1 dispatches and all the host round-trips.  Only the summaries'
    numbers leave the device, a few KB a study whatever the run count.
    ``engine="pallas"`` swaps in the float32 Kahan-ledger kernel
    (``kernels.renewal_scan``).
    """
    with jax.profiler.TraceAnnotation("sweep.study"):
        cfg_list = list(cfgs)
        if process is not None:
            mtbf_s = float(np.mean(failures.as_process(process).mean_s()))
        kw = dict(n_runs=n_runs, makespan_s=makespan_s, mtbf_s=mtbf_s,
                  max_failures=max_failures)
        study = _renewal_study_device(
            cfg_list, key, process=process, topology=topology,
            engine=engine, **kw)
        n_bytes = sum(a.nbytes for a in study)
        # one transfer a reduction array for the whole study
        with jax.profiler.TraceAnnotation("sweep.fetch", bytes=n_bytes):
            totals, moments = jax.device_get(study)
        with jax.profiler.TraceAnnotation("sweep.summarize"):
            return {
                cfg.name: _study_summary(totals[s], moments[s], **kw)
                for s, cfg in enumerate(cfg_list)
            }
