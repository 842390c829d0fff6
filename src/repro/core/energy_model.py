"""The paper's energy model (eqs. (1)-(15)), vectorized in JAX.

Every function broadcasts over a leading node dimension ``N`` and a trailing
frequency-ladder dimension ``F`` so that one jitted call evaluates every
(surviving node x candidate frequency) cell at once.  This is the scaling
departure from the paper's sequential C simulator: strategy evaluation for
tens of thousands of nodes is a single XLA program (see
``benchmarks/strategy_throughput.py``).

Notation (paper Table 2):
  t_comp_fa   T_comp at the maximum frequency fa (pure execution, no ckpt)
  t_failed    time from failure until the recovered process reaches the
              rendezvous with this node  (eq. 14: T_recover + alpha*I_comm)
  n_ckpt      checkpoints inside the intervention interval (incl. move-ahead)
  t_ckpt      checkpoint duration at fa
  beta/gamma  slowdown of execution / checkpoint at each ladder level
  p_comp/p_ckpt  power at each ladder level

Model conventions validated against the paper's Table 4 (see
``tests/test_energy_model.py``):
  * the reference case ("B: failure and no action") runs compute, checkpoints
    and the (active) wait at fa; active-wait power equals the application
    power at the spinning frequency;
  * wait duration subtracts checkpoint time as well:
    T_wait = T_failed - (T_comp*beta + N_ckpt*T_ckpt*gamma).  Algorithm 1
    line 10 omits the checkpoint term but the paper's own Table 4 rows
    (scenario 2 vs 6) include it; we follow the data;
  * sleep saving over a wait W (active ref):  W*(P_fa - P_sleep) - E_trans
    with E_trans = 25*(51-12) + 5*(91-12) = 1370 J for the paper's S3 node.
"""
from __future__ import annotations

import dataclasses
import enum
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.characterization import MachineProfile, PowerTable, SleepSpec

__all__ = [
    "WaitMode",
    "WaitAction",
    "LadderArrays",
    "SleepArrays",
    "comp_time",
    "comp_energy",
    "take_level",
    "wait_time",
    "awake_wait_energy",
    "sleep_wait_energy",
    "sleep_allowed",
    "reference_energy",
    "intervention_energy",
]


class WaitMode(enum.IntEnum):
    """How the runtime is configured to wait on messages (paper §2.1)."""

    ACTIVE = 0   # spin: dissipates application power at the spinning frequency
    IDLE = 1     # block: dissipates ~base power


class WaitAction(enum.IntEnum):
    """Selected action for the waiting phase (paper §3.2)."""

    NONE = 0       # idle wait, nothing to do
    MIN_FREQ = 1   # active wait pinned to the minimum ladder frequency
    SLEEP = 2      # ACPI S-state for the bulk of the wait


@dataclasses.dataclass(frozen=True)
class LadderArrays:
    """jnp view of a PowerTable."""

    freq_ghz: jax.Array
    p_comp: jax.Array
    beta: jax.Array
    p_ckpt: jax.Array
    gamma: jax.Array

    @classmethod
    def from_table(cls, table: PowerTable, dtype: Any = jnp.float32) -> "LadderArrays":
        return cls(
            freq_ghz=jnp.asarray(table.freq_ghz, dtype),
            p_comp=jnp.asarray(table.p_comp, dtype),
            beta=jnp.asarray(table.beta, dtype),
            p_ckpt=jnp.asarray(table.p_ckpt, dtype),
            gamma=jnp.asarray(table.gamma, dtype),
        )

    @property
    def num_levels(self) -> int:
        return len(self.freq_ghz)


@dataclasses.dataclass(frozen=True)
class SleepArrays:
    """jnp view of a SleepSpec."""

    t_go_sleep: jax.Array
    t_wakeup: jax.Array
    p_go_sleep: jax.Array
    p_wakeup: jax.Array
    p_sleep: jax.Array

    @classmethod
    def from_spec(cls, spec: SleepSpec, dtype: Any = jnp.float32) -> "SleepArrays":
        return cls(
            t_go_sleep=jnp.asarray(spec.t_go_sleep, dtype),
            t_wakeup=jnp.asarray(spec.t_wakeup, dtype),
            p_go_sleep=jnp.asarray(spec.p_go_sleep, dtype),
            p_wakeup=jnp.asarray(spec.p_wakeup, dtype),
            p_sleep=jnp.asarray(spec.p_sleep, dtype),
        )

    @property
    def transition_time(self) -> jax.Array:
        return self.t_go_sleep + self.t_wakeup

    @property
    def transition_energy(self) -> jax.Array:
        return self.t_go_sleep * self.p_go_sleep + self.t_wakeup * self.p_wakeup


jax.tree_util.register_dataclass(
    LadderArrays, data_fields=["freq_ghz", "p_comp", "beta", "p_ckpt", "gamma"], meta_fields=[]
)
jax.tree_util.register_dataclass(
    SleepArrays,
    data_fields=["t_go_sleep", "t_wakeup", "p_go_sleep", "p_wakeup", "p_sleep"],
    meta_fields=[],
)


# ---------------------------------------------------------------------------
# eqs (4)-(6): computation phase
# ---------------------------------------------------------------------------

def _ladderize(n_ckpt, per_level: bool):
    """n_ckpt is either per-node (...,) or already per-(node, level) (..., F)."""
    n_ckpt = jnp.asarray(n_ckpt)
    return n_ckpt if per_level else n_ckpt[..., None]


def take_level(a, level):
    """Gather the trailing ladder axis of ``a`` at per-node ``level``.

    ``a`` is (..., F); ``level`` broadcasts against the node batch shape.
    Used wherever a per-node *current* ladder level (renewal runs: survivors
    may still hold a non-fa level from a prior failure epoch) selects one
    column of a per-level array.

    A *concrete* scalar ``level`` (e.g. the default reference level 0 —
    a trace-time constant, not a tracer) takes the static-slice fast path:
    the slice fuses with the producers of ``a`` instead of forcing the
    whole batched (..., F) intermediate into memory, which matters when
    the device renewal engine evaluates every (scenario, run, epoch,
    survivor) point in one program.
    """
    a = jnp.asarray(a)
    if isinstance(level, int) or (
        not isinstance(level, jax.core.Tracer)
        and np.ndim(level) == 0
    ):
        return a[..., int(level)]
    level = jnp.asarray(level, jnp.int32)
    shape = jnp.broadcast_shapes(a.shape[:-1], level.shape)
    a = jnp.broadcast_to(a, shape + a.shape[-1:])
    idx = jnp.broadcast_to(level, shape)[..., None]
    return jnp.take_along_axis(a, idx, axis=-1)[..., 0]


def comp_time(t_comp_fa, n_ckpt, t_ckpt, ladder: LadderArrays, *, per_level_n_ckpt=False):
    """Duration of the computation phase at every ladder level.

    eq (5) exec term (T_comp * beta) plus eq (6) checkpoint term
    (N_ckpt * T_ckpt * gamma).  Shapes: inputs (...,), output (..., F).
    ``per_level_n_ckpt``: n_ckpt already carries the trailing ladder axis
    (used by runtimes that predict checkpoint counts per candidate level).
    """
    t_comp_fa = jnp.asarray(t_comp_fa)[..., None]
    n_ckpt = _ladderize(n_ckpt, per_level_n_ckpt)
    return t_comp_fa * ladder.beta + n_ckpt * t_ckpt * ladder.gamma


def comp_energy(t_comp_fa, n_ckpt, t_ckpt, ladder: LadderArrays, *, per_level_n_ckpt=False):
    """eq (4): E_comp = T_comp(f)*P_comp(f) + N_ckpt*T_ckpt(f)*P_ckpt(f)."""
    t_comp_fa = jnp.asarray(t_comp_fa)[..., None]
    n_ckpt = _ladderize(n_ckpt, per_level_n_ckpt)
    exec_e = t_comp_fa * ladder.beta * ladder.p_comp
    ckpt_e = n_ckpt * t_ckpt * ladder.gamma * ladder.p_ckpt
    return exec_e + ckpt_e


# ---------------------------------------------------------------------------
# eqs (9)-(13): waiting phase
# ---------------------------------------------------------------------------

def wait_time(t_failed, comp_t):
    """eq (13): T_wait = T_failed - comp phase duration.  (..., F)."""
    return jnp.asarray(t_failed)[..., None] - comp_t


def awake_wait_energy(wait_t, wait_mode, ladder: LadderArrays, p_idle_wait, *, spin_level):
    """eqs (7)/(10)/(11): awake wait energy.

    Active waits spin at ``spin_level`` of the ladder (fa for the reference
    case, the minimum frequency under intervention); idle waits draw
    ``p_idle_wait`` regardless of frequency.
    """
    p_active = ladder.p_comp[spin_level]
    active = jnp.asarray(wait_mode) == WaitMode.ACTIVE
    p_wait = jnp.where(active, p_active, p_idle_wait)
    return jnp.maximum(wait_t, 0.0) * p_wait


def sleep_wait_energy(wait_t, sleep: SleepArrays):
    """eqs (9)+(12): transition energy + sleeping at P_sleep for the rest."""
    t_sleep = jnp.maximum(wait_t - sleep.transition_time, 0.0)
    return sleep.transition_energy + t_sleep * sleep.p_sleep


def sleep_allowed(wait_t, e_sleep, e_awake, sleep: SleepArrays, mu1, mu2):
    """eq (8) gating: wait long enough AND sleeping actually cheaper."""
    long_enough = wait_t > mu1 * sleep.transition_time
    cheaper = e_sleep < mu2 * e_awake
    return long_enough & cheaper


# ---------------------------------------------------------------------------
# eqs (1)-(3): node energy with / without intervention
# ---------------------------------------------------------------------------

def reference_energy(t_comp_fa, t_failed, n_ckpt, t_ckpt, ladder: LadderArrays,
                     wait_mode, p_idle_wait, *, per_level_n_ckpt=False, ref_level=0):
    """eq (2): ENI — case B, continue as currently configured, no wait action.

    The paper's reference is "everything at fa" because its single failure
    always lands on a balanced application.  ``ref_level`` generalizes that
    to the node's *current* ladder level (renewal runs re-evaluate Algorithm 1
    at each failure, and a survivor may still hold a slowed level from a
    prior epoch): compute, checkpoints, and the active wait all run at
    ``ref_level``.  Scalar 0 (the default) is the paper's baseline.
    """
    ct = take_level(
        comp_time(t_comp_fa, n_ckpt, t_ckpt, ladder, per_level_n_ckpt=per_level_n_ckpt),
        ref_level)
    ce = take_level(
        comp_energy(t_comp_fa, n_ckpt, t_ckpt, ladder, per_level_n_ckpt=per_level_n_ckpt),
        ref_level)
    wt = jnp.asarray(t_failed) - ct
    we = awake_wait_energy(wt, wait_mode, ladder, p_idle_wait, spin_level=ref_level)
    return ce + we


def intervention_energy(
    t_comp_fa,
    t_failed,
    n_ckpt,
    t_ckpt,
    ladder: LadderArrays,
    sleep: SleepArrays,
    wait_mode,
    p_idle_wait,
    mu1=6.0,
    mu2=1.0,
    per_level_n_ckpt=False,
):
    """eq (3) for every ladder level: EI(f) plus the per-level wait decision.

    Returns a dict with (..., F) arrays:
      total      EI(f) = E_comp(f) + EI_wait(f)   (inf where infeasible)
      feasible   comp phase fits before the recovered process arrives
      sleeps     eq (8) chose the sleep branch at this level
      comp_t / wait_t / e_comp / e_wait  component terms
    """
    ct = comp_time(t_comp_fa, n_ckpt, t_ckpt, ladder, per_level_n_ckpt=per_level_n_ckpt)
    # small relative tolerance: equality (arrive exactly on time) is feasible
    # and must not be lost to float32 rounding.
    feasible = ct <= jnp.asarray(t_failed)[..., None] * (1.0 + 1e-6) + 1e-3
    wt = wait_time(t_failed, ct)
    e_comp = comp_energy(t_comp_fa, n_ckpt, t_ckpt, ladder, per_level_n_ckpt=per_level_n_ckpt)
    min_level = ladder.num_levels - 1
    e_awake = awake_wait_energy(
        wt, jnp.asarray(wait_mode)[..., None], ladder, p_idle_wait, spin_level=min_level
    )
    e_sleep = sleep_wait_energy(wt, sleep)
    sleeps = sleep_allowed(wt, e_sleep, e_awake, sleep, mu1, mu2)
    e_wait = jnp.where(sleeps, e_sleep, e_awake)
    total = e_comp + e_wait
    total = jnp.where(feasible, total, jnp.inf)
    return {
        "total": total,
        "feasible": feasible,
        "sleeps": sleeps,
        "comp_t": ct,
        "wait_t": wt,
        "e_comp": e_comp,
        "e_wait": e_wait,
        "e_awake": e_awake,
        "e_sleep": e_sleep,
    }


def t_failed_from_recovery(t_recover, alpha_ji, i_comm):
    """eq (14): T_failed = T_recover + alpha_ji * I_comm."""
    return jnp.asarray(t_recover) + jnp.asarray(alpha_ji) * jnp.asarray(i_comm)


def t_recover(t_down, t_restart, t_reexec):
    """eq (15)."""
    return jnp.asarray(t_down) + jnp.asarray(t_restart) + jnp.asarray(t_reexec)
