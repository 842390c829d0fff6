"""Algorithm 1 of the paper: per-survivor strategy selection, vectorized.

The paper evaluates each surviving process sequentially against each ladder
frequency.  Here the whole evaluation is one jitted JAX program over
``(nodes..., F)`` — the same decision procedure scales to 10^5 survivors and
Monte-Carlo failure-time grids by adding batch dimensions (everything
broadcasts).  ``benchmarks/strategy_throughput.py`` measures this.

Decision semantics (faithful to Algorithm 1 + §3.2):
  * a ladder level is infeasible if the intervened node would make the
    recovered process wait  (comp_time(f) > T_failed);
  * per level, the wait action is forced by the sleep gate (eq. 8 with
    margins mu1/mu2): sleep if gated in, otherwise MIN_FREQ for active-wait
    configs / NONE for idle-wait configs;
  * the selected level minimizes EI(f) = E_comp(f) + EI_wait(f);
  * the reference ENI is case B: *continue as currently configured* — for
    the paper's single balanced-application failure that means fa everywhere
    with the active wait spinning at fa (``ref_level=0``); renewal runs
    re-evaluate at each failure with survivors' current levels as the
    reference (``ref_level`` per node), so savings stay incremental.

mu defaults: the paper never publishes mu1/mu2.  The Table-4 decisions pin
mu1 to the open band (110/30, 230/30) ~= (3.67, 7.67): scenario 1 node 1
must NOT sleep at a 110 s wait (mu1 >= 110/30), nodes 2-3 MUST sleep at
230 s (mu1 < 230/30), and scenario 4 node 2 must not sleep at 77 s (weaker,
mu1 >= 2.57).  Any value in the band — including every integer 4..7 —
reproduces all published decisions; ``evaluate_strategies`` and
``evaluate_strategies_profile`` both default to the band midpoint mu1=6.0
(regression-pinned in tests/test_strategies.py::test_mu1_band_and_defaults),
and mu2=1.0 (plain "cheaper-than-awake").
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import energy_model as em
from repro.core.characterization import MachineProfile

__all__ = [
    "Decision",
    "evaluate_strategies",
    "evaluate_strategies_fold",
    "evaluate_strategies_impl",
    "evaluate_strategies_profile",
]


@dataclasses.dataclass(frozen=True)
class Decision:
    """Selected strategy per node. All arrays share the node batch shape."""

    level: jax.Array          # selected ladder index for the compute phase
    freq_ghz: jax.Array       # its frequency
    comp_changed: jax.Array   # bool: compute frequency differs from fa
    wait_action: jax.Array    # em.WaitAction value
    comp_time: jax.Array      # compute-phase duration under the decision (s)
    wait_time: jax.Array      # waiting-phase duration under the decision (s)
    energy_intervened: jax.Array   # EI at the decision (J)
    energy_reference: jax.Array    # ENI (J)
    saving: jax.Array         # eq (1): ENI - EI (J)
    saving_pct: jax.Array     # 100 * saving / ENI
    feasible_any: jax.Array   # at least one ladder level was feasible


jax.tree_util.register_dataclass(
    Decision,
    data_fields=[
        "level", "freq_ghz", "comp_changed", "wait_action", "comp_time",
        "wait_time", "energy_intervened", "energy_reference", "saving",
        "saving_pct", "feasible_any",
    ],
    meta_fields=[],
)


def evaluate_strategies_impl(
    t_comp_fa,
    t_failed,
    n_ckpt,
    t_ckpt,
    ladder: em.LadderArrays,
    sleep: em.SleepArrays,
    wait_mode,
    p_idle_wait,
    mu1=6.0,
    mu2=1.0,
    per_level_n_ckpt=False,
    ref_level=0,
) -> Decision:
    """Run Algorithm 1 for a batch of surviving nodes.

    This is the unjitted implementation: call it from *inside* an already
    traced program (the device renewal engine does) so XLA inlines it —
    fusing it with the surrounding computation and dead-code-eliminating
    any ``Decision`` fields the caller drops.  A nested ``jit`` would
    instead pin all eleven fields as materialized call outputs.
    ``evaluate_strategies`` below is the jitted entry point for direct
    callers.

    All node inputs broadcast; pass arrays of shape (N,) — or (T, N) to sweep
    failure times, etc.  ``wait_mode`` is per-node (em.WaitMode value).
    With ``per_level_n_ckpt`` the checkpoint count carries a trailing ladder
    axis (..., F) — used by planners that predict timer/move-ahead
    checkpoints per candidate frequency.

    ``ref_level`` is each node's *current* ladder level: the reference ENI
    runs compute/checkpoints/active-wait there (the paper's hardcoded fa
    baseline is the ``ref_level=0`` special case), ``comp_changed`` compares
    against it, and the no-feasible-level fallback keeps it.  Renewal runs
    pass survivors' live levels so a re-evaluation mid-intervention measures
    savings against what the node is actually doing, not a counterfactual fa
    run.
    """
    t_comp_fa, t_failed, wait_mode = jnp.broadcast_arrays(
        jnp.asarray(t_comp_fa, jnp.float32),
        jnp.asarray(t_failed, jnp.float32),
        jnp.asarray(wait_mode, jnp.int32),
    )
    # ref_level stays unbroadcast: a concrete scalar (the paper's fa
    # baseline, the device renewal engine) hits take_level's static-slice
    # fast path; arrays broadcast where consumed.
    n_ckpt = jnp.asarray(n_ckpt, jnp.float32)
    if not per_level_n_ckpt:
        n_ckpt = jnp.broadcast_to(n_ckpt, t_comp_fa.shape)
    ei = em.intervention_energy(
        t_comp_fa, t_failed, n_ckpt, t_ckpt, ladder, sleep, wait_mode,
        p_idle_wait, mu1=mu1, mu2=mu2, per_level_n_ckpt=per_level_n_ckpt,
    )
    level = jnp.argmin(ei["total"], axis=-1)
    # per-level arrays may carry fewer batch dims than the selection (e.g. a
    # leading mu-band axis enters only through the sleep gate); take_level
    # broadcasts both operands before gathering.
    take = lambda a: em.take_level(a, level)

    # reference ENI (eq. 2, case B at ref_level): reuse the per-level comp
    # time/energy already computed for EI instead of re-deriving the whole
    # ladder — the gathered values are bit-identical to reference_energy's
    # (same ops, same float32 rounding), it's only the redundant (..., F)
    # recomputation that goes away.  Matters inside the device renewal
    # engine, where this dispatch runs for every (scenario, run, epoch).
    ct_ref = em.take_level(ei["comp_t"], ref_level)
    ce_ref = em.take_level(ei["e_comp"], ref_level)
    eni = ce_ref + em.awake_wait_energy(
        t_failed - ct_ref, wait_mode, ladder, p_idle_wait, spin_level=ref_level)
    e_sel = take(ei["total"])
    feasible_any = jnp.any(ei["feasible"], axis=-1)
    # If nothing is feasible (can't happen when fa is feasible by
    # construction, but guard numerically) fall back to the reference:
    # keep the node's current level and take no action.
    e_sel = jnp.where(feasible_any, e_sel, eni)
    ref_level_b = jnp.broadcast_to(
        jnp.asarray(ref_level, jnp.int32), level.shape)
    level = jnp.where(feasible_any, level, ref_level_b)

    sleeps = take(ei["sleeps"]) & feasible_any
    active = wait_mode == em.WaitMode.ACTIVE
    wait_action = jnp.where(
        sleeps,
        em.WaitAction.SLEEP,
        jnp.where(active, em.WaitAction.MIN_FREQ, em.WaitAction.NONE),
    ).astype(jnp.int32)
    # no feasible level -> don't intervene at all (predict zero saving and
    # take no action, so prediction and application stay coherent).
    wait_action = jnp.where(feasible_any, wait_action, em.WaitAction.NONE)

    saving = eni - e_sel
    return Decision(
        level=level.astype(jnp.int32),
        freq_ghz=ladder.freq_ghz[level],
        comp_changed=level != ref_level,
        wait_action=wait_action,
        comp_time=take(ei["comp_t"]),
        wait_time=take(ei["wait_t"]),
        energy_intervened=e_sel,
        energy_reference=eni,
        saving=saving,
        saving_pct=100.0 * saving / jnp.maximum(eni, 1e-9),
        feasible_any=feasible_any,
    )


evaluate_strategies = functools.partial(jax.jit, static_argnames=(
    "per_level_n_ckpt",))(evaluate_strategies_impl)


def _fold_setup(t_comp_fa, t_failed, n_ckpt_cols, t_ckpt, ladder, sleep,
                wait_mode, p_idle_wait, mu1, mu2):
    """Casts shared by ``evaluate_strategies_fold`` and ``decision_margins``,
    and ``level_terms(f)``: ladder level ``f``'s Algorithm-1 terms, each
    comparison with both of its sides."""
    t_comp_fa, t_failed, wait_mode = jnp.broadcast_arrays(
        jnp.asarray(t_comp_fa, jnp.float32),
        jnp.asarray(t_failed, jnp.float32),
        jnp.asarray(wait_mode, jnp.int32),
    )
    t_ckpt = jnp.asarray(t_ckpt, jnp.float32)
    mu1 = jnp.asarray(mu1, jnp.float32)
    mu2 = jnp.asarray(mu2, jnp.float32)
    # plain ints, not IntEnum members: enum instances fail JAX's exact-type
    # literal check and would be captured as jaxpr constants, which the
    # Pallas kernel reusing this fold (kernels/renewal_scan.py) rejects
    active = wait_mode == int(em.WaitMode.ACTIVE)
    min_level = ladder.num_levels - 1
    p_awake = jnp.where(active, ladder.p_comp[min_level], p_idle_wait)
    feas_rhs = t_failed * (1.0 + 1e-6) + 1e-3
    trans_t, trans_e = sleep.transition_time, sleep.transition_energy
    gate_t = mu1 * trans_t

    def level_terms(f):
        n_f = jnp.asarray(n_ckpt_cols[f], jnp.float32)
        # same op order as comp_time / comp_energy / wait branches
        ct = t_comp_fa * ladder.beta[f] + n_f * t_ckpt * ladder.gamma[f]
        wt = t_failed - ct
        e_comp = t_comp_fa * ladder.beta[f] * ladder.p_comp[f] \
            + n_f * t_ckpt * ladder.gamma[f] * ladder.p_ckpt[f]
        e_awake = jnp.maximum(wt, 0.0) * p_awake
        e_sleep = trans_e + jnp.maximum(wt - trans_t, 0.0) * sleep.p_sleep
        e_awake_mu2 = mu2 * e_awake
        feasible = ct <= feas_rhs
        sleeps = (wt > gate_t) & (e_sleep < e_awake_mu2)
        total = jnp.where(
            feasible, e_comp + jnp.where(sleeps, e_sleep, e_awake), jnp.inf)
        return dict(ct=ct, feas_rhs=feas_rhs, wt=wt, gate_t=gate_t,
                    e_comp=e_comp, e_sleep=e_sleep, e_awake_mu2=e_awake_mu2,
                    feasible=feasible, sleeps=sleeps, total=total)

    return t_comp_fa, t_failed, wait_mode, active, level_terms


def evaluate_strategies_fold(
    t_comp_fa,
    t_failed,
    n_ckpt_cols,
    t_ckpt,
    ladder: em.LadderArrays,
    sleep: em.SleepArrays,
    wait_mode,
    p_idle_wait,
    mu1=6.0,
    mu2=1.0,
    ref_level: int = 0,
) -> Decision:
    """Algorithm 1 as an F-unrolled running-argmin fold over ladder levels.

    Equivalent to ``evaluate_strategies`` — every energy term is written in
    the same operation order (so the two can differ only by XLA's
    per-program FMA-contraction choices, ~1 ulp), the running ``<`` keeps
    the first minimum exactly like ``argmin``, and
    tests/test_renewal_device.py pins all ``Decision`` fields of the two
    implementations against each other — but it never builds a ``(..., F)``
    array: each level's column is a node-batch-shaped intermediate that XLA
    fuses and then discards.  At
    the device renewal engine's batch sizes the vectorized form's per-level
    intermediates (~10 arrays x F x batch) dominate memory traffic, which
    this shape avoids.  Restrictions vs the vectorized form: per-level
    checkpoint counts are passed as ``n_ckpt_cols`` (a static sequence of F
    node-batch arrays), ``ref_level`` must be a concrete int, and there is
    no mu-band axis — ``mu1``/``mu2`` are *batchable leaves* that broadcast
    against the node batch (scalars, or per-node arrays; the policy
    optimizer vmaps this function over a leading policy axis whose lanes
    carry different margins and wait modes).  They are cast to float32
    here so a float64 caller (the x64-traced renewal scan) cannot promote
    the Algorithm-1 energy math.
    """
    t_comp_fa, t_failed, wait_mode, active, level_terms = _fold_setup(
        t_comp_fa, t_failed, n_ckpt_cols, t_ckpt, ladder, sleep, wait_mode,
        p_idle_wait, mu1, mu2)
    ref_level = int(ref_level)

    best = None
    for f in range(ladder.num_levels):
        t = level_terms(f)
        ct, sleeps, total = t["ct"], t["sleeps"], t["total"]
        if f == ref_level:
            ct_ref, e_comp_ref = ct, t["e_comp"]
        if best is None:
            best = dict(total=total, level=jnp.zeros_like(wait_mode),
                        freq=jnp.full_like(total, ladder.freq_ghz[f]),
                        ct=ct, sleeps=sleeps, feasible_any=t["feasible"])
        else:
            better = total < best["total"]  # strict: first minimum, as argmin
            best = dict(
                total=jnp.where(better, total, best["total"]),
                level=jnp.where(better, f, best["level"]),
                freq=jnp.where(better, ladder.freq_ghz[f], best["freq"]),
                ct=jnp.where(better, ct, best["ct"]),
                # boolean select as logic: Mosaic has no i1 select
                sleeps=(better & sleeps) | (~better & best["sleeps"]),
                feasible_any=best["feasible_any"] | t["feasible"],
            )

    eni = e_comp_ref + jnp.maximum(t_failed - ct_ref, 0.0) * jnp.where(
        active, ladder.p_comp[ref_level], p_idle_wait)
    feasible_any = best["feasible_any"]
    e_sel = jnp.where(feasible_any, best["total"], eni)
    level = jnp.where(feasible_any, best["level"], ref_level)
    comp_time = jnp.where(feasible_any, best["ct"], ct_ref)
    sleeps = best["sleeps"] & feasible_any
    wait_action = jnp.where(
        sleeps,
        int(em.WaitAction.SLEEP),
        jnp.where(active, int(em.WaitAction.MIN_FREQ), int(em.WaitAction.NONE)),
    ).astype(jnp.int32)
    wait_action = jnp.where(
        feasible_any, wait_action, int(em.WaitAction.NONE))
    saving = eni - e_sel
    return Decision(
        level=level.astype(jnp.int32),
        # selected, not gathered: the Pallas kernel's ladder is a tuple of
        # SMEM scalars
        freq_ghz=jnp.where(feasible_any, best["freq"],
                           ladder.freq_ghz[ref_level]),
        comp_changed=level != ref_level,
        wait_action=wait_action,
        comp_time=comp_time,
        wait_time=t_failed - comp_time,
        energy_intervened=e_sel,
        energy_reference=eni,
        saving=saving,
        saving_pct=100.0 * saving / jnp.maximum(eni, 1e-9),
        feasible_any=feasible_any,
    )


def decision_margins(t_comp_fa, t_failed, n_ckpt_cols, t_ckpt,
                     ladder: em.LadderArrays, sleep: em.SleepArrays,
                     wait_mode, p_idle_wait, mu1=6.0, mu2=1.0) -> jax.Array:
    """Distance, in seconds, of each node's closest Algorithm-1 comparison
    from its boundary, taken from the terms ``evaluate_strategies_fold``
    decides on (same arguments, node-batch shaped result).

    The comparisons: feasibility and the sleep gate (in seconds already),
    sleep against awake, and the choice between the two cheapest feasible
    levels (energies, over the ladder's largest compute power).  Two engines
    whose inputs differ by less than the margin take the same decisions; a
    node with a margin near zero sits on a tie that rounding decides.
    """
    *_, level_terms = _fold_setup(
        t_comp_fa, t_failed, n_ckpt_cols, t_ckpt, ladder, sleep, wait_mode,
        p_idle_wait, mu1, mu2)
    levels = [level_terms(f) for f in range(ladder.num_levels)]
    p_max = functools.reduce(
        jnp.maximum, [ladder.p_comp[f] for f in range(ladder.num_levels)])
    margin = functools.reduce(jnp.minimum, [jnp.minimum(
        jnp.minimum(jnp.abs(t["ct"] - t["feas_rhs"]),
                    jnp.abs(t["wt"] - t["gate_t"])),
        jnp.abs(t["e_sleep"] - t["e_awake_mu2"]) / p_max) for t in levels])
    totals = jnp.sort(jnp.stack([t["total"] for t in levels], axis=-1),
                      axis=-1)
    if ladder.num_levels > 1:
        # inf - inf (fewer than two feasible levels) is no comparison
        gap = (totals[..., 1] - totals[..., 0]) / p_max
        margin = jnp.minimum(margin, jnp.where(jnp.isnan(gap), jnp.inf, gap))
    return margin


def evaluate_strategies_profile(
    profile: MachineProfile,
    t_comp_fa,
    t_failed,
    n_ckpt,
    t_ckpt,
    wait_mode,
    mu1=6.0,
    mu2=1.0,
    per_level_n_ckpt=False,
    ref_level=0,
) -> Decision:
    """Convenience wrapper taking a MachineProfile."""
    ladder = em.LadderArrays.from_table(profile.power_table)
    sleep = em.SleepArrays.from_spec(profile.sleep)
    return evaluate_strategies(
        t_comp_fa, t_failed, n_ckpt, t_ckpt, ladder, sleep, wait_mode,
        profile.p_idle_wait, mu1=mu1, mu2=mu2, per_level_n_ckpt=per_level_n_ckpt,
        ref_level=ref_level,
    )
