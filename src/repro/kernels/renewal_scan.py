"""Pallas kernel for the fused renewal epoch-scan + Algorithm-1 fold.

This is the float32 engine of the three-engine renewal contract
(docs/sweep.md):

  * ``core.sweep.renewal_compose``     — float64 host oracle (numpy loop);
  * ``core.sweep._renewal_scan``       — ``lax.scan`` traced under
    ``enable_x64`` (float64 geometry, float32 Algorithm 1);
  * this kernel                        — float32 geometry end to end, with
    compensated (Kahan) accumulation of the energy ledger.

One grid step composes a block of Monte-Carlo runs for one policy/scenario
lane: the whole epoch recursion (checkpoint sawtooth advance, rendezvous
wrap, re-execution race, resync point, re-anchor) plus the per-epoch
balanced-span energy, checkpoint plan, Algorithm-1 strategy fold
(``core.strategies.evaluate_strategies_fold`` — reused verbatim), and
trailing-span accounting run inside a ``fori_loop`` whose carry lives in
registers/VMEM.  Nothing per-epoch ever touches HBM except the small
``valid`` occurrence mask.

Grid and layout
---------------
``grid = (P, R // block_r)`` — policy/scenario lanes x run blocks.  Inside
a block every array is laid out survivors-first, runs-last ``(N, block_r)``
so the run axis sits on the vector lanes (TPU: the 128-wide minor
dimension; CPU interpret mode: the contiguous axis); per-run values are
``(1, block_r)`` rows.  Scalars of the lane (interval, makespan,
mu-bands, sleep spec, ...) arrive as one packed ``(P, N_PARAMS)`` row and
the power ladder as ``(P, 5, F)``, both read from SMEM one scalar at a
time; per-node state ``(P, 3, N)`` arrives as ``(N, 1)`` columns.  Every
block's last two dims are whole or (8, 128)-tiled, as the TPU lowering
requires (docs/sweep.md, "TPU layout").  See ``pack_lane_params`` for the
exact column map.

Carry layout (per run lane)
---------------------------
  * ``ages``       (N, block_r) — survivor checkpoint ages, and beside it
    the failed node's lost-work age ``(1, block_r)`` (the same sawtooth);
  * ``exec_anchor``(N,   block_r) — rendezvous anchor at the last re-anchor;
  * ``bal_elapsed``+ compensation — balanced-execution clock (Kahan pair:
    the occurrence predicate ``bal + delta <= makespan`` must not drift);
  * ``t_anchor``  + compensation — wall clock at the last re-anchor;
  * ``alive``      (1, block_r) int32 (Mosaic has no i1 loop carry);
  * four energy accumulators (balanced, reference, intervened, saving),
    each a Kahan ``(sum, comp)`` pair when ``compensated=True`` (the
    default; ``False`` is the naive-summation baseline the property test
    in tests/test_renewal_pallas.py beats it against);
  * int32 action counters (failures, points, sleep, min-freq, comp-changed,
    infeasible); the per-epoch ``valid`` mask is written straight to its
    output block, one row per epoch.

Precision contract
------------------
Whole-run energies are O(1e9 J) while per-epoch increments are O(1e5 J);
naive f32 summation of K x N increments loses up to ~2^-24 * sum * K ~
1e4-1e5 J — right at the 1e-4 cross-validation bar.  Kahan compensation
removes the accumulation term, leaving only the geometry rounding
(O(0.1 s) on O(1e4 s) epochs, i.e. O(10 J) on epoch energies), so the
kernel holds the same <= 1e-4 relative bar against the float64 oracle as
the x64 scan engine (tests/test_renewal_pallas.py pins all six Table-4
scenarios x {exponential, Weibull, correlated-topology} histories).  The
saving is additionally accumulated from per-epoch *differences*
(reference - intervened), never as the difference of two O(1e9 J) totals.

Run blocks are padded to ``block_r`` with ``inf`` gap sentinels: an
infinite first gap makes ``occurs`` false from epoch 0, and every carry
update and ledger increment is ``where(occurs)``-gated, so the NaNs the
sawtooth produces from an infinite advance never enter the carry or the
sums.

``interpret=True`` (the CPU CI path, mirroring ``ssd_scan_pallas``)
evaluates the same kernel through the Pallas interpreter; wrapped in
``jax.jit`` it lowers to ordinary XLA ops, which is what
``core.sweep``'s ``engine="pallas"`` dispatches on CPU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import energy_model as em
from repro.core import planning
from repro.core import strategies
from repro.core.scenarios import next_rendezvous

__all__ = ["renewal_scan_pallas", "pack_lane_params", "N_PARAMS",
           "PARAM_COLS", "STAT_FIELDS"]

# column map of the packed per-lane scalar row (params_ref);
# pack_lane_params builds it, the kernel unpacks by these indices
PARAM_COLS = (
    "interval", "dur", "reexec0", "t_down", "t_restart", "mu1", "mu2",
    "wait_mode", "p_idle_wait", "move_ahead", "move_frac", "makespan",
    "t_go_sleep", "t_wakeup", "p_go_sleep", "p_wakeup", "p_sleep",
)
N_PARAMS = len(PARAM_COLS)

# kernel outputs after the (P, K, R) valid mask, in ref order
STAT_FIELDS = (
    ("energy_ref", jnp.float32), ("energy_int", jnp.float32),
    ("saving", jnp.float32), ("balanced_energy", jnp.float32),
    ("end_time", jnp.float32),
    ("n_failures", jnp.int32), ("truncated", jnp.int32),
    ("n_points", jnp.int32), ("n_sleep", jnp.int32),
    ("n_min_freq", jnp.int32), ("n_comp_changed", jnp.int32),
    ("n_infeasible", jnp.int32),
)

# VMEM the double-buffered felled block (K, N, block_r) float32 may take.
# Ahead-of-time compiles for a TPU v5e at 32-128 epochs pass at 12 MiB and
# run out of VMEM at 14 MiB and above (the survivors' (N, block_r) state and
# fold take the rest), so a block past this is refused before compiling.
FELLED_BLOCK_VMEM_BYTES = 12 << 20


def _kadd(s, c, x, compensated: bool):
    """One compensated-summation step: add ``x`` into the Kahan pair
    ``(s, c)``.  Neither XLA nor Mosaic reassociates float adds, so the
    cancellation ``(t - s) - y`` survives compilation intact.
    ``compensated=False`` degrades to the naive ``s + x`` baseline (the
    property test's foil)."""
    if not compensated:
        return s + x, c
    y = x - c
    t = s + y
    return t, (t - s) - y


def _kval(s, c):
    """Read a Kahan pair out: the running sum with its carried correction
    folded back in (``c`` is zero on the naive path)."""
    return s - c


def pack_lane_params(
    *, interval, dur, reexec0, t_down, t_restart, mu1, mu2, wait_mode,
    p_idle_wait, move_ahead, move_frac, makespan, sleep: em.SleepArrays,
) -> jax.Array:
    """Pack per-lane scalars into the kernel's ``(P, N_PARAMS)`` float32
    row, broadcasting scalars across lanes.  ``wait_mode`` (small int) and
    ``move_ahead`` (bool) travel as exact float32 values; the kernel
    restores their dtypes.  Column order is ``PARAM_COLS``."""
    cols = dict(
        interval=interval, dur=dur, reexec0=reexec0, t_down=t_down,
        t_restart=t_restart, mu1=mu1, mu2=mu2, wait_mode=wait_mode,
        p_idle_wait=p_idle_wait, move_ahead=move_ahead, move_frac=move_frac,
        makespan=makespan, t_go_sleep=sleep.t_go_sleep,
        t_wakeup=sleep.t_wakeup, p_go_sleep=sleep.p_go_sleep,
        p_wakeup=sleep.p_wakeup, p_sleep=sleep.p_sleep,
    )
    lanes = jnp.broadcast_shapes(
        *(jnp.shape(jnp.asarray(v)) for v in cols.values()))
    b = lambda v: jnp.broadcast_to(
        jnp.asarray(v, jnp.float32), lanes or (1,))
    return jnp.stack([b(cols[name]) for name in PARAM_COLS], axis=1)


def _renewal_kernel(params_ref, ladder_ref, nodes_ref, gaps_ref, felled_ref,
                    valid_ref, *out_refs, compensated: bool):
    # per-lane scalars are read from SMEM one by one
    col = {name: params_ref[0, 0, i] for i, name in enumerate(PARAM_COLS)}
    interval, dur = col["interval"], col["dur"]
    t_restart = col["t_restart"]
    t_dr = col["t_down"] + t_restart
    makespan = col["makespan"]
    wait_mode = col["wait_mode"].astype(jnp.int32)
    move_ahead = col["move_ahead"] > 0.5
    sleep = em.SleepArrays(
        t_go_sleep=col["t_go_sleep"], t_wakeup=col["t_wakeup"],
        p_go_sleep=col["p_go_sleep"], p_wakeup=col["p_wakeup"],
        p_sleep=col["p_sleep"])
    n_levels = ladder_ref.shape[2]
    lad = lambda row: tuple(ladder_ref[0, row, f] for f in range(n_levels))
    ladder = em.LadderArrays(freq_ghz=lad(0), p_comp=lad(1), beta=lad(2),
                             p_ckpt=lad(3), gamma=lad(4))
    beta0, gamma0 = ladder.beta[0], ladder.gamma[0]
    p_comp0, p_ckpt0 = ladder.p_comp[0], ladder.p_ckpt[0]
    dur_fa = dur * gamma0

    # per-node state as (N, 1) columns, broadcast along the run lanes
    age0, exec0, period_c = nodes_ref[0, 0], nodes_ref[0, 1], nodes_ref[0, 2]
    n = age0.shape[0]
    n_epochs, rb = gaps_ref.shape

    row = lambda v, dt=jnp.float32: jnp.full((1, rb), v, dt)
    zero = row(0.0)
    izero = row(0, jnp.int32)
    init = (
        jnp.broadcast_to(age0, (n, rb)),                # survivor ckpt ages
        row(col["reexec0"]),                            # failed node's age
        jnp.broadcast_to(exec0, (n, rb)),               # exec_anchor
        zero, zero,                                     # bal_elapsed Kahan pair
        zero, zero,                                     # t_anchor Kahan pair
        row(1, jnp.int32),                              # alive (no i1 carry)
        zero, zero, zero, zero,                         # balanced / reference
        zero, zero, zero, zero,                         # intervened / saving
        izero, izero, izero, izero, izero, izero,       # action counters
    )
    colsum = lambda x: jnp.sum(x, axis=0, keepdims=True)
    colmax = lambda x: jnp.max(x, axis=0, keepdims=True)

    def body(k, carry):
        (ages, age_x, exec_anchor, bal, bal_c, t_anchor, t_anchor_c, alive,
         a_bal, a_bal_c, a_ref, a_ref_c, a_int, a_int_c, a_sav, a_sav_c,
         nfail, npts, nsleep, nminf, ncomp, ninf) = carry
        delta = gaps_ref[pl.ds(k, 1), :]                # (1, Rb)
        m = felled_ref[k] > 0.5                         # (N, Rb)
        occurs = (alive > 0) & (bal + delta <= makespan)

        # geometry: the same closed forms as the x64 scan, in float32; the
        # failed node's lost-work sawtooth is the (1, Rb) row beside the
        # survivors' (N, Rb) block
        age_f, work_f, _, d_eff_f = planning.advance_checkpoint_sawtooth(
            ages, delta, interval, dur)
        age_xf, work_x, _, d_eff_fail = planning.advance_checkpoint_sawtooth(
            age_x, delta, interval, dur)
        rem = planning.floor_mod(exec_anchor - work_f, period_c)
        exec_rem = jnp.where(rem == 0.0, period_c, rem)
        reexec = jnp.maximum(age_xf, colmax(jnp.where(m, age_f, -jnp.inf)))
        p_star = jnp.maximum(colmax(jnp.where(m, -jnp.inf, exec_rem)), 0.0)
        t_recover = t_dr + reexec
        t_failed = t_recover + exec_rem                 # (N, Rb)
        t_e = t_recover + p_star

        # balanced-span energy of the epoch + coordinated resync checkpoint
        e_bal = (colsum(work_f * p_comp0 + (d_eff_f - work_f) * p_ckpt0)
                 + (work_x * p_comp0 + (d_eff_fail - work_x) * p_ckpt0))
        a_bal, a_bal_c = _kadd(a_bal, a_bal_c, jnp.where(
            occurs, e_bal + (n + 1) * dur_fa * p_ckpt0, 0.0), compensated)

        epoch_failed = jnp.where(
            occurs,
            (1.0 + colsum(m.astype(jnp.float32)))
            * (t_restart * p_ckpt0 + (reexec + p_star) * p_comp0), 0.0)

        # checkpoint plan + Algorithm 1 — the very same closed forms and
        # fold as both other engines, one ladder level at a time
        n0 = planning.timer_checkpoint_count(exec_rem, age_f, beta0, interval)
        plan_move, _, _ = planning.move_ahead_plan(
            exec_rem, age_f, t_failed, n0, interval=interval, dur=dur,
            move_ahead=move_ahead, move_frac=col["move_frac"])
        move = jnp.where(plan_move, 1.0, 0.0)
        n_cols = [n0 + move] + [
            planning.timer_checkpoint_count(
                exec_rem, age_f, ladder.beta[f], interval) + move
            for f in range(1, ladder.num_levels)
        ]
        decision = strategies.evaluate_strategies_fold(
            exec_rem, t_failed, n_cols, dur, ladder, sleep,
            wait_mode, col["p_idle_wait"], mu1=col["mu1"], mu2=col["mu2"])

        ct_ref = exec_rem * beta0 + n_cols[0] * dur * gamma0
        trail_ref = jnp.maximum(
            t_e - jnp.maximum(t_failed, ct_ref), 0.0) * p_comp0
        trail_int = jnp.maximum(
            t_e - jnp.maximum(t_failed, decision.comp_time), 0.0) * p_comp0
        v2 = occurs & ~m
        eni = decision.energy_reference + trail_ref
        ei = decision.energy_intervened + trail_int
        a_ref, a_ref_c = _kadd(
            a_ref, a_ref_c,
            colsum(jnp.where(v2, eni, 0.0)) + epoch_failed, compensated)
        a_int, a_int_c = _kadd(
            a_int, a_int_c,
            colsum(jnp.where(v2, ei, 0.0)) + epoch_failed, compensated)
        # saving from per-epoch differences — never the difference of totals
        a_sav, a_sav_c = _kadd(
            a_sav, a_sav_c, colsum(jnp.where(v2, eni - ei, 0.0)),
            compensated)

        cnt = lambda mask: colsum((v2 & mask).astype(jnp.int32))
        nfail = nfail + occurs.astype(jnp.int32)
        npts = npts + colsum(v2.astype(jnp.int32))
        # int() not the IntEnum member: enum instances would be captured as
        # jaxpr constants, which pallas_call rejects
        nsleep = nsleep + cnt(
            decision.wait_action == int(em.WaitAction.SLEEP))
        nminf = nminf + cnt(
            decision.wait_action == int(em.WaitAction.MIN_FREQ))
        ncomp = ncomp + cnt(decision.comp_changed)
        ninf = ninf + cnt(~decision.feasible_any)
        valid_ref[0, pl.ds(k, 1), :] = occurs.astype(jnp.int32)

        # re-anchor: coordinated resync checkpoint -> ages 0, progress P*
        anchor_next = next_rendezvous(p_star, exec_rem, period_c)
        # the clocks stay compensated in BOTH modes: occurrence geometry is
        # held fixed so the naive-ledger baseline differs only in summation
        bal, bal_c = _kadd(
            bal, bal_c, jnp.where(occurs, d_eff_fail, 0.0), True)
        t_anchor, t_anchor_c = _kadd(
            t_anchor, t_anchor_c,
            jnp.where(occurs, d_eff_fail + t_e + dur_fa, 0.0), True)
        ages = jnp.where(occurs, 0.0, ages)
        age_x = jnp.where(occurs, 0.0, age_x)
        exec_anchor = jnp.where(occurs, anchor_next, exec_anchor)
        alive = occurs.astype(jnp.int32)
        return (ages, age_x, exec_anchor, bal, bal_c, t_anchor, t_anchor_c,
                alive, a_bal, a_bal_c, a_ref, a_ref_c, a_int, a_int_c,
                a_sav, a_sav_c, nfail, npts, nsleep, nminf, ncomp, ninf)

    (ages, age_x, _, bal, _, t_anchor, _, alive, a_bal, a_bal_c, a_ref,
     a_ref_c, a_int, a_int_c, a_sav, a_sav_c, nfail, npts, nsleep, nminf,
     ncomp, ninf) = jax.lax.fori_loop(0, n_epochs, body, init)

    # balanced tail over the remaining failure-free span
    span = jnp.maximum(makespan - bal, 0.0)
    w_t, ck_t = planning.balanced_span(ages, span, interval, dur)
    w_x, ck_x = planning.balanced_span(age_x, span, interval, dur)
    a_bal, a_bal_c = _kadd(
        a_bal, a_bal_c,
        colsum(w_t * p_comp0 + ck_t * p_ckpt0) + (w_x * p_comp0
                                                   + ck_x * p_ckpt0),
        compensated)

    e_bal = _kval(a_bal, a_bal_c)
    outs = dict(
        energy_ref=e_bal + _kval(a_ref, a_ref_c),
        energy_int=e_bal + _kval(a_int, a_int_c),
        saving=_kval(a_sav, a_sav_c),
        balanced_energy=e_bal,
        end_time=t_anchor + span,
        n_failures=nfail,
        truncated=((alive > 0) & (bal < makespan)).astype(jnp.int32),
        n_points=npts,
        n_sleep=nsleep,
        n_min_freq=nminf,
        n_comp_changed=ncomp,
        n_infeasible=ninf,
    )
    for (name, _), ref in zip(STAT_FIELDS, out_refs):
        ref[0] = outs[name]


def renewal_scan_pallas(params, nodes, ladder, gaps, felled=None, *,
                        block_r: int | None = None, interpret: bool = True,
                        compensated: bool = True) -> dict:
    """Fused renewal composition for ``P`` policy/scenario lanes over ``R``
    Monte-Carlo runs of ``K`` failure epochs each.

    Args:
      params: (P, N_PARAMS) float32 — packed per-lane scalars
        (``pack_lane_params``; includes the per-lane makespan).
      nodes: (P, 3, N) float32 — rows ``[age0, exec_rem0, period]``.
      ladder: (P, 5, F) float32 — rows ``[freq_ghz, p_comp, beta, p_ckpt,
        gamma]`` of the power ladder.
      gaps: (K, R) float32 — per-epoch balanced-execution gaps, runs on the
        trailing axis (note: transposed vs. the host sampler's (R, K)).
      felled: (K, N, R) float32 0/1 survivor-slot shock mask, or None.
      block_r: runs per grid step; defaults to 128 when R divides evenly,
        else R (no padding).  R is inf-padded up to a multiple otherwise.
      interpret: run through the Pallas interpreter (the CPU path; under
        ``jax.jit`` it lowers to plain XLA ops).
      compensated: Kahan-compensate the energy ledger (default).  ``False``
        is the naive-summation baseline for the precision property test.

    Returns a dict: ``valid`` (P, K, R) int32 plus the twelve per-run stat
    fields of ``STAT_FIELDS`` at (P, R) — exactly the payload
    ``core.sweep.RenewalDeviceStats`` is assembled from.
    """
    params = jnp.asarray(params, jnp.float32)
    nodes = jnp.asarray(nodes, jnp.float32)
    ladder = jnp.asarray(ladder, jnp.float32)
    gaps = jnp.asarray(gaps, jnp.float32)
    n_lanes, n_params = params.shape
    if n_params != N_PARAMS:
        raise ValueError(f"params must be (P, {N_PARAMS}); got {params.shape}")
    n = nodes.shape[2]
    n_levels = ladder.shape[2]
    n_epochs, n_runs = gaps.shape
    if felled is None:
        felled = jnp.zeros((n_epochs, n, n_runs), jnp.float32)
    else:
        felled = jnp.asarray(felled, jnp.float32)

    rb = block_r or (n_runs if n_runs <= 128 else 128)
    block_bytes = 2 * n_epochs * (-(-n // 8) * 8) * rb * 4
    if block_bytes > FELLED_BLOCK_VMEM_BYTES:
        raise ValueError(
            f"the renewal kernel's blocks of {n} survivors x {rb} runs over "
            f"{n_epochs} epochs need {block_bytes} B of VMEM for the felled "
            f"mask alone, over the {FELLED_BLOCK_VMEM_BYTES} B it may take; "
            "compose this many survivors with the scan engine "
            "(engine='scan'), whose fold tiles the survivor axis")
    r_pad = -(-n_runs // rb) * rb
    if r_pad != n_runs:
        # inf gap sentinel: occurs is False from epoch 0 on padded lanes and
        # every update/accumulation is where(occurs)-gated (see module doc)
        gaps = jnp.pad(gaps, ((0, 0), (0, r_pad - n_runs)),
                       constant_values=jnp.inf)
        felled = jnp.pad(felled, ((0, 0), (0, 0), (0, r_pad - n_runs)))

    # TPU layout (docs/sweep.md): per-lane scalars in SMEM, run blocks on
    # the 128-wide lane axis, and a unit second-minor axis wherever a block
    # holds one lane's row, so every block's last two dims are either whole
    # or (8, 128)-tiled
    smem = dict(memory_space=pltpu.SMEM)
    lane_out = pl.BlockSpec((1, 1, rb), lambda p, r: (p, 0, r))
    outs = pl.pallas_call(
        functools.partial(_renewal_kernel, compensated=compensated),
        grid=(n_lanes, r_pad // rb),
        in_specs=[
            pl.BlockSpec((1, 1, N_PARAMS), lambda p, r: (p, 0, 0), **smem),
            pl.BlockSpec((1, 5, n_levels), lambda p, r: (p, 0, 0), **smem),
            pl.BlockSpec((1, 3, n, 1), lambda p, r: (p, 0, 0, 0)),
            pl.BlockSpec((n_epochs, rb), lambda p, r: (0, r)),
            pl.BlockSpec((n_epochs, n, rb), lambda p, r: (0, 0, r)),
        ],
        out_specs=[pl.BlockSpec((1, n_epochs, rb), lambda p, r: (p, 0, r))]
        + [lane_out] * len(STAT_FIELDS),
        out_shape=[jax.ShapeDtypeStruct((n_lanes, n_epochs, r_pad), jnp.int32)]
        + [jax.ShapeDtypeStruct((n_lanes, 1, r_pad), dt)
           for _, dt in STAT_FIELDS],
        interpret=interpret,
    )(params[:, None, :], ladder, nodes[..., None], gaps, felled)

    result = {"valid": outs[0][:, :, :n_runs]}
    for (name, _), arr in zip(STAT_FIELDS, outs[1:]):
        result[name] = arr[:, 0, :n_runs]
    return result
