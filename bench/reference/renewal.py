"""Plain reference of the renewal Monte-Carlo: whole-run energy of a
checkpointed application under repeated failures, the energy model of
arXiv:2012.11396 (Algorithm 1, eqs. 1-15) composed over renewal epochs.

Written from the model, not from the program: straightforward numpy over
(lane, run[, survivor]) arrays with a Python loop over failure epochs.
A lane is one scenario of a study.  Precisions follow
what the configurations state: failure histories are float32 draws
(``jax.random`` uniforms and the inverse CDF, evaluated with ``jax.numpy``
on the default device, so a history is the same bits wherever it is
drawn); the epoch geometry and the energy ledgers are in ``dtype``
(float64 as stated, float32 for the control); Algorithm 1 is float32.

Semantics of an epoch (docs/sweep.md of the program describes the same
model): every node runs at the top frequency with a timer checkpoint of
``dur`` every ``interval`` of wall time; a failure ``gap`` seconds of
balanced execution after the last renewal anchor occurs while the balanced
time used stays within the makespan (the first that does not ends the
run); a failure landing inside a checkpoint is moved to its end; the
failed node goes down, restarts and re-executes its lost work; each
survivor runs Algorithm 1 over its work to the next rendezvous; the epoch
closes when the last survivor's rendezvous completes, with a coordinated
checkpoint that restarts every checkpoint age.  Nodes felled by the same
shock join the failed node's recovery.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

ACTIVE = 0                       # wait modes
NONE, MIN_FREQ, SLEEP = 0, 1, 2  # wait actions


# ---------------------------------------------------------------------------
# failure histories (float32)
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("n_runs", "max_failures", "n_nodes"))
def exponential_histories(key, mtbf, *, n_runs, max_failures, n_nodes):
    """Memoryless nodes: each epoch every node draws a fresh exponential
    time to failure; the epoch gap is the least, the failed node its
    owner.  Returns ``(gaps (R, K) float32, failed (R, K))``."""
    v = jax.random.uniform(key, (n_runs, max_failures, n_nodes), jnp.float32)
    t = jnp.float32(mtbf) * -jnp.log1p(-v)
    return jnp.min(t, axis=-1), jnp.argmin(t, axis=-1)


def _weibull_residual(v, age, k, lam):
    """Time to failure of a node of age ``age``: inverse of the Weibull
    survival conditioned on ``age``, S(age + t) / S(age) = 1 - v."""
    e = -jnp.log1p(-v)
    return jnp.maximum(lam * ((age / lam) ** k + e) ** (1.0 / k) - age, 0.0)


@partial(jax.jit, static_argnames=("n_runs", "max_failures", "n_nodes"))
def weibull_histories(key, k, lam, *, n_runs, max_failures, n_nodes):
    """Weibull nodes with failure clocks: each epoch every node draws its
    age-conditioned residual; survivors age by the gap, the failed node's
    clock restarts (clocks stand still while an epoch recovers)."""
    v = jax.random.uniform(key, (max_failures, n_runs, n_nodes), jnp.float32)
    k, lam = jnp.float32(k), jnp.float32(lam)

    def epoch(ages, v_k):
        t = _weibull_residual(v_k, ages, k, lam)
        gap, failed = jnp.min(t, axis=-1), jnp.argmin(t, axis=-1)
        ages = jnp.where(jnp.arange(n_nodes) == failed[:, None], 0.0,
                         ages + gap[:, None])
        return ages, (gap, failed)

    _, (gaps, failed) = jax.lax.scan(
        epoch, jnp.zeros((n_runs, n_nodes), jnp.float32), v)
    return gaps.T, failed.T


@partial(jax.jit, static_argnames=("n_runs", "max_failures", "n_nodes",
                                   "rack_size"))
def rack_histories(key, k, lam, shock_mtbs, p_kill, age_boost, *, n_runs,
                   max_failures, n_nodes, rack_size):
    """Weibull nodes in racks of ``rack_size`` with shared shocks: each rack
    draws a fresh exponential time to its next shock; when a shock comes
    before every node's own failure, each member falls with probability
    ``p_kill`` (at least one falls: the member with the least kill draw),
    the first to fall by kill draw is the primary, and spared members age
    by ``age_boost``.  Returns ``(gaps, felled (R, K, N) bool, primary)``."""
    n_racks = -(-n_nodes // rack_size)
    member = (jnp.arange(n_racks)[:, None]
              == (jnp.arange(n_nodes) // rack_size)[None, :])      # (G, N)
    k_res, k_shock, k_kill = jax.random.split(key, 3)
    v = jax.random.uniform(k_res, (max_failures, n_runs, n_nodes), jnp.float32)
    w = jax.random.uniform(k_kill, (max_failures, n_runs, n_nodes), jnp.float32)
    su = jax.random.uniform(k_shock, (max_failures, n_runs, n_racks),
                            jnp.float32)
    k, lam = jnp.float32(k), jnp.float32(lam)
    mtbs, pk, boost = (jnp.full((n_racks,), x, jnp.float32)
                       for x in (shock_mtbs, p_kill, age_boost))
    nodes = jnp.arange(n_nodes)

    def epoch(ages, xs):
        v_k, w_k, su_k = xs
        t = _weibull_residual(v_k, ages, k, lam)
        gap_own, own = jnp.min(t, axis=-1), jnp.argmin(t, axis=-1)
        shock_t = mtbs * -jnp.log1p(-su_k)                         # (R, G)
        gap_shock, rack = jnp.min(shock_t, axis=-1), jnp.argmin(shock_t, -1)
        shock = gap_shock < gap_own
        gap = jnp.where(shock, gap_shock, gap_own)
        in_rack = member[rack]                                     # (R, N)
        killed = in_rack & (w_k < pk[rack][:, None])
        w_in = jnp.where(in_rack, w_k, jnp.inf)
        forced = nodes == jnp.argmin(w_in, axis=-1)[:, None]
        killed = jnp.where(jnp.any(killed, axis=-1, keepdims=True),
                           killed, forced)
        felled = jnp.where(shock[:, None], killed, nodes == own[:, None])
        primary = jnp.where(
            shock, jnp.argmin(jnp.where(killed, w_k, jnp.inf), axis=-1), own)
        spared = shock[:, None] & in_rack & ~killed
        ages = jnp.where(felled, 0.0, ages + gap[:, None]
                         + jnp.where(spared, boost[rack][:, None], 0.0))
        return ages, (gap, felled, primary)

    _, (gaps, felled, primary) = jax.lax.scan(
        epoch, jnp.zeros((n_runs, n_nodes), jnp.float32), (v, w, su))
    return gaps.T, jnp.transpose(felled, (1, 0, 2)), primary.T


def survivor_slots(felled, primary) -> np.ndarray:
    """A physical-node mask (..., N+1) in survivor slots (..., N): slot i is
    node i, or i + 1 from the primary on."""
    felled, primary = np.asarray(felled), np.asarray(primary)
    n = felled.shape[-1] - 1
    idx = np.arange(n)
    phys = idx + (idx >= primary[..., None])
    return np.take_along_axis(felled, phys, axis=-1)


# ---------------------------------------------------------------------------
# checkpoint geometry (``dtype``)
# ---------------------------------------------------------------------------

def _floor_div(q, period):
    """``floor(q / period)`` and the remainder, with the quotient stepped
    so that the remainder lies in ``[0, period)``."""
    j = np.floor(q / period)
    r = q - j * period
    j = np.where(r < 0, j - 1, np.where(r >= period, j + 1, j))
    return j, q - j * period


def advance(age0, delta, interval, dur):
    """A node of checkpoint age ``age0`` runs ``delta`` wall seconds; a
    failure inside a checkpoint moves to its end.  Returns the age at the
    failure, the work done and the (moved) failure instant."""
    first = interval - age0
    period = interval + dur
    fired = delta >= first
    j, r = _floor_div(np.maximum(delta - first, 0), period)
    inside = fired & (r < dur)
    n_fired = np.where(fired, j + 1, 0)
    age = np.where(fired, np.where(inside, 0, r - dur), age0 + delta)
    at = np.where(inside, first + j * period + dur, delta)
    return age, at - n_fired * dur, at


def balanced_split(age0, span, interval, dur):
    """``span`` wall seconds of balanced execution from checkpoint age
    ``age0``, split into (work, checkpoint) seconds."""
    first = interval - age0
    j, r = _floor_div(np.maximum(span - first, 0), interval + dur)
    ckpt = np.where(span > first, j * dur + np.minimum(r, dur), 0)
    return span - ckpt, ckpt


# ---------------------------------------------------------------------------
# Algorithm 1 (float32)
# ---------------------------------------------------------------------------

def algorithm1(work, t_failed, n_ckpt, lane):
    """Per survivor: the ladder level and wait action of least energy that
    reaches the rendezvous in time (eqs. 3-13), against the reference of
    running on at the top frequency (eq. 2).  ``work``/``t_failed`` are
    (L, R, N), ``n_ckpt`` (L, R, N, F); lane parameters broadcast.  Returns
    ``(e_ref, e_int, comp_time, action, level, feasible_any)``."""
    f4 = np.float32
    lv = lambda a: np.asarray(a, f4)[:, None, None, :]        # (L,1,1,F)
    sc = lambda a: np.asarray(a, f4)[:, None, None]           # (L,1,1)
    t = np.asarray(work, f4)
    tf = np.asarray(t_failed, f4)
    n = np.asarray(n_ckpt, f4)
    beta, gamma = lv(lane["beta"]), lv(lane["gamma"])
    p_comp, p_ckpt = lv(lane["p_comp"]), lv(lane["p_ckpt"])
    t_ckpt = sc(lane["dur"])[..., None]
    active = (np.asarray(lane["wait_mode"]) == ACTIVE)[:, None, None]
    p_awake = np.where(active, sc(np.asarray(lane["p_comp"])[:, -1]),
                       sc(lane["p_idle_wait"]))[..., None]
    trans_t = sc(lane["t_go_sleep"]) + sc(lane["t_wakeup"])
    trans_e = (sc(lane["t_go_sleep"]) * sc(lane["p_go_sleep"])
               + sc(lane["t_wakeup"]) * sc(lane["p_wakeup"]))
    ct = t[..., None] * beta + n * t_ckpt * gamma               # comp time
    wt = tf[..., None] - ct                                     # wait time
    e_comp = t[..., None] * beta * p_comp + n * t_ckpt * gamma * p_ckpt
    e_awake = np.maximum(wt, 0) * p_awake
    e_sleep = trans_e[..., None] + np.maximum(
        wt - trans_t[..., None], 0) * sc(lane["p_sleep"])[..., None]
    feasible = ct <= tf[..., None] * (1.0 + 1e-6) + 1e-3
    sleeps = ((wt > (sc(lane["mu1"]) * trans_t)[..., None])
              & (e_sleep < sc(lane["mu2"])[..., None] * e_awake))
    total = np.where(feasible, e_comp + np.where(sleeps, e_sleep, e_awake),
                     np.inf)
    level = np.argmin(total, axis=-1)
    pick = lambda a: np.take_along_axis(a, level[..., None], -1)[..., 0]
    any_ok = feasible.any(axis=-1)
    e_ref = e_comp[..., 0] + np.maximum(tf - ct[..., 0], 0) * np.where(
        active, sc(np.asarray(lane["p_comp"])[:, 0]), sc(lane["p_idle_wait"]))
    e_int = np.where(any_ok, pick(total), e_ref)
    comp_time = np.where(any_ok, pick(ct), ct[..., 0])
    action = np.where(any_ok, np.where(
        pick(sleeps), SLEEP, np.where(active, MIN_FREQ, NONE)), NONE)
    return (e_ref, e_int, comp_time, action, np.where(any_ok, level, 0),
            any_ok)


# ---------------------------------------------------------------------------
# whole-run composition
# ---------------------------------------------------------------------------

def compose(lane: dict, gaps, failed, felled=None, fmask=None,
            dtype=np.float64) -> dict:
    """Whole-run energies of every (lane, run) for failure histories
    ``gaps`` (R, K) and failed nodes ``failed`` (R, K), shared by all
    lanes.  ``lane`` holds (L, ...) arrays: ``exec0``/``period``/``age0``
    (L, N), ``reexec0``, ``t_down``, ``t_restart``, ``interval``, ``dur``,
    ``move_ahead``, ``move_frac``, ``wait_mode``, ``mu1``, ``mu2``,
    ``p_idle_wait``, the sleep state and ``makespan`` (L,), the ladder
    ``beta``/``gamma``/``p_comp``/``p_ckpt`` (L, F).  ``felled`` (R, K, N)
    marks survivor slots felled with the failed node and ``fmask``
    (R, K, N+1) every felled physical node.  Returns per-run arrays (L, R)
    and per-lane counts (L,)."""
    x = lambda a: np.asarray(a, dtype)
    col = lambda a: x(a)[:, None]                  # (L, 1)
    col3 = lambda a: x(a)[:, None, None]           # (L, 1, 1)
    gaps = x(gaps)
    n_runs, n_k = gaps.shape
    exec_anchor = np.broadcast_to(x(lane["exec0"])[:, None, :],
                                  (len(lane["interval"]), n_runs,
                                   np.shape(lane["exec0"])[1])).copy()
    n = exec_anchor.shape[-1]
    period = x(lane["period"])[:, None, :]
    ages = np.broadcast_to(x(lane["age0"])[:, None, :], exec_anchor.shape).copy()
    reexec_age = np.broadcast_to(col(lane["reexec0"]), exec_anchor.shape[:2]).copy()
    interval, dur = col(lane["interval"]), col(lane["dur"])
    interval3, dur3 = col3(lane["interval"]), col3(lane["dur"])
    p_comp0, p_ckpt0 = col(x(lane["p_comp"])[:, 0]), col(x(lane["p_ckpt"])[:, 0])
    beta = x(lane["beta"])[:, None, None, :]
    beta0, gamma0 = col3(x(lane["beta"])[:, 0]), col3(x(lane["gamma"])[:, 0])
    dur_fa = dur * col(x(lane["gamma"])[:, 0])
    makespan = col(lane["makespan"])
    t_dr = col(lane["t_down"]) + col(lane["t_restart"])
    move_ahead = np.asarray(lane["move_ahead"], bool)[:, None, None]
    move_frac = col3(lane["move_frac"])
    if felled is None:
        felled = np.zeros((n_runs, n_k, n), bool)

    shape = exec_anchor.shape[:2]
    t_anchor, bal = np.zeros(shape, dtype), np.zeros(shape, dtype)
    alive = np.ones(shape, bool)
    balanced = np.zeros(shape, dtype)
    epochs_ref = np.zeros(shape, dtype)
    epochs_int = np.zeros(shape, dtype)
    epochs_failed = np.zeros(shape, dtype)
    n_valid = np.zeros(shape, np.int64)
    counts = {k: np.zeros(len(interval), np.int64)
              for k in ("points", "sleep", "min_freq", "comp_changed",
                        "infeasible")}
    failed_counts = np.zeros((len(interval), n + 1), np.int64)
    for k in range(n_k):
        delta = gaps[None, :, k]                                  # (1, R)
        occurs = alive & (bal + delta <= makespan)
        if not occurs.any():
            alive &= occurs
            break
        age_f, work, at_surv = advance(ages, delta[..., None], interval3, dur3)
        rem = np.mod(exec_anchor - work, period)
        exec_rem = np.where(rem == 0, period, rem)
        reexec, _, at_fail = advance(reexec_age, delta, interval, dur)
        m = felled[None, :, k]                                    # (1, R, N)
        reexec = np.maximum(reexec, np.max(np.where(m, age_f, -np.inf), -1))
        t_recover = t_dr + reexec
        t_failed = t_recover[..., None] + exec_rem

        # balanced execution up to each node's failure instant, and the
        # coordinated checkpoint that closes the epoch
        w_s, c_s = balanced_split(ages, at_surv, interval3, dur3)
        w_f, c_f = balanced_split(reexec_age, at_fail, interval, dur)
        e_bal = ((w_s * p_comp0[..., None] + c_s * p_ckpt0[..., None]).sum(-1)
                 + w_f * p_comp0 + c_f * p_ckpt0)
        balanced += np.where(occurs, e_bal + (n + 1) * dur_fa * p_ckpt0, 0)

        # checkpoints each level would take before the rendezvous, and the
        # move-ahead checkpoint decided on the top-frequency timeline
        n_timer = np.maximum(0, np.ceil(
            (exec_rem[..., None] * beta + age_f[..., None]
             - interval3[..., None]) / interval3[..., None] - 1e-9))
        n0 = n_timer[..., 0]
        wait_at_block = t_failed - (exec_rem + n0 * dur3)
        last_end = np.where(n0 > 0, (interval3 - age_f)
                            + (n0 - 1) * (interval3 + dur3) + dur3, -age_f)
        age_at_block = exec_rem + n0 * dur3 - last_end
        move = (move_ahead & (age_at_block > move_frac * interval3)
                & (wait_at_block > dur3))
        n_ckpt = n_timer + np.where(move, 1, 0)[..., None]

        p_star = np.maximum(np.max(np.where(m, -np.inf, exec_rem), -1), 0)
        t_e = t_recover + p_star
        e_ref, e_int, comp_time, action, level, any_ok = algorithm1(
            exec_rem, t_failed, n_ckpt, lane)
        ct_ref = exec_rem * beta0 + n_ckpt[..., 0] * dur3 * gamma0
        trail_ref = np.maximum(t_e[..., None] - np.maximum(t_failed, ct_ref),
                               0) * p_comp0[..., None]
        trail_int = np.maximum(t_e[..., None] - np.maximum(
            t_failed, x(comp_time)), 0) * p_comp0[..., None]
        v = occurs[..., None] & ~m
        epochs_ref += np.where(v, x(e_ref) + trail_ref, 0).sum(-1)
        epochs_int += np.where(v, x(e_int) + trail_int, 0).sum(-1)
        epochs_failed += np.where(occurs, (1 + m.sum(-1)) * (
            col(lane["t_restart"]) * p_ckpt0 + (reexec + p_star) * p_comp0), 0)
        counts["points"] += v.sum((1, 2))
        counts["sleep"] += (v & (action == SLEEP)).sum((1, 2))
        counts["min_freq"] += (v & (action == MIN_FREQ)).sum((1, 2))
        counts["comp_changed"] += (v & (level != 0)).sum((1, 2))
        counts["infeasible"] += (v & ~any_ok).sum((1, 2))
        if fmask is None:
            hit = np.asarray(failed)[:, k, None] == np.arange(n + 1)
        else:
            hit = np.asarray(fmask)[:, k]
        failed_counts += (occurs[..., None] & hit[None]).sum(1)
        n_valid += occurs

        # renewal: coordinated checkpoint, every survivor resumes at the
        # first rendezvous past the shared progress point
        gap_next = np.mod(p_star[..., None] - exec_rem, period)
        exec_next = np.where(gap_next == 0, period, period - gap_next)
        exec_anchor = np.where(occurs[..., None], exec_next, exec_anchor)
        ages = np.where(occurs[..., None], 0, ages)
        reexec_age = np.where(occurs, 0, reexec_age)
        bal = np.where(occurs, bal + at_fail, bal)
        t_anchor = np.where(occurs, t_anchor + at_fail + t_e + dur_fa, t_anchor)
        alive &= occurs

    span = np.maximum(makespan - bal, 0)
    w_s, c_s = balanced_split(ages, span[..., None], interval3, dur3)
    w_f, c_f = balanced_split(reexec_age, span, interval, dur)
    balanced += ((w_s * p_comp0[..., None] + c_s * p_ckpt0[..., None]).sum(-1)
                 + w_f * p_comp0 + c_f * p_ckpt0)
    energy_ref = balanced + epochs_ref + epochs_failed
    energy_int = balanced + epochs_int + epochs_failed
    return dict(energy_ref=energy_ref, energy_int=energy_int,
                saving=energy_ref - energy_int, end_time=t_anchor + span,
                n_failures=n_valid, truncated=alive & (bal < makespan),
                failed_counts=failed_counts, **counts)


# ---------------------------------------------------------------------------
# lanes from the configuration files, and the study summary
# ---------------------------------------------------------------------------

def _machine_lane(m: dict) -> dict:
    """Ladder, sleep state and idle power of a machine."""
    lad, sl = m["ladder"], m["sleep"]
    return dict(beta=lad["beta"], gamma=lad["gamma"], p_comp=lad["p_comp"],
                p_ckpt=lad["p_ckpt"], t_go_sleep=sl["t_go_sleep"],
                t_wakeup=sl["t_wakeup"], p_go_sleep=sl["p_go_sleep"],
                p_wakeup=sl["p_wakeup"], p_sleep=sl["p_sleep"],
                p_idle_wait=m["p_idle_wait"])


def stack_lanes(rows: list) -> dict:
    """Lane dicts of scalars and lists stacked into (L, ...) arrays."""
    return {k: np.asarray([r[k] for r in rows]) for k in rows[0]}


def scenario_lane(s: dict, machines: dict, makespan_s: float) -> dict:
    """One Table-4 style scenario of a configuration file as a lane."""
    sv = s["survivors"]
    return dict(
        _machine_lane(machines[s["machine"]]),
        exec0=[v["exec_to_rendezvous"] for v in sv],
        period=[v["rendezvous_period"] for v in sv],
        age0=[v["ckpt_age"] for v in sv], reexec0=s["t_reexec"],
        t_down=s["t_down"], t_restart=s["t_restart"],
        interval=s["ckpt_interval"], dur=s["ckpt_duration"],
        move_ahead=s["move_ahead"], move_frac=s["move_ahead_frac"],
        wait_mode=s["wait_mode"], mu1=s["mu1"], mu2=s["mu2"],
        makespan=makespan_s)


def study_summary(out: dict, lane: int, n_runs: int) -> dict:
    """Expectations of one lane over its runs: the numbers a study
    reports."""
    ref = np.asarray(out["energy_ref"][lane], np.float64)
    saving = np.asarray(out["saving"][lane], np.float64)
    counts = np.asarray(out["n_failures"][lane])
    pts = int(out["points"][lane])
    rate = lambda k: float(out[k][lane]) / pts if pts else 0.0
    return dict(
        mean_failures=float(counts.mean()),
        failure_count_hist={int(c): float(np.mean(counts == c))
                            for c in np.unique(counts)},
        per_node_failures=tuple(float(c) / n_runs
                                for c in out["failed_counts"][lane]),
        truncated_rate=float(np.mean(out["truncated"][lane])),
        mean_energy_ref_j=float(ref.mean()),
        mean_energy_int_j=float(
            np.asarray(out["energy_int"][lane], np.float64).mean()),
        mean_saving_j=float(saving.mean()),
        p5_saving_j=float(np.percentile(saving, 5)),
        p95_saving_j=float(np.percentile(saving, 95)),
        sleep_occupancy=rate("sleep"), min_freq_rate=rate("min_freq"),
        comp_change_rate=rate("comp_changed"),
        infeasible_rate=rate("infeasible"))
