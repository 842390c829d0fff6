"""Plain reference of an advisory's policy lanes: a job's balanced snapshot
under each policy of a grid, at equal useful work, composed over renewal
epochs by ``bench.reference.renewal``.

Written from the model, not from the program.  A job of ``n`` nodes runs
one rank a node; each failure epoch one node fails and ``n - 1`` survive.
Its snapshot is the state just after a coordinated checkpoint: survivor
``i`` (0-based) is ``period * (i + 1) / n`` seconds from its next
rendezvous, every checkpoint age 0, no lost work.  A policy sets the
checkpoint interval, the move-ahead age fraction, the wait mode and the
sleep margins ``mu1``/``mu2``.  Every policy completes the same useful
work ``W``, so a policy's wall makespan is ``W`` plus one checkpoint of
``dur`` for each timer fire strictly inside the work, ``ceil(W / interval)
- 1`` of them (none at completion).  A lane's answer is its expectation
over runs: reference and intervened energy, realized makespan and saving.
"""
from __future__ import annotations

import numpy as np

from bench.reference import renewal as ref

DAY_S = 24 * 3600.0
KNOBS = ("interval", "mu1", "mu2", "wait_mode", "move_frac")
PER_RUN = ("energy_ref", "energy_int", "end_time", "saving")


def policy_columns(grid: dict) -> dict:
    """The grid's knob columns (P,), rows in C order over (interval, mu1,
    mu2, wait mode, move-ahead fraction).  The intervals are Young's
    ``sqrt(2 dur MTBF)`` at the grid's anchor times its factors."""
    young = np.sqrt(2.0 * grid["ckpt_duration"]
                    * grid["anchor_mtbf_days"] * DAY_S)
    lo, hi, n = grid["young_factors"]["geomspace"]
    axes = [young * np.geomspace(lo, hi, int(n)), grid["mu1"], grid["mu2"],
            grid["wait_mode"], grid["move_ahead_frac"]]
    mesh = np.meshgrid(*[np.asarray(a, np.float64) for a in axes],
                       indexing="ij")
    cols = dict(zip(KNOBS, (m.reshape(-1) for m in mesh)))
    cols["wait_mode"] = cols["wait_mode"].astype(np.int64)
    return cols


def wall_makespan(work_s, interval, dur):
    """Wall seconds of a failure-free run that completes ``work_s`` of
    useful work under a checkpoint every ``interval`` of work."""
    fires = np.maximum(np.ceil(work_s / np.asarray(interval)) - 1, 0)
    return work_s + fires * dur


def job_lanes(job: dict, machine: dict, snapshot: dict, cols: dict,
              work_s: float) -> dict:
    """One lane a policy for ``job`` with ``work_s`` of work left: (P, ...)
    arrays in ``ref.compose``'s layout."""
    n, period = job["n_nodes"], job["period_s"]
    p = len(cols["interval"])
    scale = job["power_scale"]
    lad, sl = machine["ladder"], machine["sleep"]
    node = dict(
        beta=lad["beta"], gamma=lad["gamma"],
        p_comp=np.multiply(lad["p_comp"], scale),
        p_ckpt=np.multiply(lad["p_ckpt"], scale),
        t_go_sleep=sl["t_go_sleep"], t_wakeup=sl["t_wakeup"],
        p_go_sleep=sl["p_go_sleep"] * scale, p_wakeup=sl["p_wakeup"] * scale,
        p_sleep=sl["p_sleep"] * scale,
        p_idle_wait=machine["p_idle_wait"] * scale)
    exec0 = period * np.arange(1, n) / n
    row = dict(
        node, exec0=exec0, period=np.full(n - 1, period),
        age0=np.full(n - 1, snapshot["ckpt_age"]),
        reexec0=snapshot["t_reexec"], t_down=job["t_down"],
        t_restart=job["t_restart"], dur=job["ckpt_duration"],
        move_ahead=snapshot["move_ahead"])
    lane = {k: np.stack([np.asarray(v)] * p) for k, v in row.items()}
    lane.update(interval=cols["interval"], move_frac=cols["move_frac"],
                wait_mode=cols["wait_mode"], mu1=cols["mu1"], mu2=cols["mu2"],
                makespan=wall_makespan(work_s, cols["interval"],
                                       job["ckpt_duration"]))
    return lane


def compose_block(lane: dict, gaps, failed, dtype=np.float64) -> dict:
    """The per-run answers (P, runs) of a block of runs."""
    out = ref.compose(lane, gaps, failed, dtype=dtype)
    return {k: np.asarray(out[k], np.float64) for k in PER_RUN}


def expectations(per_run: dict) -> dict:
    """A lane's means over its runs, (P,) each."""
    return dict(mean_energy_ref_j=per_run["energy_ref"].mean(axis=1),
                mean_energy_int_j=per_run["energy_int"].mean(axis=1),
                mean_makespan_s=per_run["end_time"].mean(axis=1),
                mean_saving_j=per_run["saving"].mean(axis=1))
