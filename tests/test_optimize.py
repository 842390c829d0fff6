"""Policy-optimizer tests: brute-force cross-validation of the batched grid.

The optimizer's whole value rests on three mechanical properties, each
checked here against an independent implementation:

  * **CRN bitwise identity** — every policy lane of the fused grid dispatch
    must equal a standalone ``renewal_monte_carlo_device`` call on that
    policy alone at the same key, bit for bit.  This is what makes
    cross-policy comparisons variance-free and grid results independent of
    the batch they ran in.
  * **argmin correctness** — the reported optimum must match an exhaustive
    host scan over the independent per-policy evaluations.
  * **Pareto correctness** — every reported frontier point must survive the
    O(n^2) non-domination definition, and every non-frontier point must be
    dominated (or duplicate a frontier point).

On top sit the derived guarantees: enlarging a grid never worsens the
reported optimum (a direct consequence of CRN bitwise identity,
property-tested), CEM refinement is monotone and deterministic, and the
optimum is process-dependent — Weibull k=0.7 at equal MTBF shifts the
checkpoint-interval optimum longer (docs/optimize.md documents the
experiment).
"""
import jax
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import energy_model as em
from repro.core import failures as F
from repro.core import optimize as O
from repro.core import planning, sweep
from repro.core.scenarios import (
    apply_policy,
    paper_scenarios,
    sparse_rendezvous_scenario,
)

KEY = jax.random.PRNGKey(7)
MTBF_S = 0.75 * 24 * 3600.0
WORK_S = 5 * 24 * 3600.0
N_RUNS = 32
MAX_FAILURES = 12


def _cfg():
    return paper_scenarios()["scenario4_short_active_waits"]


def _long_period_cfg():
    """The canonical policy-optimization workload: with the paper's 3600 s
    period the interval optimum pins to the workload structure
    (docs/optimize.md); the 4 h period restores the classical
    checkpoint-overhead vs re-execution tradeoff the process-dependence
    tests need."""
    return sparse_rendezvous_scenario()


def _coarse_table() -> O.PolicyTable:
    """The ISSUE's 3 x 3 x 2 cross-validation grid: interval x mu1 x
    wait_mode."""
    return O.policy_grid(
        ckpt_interval=[900.0, 1800.0, 3600.0],
        mu1=[3.8, 6.0, 7.5],
        wait_mode=[em.WaitMode.ACTIVE, em.WaitMode.IDLE],
    )


@pytest.fixture(scope="module")
def grid_eval():
    """One fused evaluation of the coarse grid (the object under test)."""
    return O.evaluate_policy_grid(
        _cfg(), _coarse_table(), KEY, work_s=WORK_S, n_runs=N_RUNS,
        max_failures=MAX_FAILURES, mtbf_s=MTBF_S)


@pytest.fixture(scope="module")
def independent_stats(grid_eval):
    """The brute-force reference: one standalone device-engine Monte-Carlo
    per policy, each rebuilt as a plain ``ScenarioConfig`` via
    ``apply_policy`` with that policy's equal-work makespan."""
    out = []
    table = grid_eval.table
    for p in range(len(table)):
        cfg_p = apply_policy(_cfg(), **table.policy(p))
        out.append(jax.device_get(sweep.renewal_monte_carlo_device(
            cfg_p, KEY, n_runs=N_RUNS, makespan_s=float(grid_eval.makespan_s[p]),
            mtbf_s=MTBF_S, max_failures=MAX_FAILURES, stats=True)))
    return out


# ---------------------------------------------------------------------------
# CRN cross-validation: batched lanes == standalone device calls, bit for bit
# ---------------------------------------------------------------------------

def test_crn_bitwise_vs_independent_device_calls(grid_eval, independent_stats):
    """Each policy lane of the fused dispatch is bit-identical to running
    that policy alone through ``renewal_monte_carlo_device`` at the same
    key — the common-random-numbers contract."""
    for p, st_p in enumerate(independent_stats):
        for field in ("energy_ref", "energy_int", "saving", "end_time"):
            np.testing.assert_array_equal(
                getattr(grid_eval, field)[p],
                np.asarray(getattr(st_p, field), np.float64)[0],
                err_msg=f"policy {p} field {field}")
        np.testing.assert_array_equal(
            grid_eval.n_failures[p],
            np.asarray(st_p.n_failures)[0], err_msg=f"policy {p}")


def test_action_counts_match_independent_calls(grid_eval, independent_stats):
    """The lean stats (integer action counts) also ride the policy axis
    unchanged."""
    table = grid_eval.table
    for p, st_p in enumerate(independent_stats):
        n_pts = int(np.asarray(st_p.n_points).sum())
        occ = (np.asarray(st_p.n_sleep).sum() / n_pts) if n_pts else 0.0
        assert grid_eval.sleep_occupancy[p] == occ, f"policy {p}"
        # idle-wait lanes never report MIN_FREQ; active lanes never NONE-wait
        if int(table.wait_mode[p]) == em.WaitMode.IDLE:
            assert grid_eval.min_freq_rate[p] == 0.0


def test_argmin_matches_exhaustive_host_scan(grid_eval, independent_stats):
    """The reported optimum == argmin of the independently computed
    per-policy expected energies (same reduction, same float64 means)."""
    means = np.array([
        np.asarray(s.energy_int, np.float64)[0].mean()
        for s in independent_stats])
    assert grid_eval.best == int(np.argmin(means))
    np.testing.assert_array_equal(grid_eval.mean_energy_j, means)
    best = grid_eval.policy(grid_eval.best)
    assert best["mean_energy_j"] == means.min()


def test_compose_policies_matches_device_compose(grid_eval):
    """Explicit-history entry: the policy-stacked composition equals the
    per-policy device composition on the same gaps, bit for bit."""
    table = grid_eval.table.subset([0, len(grid_eval.table) - 1])
    gaps = np.array([[40000.0, 90000.0, 30000.0], [250000.0, 60000.0, 15000.0]])
    makespan = 400000.0
    stacked = O.policy_inputs(_cfg(), table)
    res = sweep.renewal_compose_policies(
        stacked, gaps, np.full(len(table), makespan))
    for p in range(len(table)):
        cfg_p = apply_policy(_cfg(), **table.policy(p))
        ref = sweep.renewal_compose_device(cfg_p, gaps, makespan)
        for field in ("energy_ref", "energy_int", "saving", "end_time"):
            np.testing.assert_array_equal(
                np.asarray(getattr(res, field))[p],
                np.asarray(getattr(ref, field))[0],
                err_msg=f"policy {p} field {field}")


# ---------------------------------------------------------------------------
# Pareto frontier: O(n^2) non-domination re-check + knee
# ---------------------------------------------------------------------------

def _dominates(ei, mi, ej, mj) -> bool:
    """j-beats-i under the textbook definition (minimize both)."""
    return ej <= ei and mj <= mi and (ej < ei or mj < mi)


def test_pareto_front_nondominated_O_n2(grid_eval):
    """Every frontier point survives the O(n^2) check; every non-frontier
    point is dominated by (or exactly duplicates) a frontier point."""
    e, m = grid_eval.mean_energy_j, grid_eval.mean_makespan_s
    front = O.pareto_front(e, m)
    assert front.size >= 1
    fs = set(front.tolist())
    for i in fs:
        for j in range(len(e)):
            if j != i:
                assert not _dominates(e[i], m[i], e[j], m[j]), (i, j)
    for i in range(len(e)):
        if i in fs:
            continue
        covered = any(
            _dominates(e[i], m[i], e[j], m[j]) or (e[j] == e[i] and m[j] == m[i])
            for j in fs)
        assert covered, f"non-front point {i} neither dominated nor duplicate"
    # energy-ascending, makespan-descending along the front
    assert np.all(np.diff(e[front]) > 0)
    assert np.all(np.diff(m[front]) < 0)


def test_pareto_front_constructed_cases():
    e = np.array([1.0, 2.0, 3.0, 1.0, 2.5])
    m = np.array([5.0, 3.0, 1.0, 5.0, 3.0])
    front = O.pareto_front(e, m)
    # index 3 duplicates 0 (kept once); index 4 dominated by 1
    np.testing.assert_array_equal(front, [0, 1, 2])
    with pytest.raises(ValueError):
        O.pareto_front(e, m[:2])


def test_knee_point_cases():
    # elbow front: the corner point maximizes distance to the chord
    e = np.array([0.0, 0.1, 1.0, 0.5])
    m = np.array([1.0, 0.1, 0.0, 0.9])
    front = O.pareto_front(e, m)
    np.testing.assert_array_equal(front, [0, 1, 2])
    assert O.knee_point(e, m, front) == 1
    # degenerate fronts fall back to the utopia distance
    assert O.knee_point(np.array([1.0]), np.array([2.0])) == 0
    e2, m2 = np.array([1.0, 2.0]), np.array([4.0, 3.0])
    assert O.knee_point(e2, m2) in (0, 1)
    # collinear front: utopia fallback picks the middle
    e3, m3 = np.array([0.0, 0.5, 1.0]), np.array([1.0, 0.5, 0.0])
    assert O.knee_point(e3, m3) == 1


# ---------------------------------------------------------------------------
# grid monotonicity: enlarging the grid never worsens the optimum
# ---------------------------------------------------------------------------

_CANDIDATE_INTERVALS = np.array(
    [600.0, 900.0, 1500.0, 2400.0, 3600.0, 5400.0], np.float64)


@settings(max_examples=8, deadline=None)
@given(st.lists(st.integers(0, len(_CANDIDATE_INTERVALS) - 1),
                min_size=1, max_size=3),
       st.sampled_from([3.8, 6.0]))
def test_enlarging_grid_never_worsens_optimum(subset_idx, mu1):
    """A grid and a superset of it: the superset's reported optimum can
    only be <= (CRN makes per-policy energies independent of the batch, so
    min over a superset of lanes is min over a superset of the same
    numbers).  Asserted exactly — no tolerance."""
    subset_idx = sorted(set(subset_idx))
    sub = O.policy_grid(
        ckpt_interval=_CANDIDATE_INTERVALS[subset_idx], mu1=mu1)
    sup = O.policy_grid(ckpt_interval=_CANDIDATE_INTERVALS, mu1=mu1)
    kw = dict(work_s=2 * 24 * 3600.0, n_runs=16, max_failures=8,
              mtbf_s=MTBF_S)
    res_sub = O.evaluate_policy_grid(_cfg(), sub, KEY, **kw)
    res_sup = O.evaluate_policy_grid(_cfg(), sup, KEY, **kw)
    assert res_sup.mean_energy_j.min() <= res_sub.mean_energy_j.min()
    # the mechanism: each subset lane appears bit-identically in the superset
    np.testing.assert_array_equal(
        res_sub.mean_energy_j, res_sup.mean_energy_j[subset_idx])


# ---------------------------------------------------------------------------
# equal-work makespans
# ---------------------------------------------------------------------------

@settings(max_examples=50, deadline=None)
@given(st.floats(min_value=1000.0, max_value=3.0e6),
       st.floats(min_value=300.0, max_value=20000.0),
       st.floats(min_value=10.0, max_value=600.0))
def test_wall_makespan_balanced_span_roundtrip(work, interval, dur):
    """``wall_makespan`` inverts ``balanced_span``: a balanced run of the
    returned wall length completes exactly the requested work."""
    wall = float(O.wall_makespan(work, interval, dur))
    got_work, got_ckpt = planning.balanced_span(0.0, wall, interval, dur)
    assert np.isclose(float(got_work), work, rtol=1e-12, atol=1e-6)
    assert np.isclose(float(got_ckpt), wall - work, rtol=1e-12, atol=1e-6)


def test_wall_makespan_exact_multiples():
    # work == k * interval: the k-th checkpoint lands exactly at completion
    # and is not taken
    assert float(O.wall_makespan(3600.0, 1800.0, 120.0)) == 3600.0 + 120.0
    assert float(O.wall_makespan(1800.0, 1800.0, 120.0)) == 1800.0
    assert float(O.wall_makespan(100.0, 1800.0, 120.0)) == 100.0


# ---------------------------------------------------------------------------
# validation
# ---------------------------------------------------------------------------

def test_policy_inputs_validation():
    cfg = _cfg()   # ckpt ages 60, t_reexec 60
    with pytest.raises(ValueError, match="interval"):
        O.policy_inputs(cfg, O.policy_grid(ckpt_interval=[30.0, 1800.0]))
    with pytest.raises(ValueError, match="rows"):
        O.PolicyTable(ckpt_interval=np.array([100.0, 200.0]),
                      mu1=np.array([1.0, 2.0, 3.0]),
                      mu2=1.0, wait_mode=0, move_ahead_frac=0.5)
    with pytest.raises(ValueError, match="positive"):
        O.policy_grid(ckpt_interval=[0.0])
    with pytest.raises(ValueError, match="work_s or makespan_s"):
        O.evaluate_policy_grid(cfg, _coarse_table(), KEY, mtbf_s=MTBF_S)
    with pytest.raises(ValueError, match="work_s or makespan_s"):
        O.evaluate_policy_grid(cfg, _coarse_table(), KEY, mtbf_s=MTBF_S,
                               work_s=1e5, makespan_s=1e5)


# ---------------------------------------------------------------------------
# CEM refinement
# ---------------------------------------------------------------------------

def test_cem_refine_monotone_deterministic_and_no_worse_than_seed():
    cfg = _long_period_cfg()
    kw = dict(work_s=1 * 24 * 3600.0, n_runs=48, max_failures=48,
              mtbf_s=8 * 3600.0)
    tab = O.policy_grid(ckpt_interval=[3600.0, 7200.0])
    res = O.evaluate_policy_grid(cfg, tab, KEY, **kw)
    seed_policy = res.policy(res.best)
    cem_kw = dict(init=seed_policy,
                  bounds={"ckpt_interval": (2400.0, 12000.0)},
                  n_iters=3, population=8, seed=3, **kw)
    ref = O.cem_refine(cfg, KEY, **cem_kw)
    scores = [h["best_score"] for h in ref.iterations]
    assert all(b <= a for a, b in zip(scores, scores[1:])), scores
    assert ref.best["mean_energy_j"] <= seed_policy["mean_energy_j"]
    assert ref.n_evaluations == 3 * 9
    # deterministic: same key, same seed -> identical result
    again = O.cem_refine(cfg, KEY, **cem_kw)
    assert again.best == ref.best
    assert again.iterations == ref.iterations
    with pytest.raises(ValueError, match="CEM"):
        O.cem_refine(cfg, KEY, init=seed_policy,
                     bounds={"wait_mode": (0, 1)}, **kw)


def test_cem_refine_warm_start_resumes_posterior():
    """Warm-started retunes (the online controller's path) resume the
    Gaussian from the previous posterior: the search stays narrowed, the
    no-worse-than-init guarantee holds, and chaining from a previous best
    never regresses under CRN."""
    cfg = _long_period_cfg()
    kw = dict(work_s=1 * 24 * 3600.0, n_runs=48, max_failures=48,
              mtbf_s=8 * 3600.0)
    tab = O.policy_grid(ckpt_interval=[3600.0, 7200.0])
    seed_policy = O.evaluate_policy_grid(cfg, tab, KEY, **kw).policy(0)
    bounds = {"ckpt_interval": (2400.0, 12000.0)}
    cold = O.cem_refine(cfg, KEY, init=seed_policy, bounds=bounds,
                        n_iters=2, population=8, seed=3, **kw)
    warm = O.cem_refine(cfg, KEY, init=cold.best, bounds=bounds,
                        n_iters=1, population=8, seed=3, warm=cold, **kw)
    # chained refinement never regresses (same key: CRN-paired scores)
    assert warm.best["mean_energy_j"] <= cold.best["mean_energy_j"]
    # the warm proposal resumed from the cold posterior, floored at 2 % of
    # the box — not re-widened to init_std_frac of the box
    lo, hi = bounds["ckpt_interval"]
    cold_std = cold.iterations[-1]["std"]["ckpt_interval"]
    resumed_std = max(cold_std, 0.02 * (hi - lo))
    assert resumed_std < 0.25 * (hi - lo)
    # deterministic: warm-started call replays identically
    again = O.cem_refine(cfg, KEY, init=cold.best, bounds=bounds,
                         n_iters=1, population=8, seed=3, warm=cold, **kw)
    assert again.best == warm.best
    assert again.iterations == warm.iterations


# ---------------------------------------------------------------------------
# the operator entry point + process dependence
# ---------------------------------------------------------------------------

def test_optimize_policy_report_consistency():
    cfg = _long_period_cfg()
    tab = O.policy_grid(ckpt_interval=[2400.0, 4800.0, 9600.0],
                        wait_mode=[em.WaitMode.ACTIVE, em.WaitMode.IDLE])
    opt = O.optimize_policy(cfg, KEY, table=tab, work_s=1 * 24 * 3600.0,
                            mtbf_s=8 * 3600.0, n_runs=48, max_failures=48)
    assert opt.best == opt.grid.policy(opt.grid.best)
    assert opt.scenario == cfg.name
    front = opt.pareto
    np.testing.assert_array_equal(
        front, O.pareto_front(opt.grid.mean_energy_j,
                              opt.grid.mean_makespan_s))
    knee_idx = O.knee_point(opt.grid.mean_energy_j,
                            opt.grid.mean_makespan_s, front)
    assert opt.knee == opt.grid.policy(knee_idx)
    assert knee_idx in front.tolist()


def test_equal_mtbf_process_panel():
    mtbf = 6 * 3600.0
    panel = O.equal_mtbf_processes(mtbf)
    assert set(panel) == {"exponential", "weibull_k0.7", "trace"}
    for proc in panel.values():
        assert np.isclose(float(np.mean(proc.mean_s())), mtbf, rtol=1e-6)


def test_weibull_shifted_optimum_vs_exponential():
    """Weibull k=0.7 at equal MTBF shifts the checkpoint-interval optimum
    *longer* (docs/optimize.md): failures cluster right after each
    restart, when the post-recovery resync checkpoint has just bounded the
    loss anyway, so over-long intervals are punished less.  Three paired
    (CRN) signatures, each robust where the raw argmin is basin-tied:

      * the grid argmin never moves shorter,
      * the relative energy penalty for every over-long interval is
        strictly smaller under the Weibull,
      * the softmin-weighted interval (a continuous location of the
        optimum's basin) is strictly longer.
    """
    cfg = _long_period_cfg()
    ivals = np.geomspace(2400.0, 19200.0, 13)
    tab = O.policy_grid(ckpt_interval=ivals)
    mtbf = 8 * 3600.0
    kw = dict(work_s=4 * 24 * 3600.0, n_runs=512, max_failures=160)
    key = jax.random.PRNGKey(2)
    rel = {}
    best = {}
    for name, proc in (("exp", F.Exponential(mtbf)),
                       ("wb", F.Weibull.from_mtbf(0.7, mtbf))):
        res = O.evaluate_policy_grid(cfg, tab, key, process=proc, **kw)
        assert float(res.truncated_rate.max()) == 0.0
        e = res.mean_energy_j
        rel[name] = (e - e.min()) / e.min()
        best[name] = res.best
    assert best["wb"] >= best["exp"]
    # every interval one-or-more steps past the common optimum hurts less
    # under the clustered process (margin 1e-3 relative)
    long_side = slice(best["exp"] + 3, None)
    assert np.all(rel["wb"][long_side] < rel["exp"][long_side] - 1e-3), (
        rel["exp"], rel["wb"])
    # softmin location: temperature 3e-3 relative ~ the basin's depth scale
    loc = {n: float(np.sum(ivals * np.exp(-r / 3e-3))
                    / np.sum(np.exp(-r / 3e-3))) for n, r in rel.items()}
    assert loc["wb"] > 1.02 * loc["exp"], loc
