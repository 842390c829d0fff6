"""A study's summaries, reduced over runs inside its own program.

``sweep.renewal_monte_carlo_scenarios`` runs one program a study
(``sweep._renewal_study_core``): the fused Monte-Carlo, then the reduction
over runs (``sweep._study_reduce``), so only the summaries' numbers leave
the device.  Its contract is the numpy reduction of the same per-run stats
(``sweep._run_moments``, the host oracle's), assembled by
``sweep._assemble_summary``: integer-derived fields exactly, float means to
float64 round-off, percentiles as ``np.percentile`` gives them.
"""
import glob

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.core import failures as F
from repro.core import sweep
from repro.core import topology as T
from repro.core.scenarios import paper_scenarios

MTBF = 7 * 24 * 3600.0
MAKESPAN = 30 * 24 * 3600.0
CFGS = [paper_scenarios()[n] for n in sorted(paper_scenarios())]
EXACT = ("n_runs", "makespan_s", "mtbf_s", "max_failures", "mean_failures",
         "failure_count_hist", "per_node_failures", "truncated_rate",
         "sleep_occupancy", "min_freq_rate", "comp_change_rate",
         "infeasible_rate")
CLOSE = ("mean_energy_ref_j", "mean_energy_int_j", "mean_saving_j",
         "mean_saving_pct", "annual_saving_j")
PERCENTILES = ("p5_saving_j", "p95_saving_j")


def _family(name):
    rack = T.rack_topology(4, 3, shock_mtbs_s=10 * MTBF, p_kill=0.6,
                           age_boost_s=3600.0)
    return {"exponential": (F.Exponential(MTBF), None, "scan"),
            "weibull": (F.Weibull.from_mtbf(0.7, MTBF), None, "scan"),
            "rack": (F.Weibull.from_mtbf(0.7, MTBF), rack, "scan"),
            "pallas": (F.Weibull.from_mtbf(0.7, MTBF), rack, "pallas")}[name]


def _numpy_summary(stats, s, *, n_runs, max_failures, mtbf_s):
    """Scenario ``s`` of per-run device stats, reduced by numpy and
    assembled as the host oracle assembles its own."""
    pick = lambda f: np.asarray(getattr(stats, f))[s]
    n_pts = int(pick("n_points").sum())
    rate = lambda f: int(pick(f).sum()) / n_pts if n_pts else 0.0
    return sweep._assemble_summary(
        **sweep._run_moments(pick("n_failures"), pick("truncated"),
                             pick("energy_ref"), pick("energy_int"),
                             pick("saving")),
        per_node=[float(c) / n_runs for c in pick("failed_counts")],
        sleep_occupancy=rate("n_sleep"), min_freq_rate=rate("n_min_freq"),
        comp_change_rate=rate("n_comp_changed"),
        infeasible_rate=rate("n_infeasible"),
        n_runs=n_runs, makespan_s=MAKESPAN, mtbf_s=mtbf_s,
        max_failures=max_failures)


@pytest.mark.parametrize("family", ["exponential", "weibull", "rack",
                                    "pallas"])
def test_the_study_reduction_matches_numpy_on_the_same_stats(family):
    process, topology, engine = _family(family)
    kw = dict(n_runs=256, makespan_s=MAKESPAN, max_failures=16,
              process=process, topology=topology)
    key = jax.random.PRNGKey(11)
    got = sweep.renewal_monte_carlo_scenarios(CFGS, key, engine=engine, **kw)
    stats = jax.device_get(sweep.renewal_monte_carlo_device(
        CFGS, key, stats=True, engine=engine, **kw))
    mtbf = float(np.mean(process.mean_s()))
    for s, cfg in enumerate(CFGS):
        g = got[cfg.name]
        want = _numpy_summary(stats, s, n_runs=256, max_failures=16,
                              mtbf_s=mtbf)
        for f in EXACT:
            assert getattr(g, f) == getattr(want, f), (cfg.name, f)
        for f in CLOSE:
            np.testing.assert_allclose(getattr(g, f), getattr(want, f),
                                       rtol=1e-12, err_msg=f"{cfg.name} {f}")
        floor = 1e-12 * abs(want.mean_energy_ref_j)
        for f in PERCENTILES:
            np.testing.assert_allclose(getattr(g, f), getattr(want, f),
                                       rtol=1e-12, atol=floor,
                                       err_msg=f"{cfg.name} {f}")


@pytest.mark.parametrize("rows", [
    np.array([[3.5]]),
    np.array([[2.0, -1.0], [7.0, 7.0]]),
    np.array([[5.0, -2.0, 9.0], [0.0, 1e9, -1e9]]),
    np.full((2, 7), 123.456),
    np.array([[1.0, np.nan, 3.0, 4.0], [4.0, 3.0, 2.0, 1.0]]),
    np.random.default_rng(0).normal(1e6, 3e5, (3, 4096)),
], ids=["one_run", "two_runs", "three_runs", "all_equal", "nan", "study"])
def test_percentiles_are_numpys(rows):
    with jax.enable_x64():
        got = np.asarray(jax.jit(sweep._percentiles)(rows))
    want = np.percentile(rows, [5, 95], axis=-1)
    # numpy's formula and order statistics; the compiler may fuse the
    # interpolation's multiply-add, a rounding apart
    np.testing.assert_allclose(got, want, rtol=1e-15,
                               atol=1e-15 * np.nanmax(np.abs(rows)))


def _stats(n_failures, n_points, n_sleep=None, n_nodes=4):
    """Per-run stats of one scenario, shaped as the fused Monte-Carlo
    leaves them, from a run's failure count and decision points."""
    n_failures = np.asarray([n_failures], np.int32)
    n_points = np.asarray([n_points], np.int32)
    zero = np.zeros_like(n_points)
    energy = 1e9 + 1e6 * np.arange(n_failures.size, dtype=np.float64)
    return dict(
        n_failures=n_failures, truncated=n_failures == n_failures.max(),
        energy_ref=energy[None], energy_int=energy[None] - 5e4,
        saving=np.full_like(energy[None], 5e4), n_points=n_points,
        n_sleep=zero if n_sleep is None else np.asarray([n_sleep], np.int32),
        n_min_freq=zero, n_comp_changed=zero, n_infeasible=zero,
        failed_counts=np.arange(n_nodes, dtype=np.int32)[None])


def _reduced_summary(stats, max_failures):
    with jax.enable_x64():
        totals, moments = jax.device_get(
            sweep._study_reduce_jit(stats, max_failures=max_failures))
    return sweep._study_summary(
        totals[0], moments[0], n_runs=stats["saving"].shape[-1],
        makespan_s=MAKESPAN, mtbf_s=MTBF, max_failures=max_failures)


def test_a_histogram_that_skips_counts():
    counts = [0, 3, 3, 5, 0, 3, 8, 5]
    got = _reduced_summary(_stats(counts, [3 * c for c in counts]), 8)
    want = sweep._run_moments(counts, np.ones(8), 1.0, 1.0, np.ones(8))
    assert got.failure_count_hist == want["failure_count_hist"] == {
        0: 0.25, 3: 0.375, 5: 0.25, 8: 0.125}
    assert got.mean_failures == want["mean_failures"]
    assert got.per_node_failures == (0.0, 1 / 8, 2 / 8, 3 / 8)


def test_no_valid_points_reads_zero_rates():
    got = _reduced_summary(_stats([0, 0, 0], [0, 0, 0]), 4)
    assert (got.sleep_occupancy, got.min_freq_rate, got.comp_change_rate,
            got.infeasible_rate) == (0.0, 0.0, 0.0, 0.0)
    assert got.failure_count_hist == {0: 1.0}
    some = _reduced_summary(_stats([1, 2, 0], [3, 6, 0], n_sleep=[1, 2, 0]),
                            4)
    assert some.sleep_occupancy == 3 / 9


@pytest.mark.parametrize("engine", ["scan", "pallas"])
def test_the_study_program_returns_no_run_axis(engine):
    n_runs = 64
    process, topology, _ = _family("rack")
    with sweep._staged(CFGS[:2], process, MTBF, engine) as (stacked, proc):
        makespan = (np.float32(MAKESPAN) if engine == "pallas"
                    else MAKESPAN)
        lowered = sweep._renewal_study_jit.lower(
            stacked, jax.random.PRNGKey(0), makespan, proc, n_runs=n_runs,
            max_failures=4, topology=topology, engine=engine)
    leaves = jax.tree.leaves(lowered.out_info)
    assert leaves
    assert all(np.prod(leaf.shape) < n_runs for leaf in leaves), [
        leaf.shape for leaf in leaves]


def test_the_fetch_span_carries_its_byte_count(tmp_path):
    kw = dict(n_runs=8, max_failures=4, process=F.Exponential(MTBF))
    sweep.renewal_monte_carlo_scenarios(CFGS, jax.random.PRNGKey(2), **kw)
    jax.profiler.start_trace(str(tmp_path))
    sweep.renewal_monte_carlo_scenarios(CFGS, jax.random.PRNGKey(2), **kw)
    jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    got = [dict(ev.stats) for plane in ProfileData.from_file(path).planes
           for line in plane.lines for ev in line.events
           if ev.name == "sweep.fetch"]
    n_totals = len(sweep._STUDY_TOTALS) + kw["max_failures"] + 1 + 4
    want = len(CFGS) * (4 * n_totals + 8 * len(sweep._STUDY_MOMENTS))
    assert got == [{"bytes": want}]
