"""Pallas renewal engine tests: the float32 Kahan-ledger kernel vs the
float64 host oracle and the x64 scan engine.

The kernel (``kernels.renewal_scan``, ``engine="pallas"``) re-derives the
renewal geometry in float32 with compensated accumulation of the energy
ledger.  Its contract: whole-run energies within 1e-4 relative of the
float64 host oracle (``sweep.renewal_compose``) on all six Table-4
scenarios for exponential, Weibull, and correlated failure histories at
fixed keys — with bit-identical histories (the sampler draws float32 bits
regardless of x64) and *exact* integer stats against the x64 scan.  All
tests run the interpret path (traceable, lowers to XLA under jit — the
compiled CPU path).
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import failures as F
from repro.core import optimize, sweep
from repro.core import topology as node_topology
from repro.core.scenarios import paper_scenarios

GAPS = np.array([5000.0, 9000.0, 4000.0, 2500.0])
MAKESPAN = 60000.0

SCENARIOS = sorted(paper_scenarios())

STAT_ENERGIES = ("energy_ref", "energy_int", "balanced_energy", "end_time")
STAT_COUNTS = ("n_failures", "truncated", "n_points", "n_sleep",
               "n_min_freq", "n_comp_changed", "n_infeasible",
               "failed_counts")


def _pallas_kernel_direct(cfgs, gaps, makespan, felled=None, **kw):
    """Run explicit histories straight through the kernel (no sampler):
    the scenario stack packed exactly as the engine packs it."""
    from repro.kernels import renewal_scan as rs

    _, stacked = sweep._renewal_device_inputs(cfgs, jnp.float32)
    params, nodes, ladder = sweep._pack_pallas_inputs(stacked, makespan)
    gaps_t = jnp.asarray(np.atleast_2d(gaps), jnp.float32).T       # (K, R)
    felled_t = (None if felled is None else
                jnp.transpose(jnp.asarray(felled, jnp.float32), (1, 2, 0)))
    return rs.renewal_scan_pallas(params, nodes, ladder, gaps_t, felled_t,
                                  **kw)


def _saving_close(saving, host_saving, host_ref, tol=1e-4):
    denom = np.maximum(np.abs(host_saving), 1e-4 * np.asarray(host_ref))
    np.testing.assert_array_less(
        np.abs(np.asarray(saving, np.float64) - host_saving) / denom, tol)


# ---------------------------------------------------------------------------
# kernel vs float64 host oracle, explicit histories
# ---------------------------------------------------------------------------

def test_kernel_matches_host_oracle_explicit_history():
    """All six Table-4 scenarios, one explicit multi-failure history,
    straight through the packed kernel: whole-run energies <= 1e-4
    relative of the float64 oracle, valid masks and failure counts exact."""
    cfgs = [paper_scenarios()[n] for n in SCENARIOS]
    out = _pallas_kernel_direct(cfgs, GAPS, MAKESPAN)
    for s, cfg in enumerate(cfgs):
        host = sweep.renewal_compose(cfg, GAPS, MAKESPAN)
        np.testing.assert_array_equal(
            np.asarray(out["valid"])[s, :, 0] > 0, host.valid[0],
            err_msg=cfg.name)
        assert int(out["n_failures"][s, 0]) == int(host.n_failures[0])
        assert bool(out["truncated"][s, 0]) == bool(host.truncated[0])
        for field in ("energy_ref", "energy_int", "balanced_energy",
                      "end_time"):
            np.testing.assert_allclose(
                np.asarray(out[field], np.float64)[s, 0],
                getattr(host, field)[0], rtol=1e-4,
                err_msg=f"{cfg.name} {field}")
        _saving_close(out["saving"][s, 0], host.saving[0], host.energy_ref[0])


# ---------------------------------------------------------------------------
# acceptance bar: engine="pallas" vs the oracle for exp/Weibull/correlated
# ---------------------------------------------------------------------------

def _oracle_histories(key, n_runs, max_failures, process=None, mtbf_s=None,
                      topology=None):
    got = sweep.renewal_failure_gaps(
        key, n_runs, 4, max_failures, mtbf_s=mtbf_s, process=process,
        topology=topology)
    if topology is None:
        gaps, failed = got
        return gaps, failed, None
    gaps, failed, fmask = got
    return gaps, failed, np.asarray(
        node_topology.survivor_slot_mask(jnp.asarray(fmask),
                                         jnp.asarray(failed)))


@pytest.mark.parametrize("history", ["exponential", "weibull", "correlated"])
def test_pallas_engine_matches_host_oracle(history):
    """Acceptance bar: ``engine="pallas"`` whole-run energies within 1e-4
    relative of the float64 host oracle, per run, all six Table-4
    scenarios, for exponential / Weibull / correlated fixed-key histories
    (bit-identical histories across engines — same float32 draws)."""
    cfgs = [paper_scenarios()[n] for n in SCENARIOS]
    makespan, mtbf = 40000.0, 12000.0
    kw = dict(n_runs=8, makespan_s=makespan, max_failures=8)
    hw = {}
    if history == "exponential":
        key, kw["mtbf_s"] = jax.random.PRNGKey(11), mtbf
        hw["mtbf_s"] = mtbf
    elif history == "weibull":
        key = jax.random.PRNGKey(3)
        kw["process"] = hw["process"] = F.Weibull.from_mtbf(0.7, mtbf)
    else:
        key = jax.random.PRNGKey(5)
        kw["process"] = hw["process"] = F.Weibull.from_mtbf(0.7, mtbf)
        kw["topology"] = hw["topology"] = node_topology.rack_topology(
            4, 2, shock_mtbs_s=30000.0, p_kill=0.6, age_boost_s=3600.0)
    gaps, failed, felled = _oracle_histories(key, 8, 8, **hw)
    pal = sweep.renewal_monte_carlo_device(cfgs, key, stats=True,
                                           engine="pallas", **kw)
    for s, cfg in enumerate(cfgs):
        host = sweep.renewal_compose(cfg, gaps, makespan, failed_node=failed,
                                     felled=felled)
        assert host.n_failures.mean() >= 2, cfg.name
        np.testing.assert_array_equal(
            np.asarray(pal.n_failures)[s], host.n_failures, err_msg=cfg.name)
        np.testing.assert_array_equal(
            np.asarray(pal.truncated)[s], host.truncated, err_msg=cfg.name)
        for field in ("energy_ref", "energy_int", "balanced_energy",
                      "end_time"):
            np.testing.assert_allclose(
                np.asarray(getattr(pal, field), np.float64)[s],
                getattr(host, field), rtol=1e-4,
                err_msg=f"{cfg.name} {field} {history}")
        _saving_close(np.asarray(pal.saving)[s], host.saving, host.energy_ref)


@pytest.mark.parametrize("history", ["exponential", "weibull", "correlated"])
def test_pallas_engine_integer_stats_exact_vs_scan(history):
    """The kernel's decisions are the scan engine's decisions: every
    integer stat of ``RenewalDeviceStats`` — failure counts, valid points,
    action counts, per-node attribution — matches the x64 scan *exactly*
    for the same key."""
    cfgs = [paper_scenarios()[n] for n in SCENARIOS]
    key = jax.random.PRNGKey(11)
    kw = dict(n_runs=16, makespan_s=200000.0, max_failures=16)
    if history == "exponential":
        kw["mtbf_s"] = 12000.0
    else:
        kw["process"] = F.Weibull.from_mtbf(0.7, 12000.0)
    if history == "correlated":
        kw["topology"] = node_topology.rack_topology(
            4, 2, shock_mtbs_s=40000.0, p_kill=0.6, age_boost_s=3600.0)
    scan = sweep.renewal_monte_carlo_device(cfgs, key, stats=True, **kw)
    pal = sweep.renewal_monte_carlo_device(cfgs, key, stats=True,
                                           engine="pallas", **kw)
    for field in STAT_COUNTS:
        np.testing.assert_array_equal(
            np.asarray(getattr(pal, field)), np.asarray(getattr(scan, field)),
            err_msg=f"{field} {history}")
    for field in STAT_ENERGIES + ("saving",):
        a = np.asarray(getattr(scan, field), np.float64)
        b = np.asarray(getattr(pal, field), np.float64)
        denom = np.maximum(np.abs(a), 1e-4 * np.asarray(scan.energy_ref))
        np.testing.assert_array_less(np.abs(a - b) / denom, 1e-4,
                                     err_msg=f"{field} {history}")


# ---------------------------------------------------------------------------
# Kahan property: the compensated ledger beats naive float32 accumulation
# ---------------------------------------------------------------------------

def test_compensated_ledger_beats_naive_float32():
    """On long runs (>= 64 epochs) the Kahan-compensated float32 ledger is
    strictly closer to the float64 oracle than naive float32 summation —
    and the occurrence geometry (clocks are compensated in BOTH modes) is
    identical, so the comparison isolates the summation.  At 256 epochs
    the compensated totals sit within ~1 output ulp of the oracle while
    naive drifts several ulps; the difference-accumulated ``saving``
    separates by an order of magnitude.  On the balanced and intervened
    ledgers both modes carry the same float32 geometry rounding, which is
    itself about one output ulp, so their aggregate claim is made over
    128 runs: over a handful of runs it is a coin flip."""
    cfgs = [paper_scenarios()["scenario2_long_reexec"]]
    key = jax.random.PRNGKey(7)
    max_failures, makespan = 256, 3.2e6
    proc = F.as_process(None, 4000.0)
    _, stacked = sweep._renewal_device_inputs(cfgs, jnp.float32)

    def errors(n_runs, fields):
        run = lambda comp: sweep._renewal_pallas_mc_jit(
            stacked, key, jnp.float32(makespan), proc, n_runs=n_runs,
            max_failures=max_failures, compensated=comp)
        comp, naive = run(True), run(False)
        oracle = sweep.renewal_monte_carlo_device(
            cfgs, key, stats=True, n_runs=n_runs, makespan_s=makespan,
            mtbf_s=4000.0, max_failures=max_failures)
        assert float(np.mean(np.asarray(oracle.n_failures))) >= 64
        # same geometry: identical epochs, decisions, and counters
        for field in ("n_failures", "n_points", "n_sleep", "n_min_freq"):
            np.testing.assert_array_equal(np.asarray(comp[field]),
                                          np.asarray(naive[field]),
                                          err_msg=field)
        ref_mag = np.asarray(oracle.energy_ref, np.float64)[0]
        out = {}
        for field in fields:
            ref = np.asarray(getattr(oracle, field), np.float64)[0]
            out[field] = (
                ref, ref_mag,
                np.abs(np.asarray(comp[field], np.float64)[0] - ref),
                np.abs(np.asarray(naive[field], np.float64)[0] - ref))
        return out

    # energy_ref and saving: compensated wins on EVERY run, and in sum
    for field, (ref, ref_mag, e_c, e_n) in errors(
            16, ("energy_ref", "saving")).items():
        assert np.all(e_c <= e_n + 1e-9 * ref_mag), field
        assert e_c.sum() < e_n.sum(), field
    # the remaining ledgers: compensated at least as accurate in aggregate
    for field, (ref, _, e_c, e_n) in errors(
            128, ("energy_int", "balanced_energy")).items():
        assert e_c.sum() <= e_n.sum(), field
        np.testing.assert_array_less(e_c / np.abs(ref), 1e-4)


# ---------------------------------------------------------------------------
# engine plumbing: entry points, CRN, padding, validation
# ---------------------------------------------------------------------------

def test_renewal_monte_carlo_pallas_summary():
    """``engine="pallas"`` flows through the scalar summary entry point and
    lands within the float32 bar of the host engine's summary."""
    cfg = paper_scenarios()["scenario2_long_reexec"]
    kw = dict(n_runs=32, makespan_s=200000.0, mtbf_s=12000.0,
              max_failures=16)
    pal = sweep.renewal_monte_carlo(cfg, jax.random.PRNGKey(3),
                                    engine="pallas", **kw)
    host = sweep.renewal_monte_carlo(cfg, jax.random.PRNGKey(3),
                                     engine="host", **kw)
    assert pal.n_runs == host.n_runs
    np.testing.assert_allclose(pal.mean_failures, host.mean_failures)
    np.testing.assert_allclose(pal.mean_energy_int_j, host.mean_energy_int_j,
                               rtol=1e-4)
    np.testing.assert_allclose(pal.sleep_occupancy, host.sleep_occupancy)
    # deterministic under the same key
    again = sweep.renewal_monte_carlo(cfg, jax.random.PRNGKey(3),
                                      engine="pallas", **kw)
    assert again == pal


def test_scenarios_entry_accepts_pallas_engine():
    cfgs = paper_scenarios()
    kw = dict(n_runs=16, makespan_s=30000.0, mtbf_s=9000.0, max_failures=8)
    pal = sweep.renewal_monte_carlo_scenarios(
        list(cfgs.values()), jax.random.PRNGKey(5), engine="pallas", **kw)
    scan = sweep.renewal_monte_carlo_scenarios(
        list(cfgs.values()), jax.random.PRNGKey(5), **kw)
    assert sorted(pal) == SCENARIOS
    for name in SCENARIOS:
        assert pal[name].mean_failures == scan[name].mean_failures, name
        np.testing.assert_allclose(pal[name].mean_energy_int_j,
                                   scan[name].mean_energy_int_j, rtol=1e-4)


def test_policy_grid_pallas_crn_bit_identical_to_standalone():
    """The optimizer contract carries over: policy lane p of the pallas
    grid equals a standalone pallas call on that policy's config with that
    policy's makespan, *bit-identically* (common random numbers)."""
    from repro.core import scenarios as scen_mod

    cfg = paper_scenarios()["scenario2_long_reexec"]
    table = optimize.default_policy_table(cfg, 12000.0)
    key = jax.random.PRNGKey(2)
    kw = dict(work_s=150000.0, n_runs=16, max_failures=16, mtbf_s=12000.0)
    grid_p = optimize.evaluate_policy_grid(cfg, table, key, engine="pallas",
                                           **kw)
    grid_s = optimize.evaluate_policy_grid(cfg, table, key, **kw)
    assert grid_p.best == grid_s.best
    np.testing.assert_allclose(grid_p.energy_int, grid_s.energy_int,
                               rtol=1e-4)
    p_idx = 3
    cfg_p = scen_mod.apply_policy(cfg, **table.policy(p_idx))
    stand = sweep.renewal_monte_carlo_device(
        cfg_p, key, stats=True, engine="pallas", n_runs=16,
        makespan_s=float(grid_p.makespan_s[p_idx]), mtbf_s=12000.0,
        max_failures=16)
    np.testing.assert_array_equal(
        np.asarray(grid_p.energy_int)[p_idx],
        np.asarray(stand.energy_int, np.float64)[0])


def test_kernel_run_padding_is_invisible():
    """Runs padded up to the block size (inf gaps never occur) change
    nothing: an explicit block size that forces padding reproduces the
    unpadded call bit-for-bit."""
    cfgs = [paper_scenarios()[n] for n in SCENARIOS[:2]]
    gaps = np.abs(np.random.default_rng(9).normal(8000.0, 3000.0, (6, 5)))
    whole = _pallas_kernel_direct(cfgs, gaps, MAKESPAN)
    padded = _pallas_kernel_direct(cfgs, gaps, MAKESPAN, block_r=4)
    for field in whole:
        np.testing.assert_array_equal(np.asarray(whole[field]),
                                      np.asarray(padded[field]),
                                      err_msg=field)


def test_pallas_engine_validation():
    cfg = paper_scenarios()["scenario2_long_reexec"]
    kw = dict(n_runs=8, makespan_s=30000.0, mtbf_s=9000.0, max_failures=4)
    with pytest.raises(ValueError, match="stats-only"):
        sweep.renewal_monte_carlo_device(cfg, jax.random.PRNGKey(0),
                                        stats=False, engine="pallas", **kw)
    with pytest.raises(ValueError, match="engine"):
        sweep.renewal_monte_carlo_device(cfg, jax.random.PRNGKey(0),
                                        stats=True, engine="tpu", **kw)
    with pytest.raises(ValueError, match="engine"):
        sweep.renewal_monte_carlo(cfg, jax.random.PRNGKey(0),
                                  engine="cuda", **kw)
