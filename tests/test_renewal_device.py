"""Device renewal engine tests: the fused scan vs the float64 host oracle.

The device engine (``sweep.renewal_compose_device`` /
``renewal_monte_carlo_device``) re-implements the whole-run renewal
composition as one jitted scan over epochs x runs x scenarios.  Its
contract is the host oracle: identical decisions, occurrence/truncation
semantics, and whole-run energies within 1e-4 relative (the acceptance
bar; the engine is traced under x64 so observed agreement is ~1e-12).
The fold form of Algorithm 1 it dispatches is pinned *bit-exactly* to the
vectorized ``evaluate_strategies``.
"""
import dataclasses

import jax
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import energy_model as em
from repro.core import failures as F
from repro.core import planning, strategies, sweep
from repro.core.scenarios import paper_scenarios
from repro.core.simulator import NodeStart, simulate_run

GAPS = np.array([5000.0, 9000.0, 4000.0, 2500.0])
MAKESPAN = 60000.0

SCENARIOS = sorted(paper_scenarios())


def _nonexp_processes():
    """The non-exponential processes pinned across engines (the exponential
    is covered by every pre-existing test in this file)."""
    return [
        F.Weibull.from_mtbf(0.7, 12000.0),
        F.EmpiricalTrace(
            np.random.default_rng(3).weibull(0.8, 400) * 15000.0),
    ]


def _device_slice(res, s):
    """Scenario ``s`` of a stacked device result, as numpy (``gaps`` is
    shared across scenarios and stays whole)."""
    fields = {
        f: jax.tree.map(lambda a: np.asarray(a)[s], getattr(res, f))
        for f in res.__dataclass_fields__ if f != "gaps"
    }
    return sweep.RenewalDeviceResult(gaps=np.asarray(res.gaps), **fields)


# ---------------------------------------------------------------------------
# cross-validation: device scan == host float64 oracle, pointwise
# ---------------------------------------------------------------------------

def test_device_compose_matches_host_oracle_pointwise():
    """All six Table-4 scenarios in one dispatch: per-epoch energies,
    decisions, and whole-run totals match the host oracle (bar 1e-4; the
    x64-traced scan agrees to ~1e-12)."""
    cfgs = [paper_scenarios()[n] for n in SCENARIOS]
    dev = sweep.renewal_compose_device(cfgs, GAPS, MAKESPAN)
    for s, cfg in enumerate(cfgs):
        host = sweep.renewal_compose(cfg, GAPS, MAKESPAN)
        d = _device_slice(dev, s)
        np.testing.assert_array_equal(d.valid[0], host.valid[0], err_msg=cfg.name)
        assert int(d.n_failures[0]) == int(host.n_failures[0])
        assert bool(d.truncated[0]) == bool(host.truncated[0])
        k = host.valid[0]
        np.testing.assert_array_equal(
            np.asarray(d.decision.level)[0][k],
            np.asarray(host.decision.level)[0][k], err_msg=cfg.name)
        np.testing.assert_array_equal(
            np.asarray(d.decision.wait_action)[0][k],
            np.asarray(host.decision.wait_action)[0][k], err_msg=cfg.name)
        for field in ("epoch_ref", "epoch_int", "epoch_failed"):
            np.testing.assert_allclose(
                getattr(d, field)[0], getattr(host, field)[0],
                rtol=1e-4, atol=1e-6, err_msg=f"{cfg.name} {field}")
        for field in ("balanced_energy", "energy_ref", "energy_int",
                      "end_time", "t_renewal", "t_fail"):
            np.testing.assert_allclose(
                getattr(d, field)[0], getattr(host, field)[0],
                rtol=1e-4, err_msg=f"{cfg.name} {field}")
        denom = max(abs(float(host.saving[0])), 1e-4 * float(host.energy_ref[0]))
        assert abs(float(d.saving[0]) - float(host.saving[0])) / denom < 1e-4


def test_device_first_epoch_equals_single_failure_sweep():
    """Epoch 0 of a device renewal run reproduces the single-failure sweep
    at that offset — the device engine strictly generalizes PR 1's grid."""
    cfg = paper_scenarios()["scenario2_long_reexec"]
    delta = 4321.0
    res = sweep.renewal_compose_device(cfg, np.array([delta, 1e9]), 1e7)
    single = sweep.sweep_failure_times(cfg, np.array([delta]))
    np.testing.assert_array_equal(
        np.asarray(res.decision.level)[0, 0, 0],
        np.asarray(single.decision.level)[0])
    np.testing.assert_allclose(
        np.asarray(res.decision.saving)[0, 0, 0],
        np.asarray(single.decision.saving)[0], rtol=1e-6)


# ---------------------------------------------------------------------------
# the run-wide sawtooth after epoch 0 == the host oracle's per-node one
# ---------------------------------------------------------------------------

def _varied(name, n):
    """Paper scenario ``name`` with ``n`` survivors of varied phases, ages
    and periods."""
    cfg = paper_scenarios()[name]
    return dataclasses.replace(cfg, survivors=tuple(
        NodeStart(exec_to_rendezvous=(97.0 * i) % period + 1.0,
                  rendezvous_period=period,
                  ckpt_age=(53.0 * i + 60.0) % cfg.ckpt_interval)
        for i, period in enumerate([3600.0, 1800.0, 2700.0] * n) if i < n))


def _histories():
    """Runs with 0, 1 and many failures (one truncated), and gaps that
    land inside a checkpoint after a resync (epochs 1 and 2: at interval
    3600 and at interval 1800, dur 120)."""
    rng = np.random.default_rng(19)
    return np.stack([
        [1e9, 500.0, 500.0, 500.0, 500.0, 500.0],            # no failure
        [5000.0, 1e9, 500.0, 500.0, 500.0, 500.0],           # one
        [5000.0, 3660.0, 3780.0, 4000.0, 2500.0, 1e9],       # mid-checkpoint
        [2000.0, 3000.0, 2000.0, 3000.0, 2000.0, 3000.0],    # truncated
        rng.exponential(6000.0, 6),
    ])


RUN_WIDE_CASES = {
    # N+1 = 4, the Table-4 scenarios' own per-node age0 and reexec0
    "table4": ([paper_scenarios()[n] for n in SCENARIOS], False),
    "table4-felled": ([paper_scenarios()[n] for n in SCENARIOS], True),
    "5_survivors": ([_varied("scenario1_short_reexec", 5),
                     _varied("scenario5_short_idle_waits", 5)], False),
    "6_survivors-felled": ([_varied("scenario1_short_reexec", 6),
                            _varied("scenario2_long_reexec", 6)], True),
    "7_survivors-felled": ([_varied("scenario4_short_active_waits", 7)], True),
}


@pytest.mark.parametrize("case", sorted(RUN_WIDE_CASES))
def test_the_run_wide_sawtooth_matches_the_host_oracle(case):
    """After epoch 0 the scan advances one age a run, not one a node
    (every occurring epoch ends in the resync checkpoint): its per-epoch
    outputs, its stats in both fold forms (over the stacked epochs, and in
    tiles of 2 survivors inside the scan) and the balanced tail that reads
    the carry agree with ``renewal_compose``'s per-node recursion.  Counts
    equal, geometry to 1e-12, energies to this file's 1e-4."""
    cfgs, with_felled = RUN_WIDE_CASES[case]
    gaps = _histories()
    n = len(cfgs[0].survivors)
    felled = (np.random.default_rng(5).uniform(size=gaps.shape + (n,)) < 0.3
              if with_felled else None)
    dev = sweep.renewal_compose_device(cfgs, gaps, MAKESPAN, felled=felled)
    with jax.enable_x64():
        _, stacked = sweep._renewal_device_inputs(cfgs)
        stats = {tile: jax.device_get(sweep._renewal_lanes_jit(
            stacked, jax.numpy.asarray(gaps), MAKESPAN, stats=True,
            felled=felled, survivor_tile=tile)) for tile in (None, 2)}
    geometry = ("t_fail", "t_renewal", "end_time")
    energies = ("epoch_ref", "epoch_int", "epoch_failed", "balanced_energy",
                "energy_ref", "energy_int")
    for s, cfg in enumerate(cfgs):
        host = sweep.renewal_compose(cfg, gaps, MAKESPAN, felled=felled)
        d = _device_slice(dev, s)
        np.testing.assert_array_equal(host.n_failures[:4], [0, 1, 5, 6])
        # a gap after a resync snapped to a checkpoint's end
        snapped = [planning.advance_checkpoint_sawtooth(
            0.0, gaps[r, k], cfg.ckpt_interval, cfg.ckpt_duration)[3]
            > gaps[r, k] for r, k in zip(*np.nonzero(host.valid))
            if k > 0]
        assert any(snapped), cfg.name
        np.testing.assert_array_equal(d.valid, host.valid, err_msg=cfg.name)
        np.testing.assert_array_equal(d.n_failures, host.n_failures)
        np.testing.assert_array_equal(d.truncated, host.truncated)
        v = host.valid
        for field in ("exec_rem", "t_failed"):
            np.testing.assert_allclose(
                getattr(d, field)[v], getattr(host, field)[v], rtol=1e-12,
                err_msg=f"{case} {cfg.name} {field}")
        for field in geometry:
            np.testing.assert_allclose(
                getattr(d, field), getattr(host, field), rtol=1e-12,
                err_msg=f"{case} {cfg.name} {field}")
        for field in energies:
            np.testing.assert_allclose(
                getattr(d, field), getattr(host, field), rtol=1e-4,
                atol=1e-6, err_msg=f"{case} {cfg.name} {field}")
        denom = np.maximum(np.abs(host.saving), 1e-4 * host.energy_ref)
        np.testing.assert_array_less(np.abs(d.saving - host.saving) / denom,
                                     1e-4)
        points = v[..., None] & ~np.broadcast_to(
            False if felled is None else felled, gaps.shape + (n,))
        for field in ("level", "wait_action"):
            np.testing.assert_array_equal(
                np.asarray(getattr(d.decision, field))[points],
                np.asarray(getattr(host.decision, field))[points],
                err_msg=f"{case} {cfg.name} {field}")
        dec = host.decision
        counts = dict(
            n_points=points,
            n_sleep=np.asarray(dec.wait_action) == em.WaitAction.SLEEP,
            n_min_freq=np.asarray(dec.wait_action) == em.WaitAction.MIN_FREQ,
            n_comp_changed=np.asarray(dec.comp_changed),
            n_infeasible=~np.asarray(dec.feasible_any))
        for tile, got in stats.items():
            for field, hit in counts.items():
                np.testing.assert_array_equal(
                    got[field][s], (hit & points).sum(axis=(1, 2)),
                    err_msg=f"{case} {cfg.name} tile={tile} {field}")
            np.testing.assert_array_equal(got["n_failures"][s],
                                          host.n_failures)
            np.testing.assert_array_equal(got["truncated"][s], host.truncated)
            for field in ("balanced_energy", "energy_ref", "energy_int"):
                np.testing.assert_allclose(
                    got[field][s], getattr(host, field), rtol=1e-4,
                    err_msg=f"{case} {cfg.name} tile={tile} {field}")


@pytest.mark.parametrize("count", [1, 2, 3, 4, 5, 6, 7, 8, 9, 1023, 1024,
                                   1025])
def test_ordered_sum_of_copies_is_the_tree_bit_for_bit(count):
    """The run-wide balanced energy sums N+1 equal terms: the tree over
    them without the axis gives ``_ordered_sum``'s bits."""
    x = np.random.default_rng(count).uniform(0.0, 1e7, 64)
    with jax.enable_x64():
        tree = sweep._ordered_sum(np.broadcast_to(x[:, None], (64, count)),
                                  axis=-1)
        copies = sweep._ordered_sum_of_copies(jax.numpy.asarray(x), count)
    np.testing.assert_array_equal(np.asarray(copies).view(np.int64),
                                  np.asarray(tree).view(np.int64))


# ---------------------------------------------------------------------------
# the fold form of Algorithm 1 is bit-identical to the vectorized form
# ---------------------------------------------------------------------------

def test_fold_matches_vectorized_evaluate_strategies():
    """Every Decision field of evaluate_strategies_fold matches the
    vectorized engine — discrete fields exactly, energies to XLA
    FMA-contraction round-off (~1 ulp) — including infeasible fallbacks,
    idle-wait configs, and sleep-gate boundaries."""
    cfg = paper_scenarios()["scenario1_short_reexec"]
    inp = sweep.sweep_inputs(cfg)
    rng = np.random.default_rng(7)
    shape = (64, 3)
    t_comp = rng.uniform(5.0, 4000.0, shape).astype(np.float32)
    # include infeasible points (t_failed < even the fa comp phase)
    t_failed = np.where(
        rng.uniform(size=shape) < 0.15,
        rng.uniform(1.0, 50.0, shape),
        t_comp + rng.uniform(0.0, 4000.0, shape),
    ).astype(np.float32)
    n_ckpt = rng.integers(0, 4, shape + (4,)).astype(np.float32)
    wait_mode = rng.integers(0, 2, shape).astype(np.int32)

    ref = strategies.evaluate_strategies(
        t_comp, t_failed, n_ckpt, inp.dur, inp.ladder, inp.sleep,
        wait_mode, inp.p_idle_wait, mu1=inp.mu1, mu2=inp.mu2,
        per_level_n_ckpt=True)
    fold = strategies.evaluate_strategies_fold(
        t_comp, t_failed, [n_ckpt[..., f] for f in range(4)], inp.dur,
        inp.ladder, inp.sleep, wait_mode, inp.p_idle_wait,
        mu1=inp.mu1, mu2=inp.mu2)
    assert not bool(np.all(np.asarray(ref.feasible_any)))  # both branches hit
    for field in ("level", "comp_changed", "wait_action", "feasible_any"):
        np.testing.assert_array_equal(
            np.asarray(getattr(ref, field)), np.asarray(getattr(fold, field)),
            err_msg=field)
    for field in ("freq_ghz", "comp_time", "wait_time", "energy_intervened",
                  "energy_reference", "saving", "saving_pct"):
        np.testing.assert_allclose(
            np.asarray(getattr(ref, field)), np.asarray(getattr(fold, field)),
            rtol=1e-5, atol=1.0, err_msg=field)


# ---------------------------------------------------------------------------
# property: device == host on whole-run energies under random histories
# ---------------------------------------------------------------------------

@settings(max_examples=8, deadline=None)
@given(st.integers(min_value=0, max_value=2**31 - 1))
def test_device_matches_host_energies_random_keys(seed):
    """Acceptance bar: whole-run energy_ref / energy_int / saving within
    1e-4 relative of the host float64 oracle, per run, on all six Table-4
    scenarios, for random PRNG keys (>= 2 expected failures per run)."""
    cfgs = [paper_scenarios()[n] for n in SCENARIOS]
    key = jax.random.PRNGKey(seed)
    makespan, mtbf = 40000.0, 12000.0   # ~13 expected failures over 4 nodes
    gaps, failed = sweep.renewal_failure_gaps(key, 8, 4, 8, mtbf)
    dev = sweep.renewal_compose_device(cfgs, gaps, makespan, failed_node=failed)
    np.testing.assert_array_equal(np.asarray(dev.gaps), gaps)
    for s, cfg in enumerate(cfgs):
        host = sweep.renewal_compose(cfg, gaps, makespan, failed_node=failed)
        assert host.n_failures.mean() >= 2, cfg.name
        d = _device_slice(dev, s)
        np.testing.assert_array_equal(d.n_failures, host.n_failures)
        np.testing.assert_array_equal(d.failed_node, host.failed_node)
        for field in ("energy_ref", "energy_int"):
            np.testing.assert_allclose(
                getattr(d, field), getattr(host, field),
                rtol=1e-4, err_msg=f"{cfg.name} {field} seed={seed}")
        denom = np.maximum(np.abs(host.saving), 1e-4 * host.energy_ref)
        np.testing.assert_array_less(
            np.abs(d.saving - host.saving) / denom, 1e-4)


# ---------------------------------------------------------------------------
# failure processes: device == host for Weibull / trace-driven histories
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("process", _nonexp_processes(),
                         ids=lambda p: p.label())
def test_device_matches_host_nonexponential_processes(process):
    """Acceptance bar for the failure-process axis: Weibull and
    trace-driven renewal Monte-Carlo cross-validates device-vs-host at
    <= 1e-4 relative on whole-run energies for all six Table-4 scenarios,
    with the fixed-key failure histories bit-identical across engines
    (the device engine samples the conditional-residual scan *inside* its
    fused jitted program; the host oracle samples standalone)."""
    cfgs = [paper_scenarios()[n] for n in SCENARIOS]
    key = jax.random.PRNGKey(11)
    makespan = 40000.0
    gaps, failed = sweep.renewal_failure_gaps(key, 8, 4, 8, process=process)
    dev = sweep.renewal_monte_carlo_device(
        cfgs, key, n_runs=8, makespan_s=makespan, max_failures=8,
        process=process)
    np.testing.assert_array_equal(np.asarray(dev.gaps), gaps)      # bitwise
    np.testing.assert_array_equal(
        np.asarray(dev.failed_node)[0],
        np.where(np.asarray(dev.valid)[0], failed, -1))
    for s, cfg in enumerate(cfgs):
        host = sweep.renewal_compose(cfg, gaps, makespan, failed_node=failed)
        assert host.n_failures.mean() >= 2, cfg.name
        np.testing.assert_array_equal(
            np.asarray(dev.n_failures)[s], host.n_failures, err_msg=cfg.name)
        for field in ("energy_ref", "energy_int"):
            np.testing.assert_allclose(
                np.asarray(getattr(dev, field))[s], getattr(host, field),
                rtol=1e-4, err_msg=f"{cfg.name} {field} {process.label()}")
        denom = np.maximum(np.abs(host.saving), 1e-4 * host.energy_ref)
        np.testing.assert_array_less(
            np.abs(np.asarray(dev.saving)[s] - host.saving) / denom, 1e-4)


@pytest.mark.parametrize("process", _nonexp_processes(),
                         ids=lambda p: p.label())
def test_renewal_monte_carlo_engines_pinned_nonexponential(process):
    """Fixed-key determinism pin extended to the new processes: the device
    summary equals the host oracle's — integer fields and histograms
    exactly, floats to float64 round-off — and stays deterministic under
    the same key."""
    cfg = paper_scenarios()["scenario2_long_reexec"]
    kw = dict(n_runs=32, makespan_s=200000.0, max_failures=16)
    dev = sweep.renewal_monte_carlo(cfg, jax.random.PRNGKey(3),
                                    engine="scan", process=process, **kw)
    host = sweep.renewal_monte_carlo(cfg, jax.random.PRNGKey(3),
                                     engine="host", process=process, **kw)
    for field in dev.__dataclass_fields__:
        a, b = getattr(dev, field), getattr(host, field)
        if isinstance(a, float):
            np.testing.assert_allclose(a, b, rtol=1e-9, err_msg=field)
        else:
            assert a == b, (field, a, b)
    # the summary reports the process's mean gap as its MTBF
    np.testing.assert_allclose(
        dev.mtbf_s, float(np.mean(process.mean_s())), rtol=1e-6)
    again = sweep.renewal_monte_carlo(cfg, jax.random.PRNGKey(3),
                                      engine="scan", process=process, **kw)
    assert again == dev


def test_renewal_scenarios_process_matches_per_scenario_device():
    """The one-dispatch six-scenario path accepts a process and equals
    per-scenario device calls under the same key."""
    cfgs = paper_scenarios()
    w = F.Weibull.from_mtbf(0.7, 9000.0)
    kw = dict(n_runs=16, makespan_s=30000.0, max_failures=8)
    stacked = sweep.renewal_monte_carlo_scenarios(
        list(cfgs.values()), jax.random.PRNGKey(5), process=w, **kw)
    name = SCENARIOS[2]
    single = sweep.renewal_monte_carlo(
        cfgs[name], jax.random.PRNGKey(5), engine="scan", process=w, **kw)
    for field in single.__dataclass_fields__:
        a, b = getattr(stacked[name], field), getattr(single, field)
        if isinstance(a, float):
            np.testing.assert_allclose(a, b, rtol=1e-12, err_msg=field)
        else:
            assert a == b, (field, a, b)


# ---------------------------------------------------------------------------
# determinism: renewal_monte_carlo pinned across engines for a fixed key
# ---------------------------------------------------------------------------

def test_renewal_monte_carlo_engines_pinned():
    """Fixed key: the device engine's summary equals the host oracle's —
    integer fields and histograms exactly (bit-identical failure histories
    and decisions), float fields to float64 round-off."""
    cfg = paper_scenarios()["scenario2_long_reexec"]
    kw = dict(n_runs=64, makespan_s=10 * 24 * 3600.0,
              mtbf_s=3 * 24 * 3600.0, max_failures=32)
    dev = sweep.renewal_monte_carlo(cfg, jax.random.PRNGKey(3),
                                    engine="scan", **kw)
    host = sweep.renewal_monte_carlo(cfg, jax.random.PRNGKey(3),
                                     engine="host", **kw)
    for field in dev.__dataclass_fields__:
        a, b = getattr(dev, field), getattr(host, field)
        if isinstance(a, float):
            np.testing.assert_allclose(a, b, rtol=1e-9, err_msg=field)
        else:
            assert a == b, (field, a, b)
    # deterministic under the same key; sensitive to the key
    again = sweep.renewal_monte_carlo(cfg, jax.random.PRNGKey(3),
                                      engine="scan", **kw)
    assert again == dev
    other = sweep.renewal_monte_carlo(cfg, jax.random.PRNGKey(4),
                                      engine="scan", **kw)
    assert other.mean_saving_j != dev.mean_saving_j
    with pytest.raises(ValueError, match="engine"):
        sweep.renewal_monte_carlo(cfg, jax.random.PRNGKey(3),
                                  engine="gpu", **kw)


def test_renewal_monte_carlo_scenarios_one_dispatch_matches_per_scenario():
    """The stacked six-scenario summary dict equals per-scenario device
    calls with the same key (same histories hit every scenario)."""
    cfgs = paper_scenarios()
    kw = dict(n_runs=32, makespan_s=30000.0, mtbf_s=9000.0, max_failures=16)
    stacked = sweep.renewal_monte_carlo_scenarios(
        list(cfgs.values()), jax.random.PRNGKey(5), **kw)
    assert sorted(stacked) == SCENARIOS
    for name in (SCENARIOS[0], SCENARIOS[3]):
        single = sweep.renewal_monte_carlo(
            cfgs[name], jax.random.PRNGKey(5), engine="scan", **kw)
        for field in single.__dataclass_fields__:
            a, b = getattr(stacked[name], field), getattr(single, field)
            if isinstance(a, float):
                # energy sums may tile differently across batch sizes
                np.testing.assert_allclose(a, b, rtol=1e-12,
                                           err_msg=f"{name} {field}")
            else:
                assert a == b, (name, field, a, b)


# ---------------------------------------------------------------------------
# occurrence / truncation semantics at the makespan boundary (bugfix)
# ---------------------------------------------------------------------------

def test_gap_landing_exactly_on_makespan_occurs_in_both_paths():
    """A failure gap consuming exactly the remaining makespan still occurs
    (<= comparison), in the host oracle, the device scan, and the event
    simulator; the run is complete (not truncated) afterwards.  A gap one
    ulp past the makespan is dropped and the run is not truncated either
    (its next failure genuinely lands past the end)."""
    cfg = paper_scenarios()["scenario4_short_active_waits"]
    makespan = 20000.0

    # 20000 s from a fresh anchor avoids mid-checkpoint snapping (timers at
    # 3540 + k*3720 wall seconds), so bal_elapsed hits the makespan exactly
    on = np.array([[makespan, 1.0]])
    host_on = sweep.renewal_compose(cfg, on, makespan)
    dev_on = sweep.renewal_compose_device(cfg, on, makespan)
    run_on = simulate_run(cfg, on[0], makespan)
    assert int(host_on.n_failures[0]) == 1
    assert int(np.asarray(dev_on.n_failures)[0, 0]) == 1
    assert run_on.n_failures == 1
    # the epoch consumed the whole makespan: complete, not truncated
    assert not bool(host_on.truncated[0])
    assert not bool(np.asarray(dev_on.truncated)[0, 0])
    np.testing.assert_allclose(
        float(np.asarray(dev_on.energy_ref)[0, 0]), run_on.energy_ref,
        rtol=1e-4)
    np.testing.assert_allclose(
        float(np.asarray(dev_on.energy_ref)[0, 0]), host_on.energy_ref[0],
        rtol=1e-9)

    past = np.array([[np.nextafter(makespan, np.inf), 1.0]])
    host_past = sweep.renewal_compose(cfg, past, makespan)
    dev_past = sweep.renewal_compose_device(cfg, past, makespan)
    assert int(host_past.n_failures[0]) == 0
    assert int(np.asarray(dev_past.n_failures)[0, 0]) == 0
    assert not bool(host_past.truncated[0])      # killed by an overlong gap,
    assert not bool(np.asarray(dev_past.truncated)[0, 0])  # never truncated
    assert simulate_run(cfg, past[0], makespan).n_failures == 0


def test_truncation_semantics_identical_across_paths():
    """Runs that exhaust max_failures with balanced time left are truncated
    in both paths; dead runs zero out identically (n_failures, valid)."""
    cfg = paper_scenarios()["scenario4_short_active_waits"]
    gaps = np.array([
        [2000.0, 3000.0],       # exhausts both gaps well before the makespan
        [2000.0, 1e9],          # killed at epoch 1
        [1e9, 100.0],           # killed at epoch 0: later short gap dropped
    ])
    host = sweep.renewal_compose(cfg, gaps, MAKESPAN)
    dev = sweep.renewal_compose_device(cfg, gaps, MAKESPAN)
    np.testing.assert_array_equal(host.n_failures, [2, 1, 0])
    np.testing.assert_array_equal(np.asarray(dev.n_failures)[0], [2, 1, 0])
    np.testing.assert_array_equal(host.truncated, [True, False, False])
    np.testing.assert_array_equal(np.asarray(dev.truncated)[0],
                                  [True, False, False])
    np.testing.assert_array_equal(np.asarray(dev.valid)[0], host.valid)
    np.testing.assert_allclose(np.asarray(dev.energy_ref)[0],
                               host.energy_ref, rtol=1e-9)


# ---------------------------------------------------------------------------
# input validation mirrors the host path
# ---------------------------------------------------------------------------

def test_device_inputs_validated_like_host():
    cfgs = paper_scenarios()
    slowed = cfgs["scenario4_short_active_waits"]
    slowed = dataclasses.replace(slowed, survivors=tuple(
        dataclasses.replace(sv, level=1) for sv in slowed.survivors))
    with pytest.raises(ValueError, match="balanced"):
        sweep.renewal_compose_device(slowed, GAPS, MAKESPAN)
    with pytest.raises(ValueError, match="no scenarios"):
        sweep.renewal_compose_device([], GAPS, MAKESPAN)
    # stacking requires shared survivor count
    two = dataclasses.replace(
        cfgs["scenario1_short_reexec"],
        survivors=cfgs["scenario1_short_reexec"].survivors[:2])
    with pytest.raises(ValueError, match="survivor count"):
        sweep.renewal_compose_device(
            [cfgs["scenario2_long_reexec"], two], GAPS, MAKESPAN)
