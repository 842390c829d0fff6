"""Statistical validation of the pluggable failure-process subsystem.

Three layers, all with seeded keys (deterministic reruns):

  * **Goodness of fit** — KS statistics of n = 50k sampled gaps against each
    process's analytic CDF, at the asymptotic alpha = 1e-3 critical value.
  * **Memorylessness property** — the age-conditioned residual distribution
    equals the unconditional one for the exponential and *differs* for
    Weibull k != 1 (so the conditional-residual path is demonstrably
    exercised, not silently bypassed); the Weibull residuals are then
    matched against the *correct* conditional law.  The renewal-epoch
    sampler itself is validated end to end by probability integral
    transform: replaying the failure-clock ages
    (``scenarios.failure_clock_ages``) and pushing every sampled gap
    through its own conditional CDF must yield uniforms.
  * **Equivalence pins** — Weibull(k=1) and Gamma(k=1) reduce to the
    exponential at fixed keys; the exponential process reproduces the
    legacy sampler bit-for-bit.

The cross-engine (device-vs-host) checks for these processes live in
tests/test_renewal_device.py; derivations in docs/failures.md.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import failures as F
from repro.core import sweep
from repro.core.scenarios import failure_clock_ages, paper_scenarios
from repro.core.simulator import simulate_run

N_KS = 50_000
MTBF = 9000.0


def _trace(n=512, seed=3):
    return np.random.default_rng(seed).lognormal(8.5, 1.0, n)


def _processes():
    return [
        F.Exponential(MTBF),
        F.Weibull.from_mtbf(0.7, MTBF),
        F.Weibull.from_mtbf(1.5, MTBF),
        F.LogNormal.from_mtbf(MTBF, 1.0),
        F.Gamma.from_mtbf(0.6, MTBF),
        F.Gamma.from_mtbf(2.0, MTBF),
        F.EmpiricalTrace(_trace()),
    ]


# ---------------------------------------------------------------------------
# goodness of fit: samples vs analytic CDF at n = 50k
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("process", _processes(), ids=lambda p: p.label())
def test_ks_goodness_of_fit_50k(process):
    """Every process's unconditional draws pass a two-sided KS test against
    its analytic CDF at n = 50k, alpha = 1e-3 (KS is distribution-free, so
    the critical value is shared; for the discrete trace law it is
    conservative by DKW)."""
    samples = process.sample(jax.random.PRNGKey(0), (N_KS,))
    d = F.ks_statistic(samples, process.cdf,
                       discrete=isinstance(process, F.EmpiricalTrace))
    assert d < F.ks_critical(N_KS, 1e-3), (process.label(), d)
    # and the mean matches the requested MTBF within Monte-Carlo noise
    mean = float(np.mean(np.asarray(samples, np.float64)))
    target = float(np.mean(process.mean_s()))
    assert abs(mean - target) / target < 0.05


def test_ks_statistic_detects_wrong_law():
    """The KS harness itself must reject a mismatched CDF — guards against
    a vacuous goodness-of-fit layer."""
    samples = F.Exponential(MTBF).sample(jax.random.PRNGKey(0), (N_KS,))
    wrong = F.Weibull.from_mtbf(0.7, MTBF)
    assert F.ks_statistic(samples, wrong.cdf) > 10 * F.ks_critical(N_KS, 1e-3)


# ---------------------------------------------------------------------------
# memorylessness: passes for exponential, fails for Weibull k != 1
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("age_frac", [0.5, 1.5])
def test_memorylessness_holds_only_for_exponential(age_frac):
    """Residuals at failure-clock age a: the exponential's match the
    unconditional law (memorylessness), Weibull k = 0.7's do NOT — the KS
    distance against the unconditional CDF exceeds 5x the critical value
    while the distance against the true conditional CDF
    S(a + t) / S(a) passes.  This pins that the engines' conditional-
    residual path is real, not a fresh redraw."""
    v = jax.random.uniform(jax.random.PRNGKey(7), (N_KS,), jnp.float32)
    age = jnp.full((N_KS,), jnp.float32(age_frac * MTBF))
    crit = F.ks_critical(N_KS, 1e-3)

    exp = F.Exponential(MTBF)
    d_exp = F.ks_statistic(exp.residual(v, age), exp.cdf)
    assert d_exp < crit

    wei = F.Weibull.from_mtbf(0.7, MTBF)
    res = np.asarray(wei.residual(v, age), np.float64)
    d_uncond = F.ks_statistic(res, wei.cdf)
    assert d_uncond > 5 * crit, "Weibull residuals looked memoryless"
    a = age_frac * MTBF
    cond_cdf = lambda t: 1.0 - wei.survival(a + t) / wei.survival(a)
    assert F.ks_statistic(res, cond_cdf) < crit
    # k < 1 (decreasing hazard): survivors are good — residuals
    # stochastically longer than fresh draws
    assert res.mean() > float(wei.mean_s()) * 1.1


def test_renewal_sampler_uses_conditional_residuals():
    """Engine-level memorylessness check: under Weibull k = 0.7 the
    surviving nodes' clocks age across epochs, so later epoch gaps are
    stochastically longer than epoch-0 gaps (all clocks fresh).  The
    exponential shows no such drift."""
    key = jax.random.PRNGKey(5)
    wei = F.Weibull.from_mtbf(0.7, MTBF)
    gaps_w, _ = F.renewal_gaps(wei, key, 4096, 4, 6)
    assert gaps_w[:, 3:].mean() > 1.15 * gaps_w[:, 0].mean()
    gaps_e, _ = F.renewal_gaps(F.Exponential(MTBF), key, 4096, 4, 6)
    drift = gaps_e[:, 3:].mean() / gaps_e[:, 0].mean()
    assert 0.93 < drift < 1.07


def test_renewal_sampler_probability_integral_transform():
    """Whole-sampler validation with per-node heterogeneous parameters:
    replay the failure-clock ages the sampler conditioned on
    (``scenarios.failure_clock_ages``) and push each epoch gap through its
    own conditional CDF  1 - prod_i S_i(a_i + g) / S_i(a_i)  (the law of
    the min of the nodes' conditional residuals).  The result must be
    U(0, 1) — KS-tested at alpha = 1e-3."""
    n_nodes, n_runs, k_epochs = 4, 2048, 8
    process = F.Weibull.from_mtbf(
        np.array([0.6, 1.0, 1.5, 0.8]),
        np.array([6000.0, 9000.0, 12000.0, 7000.0]))
    gaps, failed = F.renewal_gaps(
        process, jax.random.PRNGKey(9), n_runs, n_nodes, k_epochs)
    ages = failure_clock_ages(gaps, failed, n_nodes)        # (R, K, N)
    assert np.array_equal(ages[:, 0], np.zeros((n_runs, n_nodes)))
    s_ratio = process.survival(ages + gaps[..., None]) / process.survival(ages)
    pit = 1.0 - np.prod(s_ratio, axis=-1)                   # (R, K)
    d = F.ks_statistic(pit, lambda u: u)
    assert d < F.ks_critical(pit.size, 1e-3), d


def test_failure_clock_ages_validates_input():
    with pytest.raises(ValueError, match="shape"):
        failure_clock_ages(np.ones((2, 3)), np.zeros((2, 2), np.int64), 4)
    with pytest.raises(ValueError, match="outside"):
        failure_clock_ages(np.ones((1, 2)), np.array([[0, 7]]), 4)


# ---------------------------------------------------------------------------
# equivalence pins at fixed keys
# ---------------------------------------------------------------------------

def test_weibull_k1_and_gamma_k1_reduce_to_exponential():
    """At k = 1 both families ARE the exponential; fixed-key draws must
    agree with the closed-form exponential path — Weibull to float32
    round-off of the pow, Gamma to the bisected inverse's tolerance."""
    key = jax.random.PRNGKey(2)
    e = np.asarray(F.Exponential(MTBF).sample(key, (4096,)), np.float64)
    w = np.asarray(F.Weibull(1.0, MTBF).sample(key, (4096,)), np.float64)
    g = np.asarray(F.Gamma(1.0, MTBF).sample(key, (4096,)), np.float64)
    np.testing.assert_allclose(w, e, rtol=1e-5)
    np.testing.assert_allclose(g, e, rtol=1e-3, atol=0.05)
    # the conditional residual at any age also drops the age at k = 1
    v = jax.random.uniform(key, (4096,), jnp.float32)
    age = jnp.full((4096,), jnp.float32(2.0 * MTBF))
    w_res = np.asarray(F.Weibull(1.0, MTBF).residual(v, age), np.float64)
    e_res = np.asarray(F.Exponential(MTBF).residual(v, age), np.float64)
    np.testing.assert_allclose(w_res, e_res, rtol=2e-3, atol=0.5)


def test_exponential_process_matches_legacy_sampler_bitwise():
    """process=Exponential must reproduce the pre-process samplers
    bit-for-bit: the renewal gap sampler against
    ``renewal_failure_gaps(mtbf_s=...)`` and the unconditional draws
    against ``jax.random.exponential``."""
    key = jax.random.PRNGKey(4)
    g_legacy, f_legacy = sweep.renewal_failure_gaps(key, 16, 4, 8, MTBF)
    g_proc, f_proc = sweep.renewal_failure_gaps(
        key, 16, 4, 8, process=F.Exponential(MTBF))
    assert np.array_equal(g_legacy, g_proc)
    assert np.array_equal(f_legacy, f_proc)
    a = np.asarray(F.Exponential(MTBF).sample(key, (1024,)))
    b = np.asarray(
        jax.random.exponential(key, (1024,), jnp.float32) * jnp.float32(MTBF))
    assert np.array_equal(a, b)


# ---------------------------------------------------------------------------
# trace-driven process semantics
# ---------------------------------------------------------------------------

def test_trace_residual_is_age_conditioned():
    """Residuals at age a resample exactly from {g - a : g > a}; an age
    beyond the trace's support falls back to an unconditional resample."""
    trace = np.array([100.0, 200.0, 400.0, 800.0], np.float32)
    p = F.EmpiricalTrace(trace)
    v = jax.random.uniform(jax.random.PRNGKey(1), (4096,), jnp.float32)
    res = np.asarray(p.residual(v, jnp.full((4096,), jnp.float32(150.0))))
    assert set(np.unique(res)) == {50.0, 250.0, 650.0}
    # conditional frequencies are uniform over the surviving gaps
    assert abs(np.mean(res == 250.0) - 1.0 / 3.0) < 0.05
    beyond = np.asarray(p.residual(v, jnp.full((4096,), jnp.float32(900.0))))
    assert set(np.unique(beyond)) <= set(trace.tolist())
    # unconditional draws hit every atom
    uncond = np.asarray(p.sample(jax.random.PRNGKey(2), (4096,)))
    assert set(np.unique(uncond)) == set(trace.tolist())


def test_trace_validation():
    with pytest.raises(ValueError, match="positive"):
        F.EmpiricalTrace([0.0, 1.0])
    with pytest.raises(ValueError, match="L >= 2"):
        F.EmpiricalTrace([5.0])
    with pytest.raises(ValueError, match="L >= 2"):
        F.EmpiricalTrace(np.ones((2, 2, 2)))


def test_per_node_traces():
    """2-D (n_nodes, L) traces drive per-node laws: a node whose trace is
    uniformly short fails far more often than the others."""
    rng = np.random.default_rng(0)
    traces = np.stack([
        rng.uniform(500.0, 1500.0, 64),          # flaky node
        rng.uniform(5000.0, 15000.0, 64),
        rng.uniform(5000.0, 15000.0, 64),
    ])
    p = F.EmpiricalTrace(traces)
    gaps, failed = F.renewal_gaps(p, jax.random.PRNGKey(0), 512, 3, 4)
    counts = np.bincount(failed.ravel(), minlength=3)
    assert counts[0] > 4 * max(counts[1], counts[2])
    assert np.all(gaps > 0.0)


# ---------------------------------------------------------------------------
# heterogeneity, fitting, plumbing
# ---------------------------------------------------------------------------

def test_per_node_heterogeneous_mtbf_drives_argmin():
    """A node with a 10x shorter exponential MTBF collects the failures."""
    p = F.Exponential(np.array([900.0, 9000.0, 9000.0, 9000.0]))
    _, failed = F.renewal_gaps(p, jax.random.PRNGKey(0), 512, 4, 4)
    counts = np.bincount(failed.ravel(), minlength=4)
    assert counts[0] > 3 * counts[1:].max()


def test_fit_weibull_recovers_parameters():
    """MLE fit on 20k sampled gaps recovers (k, scale) within a few percent
    — the docs/failures.md workflow for calibrating from a failure log."""
    true = F.Weibull.from_mtbf(0.7, MTBF)
    gaps = np.asarray(true.sample(jax.random.PRNGKey(6), (20_000,)))
    k, scale = F.fit_weibull(gaps)
    assert abs(k - 0.7) / 0.7 < 0.05
    assert abs(scale - float(true.scale_s)) / float(true.scale_s) < 0.05
    with pytest.raises(ValueError, match="positive"):
        F.fit_weibull([1.0, -2.0])


def test_fit_weibull_censored_reduces_to_complete():
    """Empty / zero censoring is bit-identical to the complete-sample fit
    (documented reduction — the online fitter with no open clocks)."""
    gaps = np.asarray(F.Weibull.from_mtbf(1.4, MTBF).sample(
        jax.random.PRNGKey(8), (50,)))
    base = F.fit_weibull(gaps)
    assert F.fit_weibull(gaps, censored=None) == base
    assert F.fit_weibull(gaps, censored=[]) == base
    assert F.fit_weibull(gaps, censored=[0.0, -5.0]) == base


def test_fit_weibull_short_censored_sequence():
    """The online controller's regime: a handful of complete lifetimes plus
    right-censored open clock ages.  The censored MLE must stay in a sane
    band around the truth where the complete-only fit is biased low in
    scale (it treats survivors as failures at their current age)."""
    true = F.Weibull.from_mtbf(0.7, MTBF)
    key = jax.random.PRNGKey(12)
    draws = np.asarray(true.sample(key, (10,)))
    cutoff = float(np.median(draws))            # Type-I censor at the median
    complete = draws[draws <= cutoff]
    censored = np.full((draws > cutoff).sum(), cutoff)
    assert complete.size >= 3 and censored.size >= 3
    k_c, scale_c = F.fit_weibull(complete, censored=censored)
    assert 0.2 < k_c < 2.5
    # censoring adds survival mass: the fitted scale must exceed the
    # complete-only fit's, which can't see beyond the cutoff
    _, scale_naive = F.fit_weibull(complete)
    assert scale_c > scale_naive


def test_fit_weibull_convergence_with_sample_size():
    """Property: more observed gaps -> tighter estimate, at a fixed key
    (the controller's estimate improves as the run accumulates failures)."""
    true = F.Weibull.from_mtbf(0.7, MTBF)
    all_gaps = np.asarray(true.sample(jax.random.PRNGKey(22), (4000,)))
    err = {}
    for n in (12, 4000):
        k, scale = F.fit_weibull(all_gaps[:n])
        err[n] = abs(k - 0.7) / 0.7 + \
            abs(scale - float(true.scale_s)) / float(true.scale_s)
    assert err[4000] < err[12]
    assert err[4000] < 0.1


def test_fit_weibull_degenerate_fallbacks():
    """Regression: the burst detector feeds this short, sometimes
    pathological windows — every documented fallback must return finite
    numbers instead of NaN/divergence (docs/failures.md)."""
    # nothing to fit at all
    with pytest.raises(ValueError, match="at least one"):
        F.fit_weibull([])
    with pytest.raises(ValueError, match="at least one"):
        F.fit_weibull([], censored=[0.0, -1.0])
    # all-censored: exponential total-exposure bound with zero events
    assert F.fit_weibull([], censored=[100.0, 250.0]) == (1.0, 350.0)
    # a single complete gap: the exponential MLE
    assert F.fit_weibull([500.0]) == (1.0, 500.0)
    # ... with censored mass the fixed point runs but must stay clamped
    # and finite (censored ages below the gap can't constrain the shape)
    k, scale = F.fit_weibull([500.0], censored=[300.0])
    assert np.isfinite(k) and np.isfinite(scale)
    assert 1e-2 <= k <= 1e2 and scale > 0
    # zero spread: the fixed point diverges upward -> shape saturates at
    # the clamp and the scale lands at ~the common value
    k, scale = F.fit_weibull([600.0] * 8)
    assert np.isfinite(k) and np.isfinite(scale)
    assert k == 100.0
    assert scale == pytest.approx(600.0, rel=0.05)
    # heavy censoring + extreme spread must not overflow t**k
    k, scale = F.fit_weibull([1e-3, 1.0, 1e6], censored=[1e7] * 50)
    assert np.isfinite(k) and np.isfinite(scale) and k > 0 and scale > 0
    # near-zero spread stays finite on the way to the clamp
    k, scale = F.fit_weibull([600.0, 600.0 + 1e-9, 600.0 - 1e-9])
    assert np.isfinite(k) and np.isfinite(scale)


def test_as_process_and_validation():
    assert isinstance(F.as_process(None, MTBF), F.Exponential)
    w = F.Weibull.from_mtbf(0.7, MTBF)
    assert F.as_process(w) is w
    with pytest.raises(ValueError, match="mtbf_s"):
        F.as_process(None)
    with pytest.raises(TypeError, match="FailureProcess"):
        F.as_process(object())
    with pytest.raises(ValueError, match="positive"):
        F.Exponential(-1.0)
    with pytest.raises(ValueError, match="positive"):
        F.Weibull(0.0, 100.0)


def test_monte_carlo_accepts_process():
    """The single-failure Monte-Carlo path: process=None is bit-compatible
    with the legacy exponential sampler; a Weibull process at equal MTBF
    changes the arrival phases (different expectations) and reports the
    process mean as its mtbf_s; per-node parameter arrays are rejected
    (single arrival stream)."""
    cfg = paper_scenarios()["scenario4_short_active_waits"]
    key = jax.random.PRNGKey(0)
    legacy = sweep.monte_carlo(cfg, key, n_samples=256)
    pinned = sweep.monte_carlo(cfg, key, n_samples=256,
                               process=F.Exponential(30 * 24 * 3600.0))
    # same wrap, same draws modulo the f32/f64 multiply order — compare
    # loosely on the expectation, exactly on the occupancy fields
    assert pinned.sleep_occupancy == legacy.sleep_occupancy
    np.testing.assert_allclose(pinned.mean_saving_j, legacy.mean_saving_j,
                               rtol=1e-3)
    wei = sweep.monte_carlo(cfg, key, n_samples=256,
                            process=F.Weibull.from_mtbf(0.7, 30 * 24 * 3600.0))
    assert wei.mean_saving_j != legacy.mean_saving_j
    np.testing.assert_allclose(wei.mtbf_s, 30 * 24 * 3600.0, rtol=1e-6)
    with pytest.raises(ValueError, match="per-node"):
        sweep.monte_carlo(cfg, key, n_samples=64,
                          process=F.Exponential(np.array([1e6, 2e6])))


def test_simulate_run_accepts_process():
    """The event engine runs from a FailureProcess and reproduces the
    explicit-gap run for the history the shared sampler yields."""
    cfg = paper_scenarios()["scenario2_long_reexec"]
    w = F.Weibull.from_mtbf(0.7, MTBF)
    key = jax.random.PRNGKey(0)
    run = simulate_run(cfg, None, 30_000.0, process=w, key=key, max_failures=8)
    gaps, _ = F.renewal_gaps(w, key, 1, len(cfg.survivors) + 1, 8)
    explicit = simulate_run(cfg, gaps[0], 30_000.0)
    assert run.n_failures == explicit.n_failures
    assert run.energy_ref == explicit.energy_ref
    assert run.energy_int == explicit.energy_int
    with pytest.raises(ValueError, match="requires"):
        simulate_run(cfg, None, 30_000.0, process=w)
    with pytest.raises(ValueError, match="not both"):
        simulate_run(cfg, [100.0], 30_000.0, process=w)


# ---------------------------------------------------------------------------
# nightly statistical stress tier (fixed seeds; ci.yml runs -m slow)
# ---------------------------------------------------------------------------

@pytest.mark.slow
@pytest.mark.parametrize("process", _processes(), ids=lambda p: p.label())
def test_ks_goodness_of_fit_dense_age_grid_slow(process):
    """Nightly: conditional residuals pass KS against the analytic
    conditional law S(a + t) / S(a) on a dense grid of failure-clock ages
    for every process (the tier-1 test covers age 0 and two Weibull ages)."""
    n = 100_000
    crit = F.ks_critical(n, 1e-3)
    for i, age_frac in enumerate((0.0, 0.25, 1.0, 3.0)):
        a = age_frac * float(np.mean(process.mean_s()))
        if isinstance(process, F.EmpiricalTrace) and a >= float(
                np.max(np.asarray(process.gaps))):
            continue        # beyond-support fallback is unconditional
        v = jax.random.uniform(jax.random.PRNGKey(100 + i), (n,), jnp.float32)
        res = np.asarray(
            process.residual(v, jnp.full((n,), jnp.float32(a))), np.float64)
        s_a = process.survival(a)
        # trace atoms: t[j] - age rounds in f32, and evaluating the step
        # CDF exactly at a rounded atom can drop that atom's whole mass —
        # nudge right by far less than the atom spacing
        discrete = isinstance(process, F.EmpiricalTrace)
        shift = 0.5 if discrete else 0.0
        cond = lambda t: 1.0 - process.survival(a + t + shift) / s_a
        d = F.ks_statistic(res, cond, discrete=discrete)
        assert d < crit, (process.label(), age_frac, d, crit)
