"""Correlated-failure subsystem tests: topology shocks, trace ingestion,
engine threading, burst-hardened control.

Four layers, mirroring docs/failures.md's correlated section:

  * sampler statistics — with shocks effectively off the correlated
    sampler reproduces the declared iid law (KS at n = 50k), with shocks
    on the event stream is measurably over-dispersed;
  * cross-engine contract — fixed-key correlated histories are
    bit-identical host vs device, and the extended multi-felled event
    simulator cross-validates the device scan's epoch energies at
    <= 1e-4 relative on all six Table-4 scenarios (driven with an
    aggressive topology so multi-felled AND all-felled epochs are
    actually exercised);
  * trace ingestion — LANL-style CSV round-trip, burst detection,
    correlation-preserving replay, and shock-rate recovery from a
    synthetic log with known generating rates;
  * live stack — the injector replays kill sets as zero-gap bursts and
    the degrade-enabled controller holds a conservative policy through a
    burst storm (never worse than the static conservative baseline on
    realized ledger energy) while a naive always-retune controller is
    measurably worse.
"""
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint.manager import CheckpointConfig
from repro.core import failures, simulator, sweep
from repro.core import topology as nt
from repro.core.scenarios import paper_scenarios
from repro.ft.controller import AdaptiveController, StochasticFailureInjector
from repro.ft.runtime import ClusterSpec, FTTrainer

KEY = jax.random.PRNGKey(3)
MTBF_S = 7 * 24 * 3600.0
MAKESPAN_S = 30 * 24 * 3600.0


# ---------------------------------------------------------------------------
# sampler statistics
# ---------------------------------------------------------------------------

def test_shock_off_marginals_match_declared_law():
    # with the shock clock pushed to an astronomic MTBS the correlated
    # sampler is the iid renewal model; for exponential marginals every
    # epoch gap is then Exp(mtbf / n) regardless of ages (memorylessness),
    # so one-sample KS at n = 50k against the analytic CDF applies
    n_nodes, n_runs, max_failures = 4, 2000, 25
    proc = failures.Exponential(mtbf_s=MTBF_S)
    topo = nt.rack_topology(n_nodes, 2, shock_mtbs_s=1e15, p_kill=1.0)
    gaps, fmask, primary = nt.correlated_renewal_gaps(
        topo, proc, KEY, n_runs=n_runs, n_nodes=n_nodes,
        max_failures=max_failures)
    assert int(np.sum(fmask.sum(-1) > 1)) == 0      # no shock ever fired
    g = np.asarray(gaps).ravel()
    assert g.size == 50_000
    scale = MTBF_S / n_nodes
    ks = failures.ks_statistic(g, lambda t: 1.0 - np.exp(-t / scale))
    assert ks < failures.ks_critical(g.size, alpha=1e-3)
    # primaries live on the node axis and match the mask
    assert np.all(fmask[np.arange(n_runs)[:, None],
                        np.arange(max_failures)[None, :], primary])


def test_dispersion_index_separates_shock_on_off():
    n_nodes = 8
    proc = failures.Exponential(mtbf_s=MTBF_S)

    def events(topo, key):
        gaps, fmask, _ = nt.correlated_renewal_gaps(
            topo, proc, key, n_runs=1, n_nodes=n_nodes, max_failures=4096)
        t = np.cumsum(np.asarray(gaps[0]))
        return np.repeat(t, np.asarray(fmask[0]).sum(-1))

    off = nt.rack_topology(n_nodes, 4, shock_mtbs_s=1e15, p_kill=1.0)
    on = nt.rack_topology(n_nodes, 4, shock_mtbs_s=5 * 24 * 3600.0,
                          p_kill=0.9)
    di_off = nt.dispersion_index(events(off, KEY))
    di_on = nt.dispersion_index(events(on, KEY))
    # superposed iid exponentials are Poisson-like (~1); shared shocks
    # over-disperse the counts
    assert 0.7 < di_off < 1.3
    assert di_on > di_off + 0.2
    assert di_on > 1.2


# ---------------------------------------------------------------------------
# cross-engine contract
# ---------------------------------------------------------------------------

def _aggressive_topology(n_nodes):
    # whole-machine shocks with high p_kill + age boosts: guarantees the
    # multi-felled AND all-felled branches are exercised, not just sampled
    # occasionally (a gentle topology leaves them untested)
    return nt.rack_topology(n_nodes, n_nodes, shock_mtbs_s=3 * 24 * 3600.0,
                            p_kill=0.95, age_boost_s=3600.0)


def test_correlated_histories_bit_identical_host_device():
    cfg = paper_scenarios()["scenario2_long_reexec"]
    n_nodes = len(cfg.survivors) + 1
    proc = failures.Weibull.from_mtbf(0.7, MTBF_S)
    topo = nt.rack_topology(n_nodes, 3, shock_mtbs_s=8 * 24 * 3600.0,
                            p_kill=0.6, age_boost_s=1800.0)
    g_h, pri_h, fm_h = sweep.renewal_failure_gaps(
        KEY, 32, n_nodes, 12, process=proc, topology=topo)
    res_d = sweep.renewal_monte_carlo_device(
        cfg, KEY, n_runs=32, max_failures=12, process=proc, topology=topo)
    np.testing.assert_array_equal(np.float32(g_h), np.asarray(res_d.gaps))
    valid = np.asarray(res_d.valid)
    np.testing.assert_array_equal(np.where(valid, pri_h, -1),
                                  np.asarray(res_d.failed_node))
    # shocks actually present in the fixture
    assert int(np.sum(fm_h.sum(-1) > 1)) > 0


def test_correlated_summaries_pinned_host_vs_device_all_scenarios():
    proc = failures.Weibull.from_mtbf(0.7, MTBF_S)
    for name, cfg in paper_scenarios().items():
        n_nodes = len(cfg.survivors) + 1
        topo = _aggressive_topology(n_nodes)
        kw = dict(n_runs=32, max_failures=12, process=proc, topology=topo)
        s_h = sweep.renewal_monte_carlo(cfg, KEY, engine="host", **kw)
        s_d = sweep.renewal_monte_carlo(cfg, KEY, **kw)
        assert s_d.per_node_failures == s_h.per_node_failures, name
        assert s_d.mean_failures == s_h.mean_failures, name
        for f in ("mean_energy_ref_j", "mean_energy_int_j", "mean_saving_j"):
            a, b = getattr(s_h, f), getattr(s_d, f)
            assert abs(a - b) <= 1e-4 * max(abs(a), 1.0), (name, f)


def test_simulator_cross_validates_multi_felled_epochs():
    proc = failures.Weibull.from_mtbf(0.7, MTBF_S)
    n_multi = n_all = 0
    for name, cfg in paper_scenarios().items():
        n_nodes = len(cfg.survivors) + 1
        n_surv = n_nodes - 1
        topo = _aggressive_topology(n_nodes)
        gaps, primary, fmask = sweep.renewal_failure_gaps(
            jax.random.PRNGKey(9), 4, n_nodes, 12, process=proc,
            topology=topo)
        felled = np.asarray(nt.survivor_slot_mask(fmask, primary))
        res = sweep.renewal_compose(cfg, gaps, MAKESPAN_S,
                                    failed_node=primary, felled=felled)
        for r in range(4):
            run = simulator.simulate_run(cfg, gaps[r], MAKESPAN_S,
                                         felled=felled[r])
            for e in run.epochs:
                k = e.index
                if e.felled is not None and e.felled.any():
                    n_multi += 1
                    n_all += int(e.felled.sum() == n_surv)
                for fld, oracle in (("energy_ref", res.epoch_ref),
                                    ("energy_int", res.epoch_int)):
                    a = getattr(e, fld)
                    b = np.asarray(oracle)[r, k]
                    rel = np.max(np.abs(a - b) / np.maximum(np.abs(b), 1.0))
                    assert rel < 1e-4, (name, r, k, fld)
                bf = float(np.asarray(res.epoch_failed)[r, k])
                assert abs(e.energy_failed - bf) <= 1e-4 * max(abs(bf), 1.0)
            for fld in ("energy_ref", "energy_int", "saving"):
                a = getattr(run, fld)
                b = float(np.asarray(getattr(res, fld))[r])
                assert abs(a - b) <= 1e-4 * max(abs(b), 1.0), (name, r, fld)
            assert run.n_failures == int(np.asarray(res.valid)[r].sum())
    # the whole point of the aggressive fixture: both shock branches ran
    assert n_multi > 10
    assert n_all > 0


def test_simulator_topology_sampling_path():
    cfg = paper_scenarios()["scenario2_long_reexec"]
    n_nodes = len(cfg.survivors) + 1
    proc = failures.Weibull.from_mtbf(0.7, MTBF_S)
    topo = _aggressive_topology(n_nodes)
    run = simulator.simulate_run(cfg, None, MAKESPAN_S, process=proc,
                                 key=KEY, topology=topo, max_failures=12)
    assert run.n_failures > 0
    with pytest.raises(ValueError):
        simulator.simulate_run(cfg, np.full(4, 1e5), MAKESPAN_S,
                               topology=topo)


# ---------------------------------------------------------------------------
# the sampler's lookups: compare-and-select, pinned to the gather form
# ---------------------------------------------------------------------------

def _slot_mask_by_gather(failed_mask, primary):
    # the reference formulation: slot i is node i + (i >= primary), picked
    # with take_along_axis
    xp = jnp if isinstance(failed_mask, jax.Array) else np
    idx = xp.arange(failed_mask.shape[-1] - 1)
    phys = idx + (idx >= primary[..., None])
    return xp.take_along_axis(failed_mask, phys, axis=-1)


def _sample_by_gather(topology, process, key, n_runs, max_failures,
                      n_nodes):
    # the correlated sampler with its rack lookups as gathers: a (G, N)
    # membership matrix and the per-group parameters indexed by the struck
    # group; draws, argmins and ties as in ``sample_correlated_renewal_gaps``
    member = jnp.asarray(np.concatenate(
        [np.arange(lv.n_groups)[:, None] == np.asarray(lv.group_of)[None, :]
         for lv in topology.levels]))
    mtbs, pkill, boost = nt._group_params(topology)
    n_groups = member.shape[0]
    k_res, k_shock, k_kill = jax.random.split(key, 3)
    v = jax.random.uniform(
        k_res, (max_failures, n_runs, n_nodes), dtype=jnp.float32)
    w = jax.random.uniform(
        k_kill, (max_failures, n_runs, n_nodes), dtype=jnp.float32)
    su = jax.random.uniform(
        k_shock, (max_failures, n_runs, n_groups), dtype=jnp.float32)
    node_ids = jnp.arange(n_nodes)

    def step(ages, xs):
        v_k, w_k, su_k = xs
        t = process.residual(v_k, ages)
        gap_ind = jnp.min(t, axis=-1)
        i_ind = jnp.argmin(t, axis=-1)
        s_times = mtbs * (-jnp.log1p(-su_k))
        gap_shk = jnp.min(s_times, axis=-1)
        g_shk = jnp.argmin(s_times, axis=-1)
        shock = gap_shk < gap_ind
        gap = jnp.where(shock, gap_shk, gap_ind)
        member_g = member[g_shk]
        killed = member_g & (w_k < pkill[g_shk][:, None])
        w_m = jnp.where(member_g, w_k, jnp.inf)
        forced = node_ids == jnp.argmin(w_m, axis=-1)[:, None]
        killed = jnp.where(jnp.any(killed, axis=-1, keepdims=True),
                           killed, forced)
        mask = jnp.where(shock[:, None], killed, node_ids == i_ind[:, None])
        primary = jnp.where(
            shock, jnp.argmin(jnp.where(killed, w_k, jnp.inf), axis=-1),
            i_ind).astype(jnp.int32)
        spared = shock[:, None] & member_g & ~killed
        ages = jnp.where(
            mask, 0.0,
            ages + gap[:, None]
            + jnp.where(spared, boost[g_shk][:, None], 0.0))
        return ages, (gap, mask, primary)

    init = jnp.zeros((n_runs, n_nodes), jnp.float32)
    _, (gaps, mask, primary) = jax.lax.scan(step, init, (v, w, su))
    return (jnp.transpose(gaps), jnp.transpose(mask, (1, 0, 2)),
            jnp.transpose(primary))


def _rack_psu_topology(n_nodes):
    # racks of 2 under PSUs of 4: two levels, so the sampler's (L, N) table
    # of global group ids has two rows and a PSU shock can fell nodes of
    # two racks at once
    return nt.Topology(n_nodes=n_nodes, levels=(
        nt.TopologyLevel(name="rack", group_of=[i // 2 for i in range(n_nodes)],
                         shock_mtbs_s=6 * 24 * 3600.0, p_kill=0.7,
                         age_boost_s=1800.0),
        nt.TopologyLevel(name="psu", group_of=[i // 4 for i in range(n_nodes)],
                         shock_mtbs_s=[4 * 24 * 3600.0, 9 * 24 * 3600.0],
                         p_kill=[0.5, 0.8], age_boost_s=[3600.0, 600.0]),
    ))


@pytest.mark.parametrize("xp", [np, jnp], ids=["numpy", "jnp"])
@pytest.mark.parametrize("where", ["first", "middle", "last"])
@pytest.mark.parametrize("n_nodes", [2, 4, 1024])
def test_survivor_slot_mask_matches_gather(n_nodes, where, xp):
    primary_at = {"first": 0, "middle": n_nodes // 2,
                  "last": n_nodes - 1}[where]
    rng = np.random.default_rng(n_nodes)
    fmask = rng.random((3, 5, n_nodes)) < 0.5
    primary = np.full((3, 5), primary_at, np.int32)
    # a row of mixed primaries besides the pinned ones
    primary[0] = rng.integers(0, n_nodes, size=5)
    fmask, primary = xp.asarray(fmask), xp.asarray(primary)
    got = nt.survivor_slot_mask(fmask, primary)
    assert type(got) is type(fmask)
    assert got.shape == (3, 5, n_nodes - 1) and got.dtype == fmask.dtype
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(_slot_mask_by_gather(fmask,
                                                                  primary)))


@pytest.mark.parametrize("x64", [False, True], ids=["f32", "x64"])
@pytest.mark.parametrize("topo_name", ["rack", "rack_psu", "aggressive"])
def test_correlated_sampler_bit_identical_to_gather_form(topo_name, x64):
    n_nodes = {"rack": 4, "rack_psu": 8, "aggressive": 4}[topo_name]
    topo = {"rack": lambda n: nt.rack_topology(
                n, 3, shock_mtbs_s=10 * 24 * 3600.0, p_kill=0.6,
                age_boost_s=3600.0),
            "rack_psu": _rack_psu_topology,
            "aggressive": _aggressive_topology}[topo_name](n_nodes)
    proc = failures.Weibull.from_mtbf(0.7, MTBF_S)
    shape = dict(n_runs=64, max_failures=24, n_nodes=n_nodes)
    with jax.enable_x64(x64):
        got = jax.jit(nt.sample_correlated_renewal_gaps,
                      static_argnames=tuple(shape))(topo, proc, KEY, **shape)
        ref = jax.jit(_sample_by_gather,
                      static_argnames=tuple(shape))(topo, proc, KEY, **shape)
    for a, b in zip(got, ref):
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    fmask = np.asarray(got[1])
    # shocks fired and spared members, so every branch of the step ran
    assert int(np.sum(fmask.sum(-1) > 1)) > 0
    assert int(np.sum(fmask.sum(-1) < n_nodes)) > 0
    if topo_name == "rack_psu":
        # a PSU shock felled nodes of two racks in one epoch
        racks = fmask.reshape(fmask.shape[:2] + (n_nodes // 2, 2)).any(-1)
        assert int(np.sum(racks.sum(-1) > 1)) > 0


def test_correlated_sampler_lowers_without_gather():
    # the rack cell's sampler shape: 4096 runs x 64 epochs x 4 nodes in
    # racks of 3, traced as the study traces it (x64 on)
    proc = failures.Weibull.from_mtbf(0.7, MTBF_S)
    topo = nt.rack_topology(4, 3, shock_mtbs_s=10 * 24 * 3600.0,
                            p_kill=0.6, age_boost_s=3600.0)

    def lowered(sample, slots):
        def fn(topo, proc, key):
            gaps, fmask, primary = sample(topo, proc, key, 4096, 64, 4)
            return gaps, slots(fmask, primary)
        with jax.enable_x64(True):
            return jax.jit(fn).lower(topo, proc, KEY).as_text()

    assert "stablehlo.gather" not in lowered(
        nt.sample_correlated_renewal_gaps, nt.survivor_slot_mask)
    # the check sees a gather where one is: the reference form has four
    assert lowered(_sample_by_gather,
                   _slot_mask_by_gather).count('"stablehlo.gather"') == 4


# ---------------------------------------------------------------------------
# trace ingestion
# ---------------------------------------------------------------------------

def _synthetic_log(n_nodes=8, max_failures=400):
    proc = failures.Exponential(mtbf_s=MTBF_S)
    topo = nt.rack_topology(n_nodes, 2, shock_mtbs_s=10 * 24 * 3600.0,
                            p_kill=0.9)
    gaps, fmask, _ = nt.correlated_renewal_gaps(
        topo, proc, jax.random.PRNGKey(1), n_runs=1, n_nodes=n_nodes,
        max_failures=max_failures)
    return nt.history_to_log(gaps, fmask, downtime_s=600.0), topo


def test_lanl_csv_roundtrip_exact():
    log, _ = _synthetic_log()
    csv = nt.to_lanl_csv(log)
    log2 = nt.parse_lanl_csv(csv, n_nodes=8)
    np.testing.assert_array_equal(log.node, log2.node)
    np.testing.assert_allclose(log.t_s, log2.t_s, atol=1e-5)
    np.testing.assert_allclose(log.downtime_s, log2.downtime_s)


def test_fit_shock_rates_recovers_generating_rates():
    log, topo = _synthetic_log()
    fit = nt.fit_shock_rates(log, topo, burst_window_s=1.0)
    assert fit["rack"]["n_bursts"] > 10
    # attribution bias is real (spared-member shocks look individual), so
    # the tolerance is loose but the order of magnitude must be right
    assert abs(fit["rack"]["shock_mtbs_s"] / (10 * 24 * 3600.0) - 1.0) < 0.5
    assert abs(fit["individual"]["mtbf_s"] / MTBF_S - 1.0) < 0.35


def test_burst_replay_preserves_simultaneity():
    log, _ = _synthetic_log()
    gaps, mask, primary = nt.burst_replay_gaps(
        log, KEY, n_runs=4, max_failures=16, burst_window_s=1.0)
    assert gaps.shape == (4, 16) and mask.shape == (4, 16, 8)
    assert np.all(gaps > 0)
    assert np.all(mask[np.arange(4)[:, None], np.arange(16)[None, :],
                       primary])
    # the source log is bursty; the replay must keep multi-node epochs
    assert float(mask.sum(-1).mean()) > 1.05


def test_trace_to_empirical_marginals():
    log, _ = _synthetic_log()
    emp = nt.trace_to_empirical(log)
    assert isinstance(emp, failures.EmpiricalTrace)
    # a usable marginal process: mean in the same decade as the truth
    mean = float(np.mean(np.asarray(emp.mean_s())))
    assert 0.2 * MTBF_S < mean < 5.0 * MTBF_S


# ---------------------------------------------------------------------------
# live stack: injector bursts + controller degradation
# ---------------------------------------------------------------------------

N_PODS = 4
STEP_S = 100.0
DUR_S = 120.0
PROCESS = failures.Weibull.from_mtbf(0.7, 2000.0)


class TinyPipeline:
    def batch_at(self, step):
        return jnp.full((4,), float(step))


@jax.jit
def _tiny_step(params, opt_state, batch):
    g = jnp.mean(batch) * 0.01
    params = jax.tree.map(lambda p: p - 0.001 * (p + g), params)
    return params, opt_state, {"total_loss": jnp.mean(batch)}


def test_injector_replays_correlated_bursts():
    topo = nt.rack_topology(N_PODS, N_PODS, shock_mtbs_s=1500.0,
                            p_kill=0.9, age_boost_s=0.0)
    key = jax.random.PRNGKey(0)     # a key whose run 1 holds bursts
    inj = StochasticFailureInjector(PROCESS, key, n_pods=N_PODS,
                                    max_failures=16, n_runs=2, run_index=1,
                                    topology=topo)
    gaps, primary, fmask = sweep.renewal_failure_gaps(
        key, 2, N_PODS, 16, process=PROCESS, topology=topo)
    # the flat queue is the epoch sequence with co-felled nodes expanded
    # as zero-gap entries right after their primary
    i = 0
    for k in range(16):
        assert inj.gaps[i] == gaps[1, k]
        assert inj.failed_node[i] == primary[1, k]
        i += 1
        for node in np.nonzero(fmask[1, k])[0]:
            if int(node) != int(primary[1, k]):
                assert inj.gaps[i] == 0.0
                assert inj.failed_node[i] == int(node)
                i += 1
    assert i == inj.gaps.shape[0]
    assert np.any(inj.gaps == 0.0)      # bursts present at this key


# handcrafted storm + moderate tail: three whole-cluster shock bursts in
# the first ~1000 s, then iid-looking ~900 s gaps for the rest of the run
STORM_GAPS = [600.0, 0.0, 0.0, 0.0, 200.0, 0.0, 0.0, 0.0,
              200.0, 0.0, 0.0, 0.0]
STORM_NODES = [0, 1, 2, 3] * 3
TAIL_GAPS = [800.0, 950.0, 900.0, 1000.0, 850.0, 900.0, 950.0, 800.0,
             1000.0, 900.0, 850.0, 950.0, 900.0, 800.0, 1000.0, 900.0,
             850.0, 950.0]
TAIL_NODES = [0, 1, 2, 3, 0, 1, 2, 3, 0, 1, 2, 3, 0, 1, 2, 3, 0, 1]


def _storm_injector():
    inj = StochasticFailureInjector(PROCESS, KEY, n_pods=N_PODS,
                                    max_failures=32, n_runs=4, run_index=1)
    inj.gaps = np.asarray(STORM_GAPS + TAIL_GAPS, np.float64)
    inj.failed_node = np.asarray(STORM_NODES + TAIL_NODES, np.int64)
    return inj


def _trainer(root, *, controller=None, interval_steps=6):
    state = ({"w": jnp.ones((8,))}, {"m": jnp.zeros((8,))})
    return FTTrainer(
        step_fn=_tiny_step, pipeline=TinyPipeline(), state=state,
        cluster=ClusterSpec(n_pods=N_PODS, step_time_s=STEP_S),
        ckpt_cfg=CheckpointConfig(root=str(root),
                                  interval_steps=interval_steps, keep=3,
                                  phase_offset_steps=1),
        injector=_storm_injector(), ckpt_duration_s=DUR_S,
        controller=controller)


def _controller(degrade, hysteresis=99):
    return AdaptiveController(
        failures.Exponential(mtbf_s=2000.0), n_pods=N_PODS, retune_every=2,
        min_complete_gaps=3, cem_iters=2, cem_population=10, cem_n_runs=32,
        cem_max_failures=32, seed=0, degrade=degrade,
        conservative_policy={"ckpt_interval": 600.0},
        burst_window=2, near_zero_frac=0.25, hysteresis=hysteresis)


def test_degrade_controller_survives_burst_storm(tmp_path):
    """Acceptance: under an injected burst storm the degrade-enabled
    controller is never worse than the static conservative baseline on
    realized ledger energy, while a naive always-retune controller is
    measurably worse (it tunes on the poisoned window and carries the
    bad policy through the tail)."""
    n_steps = 200

    static = _trainer(tmp_path / "s")
    static.run(n_steps)
    static_j = static.energy.ledger_total_j()

    ctl_d = _controller(degrade=True)
    deg = _trainer(tmp_path / "d", controller=ctl_d)
    deg.run(n_steps)
    deg_j = deg.energy.ledger_total_j()

    ctl_n = _controller(degrade=False)
    naive = _trainer(tmp_path / "n", controller=ctl_n)
    naive.run(n_steps)
    naive_j = naive.energy.ledger_total_j()

    # the detector tripped and the controller refused to tune on the storm
    assert any(e["action"] == "degrade" for e in ctl_d.degrade_events)
    assert ctl_d.retunes == []
    assert deg.cluster.ckpt_interval_s == 600.0
    # PIT residuals collapse to ~0 on the zero-gap burst entries
    zero_resid = [u for g, u in zip(ctl_d._gap_log, ctl_d.pit) if g == 0.0]
    assert zero_resid and max(zero_resid) < 1e-6
    # the naive controller did keep refitting through the storm
    assert len(ctl_n.retunes) >= 5
    assert ctl_n.fitted is not None

    assert deg_j <= static_j
    assert naive_j > 1.03 * static_j
    assert naive_j > 1.03 * deg_j


def test_degrade_controller_reengages_after_calm():
    # prior stays in force (min_complete_gaps high), so with an exponential
    # prior the PIT residual is 1 - exp(-n·g/mtbf): zero gaps -> u ~ 0,
    # ~350 s gaps -> mid-range u that passes the uniform KS check
    ctl = AdaptiveController(
        failures.Exponential(mtbf_s=2000.0), n_pods=N_PODS, retune_every=4,
        min_complete_gaps=99, cem_iters=2, cem_population=10, cem_n_runs=32,
        cem_max_failures=32, seed=0, degrade=True,
        conservative_policy={"ckpt_interval": 600.0},
        burst_window=4, near_zero_frac=0.25, hysteresis=2)
    trainer = types.SimpleNamespace(
        cluster=ClusterSpec(n_pods=N_PODS, step_time_s=STEP_S),
        ckpt_duration_s=DUR_S)

    def fail(gap, pod, step):
        ctl.observe_failure(gap_s=gap, failed_pod=pod)
        return ctl.maybe_retune(trainer=trainer, remaining_work_s=1e5,
                                step=step)

    # storm: gate fires at failure 4 with window [300, 0, 0, 0] -> degrade
    for gap, pod in [(300.0, 0), (0.0, 1), (0.0, 2)]:
        assert fail(gap, pod, 1) is None
    pol = fail(0.0, 3, 4)
    assert ctl.degraded
    assert pol == {"ckpt_interval": 600.0}
    assert ctl.retunes == []            # no refit on the poisoned window
    # one more burst straggler, then calm gaps; the failure-8 window
    # [0, 400, 300, 500] still holds a zero -> still degraded
    seq = [(0.0, 0), (400.0, 1), (300.0, 2), (500.0, 3),
           (350.0, 0), (420.0, 1), (380.0, 2), (450.0, 3)]
    for gap, pod in seq[:4]:
        assert fail(gap, pod, 8) is None
    assert ctl.degraded
    # failure 12: all-calm window -> first calm check only arms hysteresis
    for gap, pod in seq[4:]:
        pol = fail(gap, pod, 12)
    assert pol is None and ctl.degraded
    # failure 16: second calm check -> re-engage and actually retune
    for gap, pod in [(390.0, 0), (410.0, 1), (360.0, 2)]:
        fail(gap, pod, 15)
    pol = fail(430.0, 3, 16)
    assert not ctl.degraded
    assert [e["action"] for e in ctl.degrade_events] == \
        ["degrade", "re-engage"]
    assert pol is not None and ctl.retunes
