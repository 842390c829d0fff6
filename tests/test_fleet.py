"""Fleet-advisory tests: the cluster axis never changes any answer.

The fleet layer's entire contract is mechanical and testable:

  * **fleet CRN bitwise identity** — every cluster row of the fused
    ``(C, P)`` dispatch must equal a standalone ``optimize_policy`` /
    ``evaluate_policy_grid`` call for that cluster alone at the same key,
    bit for bit (each lane re-samples its OWN histories at the shared
    key) — for both failure families.  This is the PR-5 CRN contract
    extended over the cluster axis: batching is a throughput decision,
    never an accuracy one.
  * **padding inertness** — padding a batch up to a shape bucket by
    repeating the last request must leave the real rows bit-identical to
    the unpadded dispatch (vmap lanes are independent).
  * **scatter order** — a shuffled multi-bucket request stream comes back
    in submit order, each answer belonging to its own profile.
  * **memoization** — repeat fleet shapes are pure cache hits (no
    retrace, probed by trace counters); new static shapes miss; the LRU
    bound holds and evicts.

Plus the acceptance bar: a 256-cluster heterogeneous fleet answered by
ONE compiled program, spot-checked bit-identical to standalone calls.
"""
import jax
import numpy as np
import pytest

from repro import fleet
from repro.core import energy_model as em
from repro.core import failures as F
from repro.core import optimize as O

KEY = jax.random.PRNGKey(11)
N_RUNS = 8
MAX_FAILURES = 6
KW = dict(n_runs=N_RUNS, max_failures=MAX_FAILURES)


def _table() -> O.PolicyTable:
    return O.policy_grid(
        ckpt_interval=[3600.0, 7200.0, 14400.0],
        mu1=[6.0],
        wait_mode=[em.WaitMode.ACTIVE, em.WaitMode.IDLE],
    )


def _fleet(n=4, *, family_frac=0.0, seed=2, node_buckets=(4,)):
    return fleet.synthetic_fleet(n, seed=seed, node_buckets=node_buckets,
                                 weibull_frac=family_frac)


def _solo(profile, table):
    """The reference answer: tune this cluster alone at the same key."""
    return O.optimize_policy(
        profile.scenario(), KEY, table=table,
        process=profile.failure_process(), work_s=profile.work_s, **KW)


def _assert_grids_bitwise(got: O.PolicyEvalResult, ref: O.PolicyEvalResult,
                          label: str):
    for field in ("energy_ref", "energy_int", "saving", "end_time",
                  "n_failures", "mean_energy_j", "mean_makespan_s",
                  "makespan_s"):
        np.testing.assert_array_equal(
            getattr(got, field), getattr(ref, field),
            err_msg=f"{label} field {field}")


# ---------------------------------------------------------------------------
# fleet CRN: per-cluster rows == standalone calls, bit for bit
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("family_frac", [0.0, 1.0],
                         ids=["exponential", "weibull"])
def test_fleet_rows_bit_identical_to_standalone(family_frac):
    """Each cluster row of ``optimize_policy(clusters=)`` equals tuning
    that cluster alone — grids, argmin, and knee — for both families."""
    table = _table()
    profiles = _fleet(3, family_frac=family_frac)
    batch = O.optimize_policy(None, KEY, table=table,
                              clusters=[p.spec() for p in profiles], **KW)
    assert len(batch) == len(profiles)
    for p, opt in zip(profiles, batch):
        ref = _solo(p, table)
        _assert_grids_bitwise(opt.grid, ref.grid, p.name)
        assert opt.best == ref.best, p.name
        assert opt.knee == ref.knee, p.name
        np.testing.assert_array_equal(opt.pareto, ref.pareto)


def test_evaluate_policy_grid_clusters_matches_single():
    """The grid-evaluator arm: ``clusters=`` rows == single-cfg calls with
    the same per-cluster process and work."""
    table = _table()
    profiles = _fleet(3, family_frac=1.0, seed=5)
    rows = O.evaluate_policy_grid(
        None, table, KEY, work_s=6 * 24 * 3600.0,
        clusters=[(p.scenario(), p.failure_process()) for p in profiles],
        **KW)
    for p, got in zip(profiles, rows):
        ref = O.evaluate_policy_grid(
            p.scenario(), table, KEY, work_s=6 * 24 * 3600.0,
            process=p.failure_process(), **KW)
        _assert_grids_bitwise(got, ref, p.name)


def test_fleet_policy_inputs_lanes_match_policy_inputs():
    """The host-numpy stacker: each cluster slice of the stacked pytree
    carries exactly what ``policy_inputs`` builds for that cfg alone."""
    table = _table()
    cfgs = [p.scenario() for p in _fleet(3, seed=9)]
    stacked = O.fleet_policy_inputs(cfgs, table)
    for c, cfg in enumerate(cfgs):
        solo = O.policy_inputs(cfg, table)
        jax.tree.map(
            lambda s, r, _c=c: np.testing.assert_array_equal(
                np.asarray(s)[_c], np.asarray(r)),
            stacked, solo)


# ---------------------------------------------------------------------------
# padding inertness and scatter order
# ---------------------------------------------------------------------------

def test_padding_is_inert():
    """Forcing 5 requests through an 8-wide bucket (3 padded lanes) gives
    the same bits as the exact-fit dispatch."""
    table = _table()
    profiles = _fleet(5, seed=4)
    exact = fleet.FleetAdvisor(table, key=KEY, buckets=(5,), **KW)
    padded = fleet.FleetAdvisor(table, key=KEY, buckets=(8,), **KW)
    for a, b in zip(exact.advise(profiles), padded.advise(profiles)):
        _assert_grids_bitwise(b.optimum.grid, a.optimum.grid, a.profile.name)
        assert a.best == b.best and a.knee == b.knee


def test_scatter_returns_submit_order():
    """A shuffled multi-bucket stream: answers come back in submit order,
    each bit-identical to that profile advised on its own."""
    table = _table()
    profiles = fleet.synthetic_fleet(7, seed=6, node_buckets=(4, 8),
                                     weibull_frac=0.5)
    order = [3, 0, 6, 2, 5, 1, 4]
    shuffled = [profiles[i] for i in order]
    advisor = fleet.FleetAdvisor(table, key=KEY, **KW)
    advisories = advisor.advise(shuffled)
    assert [a.request_id for a in advisories] == list(range(len(shuffled)))
    assert len({p.bucket_key() for p in shuffled}) > 1
    solo = fleet.FleetAdvisor(table, key=KEY, **KW)
    for a, p in zip(advisories, shuffled):
        assert a.profile is p
        (alone,) = solo.advise([p])
        _assert_grids_bitwise(a.optimum.grid, alone.optimum.grid, p.name)


def test_empty_and_singleton_flush():
    # no table: the advisor builds the default grid around its MTBF anchor
    advisor = fleet.FleetAdvisor(key=KEY, **KW)
    assert advisor.flush() == []
    profile = fleet.ClusterProfile()
    rid = advisor.submit(profile)
    assert rid == 0
    (a,) = advisor.flush()
    assert a.profile is profile
    assert advisor.flush() == []        # queue drained


def test_sharded_path_matches_unsharded():
    """``shard=True`` splits the cluster axis over the host's devices via
    pmap with a broadcast key — answers must stay bit-identical to the
    unsharded dispatch (on one device: a 1-lane pmap)."""
    table = _table()
    profiles = _fleet(3, seed=8)
    plain = fleet.FleetAdvisor(table, key=KEY, **KW).advise(profiles)
    sharded_adv = fleet.FleetAdvisor(table, key=KEY, shard=True, **KW)
    for a, b in zip(plain, sharded_adv.advise(profiles)):
        _assert_grids_bitwise(b.optimum.grid, a.optimum.grid, a.profile.name)
        assert a.best == b.best and a.knee == b.knee
    # the pmap program lives in its own cache but shares the counters
    stats = sharded_adv.cache_stats()
    assert stats.misses == 1 and stats.traces == 1


# ---------------------------------------------------------------------------
# acceptance bar: 256 heterogeneous clusters, one compiled program
# ---------------------------------------------------------------------------

def test_256_cluster_fleet_one_program():
    table = _table()
    profiles = _fleet(256, seed=0)
    advisor = fleet.FleetAdvisor(table, key=KEY, **KW)
    advisories = advisor.advise(profiles)
    assert len(advisories) == 256
    stats = advisor.cache_stats()
    assert stats.misses == 1 and stats.traces == 1 and stats.entries == 1
    # heterogeneity made it through: MTBFs differ, so do some answers
    assert len({a.profile.mtbf_s for a in advisories}) == 256
    for c in (0, 101, 255):
        ref = _solo(profiles[c], table)
        _assert_grids_bitwise(advisories[c].optimum.grid, ref.grid, f"c{c}")
        assert advisories[c].best == ref.best


# ---------------------------------------------------------------------------
# memoization: hits, misses, eviction — probed by trace counters
# ---------------------------------------------------------------------------

def test_repeat_fleet_shape_never_retraces():
    table = _table()
    advisor = fleet.FleetAdvisor(table, key=KEY, **KW)
    advisor.advise(_fleet(3, seed=1))
    first = advisor.cache_stats()
    assert first.misses == 1 and first.traces == 1
    # a DIFFERENT fleet padding into the same 4-wide bucket: new values,
    # same static shapes — must reuse the compiled program untouched
    advisor.advise(_fleet(4, seed=2))
    again = advisor.cache_stats()
    assert again.traces == first.traces     # no retrace
    assert again.hits == first.hits + 1
    assert again.misses == first.misses


def test_new_node_count_bucket_misses():
    advisor = fleet.FleetAdvisor(_table(), key=KEY, **KW)
    advisor.advise(_fleet(2, node_buckets=(4,)))
    advisor.advise(_fleet(2, node_buckets=(8,)))
    stats = advisor.cache_stats()
    assert stats.misses == 2 and stats.entries == 2


def test_dispatch_cache_lru_eviction():
    calls = []
    cache = fleet.DispatchCache(lambda x: x + 1, max_entries=2,
                                compile=lambda f: (calls.append(1), f)[1])
    for k in ("a", "b", "a", "c"):          # c evicts b (a was refreshed)
        cache.get(k)(0)
    assert len(cache) == 2
    assert "b" not in cache and "a" in cache and "c" in cache
    st = cache.stats()
    assert (st.hits, st.misses, st.evictions) == (1, 3, 1)
    cache.get("b")(0)                       # re-entry is a fresh miss
    assert cache.stats().misses == 4
    with pytest.raises(ValueError):
        fleet.DispatchCache(lambda x: x, max_entries=0)


def test_dispatch_cache_clear():
    cache = fleet.DispatchCache(lambda x: x + 1, max_entries=4)
    cache.get("a")(jax.numpy.ones(2))
    cache.get("b")
    cache.clear()
    assert len(cache) == 0 and "a" not in cache
    st = cache.stats()
    assert st.evictions == 2 and st.entries == 0
    assert st.traces == 1               # the paid trace survives the clear


def test_dispatch_cache_trace_counting():
    cache = fleet.DispatchCache(lambda x: x * 2, static_argnames=())
    fn = cache.get("k")
    assert cache.trace_count("k") == 0      # compiled lazily
    fn(jax.numpy.ones(3)); fn(jax.numpy.ones(3))
    assert cache.trace_count("k") == 1      # second call hit the jit cache
    fn(jax.numpy.ones(4))                   # new shape retraces
    assert cache.trace_count("k") == 2
    assert cache.stats().traces == 2


# ---------------------------------------------------------------------------
# error paths: the cluster axis refuses silent misuse
# ---------------------------------------------------------------------------

def test_clusters_reject_cfg_and_refine():
    spec = fleet.ClusterProfile().spec()
    with pytest.raises(ValueError, match="cfg=None"):
        O.optimize_policy(fleet.ClusterProfile().scenario(), KEY,
                          clusters=[spec], **KW)
    with pytest.raises(ValueError, match="single-cluster"):
        O.optimize_policy(None, KEY, clusters=[spec], refine=True, **KW)
    with pytest.raises(ValueError, match="no clusters"):
        O.optimize_policy(None, KEY, clusters=[], **KW)


def test_clusters_reject_topology_and_mixed_families():
    table = _table()
    exp = fleet.ClusterProfile(family="exponential").spec()
    wb = fleet.ClusterProfile(family="weibull").spec()
    with pytest.raises(ValueError, match="single-cluster"):
        O.evaluate_policy_grid(None, table, KEY, work_s=1e5,
                               clusters=[exp], topology=object(), **KW)
    with pytest.raises(ValueError, match="family"):
        O.evaluate_policy_grid(None, table, KEY, work_s=1e5,
                               clusters=[exp, wb], **KW)


def test_clusters_reject_shape_mismatch_and_bad_makespan():
    table = _table()
    n4 = fleet.ClusterProfile(n_nodes=4).spec()
    n8 = fleet.ClusterProfile(n_nodes=8).spec()
    with pytest.raises(ValueError, match="survivor count"):
        O.evaluate_policy_grid(None, table, KEY, work_s=1e5,
                               clusters=[n4, n8], **KW)
    with pytest.raises(ValueError, match="exactly one"):
        O.evaluate_policy_grid(None, table, KEY, clusters=[n4], **KW)
    with pytest.raises(ValueError, match="work_s"):
        O.evaluate_policy_grid(None, table, KEY, makespan_s=1e5,
                               clusters=[n4], **KW)


def test_cluster_scenario_builder():
    """The campaign-registry lowering reuses the profile's balanced
    snapshot: node/power axes address it as an ordinary scenario."""
    cfg = fleet.cluster_scenario(n_nodes=8, power_scale=0.8)
    assert cfg.name == "fleet_n8_x0.8"
    assert len(cfg.survivors) == 7
    ref = fleet.ClusterProfile(name=cfg.name, n_nodes=8,
                               power_scale=0.8).scenario()
    assert cfg.survivors == ref.survivors
    assert cfg.ckpt_duration == ref.ckpt_duration
    assert cfg.profile.p_base == ref.profile.p_base
    np.testing.assert_array_equal(cfg.profile.power_table.p_comp,
                                  ref.profile.power_table.p_comp)


def test_profile_validation():
    with pytest.raises(ValueError, match="nodes"):
        fleet.ClusterProfile(n_nodes=1)
    with pytest.raises(ValueError, match="family"):
        fleet.ClusterProfile(family="lognormal")
    with pytest.raises(ValueError, match="positive"):
        fleet.ClusterProfile(mtbf_s=-1.0)
    with pytest.raises(ValueError, match=">= 1"):
        fleet.synthetic_fleet(0)


# ---------------------------------------------------------------------------
# the machine a profile lowers onto
# ---------------------------------------------------------------------------

def _machine():
    """A node other than the paper's: its own ladder, sleep state and
    powers (a deployment's machine, as a configuration file gives one)."""
    from repro.core.characterization import (MachineProfile, PowerTable,
                                             SleepSpec)
    return MachineProfile(
        name="site-node", power_table=PowerTable(
            freq_ghz=[3.0, 2.0, 1.0], p_comp=[200.0, 150.0, 110.0],
            beta=[1.0, 1.4, 2.6], p_ckpt=[180.0, 140.0, 120.0],
            gamma=[1.0, 1.2, 1.5]),
        sleep=SleepSpec(t_go_sleep=20.0, t_wakeup=8.0, p_go_sleep=40.0,
                        p_wakeup=80.0, p_sleep=10.0),
        p_base=70.0, p_idle_wait=75.0)


@pytest.mark.parametrize("power_scale", [1.0, 1.25])
def test_profile_lowers_onto_its_machine(power_scale):
    """A profile given a machine lowers to the balanced snapshot built by
    hand on that machine, its powers scaled; without one it keeps the
    paper's machine, as before."""
    from repro.core.characterization import paper_machine_profile
    from repro.core.simulator import NodeStart, ScenarioConfig
    machine = _machine()
    profile = fleet.ClusterProfile(name="job", n_nodes=5, period_s=3600.0,
                                   power_scale=power_scale, t_down=30.0,
                                   t_restart=45.0, machine=machine)
    cfg = profile.scenario()
    s = power_scale
    want = ScenarioConfig(
        name="job", survivors=tuple(
            NodeStart(exec_to_rendezvous=3600.0 * (i + 1) / 5,
                      rendezvous_period=3600.0, ckpt_age=0.0)
            for i in range(4)),
        t_down=30.0, t_restart=45.0, t_reexec=0.0, profile=machine,
        ckpt_duration=120.0)
    assert cfg.survivors == want.survivors
    assert (cfg.t_down, cfg.t_restart, cfg.t_reexec, cfg.ckpt_duration,
            cfg.move_ahead) == (30.0, 45.0, 0.0, 120.0, True)
    pt, want_pt = cfg.profile.power_table, machine.power_table
    np.testing.assert_array_equal(pt.freq_ghz, want_pt.freq_ghz)
    np.testing.assert_array_equal(pt.beta, want_pt.beta)
    np.testing.assert_array_equal(pt.gamma, want_pt.gamma)
    np.testing.assert_array_equal(pt.p_comp, want_pt.p_comp * s)
    np.testing.assert_array_equal(pt.p_ckpt, want_pt.p_ckpt * s)
    assert cfg.profile.p_base == machine.p_base * s
    assert cfg.profile.p_idle_wait == machine.p_idle_wait * s
    assert cfg.profile.sleep.p_sleep == machine.sleep.p_sleep * s
    assert cfg.profile.sleep.t_wakeup == machine.sleep.t_wakeup
    # the policy stack carries the machine's ladder
    stacked = O.policy_inputs(cfg, _table())
    np.testing.assert_array_equal(np.asarray(stacked.ladder.p_comp)[0],
                                  want_pt.p_comp * s)
    # no machine: the paper's, unchanged
    default = fleet.ClusterProfile(n_nodes=5).scenario()
    paper = paper_machine_profile()
    assert default.profile.name == paper.name
    np.testing.assert_array_equal(default.profile.power_table.p_comp,
                                  paper.power_table.p_comp)
    assert default.profile.p_idle_wait == paper.p_idle_wait


def test_advisor_answers_on_the_profiles_machine():
    """The advisor's answer for a profile on its own machine is the
    standalone answer for that scenario, and differs from the paper
    machine's."""
    table = _table()
    on_site = fleet.ClusterProfile(n_nodes=4, machine=_machine())
    (a,) = fleet.FleetAdvisor(table, key=KEY, **KW).advise([on_site])
    _assert_grids_bitwise(a.optimum.grid, _solo(on_site, table).grid, "site")
    (b,) = fleet.FleetAdvisor(table, key=KEY, **KW).advise(
        [fleet.ClusterProfile(n_nodes=4)])
    assert not np.array_equal(a.optimum.grid.mean_energy_j,
                              b.optimum.grid.mean_energy_j)


# ---------------------------------------------------------------------------
# the dispatch bound: a bucket goes out in dispatches that each fit
# ---------------------------------------------------------------------------

@pytest.fixture
def fold_budget(monkeypatch):
    """Sets ``sweep._SURVIVOR_TILE_ELEMENTS``; programs read it when they
    are traced, so JAX's caches are cleared with each change and after."""
    from repro.core import sweep

    def set_budget(elements):
        monkeypatch.setattr(sweep, "_SURVIVOR_TILE_ELEMENTS", elements)
        jax.clear_caches()
    yield set_budget
    monkeypatch.undo()
    jax.clear_caches()


def _lane_elements(n_clusters, n_nodes, table):
    return n_clusters * len(table) * N_RUNS * MAX_FAILURES * (n_nodes - 1)


def _dispatch_spans(advisor, profiles, tmp_path):
    """The ``fleet.dispatch`` spans' args of one advise of ``profiles``,
    in dispatch order."""
    import glob

    from jax.profiler import ProfileData
    jax.profiler.start_trace(str(tmp_path))
    answers = advisor.advise(profiles)
    jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    events = sorted((ev.start_ns, dict(ev.stats))
                    for plane in ProfileData.from_file(path).planes
                    for line in plane.lines for ev in line.events
                    if ev.name == "fleet.dispatch")
    return answers, [args for _, args in events]


def test_a_bucket_over_the_budget_splits_into_dispatches(fold_budget, tmp_path):
    """Five clusters, two of whose folds fit the budget together: the
    bucket goes out in dispatches of two, two and one, none padded, and
    every row is bit-identical to the standalone optimizer's."""
    from repro.core import sweep
    table = _table()
    profiles = _fleet(5, family_frac=1.0, seed=3, node_buckets=(8,))
    fold_budget(2 * _lane_elements(1, 8, table))
    assert sweep._fleet_fold(len(table), N_RUNS, MAX_FAILURES, 7) == (None, 2)
    advisor = fleet.FleetAdvisor(table, key=KEY, **KW)
    answers, spans = _dispatch_spans(advisor, profiles, tmp_path)
    assert [(d["clusters"], d["padded"], d["survivors"], d["survivor_tile"],
             d["tiles"], d["dispatches"]) for d in spans] == [
        (2, 0, 7, 7, 1, 3), (2, 0, 7, 7, 1, 3), (1, 0, 7, 7, 1, 3)]
    assert advisor.stats().lanes == 5 and advisor.stats().padded_lanes == 0
    for p, a in zip(profiles, answers):
        assert a.profile is p
        _assert_grids_bitwise(a.optimum.grid, _solo(p, table).grid, p.name)
    # the library's cluster axis splits alike, with the same rows
    rows = O.evaluate_policy_grid(
        None, table, KEY, work_s=6 * 24 * 3600.0,
        clusters=[(p.scenario(), p.failure_process()) for p in profiles],
        **KW)
    for p, got in zip(profiles, rows):
        ref = O.evaluate_policy_grid(
            p.scenario(), table, KEY, work_s=6 * 24 * 3600.0,
            process=p.failure_process(), **KW)
        _assert_grids_bitwise(got, ref, p.name)


def test_clusters_each_over_the_budget_stay_bit_identical(fold_budget,
                                                          tmp_path):
    """Where each cluster alone folds one epoch's survivors at a time, its
    dispatch holds as many clusters as one epoch's fold of each fits: the
    padded bucket of four goes out whole, and its rows are bit-identical
    to standalone optimizer calls."""
    from repro.core import sweep
    table = _table()
    profiles = _fleet(3, family_frac=1.0, seed=7, node_buckets=(8,))
    # one epoch's survivors of four clusters fit, a cluster's whole grid
    # does not
    fold_budget(_lane_elements(4, 8, table) // MAX_FAILURES)
    assert sweep._fleet_fold(len(table), N_RUNS, MAX_FAILURES, 7) == (7, 4)
    answers, spans = _dispatch_spans(
        fleet.FleetAdvisor(table, key=KEY, **KW), profiles, tmp_path)
    assert [(d["clusters"], d["padded"], d["survivor_tile"], d["tiles"],
             d["dispatches"]) for d in spans] == [(3, 1, 7, MAX_FAILURES, 1)]
    for p, a in zip(profiles, answers):
        _assert_grids_bitwise(a.optimum.grid, _solo(p, table).grid, p.name)


# ---------------------------------------------------------------------------
# tracing an advisory flush, and the advisor's counters
# ---------------------------------------------------------------------------

def test_a_traced_flush_names_its_stages(tmp_path):
    """The flush's spans and their args are in a CPU trace; nothing of
    JAX runs under ``fleet.evaluate``."""
    import glob

    from jax.profiler import ProfileData
    table = _table()
    profiles = fleet.synthetic_fleet(5, seed=6, node_buckets=(4, 8),
                                     weibull_frac=0.0)
    advisor = fleet.FleetAdvisor(table, key=KEY, **KW)
    want = advisor.advise(profiles)
    # host events of the program and of JAX, not of every Python call
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    got = advisor.advise(profiles)
    jax.profiler.stop_trace()
    for a, b in zip(got, want):
        _assert_grids_bitwise(a.optimum.grid, b.optimum.grid, a.profile.name)
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    lines = [list(line.events) for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host:") for line in plane.lines]
    events = [ev for line in lines for ev in line]
    named = lambda n: [dict(ev.stats) for ev in events if ev.name == n]
    flush, = named("fleet.flush")
    assert (flush["requests"], flush["buckets"]) == (5, 2)
    for span in ("fleet.stage", "fleet.evaluate"):
        assert len(named(span)) == 2, span
    dispatch = sorted(named("fleet.dispatch"), key=lambda d: d["survivors"])
    buckets = sorted({p.n_nodes: sum(q.n_nodes == p.n_nodes
                                     for q in profiles)
                      for p in profiles}.items())
    for d, (n, count) in zip(dispatch, buckets):
        padded = {1: 0, 2: 0, 3: 1, 4: 0}[count]
        assert (d["clusters"], d["padded"], d["survivors"],
                d["survivor_tile"], d["tiles"], d["dispatches"]) == (
            count, padded, n - 1, n - 1, 1, 1)
    fetch = named("fleet.fetch")
    assert len(fetch) == 2 and all(f["bytes"] > 0 for f in fetch)
    for line in lines:
        for ev in line:
            if ev.name != "fleet.evaluate":
                continue
            end = ev.start_ns + ev.duration_ns
            inside = [o.name for o in line if o is not ev
                      and ev.start_ns <= o.start_ns < end]
            assert inside == [], inside


def test_the_counters_count_lanes_padding_and_decisions():
    """Three 4-node clusters pad to a bucket of 4, one 8-node cluster rides
    alone; a second flush of one cluster adds its lane."""
    table = _table()
    profiles = [fleet.ClusterProfile(name=f"c{i}", n_nodes=n,
                                     work_s=(3 + i) * 24 * 3600.0)
                for i, n in enumerate((4, 8, 4, 4))]
    advisor = fleet.FleetAdvisor(table, key=KEY, **KW)
    assert advisor.stats() == fleet.AdvisorStats()
    advisor.advise(profiles)
    per_survivor = len(table) * N_RUNS * MAX_FAILURES
    got = advisor.stats()
    assert got == fleet.AdvisorStats(
        requests=4, lanes=5, padded_lanes=1,
        decisions_dispatched=(4 * 3 + 7) * per_survivor,
        decisions_answered=(3 * 3 + 7) * per_survivor)
    # the padded share of the dispatched decisions: 3 of 19 survivors
    assert 19 * (got.decisions_dispatched - got.decisions_answered) == (
        3 * got.decisions_dispatched)
    advisor.advise(profiles[1:2])
    assert advisor.stats() == fleet.AdvisorStats(
        requests=5, lanes=6, padded_lanes=1,
        decisions_dispatched=(4 * 3 + 7 + 7) * per_survivor,
        decisions_answered=(3 * 3 + 7 + 7) * per_survivor)
