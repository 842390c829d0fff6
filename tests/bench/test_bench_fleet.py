"""The advisory cell (``fleet.lanl1024.bulk``) on the CPU at a tiny size:
the configuration cut to 4 jobs of 4-16 nodes (three 4-node jobs, so one
bucket is padded), 16 runs of 8 epochs and a 4-policy grid.  A run reads
every metric and is correct against the plain reference of a policy lane
(``bench/reference/policy.py``); the float32 control and three planted
faults of the serving layer are not."""
import copy
import dataclasses
import json
import pathlib
import time

import numpy as np
import pytest

from bench import harness
from bench.drivers import fleet_bulk
from bench.reference import policy as ref_policy
from repro import fleet
from repro.core import optimize

ROOT = pathlib.Path(__file__).resolve().parents[2]
CELL = "fleet.lanl1024.bulk"
TINY = dict(n_runs=16, max_failures=8)
NODES = (4, 16, 4, 4)
# a sound run on the CPU reads 3e-8 to 3e-7 here (float32 rounding of
# Algorithm 1 differs between XLA's CPU code and numpy, and a mean saving
# over 16 runs moves with one changed decision); the float32 control 1.4e-5.
# The cell's own limit is set from readings on the chip (PERF.md section 2),
# so the tiny cell is held to this one.
CPU_SOUND = 1e-6
DAY_S = 24 * 3600.0


@pytest.fixture(autouse=True)
def few_workers(monkeypatch):
    monkeypatch.setattr(fleet_bulk.Driver, "workers", 2)
    monkeypatch.setattr(fleet_bulk.Driver, "runs_per_block", 8)


def tiny_spec():
    spec = harness.load_cell(CELL)
    config = copy.deepcopy(spec["config"])
    config["jobs"] = [dict(config["jobs"][0], name=f"job{i}", n_nodes=n)
                      for i, n in enumerate(NODES)]
    config["advisor"]["grid"].update(
        young_factors={"geomspace": [0.5, 2.0, 2]}, mu1=[6.0])
    return dict(spec, config=config, limits={"energy_rel": CPU_SOUND})


def run(seed, trace=False):
    return harness.run_cell(tiny_spec(), seed, 0.3, trace,
                            time.perf_counter(), **TINY)


@pytest.fixture(scope="module")
def runs():
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(fleet_bulk.Driver, "workers", 2)
        mp.setattr(fleet_bulk.Driver, "runs_per_block", 8)
        return run(2 ** 31 + 61), run(2 ** 31 + 67, trace=True)


def test_a_run_reads_every_end_to_end_metric_and_is_correct(runs):
    spec, (out, _) = tiny_spec(), runs
    assert set(out["metrics"]) == {m["name"] for m in spec["end_to_end"]}
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert out["attempted"] > 0 and out["attempted"] % len(NODES) == 0
    assert out["failed"] == 0
    assert out["correct"] is True
    assert set(out["checks"]) == set(spec["limits"]) == {"energy_rel"}


def test_a_traced_run_reads_the_per_layer_metrics(runs):
    spec, (_, out) = tiny_spec(), runs
    want = {m["name"] for m in spec["per_layer"]}
    assert "padded_lane_pct.fleet" in want
    # the roofline needs a chip's peaks, which a CPU run has not
    assert set(out["metrics"]) == {n for n in want if "roofline" not in n}
    assert 0 < out["device"]["busy_s"] < out["device"]["window_s"]
    # one padded 4-node lane of 3 survivors in 4 x 3 + 15 dispatched
    assert out["metrics"]["padded_lane_pct.fleet"]["value"] == (
        pytest.approx(100 * 3 / 27, rel=1e-12))
    assert out["correct"] is True


def test_float32_reference_fails_the_check():
    spec = tiny_spec()
    d = harness.make_driver(spec, 2 ** 31 + 71, **TINY)
    d.warmup()
    for i in range(2):
        d.call(i)
    got = d.check(2, spec["traffic"]["checks"], control=True)
    assert any(got[k] > lim for k, lim in spec["limits"].items()), got


def _out_of_order(monkeypatch):
    from repro.fleet import advisor
    real = advisor.scatter
    monkeypatch.setattr(advisor, "scatter",
                        lambda groups, res: real(groups, res)[::-1])


def _padded_lane(monkeypatch):
    """Each bucket answers its first request with its dispatch's last lane:
    a padded lane where the bucket is padded."""
    real = fleet.FleetAdvisor._evaluate

    def evaluate(self, out, specs, makespans, n_real):
        out = {k: np.concatenate([v[-1:], v[1:]]) for k, v in out.items()}
        return real(self, out, specs, makespans, n_real)
    monkeypatch.setattr(fleet.FleetAdvisor, "_evaluate", evaluate)


def _previous_flush(monkeypatch):
    real = fleet.FleetAdvisor.flush
    last = []

    def flush(self):
        answers = real(self)
        out = last[-1] if last else answers
        last.append(answers)
        return out
    monkeypatch.setattr(fleet.FleetAdvisor, "flush", flush)


@pytest.mark.parametrize("fault", [_out_of_order, _padded_lane,
                                   _previous_flush])
def test_a_broken_advisor_is_not_correct(monkeypatch, fault):
    fault(monkeypatch)
    assert run(2 ** 31 + 73)["correct"] is False


def test_the_cell_is_the_whole_machine_at_the_advisors_defaults():
    """The configuration's jobs fill the 1,024 nodes; its grid is the
    advisor's ``default_policy_table`` at the grid's anchor, its runs the
    advisor's and its epochs twice the advisor's (so that the largest job
    is not truncated); each flush answers 348,880,896 decisions."""
    spec = harness.load_cell(CELL)
    config = spec["config"]
    nodes = sorted(j["n_nodes"] for j in config["jobs"])
    assert nodes == [16] * 4 + [32] * 2 + [128] * 3 + [512]
    assert sum(nodes) == config["n_nodes"] == 1024
    grid = config["advisor"]["grid"]
    job = config["jobs"][0]
    anchor = fleet.ClusterProfile(n_nodes=64, ckpt_duration=job[
        "ckpt_duration"]).scenario()
    table = optimize.default_policy_table(anchor, 365 * DAY_S / 64)
    assert grid["anchor_mtbf_days"] * DAY_S == 365 * DAY_S / 64
    cols = ref_policy.policy_columns(grid)
    for ours, theirs in (("interval", "ckpt_interval"), ("mu1", "mu1"),
                         ("mu2", "mu2"), ("wait_mode", "wait_mode"),
                         ("move_frac", "move_ahead_frac")):
        np.testing.assert_array_equal(cols[ours], getattr(table, theirs))
    assert len(table) == 42
    adv = config["advisor"]
    default = fleet.FleetAdvisor()
    assert (adv["n_runs"], adv["max_failures"]) == (
        default.n_runs, 2 * default.max_failures)
    d = harness.make_driver(spec, 2 ** 31 + 79)
    assert d.work(1)["decisions"] == 348_880_896
    # every job's Young interval lies inside the grid
    for j in config["jobs"]:
        young = np.sqrt(2 * j["ckpt_duration"] * j["mtbf_days"] * DAY_S
                        / j["n_nodes"])
        assert cols["interval"].min() < young < cols["interval"].max()
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell, = [w for w in bench["workloads"] if w["name"] == CELL]
    assert cell["chips"] == 1


def test_the_reference_lane_is_the_profiles_policy_stack():
    """What the reference composes for a (job, policy) is what the program
    stacks for that job's profile under that policy: the snapshot, the
    knobs, the machine and the equal-work makespans."""
    spec = tiny_spec()
    d = harness.make_driver(spec, 2 ** 31 + 83, **TINY)
    job, profile = spec["config"]["jobs"][1], d.profiles[1]
    work = 2.5 * DAY_S
    lane = ref_policy.job_lanes(job, d.machines[job["machine"]],
                                spec["config"]["snapshot"], d.cols, work)
    stacked = optimize.policy_inputs(profile.scenario(), d.table)
    pairs = dict(exec0=stacked.exec_rem0, period=stacked.period,
                 age0=stacked.age0, reexec0=stacked.reexec0,
                 t_down=stacked.t_down, t_restart=stacked.t_restart,
                 interval=stacked.interval, dur=stacked.dur,
                 move_ahead=stacked.move_ahead, move_frac=stacked.move_frac,
                 wait_mode=stacked.wait_mode, mu1=stacked.mu1,
                 mu2=stacked.mu2, p_idle_wait=stacked.p_idle_wait,
                 beta=stacked.ladder.beta, gamma=stacked.ladder.gamma,
                 p_comp=stacked.ladder.p_comp, p_ckpt=stacked.ladder.p_ckpt,
                 t_go_sleep=stacked.sleep.t_go_sleep,
                 t_wakeup=stacked.sleep.t_wakeup,
                 p_go_sleep=stacked.sleep.p_go_sleep,
                 p_wakeup=stacked.sleep.p_wakeup,
                 p_sleep=stacked.sleep.p_sleep)
    assert set(pairs) | {"makespan"} == set(lane)
    for k, v in pairs.items():
        np.testing.assert_array_equal(lane[k], np.asarray(v), err_msg=k)
    np.testing.assert_array_equal(
        lane["makespan"], optimize.wall_makespan(
            work, d.table.ckpt_interval, profile.ckpt_duration))


def test_the_largest_job_is_not_truncated_at_the_cells_epochs():
    """The 512-node job at the longest remaining work and the grid's
    shortest interval (its longest makespan): at the configuration's 64
    epochs no run exhausts its epochs before the makespan; at the
    advisor's default 32 most do."""
    spec = harness.load_cell(CELL)
    d = harness.make_driver(spec, 2 ** 31 + 89)
    t, p = d.table, int(np.argmin(d.table.ckpt_interval))
    shortest = optimize.PolicyTable(
        ckpt_interval=t.ckpt_interval[p:p + 1], mu1=t.mu1[p:p + 1],
        mu2=t.mu2[p:p + 1], wait_mode=t.wait_mode[p:p + 1],
        move_ahead_frac=t.move_ahead_frac[p:p + 1])
    job, = [q for q in d.profiles if q.n_nodes == 512]
    job = dataclasses.replace(
        job, work_s=spec["traffic"]["work_days"]["uniform"][1] * DAY_S)
    rates = {}
    for epochs in (spec["config"]["advisor"]["max_failures"], 32):
        advisor = fleet.FleetAdvisor(shortest, key=d.key, n_runs=d.n_runs,
                                     max_failures=epochs)
        answer, = advisor.advise([job])
        rates[epochs] = float(answer.optimum.grid.truncated_rate[0])
    assert rates[64] == 0.0 and rates[32] > 0.5, rates
