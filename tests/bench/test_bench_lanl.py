"""The whole-machine cell (``mc.lanl1024.weibull``) on the CPU at a tiny
size: the cell's configuration cut to 64 nodes (63 survivors, phased by the
configuration's own rule), 64 runs of 12 epochs.  A run reads every metric
and is correct against the reference composed in blocks of runs
(``bench/drivers/mc_cluster.py``); the float32 control and a study over half
the runs are not."""
import copy
import time

import jax
import pytest

from bench import harness
from bench.drivers import mc_cluster, mc_study
from repro.core import sweep

CELL = "mc.lanl1024.weibull"
TINY = dict(n_runs=64, max_failures=12)
NODES = 64
# a sound run on the CPU reads ~1e-8 here (float32 rounding of Algorithm 1
# differs between XLA's CPU code and numpy); the float32 control ~1e-5
CPU_SOUND = 1e-6


@pytest.fixture(autouse=True)
def few_workers(monkeypatch):
    monkeypatch.setattr(mc_cluster.Driver, "workers", 2)
    monkeypatch.setattr(mc_cluster.Driver, "runs_per_block", 16)


def tiny_spec():
    spec = harness.load_cell(CELL)
    config = copy.deepcopy(spec["config"])
    survivors = config["scenarios"][0]["survivors"]
    del survivors[NODES - 1:]
    for i, sv in enumerate(survivors):
        sv["exec_to_rendezvous"] = 3600.0 * (i + 1) / NODES
    return dict(spec, config=config)


def run(seed, trace=False):
    return harness.run_cell(tiny_spec(), seed, 0.3, trace,
                            time.perf_counter(), **TINY)


def test_a_run_reads_every_end_to_end_metric_and_is_correct():
    spec = tiny_spec()
    out = run(2 ** 31 + 41)
    assert set(out["metrics"]) == {m["name"] for m in spec["end_to_end"]}
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["correct"] is True
    assert all(c["value"] < CPU_SOUND for c in out["checks"].values())


def test_a_traced_run_reads_the_per_layer_metrics():
    spec = tiny_spec()
    out = run(2 ** 31 + 43, trace=True)
    want = {m["name"] for m in spec["per_layer"]}
    # the roofline needs a chip's peaks, which a CPU run has not
    assert set(out["metrics"]) == {n for n in want if "roofline" not in n}
    assert 0 < out["device"]["busy_s"] < out["device"]["window_s"]


def test_the_blocked_reference_is_the_whole_one():
    d = harness.make_driver(tiny_spec(), 2 ** 31 + 47, **TINY)
    key = jax.random.PRNGKey(3)
    assert d.reference(key) == mc_study.Driver.reference(d, key)


def test_float32_reference_fails_the_check():
    spec = tiny_spec()
    d = harness.make_driver(spec, 2 ** 31 + 53, **TINY)
    for i in range(2):
        d.call(i)
    got = d.check(2, spec["traffic"]["checks"], control=True)
    assert any(got[k] > lim for k, lim in spec["limits"].items()), got


def test_a_study_over_half_the_runs_is_not_correct(monkeypatch):
    real = sweep.renewal_monte_carlo_scenarios

    def half(*a, n_runs, **kw):
        return real(*a, n_runs=n_runs // 2, **kw)
    monkeypatch.setattr(sweep, "renewal_monte_carlo_scenarios", half)
    assert run(2 ** 31 + 59)["correct"] is False
