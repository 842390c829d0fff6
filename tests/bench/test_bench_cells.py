"""Each cell's driver through a whole run (``harness.run_cell``) on the CPU
at a tiny size: the result line's keys, the metrics its cell reports, and
a check that reads far below the control."""
import time

import pytest

from bench import harness

TINY = {"mc.table4.exp": dict(n_runs=64, max_failures=12),
        "mc.table4.rack": dict(n_runs=64, max_failures=12)}
# a sound run on the CPU reads ~1e-7 here (float32 rounding of Algorithm 1
# differs between XLA's CPU code and numpy); the float32 control ~1e-5
CPU_SOUND = 1e-6


def run(name, trace=False, seconds=0.3):
    spec = harness.load_cell(name)
    return spec, harness.run_cell(spec, 2 ** 31 + 17, seconds, trace,
                                  time.perf_counter(), **TINY[name])


@pytest.mark.parametrize("name", sorted(TINY))
def test_a_run_reads_every_end_to_end_metric(name):
    spec, out = run(name)
    assert list(out) == ["correct", "attempted", "failed", "metrics",
                         "device", "checks"]
    assert set(out["metrics"]) == {m["name"] for m in spec["end_to_end"]}
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["device"]["platform"] == "cpu"
    assert set(out["checks"]) == set(spec["limits"])
    assert all(c["value"] < CPU_SOUND for c in out["checks"].values())


@pytest.mark.parametrize("name", sorted(TINY))
def test_a_traced_run_reads_the_per_layer_metrics(name):
    spec, out = run(name, trace=True)
    want = {m["name"] for m in spec["per_layer"]}
    # the roofline needs a chip's peaks, which a CPU run has not
    want = {n for n in want if "roofline" not in n}
    assert set(out["metrics"]) == want
    assert 0 < out["device"]["busy_s"] < out["device"]["window_s"]
    assert out["breakdown"]["device_ops"] and out["breakdown"]["idle_gaps"]
    assert len(out["breakdown"]["idle_gaps"]) <= 10
