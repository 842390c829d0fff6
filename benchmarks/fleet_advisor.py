"""Fleet-advisory benchmark: advisories/s for the batched cluster axis.

The fleet advisor's value proposition is that a whole fleet of
heterogeneous cluster profiles — per-cluster MTBF, power class,
rendezvous period, remaining work — gets its policy grids evaluated in
ONE fused (clusters x policies) dispatch instead of one
``optimize_policy`` program per cluster.  Both sides are measured on the
same task:

  * ``batched``  — ``FleetAdvisor.advise`` over a C-cluster single-bucket
    fleet: advisories/s through one compiled program (steady state: the
    dispatch cache is warm, so repeat fleets pay zero retraces);
  * ``loop``     — the same advisory work as standalone per-cluster
    ``optimize_policy`` calls (identical answers, by the fleet CRN
    contract), timed on a subsample and reported per advisory — the
    dispatch-per-cluster baseline the advisor replaces;
  * ``speedup``  — the advisories/s ratio (gated for presence, not
    magnitude — the optimizer-ratio precedent);
  * ``sharded``  — the same batched fleet with the cluster axis pmap-split
    over every local device: two forced host devices on a CPU host
    (``--xla_force_host_platform_device_count=2``, SNIPPETS 2/3), the
    chips on a TPU host (the flag does not apply there).  The row name
    carries the device count it actually sharded over (``_d<n>``);
  * ``cache``    — the dispatch-cache counters after the run (hits /
    misses / traces), recording that steady-state serving retraced
    nothing.

``benchmarks/check_regression.py`` gates ``batched`` and ``speedup`` row
presence on every run and absolute advisories/s on like hardware against
the committed baseline (``benchmarks/artifacts/BENCH_fleet_advisor.json``).

Run:  PYTHONPATH=src python -m benchmarks.fleet_advisor [--json PATH]
"""
from __future__ import annotations

import os
import statistics
import sys
import time

# on a CPU host the sharded row needs forced host devices, and XLA reads
# the flag at backend init — set it before anything imports jax (a TPU
# backend ignores it and shards over its chips)
_FLAG = "--xla_force_host_platform_device_count=2"
if _FLAG not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") + " " + _FLAG).strip()

import jax                                                    # noqa: E402
import numpy as np                                            # noqa: E402

from repro import fleet                                       # noqa: E402
from repro.core import energy_model as em                     # noqa: E402
from repro.core import optimize                               # noqa: E402
from benchmarks._record import emit, meta_row, parse_json_arg, row  # noqa: E402

N_CLUSTERS = 256        # one bucket: the acceptance-bar fleet size
N_RUNS = 32
MAX_FAILURES = 16
REPS = 3
LOOP_N = 8              # standalone-loop subsample (extrapolated per advisory)
ENGINE = "scan-x64"


def benchmark_fleet():
    """C heterogeneous exponential clusters in ONE shape bucket: node
    count fixed (the bucket key), MTBF / power class / period / work all
    per-cluster."""
    return fleet.synthetic_fleet(N_CLUSTERS, seed=0, node_buckets=(4,),
                                 weibull_frac=0.0)


def benchmark_table() -> optimize.PolicyTable:
    return optimize.policy_grid(
        ckpt_interval=np.geomspace(2400.0, 19200.0, 7),
        mu1=[6.0],
        wait_mode=[em.WaitMode.ACTIVE, em.WaitMode.IDLE],
    )


def throughput() -> dict:
    profiles = benchmark_fleet()
    table = benchmark_table()
    key = jax.random.PRNGKey(1)
    kw = dict(key=key, n_runs=N_RUNS, max_failures=MAX_FAILURES)

    advisor = fleet.FleetAdvisor(table, **kw)
    sharded = fleet.FleetAdvisor(table, shard=True, **kw)

    def batched():
        return advisor.advise(profiles)

    def sharded_batched():
        return sharded.advise(profiles)

    def loop(sample):
        out = [optimize.optimize_policy(
            p.scenario(), key, table=table, process=p.failure_process(),
            work_s=p.work_s, n_runs=N_RUNS, max_failures=MAX_FAILURES)
            for p in sample]
        return out

    res = batched()             # warm: compile + input caches
    sharded_batched()
    loop(profiles[:2])

    t_batched, t_sharded = [], []
    for _ in range(REPS):
        t0 = time.perf_counter(); batched()
        t_batched.append(time.perf_counter() - t0)
        t0 = time.perf_counter(); sharded_batched()
        t_sharded.append(time.perf_counter() - t0)
    t0 = time.perf_counter()
    loop(profiles[:LOOP_N])
    t_loop = (time.perf_counter() - t0) / LOOP_N        # seconds/advisory
    t_batched = statistics.median(t_batched)
    t_sharded = statistics.median(t_sharded)

    n_policies = len(table)
    return {
        "result": res,
        "n_policies": n_policies,
        "batched_s": t_batched,
        "sharded_s": t_sharded,
        "loop_s_per_advisory": t_loop,
        "batched_per_s": N_CLUSTERS / t_batched,
        "sharded_per_s": N_CLUSTERS / t_sharded,
        "loop_per_s": 1.0 / t_loop,
        "speedup": (N_CLUSTERS / t_batched) * t_loop,
        "cache": advisor.cache_stats(),
        "n_devices": jax.local_device_count(),
    }


def run() -> list:
    thr = throughput()
    shape = f"{N_CLUSTERS}x{thr['n_policies']}x{N_RUNS}"
    cache = thr["cache"]
    rows = [meta_row(), row(
        f"fleet_advisor/batched_{shape}",
        us_per_call=thr["batched_s"] * 1e6,
        decisions_per_s=thr["batched_per_s"],
        derived=f"{thr['batched_per_s']:.1f}advisories/s_one_dispatch",
        engine=ENGINE,
    ), row(
        f"fleet_advisor/loop_{shape}",
        us_per_call=thr["loop_s_per_advisory"] * 1e6,
        decisions_per_s=thr["loop_per_s"],
        derived=f"{thr['loop_per_s']:.1f}advisories/s_per_cluster_dispatch",
        engine=ENGINE,
    ), row(
        "fleet_advisor/speedup",
        derived=f"{thr['speedup']:.1f}x_batched_vs_per_cluster_loop",
    ), row(
        f"fleet_advisor/sharded_{shape}_d{thr['n_devices']}",
        us_per_call=thr["sharded_s"] * 1e6,
        decisions_per_s=thr["sharded_per_s"],
        derived=(f"{thr['sharded_per_s']:.1f}advisories/s"
                 f"_pmap{thr['n_devices']}dev"),
        engine=ENGINE,
    ), row(
        "fleet_advisor/cache",
        derived=(f"hits={cache.hits}_misses={cache.misses}"
                 f"_traces={cache.traces}_entries={cache.entries}"),
    )]

    # what the advisor answered, not just how fast: the fleet-wide spread
    # of tuned intervals — the heterogeneity the cluster axis exists for
    best_t = np.array([a.best["ckpt_interval"] for a in thr["result"]])
    rows.append(row(
        "fleet_advisor/advised_intervals",
        derived=(f"min_T={best_t.min():.0f}s_med_T={np.median(best_t):.0f}s"
                 f"_max_T={best_t.max():.0f}s_distinct={len(np.unique(best_t))}"),
    ))
    return rows


def main(argv=None):
    argv = sys.argv[1:] if argv is None else argv
    argv, json_path = parse_json_arg(
        argv, "usage: python -m benchmarks.fleet_advisor [--json PATH]")
    emit(run(), json_path)


if __name__ == "__main__":
    main()
