"""The fleet advisory driver: requests in, tuned policies out, one fused
program per shape bucket.

Serving protocol (the ``launch/serve`` recipe applied to policy tuning):

  1. **accumulate** — ``submit`` queues ``ClusterProfile`` requests;
  2. **group** — ``flush`` partitions pending requests by their static
     dispatch signature (survivor count, process family — the shapes and
     pytree structure the compiled program is specialized to);
  3. **pad** — each group is padded up to a batch bucket by repeating its
     last request (inert: vmap cluster lanes are independent, so padded
     lanes cannot perturb real answers — property-tested);
  4. **dispatch** — one fused ``(C, P)`` program per bucket, compiled at
     most once per bucket key (``DispatchCache``); a bucket whose
     clusters' survivor folds together outgrow the budget goes out in as
     many dispatches as keep each within it (``sweep._fleet_fold``);
  5. **scatter** — per-cluster optima return in original submit order.

Every answer is bit-identical (CRN, the advisor's fixed key) to a
standalone ``optimize_policy`` call for that cluster alone — batching is
a throughput decision, never an accuracy one (tests/test_fleet.py,
docs/fleet.md).

A flush names its stages in a profiler trace (``fleet.flush``, then per
dispatch ``fleet.stage``, ``fleet.dispatch``, ``fleet.fetch`` and
``fleet.evaluate``), and ``stats()`` counts what was served
(docs/fleet.md, "Tracing an advisory flush").

``shard=True`` additionally splits the cluster axis across the host's
JAX devices with ``jax.pmap`` — pair with
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` (set before jax
import) to fan one CPU host out over N device lanes
(examples/fleet_advisor.py).  The PRNG key broadcasts to every device, so
per-cluster rows stay bit-identical to the unsharded path.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import failures, optimize, sweep
from repro.fleet.cache import CacheStats, DispatchCache
from repro.fleet.profiles import ClusterProfile
from repro.launch.batching import (
    DEFAULT_BUCKETS,
    bucket_size,
    group_indices,
    pad_rows,
    scatter,
)

__all__ = ["Advisory", "AdvisorStats", "FleetAdvisor"]


@dataclasses.dataclass(frozen=True)
class Advisory:
    """One answered request: the profile it was asked for and its tuned
    policy.  ``best``/``knee`` are policy dicts (knobs + objectives);
    ``optimum`` keeps the full per-cluster grid for auditing."""

    request_id: int
    profile: ClusterProfile
    optimum: optimize.PolicyOptimum

    @property
    def best(self) -> dict:
        return self.optimum.best

    @property
    def knee(self) -> dict:
        return self.optimum.knee


@dataclasses.dataclass(frozen=True)
class AdvisorStats:
    """Counters snapshot of what an advisor has served: requests answered,
    cluster lanes dispatched (padding included) and of them the padded
    ones, and the (lane, policy, run, epoch, survivor) decision points
    dispatched and, in real lanes, answered."""

    requests: int = 0
    lanes: int = 0
    padded_lanes: int = 0
    decisions_dispatched: int = 0
    decisions_answered: int = 0


class FleetAdvisor:
    """Batched policy-advisory service over one shared policy grid.

    ``table`` is the grid every request is scored on (default: the
    standard grid of the default ``ClusterProfile`` at the engine's 14-day
    MTBF anchor); ``key`` fixes the CRN draws, making every advisory
    reproducible and bit-comparable to a standalone ``optimize_policy``
    call.  ``max_cached_programs`` bounds resident compiled programs
    (LRU); ``buckets`` quantizes batch sizes.
    """

    def __init__(self, table: Optional[optimize.PolicyTable] = None, *,
                 key: Optional[jax.Array] = None, n_runs: int = 128,
                 max_failures: int = 32,
                 buckets: Sequence[int] = DEFAULT_BUCKETS,
                 max_cached_programs: int = 8, shard: bool = False):
        if table is None:
            table = optimize.default_policy_table(
                ClusterProfile().scenario(), 14 * 24 * 3600.0)
        self.table = table
        self.key = jax.random.PRNGKey(0) if key is None else key
        self.n_runs = int(n_runs)
        self.max_failures = int(max_failures)
        self.buckets = tuple(buckets)
        self.shard = bool(shard)
        self._pending: List[ClusterProfile] = []
        self._counts = dict.fromkeys(
            (f.name for f in dataclasses.fields(AdvisorStats)), 0)

        def fleet_core(inp, key, makespan, proc):
            return sweep._renewal_fleet_mc_core(
                inp, key, makespan, proc, self.n_runs, self.max_failures)

        self._cache = DispatchCache(fleet_core,
                                    max_entries=max_cached_programs)
        # sharded twin: same core per device shard, cluster axis split
        # over pmap lanes, key broadcast (in_axes=None) so every lane
        # draws exactly what the unsharded program draws for its rows
        self._pmap_cache = DispatchCache(
            fleet_core,
            max_entries=max_cached_programs,
            compile=lambda f: jax.pmap(f, in_axes=(0, None, 0, 0)))

    # -- serving surface ----------------------------------------------------

    def submit(self, profile: ClusterProfile) -> int:
        """Queue one request; returns its id (position in the next flush)."""
        self._pending.append(profile)
        return len(self._pending) - 1

    def flush(self) -> List[Advisory]:
        """Answer every pending request: group -> pad -> dispatch ->
        scatter.  Answers come back in submit order; the queue empties."""
        profiles, self._pending = self._pending, []
        if not profiles:
            return []
        groups = group_indices([p.bucket_key() for p in profiles])
        with jax.profiler.TraceAnnotation(
                "fleet.flush", requests=len(profiles), buckets=len(groups)):
            results = {
                bkey: self._dispatch_bucket([profiles[i] for i in idx])
                for bkey, idx in groups.items()
            }
            optima = scatter(groups, results)
        self._counts["requests"] += len(profiles)
        return [Advisory(request_id=i, profile=p, optimum=o)
                for i, (p, o) in enumerate(zip(profiles, optima))]

    def advise(self, profiles: Sequence[ClusterProfile]) -> List[Advisory]:
        """submit + flush in one call (the batch-mode entry point)."""
        for p in profiles:
            self.submit(p)
        return self.flush()

    def stats(self) -> AdvisorStats:
        """What the advisor has served so far (``AdvisorStats``)."""
        return AdvisorStats(**self._counts)

    def cache_stats(self) -> CacheStats:
        """Aggregated compiled-program cache counters (jit + pmap paths)."""
        a, b = self._cache.stats(), self._pmap_cache.stats()
        return CacheStats(hits=a.hits + b.hits, misses=a.misses + b.misses,
                          evictions=a.evictions + b.evictions,
                          traces=a.traces + b.traces,
                          entries=a.entries + b.entries)

    # -- one bucket ---------------------------------------------------------

    def _dispatch_bucket(self, profiles: List[ClusterProfile]) -> list:
        """One bucket's answers, in as many dispatches as keep each one's
        survivor fold within the budget (``sweep._fleet_fold``): each
        cluster folds as it would alone, so splitting moves no answer."""
        n_dev = jax.local_device_count() if self.shard else 1
        n_surv = profiles[0].n_nodes - 1
        tile, per_dev = sweep._fleet_fold(len(self.table), self.n_runs,
                                          self.max_failures, n_surv)
        cap = per_dev * n_dev
        starts = range(0, len(profiles), cap)
        return [answer for lo in starts for answer in self._dispatch(
            profiles[lo:lo + cap], cap, tile, len(starts), n_dev)]

    def _dispatch(self, profiles: List[ClusterProfile], cap: int,
                  tile: Optional[int], dispatches: int, n_dev: int) -> list:
        n_real = len(profiles)
        with jax.profiler.TraceAnnotation("fleet.stage"):
            # the dispatch's cap stands in for every larger batch bucket
            padded = pad_rows(profiles, bucket_size(
                n_real, [b for b in self.buckets if b <= cap],
                multiple_of=n_dev))
            specs = [p.spec() for p in padded]
            procs = [s.process for s in specs]
            stacked_proc = failures.stack_processes(procs)
            with jax.enable_x64():
                stacked = optimize.fleet_policy_inputs(
                    [s.cfg for s in specs], self.table)
            makespans = np.stack([
                optimize.wall_makespan(s.work_s, self.table.ckpt_interval,
                                       s.cfg.ckpt_duration)
                for s in specs])                               # (C, P)
        c, n_policies = makespans.shape
        n_surv = len(specs[0].cfg.survivors)
        bkey = (c, n_surv, padded[0].family, n_policies, self.n_runs,
                self.max_failures)
        tiles = 1 if tile is None else self.max_failures * -(-n_surv // tile)
        with jax.profiler.TraceAnnotation(
                "fleet.dispatch", clusters=n_real, padded=c - n_real,
                survivors=n_surv, survivor_tile=tile or n_surv, tiles=tiles,
                dispatches=dispatches), jax.enable_x64():
            if self.shard:
                fn = self._pmap_cache.get(bkey + ("pmap", n_dev))
                shard = lambda a: jnp.asarray(a).reshape(
                    (n_dev, c // n_dev) + np.shape(a)[1:])
                out = fn(jax.tree.map(shard, stacked), self.key,
                         shard(makespans), jax.tree.map(shard, stacked_proc))
                out = jax.tree.map(
                    lambda a: a.reshape((c,) + a.shape[2:]), out)
            else:
                out = self._cache.get(bkey)(
                    stacked, self.key, jnp.asarray(makespans), stacked_proc)
        n_bytes = sum(a.nbytes for a in out.values())
        with jax.profiler.TraceAnnotation("fleet.fetch", bytes=n_bytes):
            out = jax.device_get(out)
        # host numpy only: the device is idle here, under this span alone
        with jax.profiler.TraceAnnotation("fleet.evaluate"):
            optima = self._evaluate(out, specs, makespans, n_real)
        per_lane = n_policies * self.n_runs * self.max_failures * n_surv
        counts = self._counts
        counts["lanes"] += c
        counts["padded_lanes"] += c - n_real
        counts["decisions_dispatched"] += c * per_lane
        counts["decisions_answered"] += n_real * per_lane
        return optima

    def _evaluate(self, out: dict, specs: list, makespans: np.ndarray,
                  n_real: int) -> list:
        """Each real cluster's grid and optimum from the fetched statistics
        (``out``: host arrays with a leading cluster axis), numpy only."""
        optima = []
        for ci in range(n_real):
            stats_c = sweep._wrap_device_stats(
                {k: v[ci] for k, v in out.items()})
            proc_c = specs[ci].process
            res = optimize._policy_eval_from_stats(
                self.table, specs[ci].cfg.name, stats_c, makespans[ci],
                specs[ci].work_s, float(np.mean(proc_c.mean_s())),
                proc_c.label(), self.n_runs, self.max_failures)
            optima.append(optimize._optimum_from_grid(res))
        return optima
