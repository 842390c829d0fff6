"""Fleet-scale policy advisory: batched multi-cluster tuning in one
dispatch.

The serving layer over the cluster axis of ``core.optimize`` /
``core.sweep``: describe each cluster with a ``ClusterProfile``, hand a
batch of them to a ``FleetAdvisor``, and get back per-cluster tuned
policies (grid optimum, Pareto knee) — grouped into shape buckets, padded
with inert lanes, answered by one fused compiled program per bucket, and
bit-identical to standalone per-cluster ``optimize_policy`` calls at the
same key.  See docs/fleet.md.
"""
from repro.fleet.advisor import Advisory, AdvisorStats, FleetAdvisor
from repro.fleet.cache import CacheStats, DispatchCache
from repro.fleet.profiles import ClusterProfile, cluster_scenario, synthetic_fleet

__all__ = [
    "Advisory",
    "AdvisorStats",
    "FleetAdvisor",
    "CacheStats",
    "DispatchCache",
    "ClusterProfile",
    "cluster_scenario",
    "synthetic_fleet",
]
