"""Fleet advisory demo: batched per-cluster policy tuning in one fused
dispatch per shape bucket, with a pmap-sharded serving path.

A small heterogeneous fleet (mixed node counts and failure families, so
the advisor exercises several shape buckets) is advised three ways —
batched, per-cluster standalone, and sharded over the local devices —
and the answers are asserted bit-identical across all three (the CRN
contract, docs/fleet.md).

Run:  PYTHONPATH=src python examples/fleet_advisor.py
"""
import os

# the sharded path fans the cluster axis over every local device.  On a
# CPU host the flag below makes two; XLA reads it at backend init, so it is
# set before anything imports jax.  A TPU backend ignores it and shards over
# its chips (one chip: a one-lane pmap).
_FLAG = "--xla_force_host_platform_device_count=2"
if _FLAG not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "") + " " + _FLAG).strip()

import jax                                                    # noqa: E402
import numpy as np                                            # noqa: E402

from repro import fleet                                       # noqa: E402
from repro.core import optimize                               # noqa: E402


def main():
    key = jax.random.PRNGKey(7)
    profiles = fleet.synthetic_fleet(6, seed=3, node_buckets=(4, 8),
                                     weibull_frac=0.5)
    kw = dict(key=key, n_runs=16, max_failures=8)

    advisor = fleet.FleetAdvisor(**kw)
    advisories = advisor.advise(profiles)

    devices = f"{jax.local_device_count()} {jax.default_backend()} device(s)"
    print(f"{len(advisories)} advisories over "
          f"{len({p.bucket_key() for p in profiles})} shape buckets:")
    for a in advisories:
        p = a.profile
        print(f"  {p.name}: n={p.n_nodes} {p.family:<11} "
              f"mtbf={p.mtbf_s / 86400:.1f}d -> "
              f"T={a.best['ckpt_interval']:.0f}s "
              f"knee_T={a.knee['ckpt_interval']:.0f}s")

    # every batched answer is bit-identical to tuning that cluster alone
    for a in advisories[:2]:
        p = a.profile
        solo = optimize.optimize_policy(
            p.scenario(), key, table=advisor.table,
            process=p.failure_process(), work_s=p.work_s,
            n_runs=16, max_failures=8)
        assert a.best == solo.best, p.name
        assert a.knee == solo.knee, p.name
    print("batched == standalone optimize_policy (bit-identical, CRN)")

    # the pmap-sharded path answers the same fleet identically
    sharded = fleet.FleetAdvisor(shard=True, **kw).advise(profiles)
    for a, b in zip(advisories, sharded):
        assert a.best == b.best and a.knee == b.knee, a.profile.name
    print(f"sharded over {devices} == unsharded")

    # a repeat fleet is pure cache hits: no new trace, no new program
    before = advisor.cache_stats()
    advisor.advise(profiles)
    after = advisor.cache_stats()
    assert after.traces == before.traces, "repeat fleet retraced"
    assert after.hits > before.hits
    print(f"dispatch cache: {after.hits} hits / {after.misses} misses / "
          f"{after.traces} traces / {after.entries} resident programs")

    spread = np.array([a.best["ckpt_interval"] for a in advisories])
    print(f"advised intervals span {spread.min():.0f}s - {spread.max():.0f}s "
          f"({len(np.unique(spread))} distinct)")


if __name__ == "__main__":
    main()
