"""Closed loop of renewal Monte-Carlo studies
(``sweep.renewal_monte_carlo_scenarios``): each call is one what-if study
over every scenario of the configuration, at the configuration's run
count and epoch count, with fresh histories (its own PRNG key)."""
from __future__ import annotations

import math

import numpy as np

from bench import lower
from bench.reference import renewal as ref

DAY_S = 24 * 3600.0
MAX_KEYS = 1 << 14          # distinct study keys; the loop wraps past them
_ENERGY = ("mean_energy_ref_j", "mean_energy_int_j")
_SAVING = ("mean_saving_j", "p5_saving_j", "p95_saving_j")
_COUNTS = ("mean_failures", "truncated_rate")
_RATES = ("sleep_occupancy", "min_freq_rate", "comp_change_rate",
          "infeasible_rate")


class Driver:
    span = "study"

    def __init__(self, config: dict, traffic: dict, seed: int, **sizes):
        import jax
        import jax.numpy as jnp
        self.config, self.traffic, self.seed = config, traffic, seed
        self.cfgs = lower.scenarios(config)
        self.n_runs = sizes.get("n_runs", config["n_runs"])
        self.max_failures = sizes.get("max_failures", config["max_failures"])
        self.makespan_s = config["makespan_days"] * DAY_S
        self.n_nodes = len(config["scenarios"][0]["survivors"]) + 1
        self.process = lower.failure_process(
            traffic["failure"], config["mtbf_days"] * DAY_S)
        self.topology = (lower.rack_topology(traffic["topology"], self.n_nodes)
                         if "topology" in traffic else None)
        # a key holds 32 bits of its seed; the rest are folded in
        base = jax.random.fold_in(jax.random.PRNGKey(seed % 2 ** 32),
                                  seed // 2 ** 32 % 2 ** 32)
        self.keys = np.asarray(jax.vmap(lambda i: jax.random.fold_in(base, i))(
            jnp.arange(MAX_KEYS + 1)))
        self.outputs = []

    def _study(self, key):
        from repro.core import sweep
        return sweep.renewal_monte_carlo_scenarios(
            self.cfgs, key, n_runs=self.n_runs, makespan_s=self.makespan_s,
            max_failures=self.max_failures, process=self.process,
            topology=self.topology)

    def warmup(self) -> None:
        self._study(self.keys[MAX_KEYS])

    def call(self, i: int) -> tuple:
        """One study; returns (answers attempted, answers failed): a study
        with a non-finite number fails."""
        out = self._study(self.keys[i % MAX_KEYS])
        self.outputs.append(out)
        ok = all(math.isfinite(getattr(s, f)) for s in out.values()
                 for f in _ENERGY + _SAVING)
        return 1, 0 if ok else 1

    def work(self, n_calls: int) -> dict:
        """What ``n_calls`` studies dispatch (``bench.work`` units)."""
        lane_runs = n_calls * len(self.cfgs) * self.n_runs
        family = ("rack" if self.topology is not None
                  else self.traffic["failure"]["family"])
        return dict(
            decisions=lane_runs * self.max_failures * (self.n_nodes - 1),
            lane_epochs=lane_runs * self.max_failures, lane_runs=lane_runs,
            node_epochs={family: n_calls * self.n_runs * self.max_failures
                         * self.n_nodes})

    def release(self) -> None:
        """The studies' answers are host summaries: nothing to free."""

    # -- the check against the plain reference --------------------------

    def reference(self, key, dtype=np.float64) -> dict:
        """name -> the reference's summary of the study at ``key``."""
        shape = dict(n_runs=self.n_runs, max_failures=self.max_failures,
                     n_nodes=self.n_nodes)
        mtbf = self.config["mtbf_days"] * DAY_S
        fail = self.traffic["failure"]
        felled = fmask = None
        if fail["family"] == "exponential":
            gaps, failed = ref.exponential_histories(
                key, np.float32(mtbf), **shape)
        else:
            k = fail["k"]
            lam = np.float32(mtbf / math.gamma(1.0 + 1.0 / k))
            if "topology" in self.traffic:
                t = self.traffic["topology"]
                gaps, fmask, failed = ref.rack_histories(
                    key, np.float32(k), lam,
                    np.float32(t["shock_mtbs_days"] * DAY_S),
                    np.float32(t["p_kill"]), np.float32(t["age_boost_s"]),
                    rack_size=t["rack_size"], **shape)
                fmask = np.asarray(fmask)
                felled = ref.survivor_slots(fmask, failed)
            else:
                gaps, failed = ref.weibull_histories(
                    key, np.float32(k), lam, **shape)
        gaps, failed = np.asarray(gaps), np.asarray(failed)
        machines = {m["name"]: m for m in self.config["machines"]}
        out = {}
        for s in self.config["scenarios"]:
            lane = ref.stack_lanes([ref.scenario_lane(s, machines,
                                                      self.makespan_s)])
            res = ref.compose(lane, gaps, failed, felled=felled, fmask=fmask,
                              dtype=dtype)
            out[s["name"]] = dict(ref.study_summary(res, 0, self.n_runs),
                                  points=int(res["points"][0]))
        return out

    @staticmethod
    def gaps(got: dict, want: dict) -> dict:
        """How far a study's summaries lie from the reference's:
        ``energy_rel`` the widest relative gap of the expected energies and
        savings (a saving relative to the larger of itself and 1e-4 of the
        reference energy), ``count_gap`` the widest gap of the failure
        counts, ``decision_gap`` the most decision points of one kind by
        which a scenario's action counts differ."""
        e = c = d = 0.0
        for name, w in want.items():
            g = got[name]
            g = g if isinstance(g, dict) else vars(g)
            for f in _ENERGY:
                e = max(e, abs(g[f] - w[f]) / abs(w[f]))
            floor = 1e-4 * abs(w["mean_energy_ref_j"])
            for f in _SAVING:
                e = max(e, abs(g[f] - w[f]) / max(abs(w[f]), floor))
            for f in _COUNTS:
                c = max(c, abs(g[f] - w[f]))
            c = max(c, max(abs(a - b) for a, b in zip(
                g["per_node_failures"], w["per_node_failures"])))
            for f in _RATES:
                d = max(d, abs(g[f] - w[f]) * w["points"])
        return dict(energy_rel=e, count_gap=c, decision_gap=d)

    def check(self, n_calls: int, n_checks: int, control=False) -> dict:
        """The widest gaps over ``n_checks`` studies of the window, drawn
        from the seed.  ``control`` puts the reference computed in float32
        in the program's place."""
        rng = np.random.default_rng([self.seed, 1])
        picks = rng.choice(n_calls, size=min(n_checks, n_calls), replace=False)
        worst = {}
        for i in sorted(int(p) for p in picks):
            key = self.keys[i % MAX_KEYS]
            got = (self.reference(key, np.float32) if control
                   else self.outputs[i])
            gaps = self.gaps(got, self.reference(key))
            worst = {k: max(v, worst.get(k, 0.0)) for k, v in gaps.items()}
        return worst
