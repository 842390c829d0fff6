"""Closed loop of advisory flushes (``repro.fleet.FleetAdvisor.advise``):
each call re-advises every running job of the configuration's machine in
one flush, each job one ``ClusterProfile`` lowered onto the
configuration's machine, with its remaining work drawn from the seed for
that call.  The check recomputes sampled flushes with the plain reference
of a policy lane (``bench/reference/policy.py``), composed in blocks of
runs across the host's cores."""
from __future__ import annotations

import concurrent.futures
import dataclasses
import math
import multiprocessing
import os
import sys

import numpy as np

from bench import lower
from bench.reference import policy as ref_policy
from bench.reference import renewal as ref

DAY_S = 24 * 3600.0
_MEANS = ("mean_energy_ref_j", "mean_energy_int_j", "mean_makespan_s")
_WARMUP = -1                  # the call index the warm-up flush draws for


class Driver:
    span = "fleet"
    runs_per_block = 16       # a block's arrays are (policies, runs, survivors)
    workers = os.cpu_count()

    def __init__(self, config: dict, traffic: dict, seed: int, **sizes):
        import jax
        from repro import fleet
        from repro.core import optimize
        self.config, self.traffic, self.seed = config, traffic, seed
        adv = config["advisor"]
        self.n_runs = sizes.get("n_runs", adv["n_runs"])
        self.max_failures = sizes.get("max_failures", adv["max_failures"])
        self.machines = {m["name"]: m for m in config["machines"]}
        lowered = {n: lower.machine_profile(m)
                   for n, m in self.machines.items()}
        self.jobs = config["jobs"]
        self.cols = ref_policy.policy_columns(adv["grid"])
        c = self.cols
        self.table = optimize.PolicyTable(
            ckpt_interval=c["interval"], mu1=c["mu1"], mu2=c["mu2"],
            wait_mode=c["wait_mode"], move_ahead_frac=c["move_frac"])
        self.key = jax.random.PRNGKey(adv["key"])
        self.advisor = fleet.FleetAdvisor(
            self.table, key=self.key, n_runs=self.n_runs,
            max_failures=self.max_failures)
        self.profiles = [
            fleet.ClusterProfile(
                name=j["name"], n_nodes=j["n_nodes"], period_s=j["period_s"],
                mtbf_s=j["mtbf_days"] * DAY_S, family=j["failure"]["family"],
                weibull_k=j["failure"].get("k", 0.7),
                power_scale=j["power_scale"],
                ckpt_duration=j["ckpt_duration"], t_down=j["t_down"],
                t_restart=j["t_restart"], machine=lowered[j["machine"]])
            for j in self.jobs]
        self.outputs = []
        self._served = None

    def works(self, i: int) -> np.ndarray:
        """Each job's remaining work (s) in call ``i``, drawn from the seed."""
        lo, hi = self.traffic["work_days"]["uniform"]
        rng = np.random.default_rng([self.seed, 2, i + 1])
        return rng.uniform(lo, hi, size=len(self.jobs)) * DAY_S

    def _advise(self, i: int) -> list:
        return self.advisor.advise([
            dataclasses.replace(p, work_s=float(w))
            for p, w in zip(self.profiles, self.works(i))])

    def warmup(self) -> None:
        self._advise(_WARMUP)
        self._served = self.advisor.stats()

    def call(self, i: int) -> tuple:
        """One flush of every job; returns (answers attempted, answers
        failed): an answer with a non-finite expectation fails."""
        answers = self._advise(i)
        kept = []
        for a in answers:
            g = a.optimum.grid
            kept.append(dict(
                mean_energy_ref_j=g.mean_energy_ref_j,
                mean_energy_int_j=g.mean_energy_j,
                mean_makespan_s=g.mean_makespan_s,
                mean_saving_j=g.mean_saving_j, best=a.best, knee=a.knee,
                knobs=(g.table.ckpt_interval, g.table.mu1, g.table.mu2,
                       g.table.wait_mode, g.table.move_ahead_frac)))
        self.outputs.append(kept)
        bad = sum(not all(np.isfinite(k[f]).all()
                          for f in _MEANS + ("mean_saving_j",)) for k in kept)
        return len(answers), bad

    def work(self, n_calls: int) -> dict:
        """What ``n_calls`` flushes answer (``bench.work`` units, real lanes
        only), and the lanes and decisions they dispatched, padding
        included, from the advisor's counters."""
        p, r, k = len(self.table), self.n_runs, self.max_failures
        nodes = [j["n_nodes"] for j in self.jobs]
        lane_runs = n_calls * p * r * len(nodes)
        out = dict(
            decisions=n_calls * p * r * k * sum(n - 1 for n in nodes),
            lane_epochs=lane_runs * k, lane_runs=lane_runs,
            node_epochs={"weibull": n_calls * r * k * sum(nodes)})
        if self._served is not None:
            now, then = self.advisor.stats(), self._served
            out.update(
                lanes_dispatched=now.lanes - then.lanes,
                lanes_padded=now.padded_lanes - then.padded_lanes,
                decisions_dispatched=(now.decisions_dispatched
                                      - then.decisions_dispatched),
                decisions_padded=(
                    now.decisions_dispatched - then.decisions_dispatched
                    - now.decisions_answered + then.decisions_answered))
        return out

    def release(self) -> None:
        """The answers are host arrays: nothing to free."""

    # -- the check against the plain reference --------------------------

    def _histories(self, job: dict) -> tuple:
        fail = job["failure"]
        if fail["family"] != "weibull":
            raise ValueError(f"no reference sampler for {fail['family']!r}")
        k, mtbf = fail["k"], job["mtbf_days"] * DAY_S
        gaps, failed = ref.weibull_histories(
            np.asarray(self.key), np.float32(k),
            np.float32(mtbf / math.gamma(1.0 + 1.0 / k)), n_runs=self.n_runs,
            max_failures=self.max_failures, n_nodes=job["n_nodes"])
        return np.asarray(gaps), np.asarray(failed)

    def reference(self, i: int, pool, dtype=np.float64) -> list:
        """Per job of call ``i``: the reference's expectations of every
        policy lane, its histories drawn by the reference's own sampler at
        the advisor's key, composed in blocks of runs by ``pool``."""
        snap = self.config["snapshot"]
        step = self.runs_per_block
        starts = range(0, self.n_runs, step)
        futures, histories = [], {}
        for job, w in zip(self.jobs, self.works(i)):
            law = (job["n_nodes"], job["mtbf_days"],
                   tuple(sorted(job["failure"].items())))
            if law not in histories:
                histories[law] = self._histories(job)
            gaps, failed = histories[law]
            lane = ref_policy.job_lanes(job, self.machines[job["machine"]],
                                        snap, self.cols, float(w))
            futures.append([pool.submit(
                ref_policy.compose_block, lane, gaps[b:b + step],
                failed[b:b + step], dtype) for b in starts])
        out = []
        for parts in futures:
            parts = [f.result() for f in parts]
            out.append(ref_policy.expectations(
                {k: np.concatenate([p[k] for p in parts], axis=1)
                 for k in ref_policy.PER_RUN}))
        return out

    def gaps(self, got: list, want: list) -> dict:
        """``energy_rel``: the widest relative gap, over every (job,
        policy), of the expected reference and intervened energies, the
        expected makespan and the mean saving (relative to the larger of
        itself and 1e-4 of the reference energy); infinite where an answer
        is missing or scored another grid.  ``best_mismatch``: the jobs
        whose best policy is not the reference's least expected energy."""
        if len(got) != len(want):
            return dict(energy_rel=math.inf, best_mismatch=float(len(want)))
        e, best = 0.0, 0
        cols = self.cols
        knobs = tuple(cols[k] for k in ref_policy.KNOBS)
        for g, w in zip(got, want):
            if not all(np.array_equal(a, b) for a, b in zip(g["knobs"], knobs)):
                return dict(energy_rel=math.inf,
                            best_mismatch=float(len(want)))
            for f in _MEANS:
                e = max(e, float(np.max(np.abs(g[f] - w[f]) / np.abs(w[f]))))
            floor = 1e-4 * np.abs(w["mean_energy_ref_j"])
            e = max(e, float(np.max(
                np.abs(g["mean_saving_j"] - w["mean_saving_j"])
                / np.maximum(np.abs(w["mean_saving_j"]), floor))))
            p = int(np.argmin(w["mean_energy_int_j"]))
            best += g["best"]["ckpt_interval"] != cols["interval"][p] or (
                g["best"]["mu1"] != cols["mu1"][p]) or (
                g["best"]["wait_mode"] != cols["wait_mode"][p])
        return dict(energy_rel=e, best_mismatch=float(best))

    def check(self, n_calls: int, n_checks: int, control=False) -> dict:
        """The widest gaps over ``n_checks`` flushes of the window, drawn
        from the seed.  ``control`` puts the reference computed in float32
        in the program's place."""
        rng = np.random.default_rng([self.seed, 1])
        picks = rng.choice(n_calls, size=min(n_checks, n_calls), replace=False)
        worst = {}
        with concurrent.futures.ProcessPoolExecutor(
                max_workers=max(1, min(self.workers, 32)),
                mp_context=multiprocessing.get_context("spawn")) as pool:
            for i in sorted(int(p) for p in picks):
                want = self.reference(i, pool)
                if control:
                    got = [dict(w, best=self.outputs[i][j]["best"],
                                knee=self.outputs[i][j]["knee"],
                                knobs=self.outputs[i][j]["knobs"])
                           for j, w in enumerate(
                               self.reference(i, pool, np.float32))]
                else:
                    got = self.outputs[i]
                for job, g in zip(self.jobs, got):
                    print(f"flush {i} {job['name']}: best "
                          f"{_policy(g['best'])}, knee {_policy(g['knee'])}",
                          file=sys.stderr)
                gaps = self.gaps(got, want)
                worst = {k: max(v, worst.get(k, 0.0)) for k, v in gaps.items()}
        return worst


def _policy(p: dict) -> str:
    return (f"interval {p['ckpt_interval']:.1f} s, mu1 {p['mu1']:g}, "
            f"wait mode {p['wait_mode']}")
