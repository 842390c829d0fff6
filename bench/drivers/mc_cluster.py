"""Closed loop of renewal Monte-Carlo studies of one job over a whole
machine: the program path of ``mc_study`` (``sweep.renewal_monte_carlo_
scenarios``), checked against the plain reference composed in blocks of
runs across the host's cores, since a study of a thousand survivors takes
the reference minutes on one core."""
from __future__ import annotations

import concurrent.futures
import functools
import math
import multiprocessing
import os

import numpy as np

from bench.drivers import mc_study
from bench.reference import renewal as ref

DAY_S = mc_study.DAY_S
_PER_RUN = ("energy_ref", "energy_int", "saving", "end_time", "n_failures",
            "truncated")
_PER_LANE = ("points", "sleep", "min_freq", "comp_changed", "infeasible",
             "failed_counts")


class Driver(mc_study.Driver):
    runs_per_block = 64          # a block's arrays are (runs, survivors)
    workers = os.cpu_count()

    def reference(self, key, dtype=np.float64) -> dict:
        """name -> the reference's summary of the study at ``key``: the
        histories drawn whole by the reference's own sampler, composed in
        blocks of runs by a pool of processes (numpy only, so none of them
        touches the chip), the per-run arrays joined in run order."""
        shape = dict(n_runs=self.n_runs, max_failures=self.max_failures,
                     n_nodes=self.n_nodes)
        fail = self.traffic["failure"]
        mtbf = self.config["mtbf_days"] * DAY_S
        if fail["family"] == "exponential":
            gaps, failed = ref.exponential_histories(
                key, np.float32(mtbf), **shape)
        else:
            k = fail["k"]
            gaps, failed = ref.weibull_histories(
                key, np.float32(k),
                np.float32(mtbf / math.gamma(1.0 + 1.0 / k)), **shape)
        gaps, failed = np.asarray(gaps), np.asarray(failed)
        machines = {m["name"]: m for m in self.config["machines"]}
        step = self.runs_per_block
        starts = range(0, self.n_runs, step)
        out = {}
        with concurrent.futures.ProcessPoolExecutor(
                max_workers=min(self.workers, len(starts)),
                mp_context=multiprocessing.get_context("spawn")) as pool:
            for s in self.config["scenarios"]:
                lane = ref.stack_lanes([ref.scenario_lane(s, machines,
                                                          self.makespan_s)])
                parts = list(pool.map(
                    functools.partial(ref.compose, lane, dtype=dtype),
                    [gaps[b:b + step] for b in starts],
                    [failed[b:b + step] for b in starts]))
                res = {k: np.concatenate([p[k] for p in parts], axis=1)
                       for k in _PER_RUN}
                res.update({k: sum(p[k] for p in parts) for k in _PER_LANE})
                out[s["name"]] = dict(ref.study_summary(res, 0, self.n_runs),
                                      points=int(res["points"][0]))
        return out
