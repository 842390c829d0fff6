"""A run with the timed path broken underneath must come out not correct:
the harness's look for a chip is skipped, the rest of the run is driven
as on the chip, for each fault a cell can have — an answer altered where
it is produced, an answer computed on half the runs, the first answer
returned again for every later study (histories that never advance)."""
import dataclasses
import time

import pytest

from bench import harness
from repro.core import sweep

from test_bench_cells import TINY


def run(name):
    spec = harness.load_cell(name)
    return harness.run_cell(spec, 2 ** 31 + 29, 0.3, False,
                            time.perf_counter(), **TINY[name])


def _altered_study(real):
    def study(*a, **kw):
        out = dict(real(*a, **kw))
        name = next(iter(out))
        out[name] = dataclasses.replace(
            out[name], mean_energy_int_j=out[name].mean_energy_int_j * 1.0001)
        return out
    return study


def _half_runs_study(real):
    def study(*a, n_runs, **kw):
        return real(*a, n_runs=n_runs // 2, **kw)
    return study


def _stale_study(real):
    first = []

    def study(*a, **kw):
        if not first:
            first.append(real(*a, **kw))
        return first[0]
    return study


@pytest.mark.parametrize("name", ["mc.table4.exp", "mc.table4.rack"])
@pytest.mark.parametrize("fault", [_altered_study, _half_runs_study,
                                   _stale_study])
def test_a_broken_study_is_not_correct(monkeypatch, name, fault):
    monkeypatch.setattr(sweep, "renewal_monte_carlo_scenarios",
                        fault(sweep.renewal_monte_carlo_scenarios))
    assert run(name)["correct"] is False
