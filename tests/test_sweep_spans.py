"""The study path's names for its layers: the device scopes inside the
fused Monte-Carlo program, and the host spans of one study in a profiler
trace (docs/sweep.md, "Tracing a study")."""
import glob
import re

import jax
import pytest
from jax.profiler import ProfileData

from repro.core import failures as F
from repro.core import sweep
from repro.core import topology as T
from repro.core.scenarios import paper_scenarios

SCOPES = ("renewal_sample", "renewal_scan", "renewal_fold")
SPANS = ("sweep.study", "sweep.stage", "sweep.dispatch", "sweep.fetch",
         "sweep.summarize")
MTBF = 7 * 24 * 3600.0
CFGS = list(paper_scenarios().values())


def _processes():
    rack = T.rack_topology(4, 3, shock_mtbs_s=10 * MTBF, p_kill=0.6,
                           age_boost_s=3600.0)
    return {"exponential": (F.Exponential(MTBF), None),
            "weibull": (F.Weibull.from_mtbf(0.7, MTBF), None),
            "rack": (F.Weibull.from_mtbf(0.7, MTBF), rack)}


@pytest.mark.parametrize("family,program", [
    *(pytest.param(f, "mc", id=f) for f in sorted(_processes())),
    *(pytest.param(f, "study", id=f"{f}-study") for f in sorted(_processes())),
])
def test_the_fused_program_carries_the_three_scopes(family, program):
    process, topology = _processes()[family]
    with jax.enable_x64():
        _, stacked = sweep._renewal_device_inputs(CFGS)
        args = (stacked, jax.random.PRNGKey(0), 30 * 24 * 3600.0, process)
        kw = dict(n_runs=8, max_failures=4, topology=topology)
        lowered = (sweep._renewal_mc_jit.lower(*args, stats=True, **kw)
                   if program == "mc"
                   else sweep._renewal_study_jit.lower(*args, **kw))
        text = lowered.as_text(debug_info=True)
    # a scope is a component of an operation's name, perhaps inside a
    # transform's wrapper: "jit(f)/vmap(vmap(renewal_fold))/mul"
    for scope in SCOPES:
        assert re.search(rf'loc\("[^"]*[/(]{scope}[)/][^"]*"', text), scope


def test_a_traced_study_shows_its_host_spans(tmp_path):
    process, topology = _processes()["rack"]
    kw = dict(n_runs=8, max_failures=4, process=process, topology=topology)
    want = sweep.renewal_monte_carlo_scenarios(CFGS[:2], jax.random.PRNGKey(1),
                                               **kw)
    jax.profiler.start_trace(str(tmp_path))
    got = sweep.renewal_monte_carlo_scenarios(CFGS[:2], jax.random.PRNGKey(1),
                                              **kw)
    jax.profiler.stop_trace()
    assert got == want
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    names = {ev.name for plane in ProfileData.from_file(path).planes
             for line in plane.lines for ev in line.events}
    assert set(SPANS) <= names


@pytest.mark.parametrize("fold", ["stacked", "tiled"])
def test_the_dispatch_span_counts_the_epochs_of_each_sawtooth(
        fold, tmp_path, monkeypatch):
    """``sweep.dispatch`` says over how many epochs the scan advanced the
    checkpoint sawtooth per node (epoch 0) and per run (the rest), whether
    a run's fold takes the stacked epochs or survivor tiles in the scan."""
    process, _ = _processes()["weibull"]
    n_runs = 8
    if fold == "tiled":
        monkeypatch.setattr(sweep, "_SURVIVOR_TILE_ELEMENTS", 64)
        n_runs = 16           # a shape of its own, traced under this budget
    kw = dict(n_runs=n_runs, max_failures=6, process=process,
              makespan_s=30 * 24 * 3600.0)
    sweep.renewal_monte_carlo_scenarios(CFGS, jax.random.PRNGKey(1), **kw)
    jax.profiler.start_trace(str(tmp_path))
    sweep.renewal_monte_carlo_scenarios(CFGS, jax.random.PRNGKey(1), **kw)
    jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    dispatch, = [dict(ev.stats) for plane in ProfileData.from_file(path).planes
                 for line in plane.lines for ev in line.events
                 if ev.name == "sweep.dispatch"]
    assert (dispatch["node_epochs"], dispatch["run_epochs"]) == (1, 5)
    assert (dispatch["tiles"] > 1) == (fold == "tiled")
