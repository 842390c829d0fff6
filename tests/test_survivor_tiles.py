"""The survivor fold in tiles (``sweep._survivor_tile``): a stack of lanes,
scenarios or policies, whose (lane, run, epoch, survivor) grid exceeds the
fold's element budget folds inside the epoch scan, each epoch's survivors a
tile at a time, and gives the summaries the fold over the stacked epochs
gives.  The sums over survivors and epochs are taken in another order, so
the energies agree to float64 round-off (1e-12 relative) and every count is
equal."""
import dataclasses
import glob
import json
import pathlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.core import failures as F
from repro.core import sweep
from repro.core import topology as T
from repro.core.scenarios import paper_scenarios
from repro.core.simulator import NodeStart

ROOT = pathlib.Path(__file__).resolve().parents[1]
N_SURVIVORS = 39
MTBF = 90 * 24 * 3600.0            # ~13 failures in a 30-day run of 40 nodes
MAKESPAN = 30 * 24 * 3600.0
MAKESPANS = MAKESPAN * np.array([1.0, 1.1])     # a policy stack's, one a lane
KW = dict(n_runs=64, max_failures=16)
# the tile scope in a compiled operation's name:
# "vmap(vmap(renewal_scan))/while/body/closed_call/survivor_tile/add"
TILE_SCOPE = re.compile(r"renewal_scan\)*/while/body/[\w/]*survivor_tile/")


def _wide(cfg, n=N_SURVIVORS):
    """``cfg`` with ``n`` survivors of varied phases, ages and periods."""
    return dataclasses.replace(cfg, survivors=tuple(
        NodeStart(exec_to_rendezvous=(97.0 * i) % period + 1.0,
                  rendezvous_period=period,
                  ckpt_age=(53.0 * i) % cfg.ckpt_interval)
        for i, period in enumerate([3600.0, 1800.0, 2700.0] * n)
        if i < n))


CFGS = [_wide(paper_scenarios()[name]) for name in (
    "scenario1_short_reexec", "scenario5_short_idle_waits")]


def _family(name):
    weibull = F.Weibull.from_mtbf(0.7, MTBF)
    rack = T.rack_topology(N_SURVIVORS + 1, 4, shock_mtbs_s=MTBF / 2,
                           p_kill=0.6, age_boost_s=3600.0)
    return {"exponential": (F.Exponential(MTBF), None),
            "weibull": (weibull, None), "rack": (weibull, rack)}[name]


def _study(family, tile, key=jax.random.PRNGKey(5)):
    process, topology = _family(family)
    with sweep._staged(CFGS, process, None, "scan") as (stacked, proc):
        return jax.device_get(sweep._renewal_study_jit(
            stacked, key, MAKESPAN, proc, topology=topology,
            engine="scan", survivor_tile=tile, **KW))


def _policy_study(family, key=jax.random.PRNGKey(5)):
    """A policy stack's per-run Monte-Carlo (``_renewal_mc_jit`` with one
    makespan a lane) under the fold budget in force, reduced as a study's:
    ``(totals, moments)`` and the program's compiled text."""
    process, topology = _family(family)
    with jax.enable_x64():
        _, stacked = sweep._renewal_device_inputs(CFGS)
        args = (stacked, key, jnp.asarray(MAKESPANS), process)
        compiled = sweep._renewal_mc_jit.lower(
            *args, stats=True, topology=topology, **KW).compile()
        out, _, _ = compiled(*args, topology=topology)
        study = sweep._study_reduce_jit(out, max_failures=KW["max_failures"])
    return jax.device_get(study), compiled.as_text()


@pytest.fixture
def fold_budget(monkeypatch):
    """Sets ``sweep._SURVIVOR_TILE_ELEMENTS``.  A program reads it when it
    is traced, so JAX's caches of traced programs are cleared with each
    change and at the end of a test that made one."""
    changed = []

    def set_budget(elements):
        monkeypatch.setattr(sweep, "_SURVIVOR_TILE_ELEMENTS", elements)
        jax.clear_caches()
        changed.append(elements)
    yield set_budget
    if changed:
        monkeypatch.undo()
        jax.clear_caches()


# 8: the last tile holds 7; the policy stack's ids add "-policies"
@pytest.mark.parametrize("family,tile,stack", [
    pytest.param(family, tile, stack,
                 id="-".join([family, tile_id]
                             + ([stack] if stack == "policies" else [])))
    for stack in ("scenarios", "policies")
    for family in ("exponential", "weibull", "rack")
    for tile, tile_id in ((8, "tiles_of_8"), (N_SURVIVORS, "one_tile"))])
def test_tiles_give_the_stacked_fold_summaries(family, tile, stack,
                                               fold_budget):
    assert sweep._survivor_tile(len(CFGS), KW["n_runs"], KW["max_failures"],
                                N_SURVIVORS) is None
    if stack == "scenarios":
        whole_totals, whole_moments = _study(family, None)
        totals, moments = _study(family, tile)
    else:
        # the policy stack decides its tiles from its shapes, as a study
        # does: over a budget of ``tile`` survivors of every lane's runs
        (whole_totals, whole_moments), text = _policy_study(family)
        assert not TILE_SCOPE.search(text)
        fold_budget(tile * len(CFGS) * KW["n_runs"])
        (totals, moments), text = _policy_study(family)
        assert TILE_SCOPE.search(text)
    np.testing.assert_array_equal(totals, whole_totals)
    np.testing.assert_allclose(moments, whole_moments, rtol=1e-12, atol=0)
    # the histories did fail, and the fold took every kind of action
    assert whole_moments[:, 0].min() > 3
    assert whole_totals[:, :5].sum(axis=0).min() > 0


def _compiled(cells, n_runs, max_failures, n_survivors, **kw):
    """The compiled text of a Weibull study's program at these shapes."""
    cfg = _wide(paper_scenarios()["scenario1_short_reexec"], n_survivors)
    process = F.Weibull.from_mtbf(0.7, MTBF)
    with sweep._staged([cfg] * cells, process, None, "scan") as (stacked,
                                                                 proc):
        return sweep._renewal_study_jit.lower(
            stacked, jax.random.PRNGKey(0), MAKESPAN, proc, n_runs=n_runs,
            max_failures=max_failures, engine="scan", **kw).compile(
            ).as_text()


def test_table4_studies_fold_the_stacked_epochs():
    config = json.loads(
        (ROOT / "bench/configs/table4_paper.json").read_text())
    n = len(config["scenarios"][0]["survivors"])
    assert sweep._survivor_tile(len(config["scenarios"]), config["n_runs"],
                                config["max_failures"], n) is None
    assert not TILE_SCOPE.search(_compiled(2, 64, 16, n))


def test_a_whole_machine_study_folds_in_the_epoch_scan():
    # 1,023 survivors x 4,096 runs x 48 epochs: 201M points, over the
    # budget; one epoch's 4,096 x 1,023 fit it, so one tile an epoch
    assert sweep._survivor_tile(1, 4096, 48, 1023) == 1023
    # 10,000 survivors: tiles of 8,192 (64 x 128)
    assert sweep._survivor_tile(1, 4096, 48, 10_000) == 8192
    assert TILE_SCOPE.search(_compiled(2, 64, 16, N_SURVIVORS,
                                       survivor_tile=8))


def _hlo_computations(text):
    """Computation name -> its instruction lines, of an HLO module's text,
    and the name of its entry computation."""
    comps, name, entry = {}, None, None
    for line in text.splitlines():
        if line and not line[0].isspace() and line.endswith("{"):
            words = line.split()
            name = words[1] if words[0] == "ENTRY" else words[0]
            entry = name if words[0] == "ENTRY" else entry
            comps[name] = []
        elif line == "}":
            name = None
        elif name:
            comps[name].append(line)
    return comps, entry


_CALLED = re.compile(r"(?:body|condition|to_apply|calls)=([\w.-]+)"
                     r"|branch_computations=\{([^}]*)\}")


def _reached(comps, roots):
    """The computations ``roots`` call, themselves included."""
    seen, todo = set(), list(roots)
    while todo:
        name = todo.pop()
        if name not in seen:
            seen.add(name)
            for line in comps[name]:
                for one, many in _CALLED.findall(line):
                    todo.extend([one] if one else
                                [c.strip() for c in many.split(",")])
    return seen


def _f64_of_width(comps, names, width, scope=""):
    """Instructions of ``names`` with a float64 value whose trailing
    dimension is ``width`` (those of ``scope``, if given)."""
    return [line.strip() for name in names for line in comps[name]
            if scope in line and any(
                dims.split(",")[-1] == str(width) for dims in re.findall(
                    r"f64\[([\d,]+)\]", line.split(", metadata=")[0]))]


def test_a_whole_machine_scan_advances_one_age_a_run_after_epoch_0():
    """Every occurring epoch ends in the resync checkpoint (all N+1 ages
    0) and one that does not ends the run, so the epoch scan carries the
    sawtooth as one age a run: no float64 value of its loop is N+1 wide.
    Epoch 0, before the loop, advances each node's own age.  Tiles of 512
    keep the fold's own trees (over 512 and 511 survivors) narrower than
    N+1 = 1,024."""
    cfg = dataclasses.replace(
        paper_scenarios()["scenario1_short_reexec"], t_reexec=0.0,
        survivors=tuple(NodeStart(3600.0 * (i + 1) / 1024, 3600.0, 0.0)
                        for i in range(1023)))
    process = F.Weibull.from_mtbf(0.7, 365 * 24 * 3600.0)
    with sweep._staged([cfg], process, None, "scan") as (stacked, proc):
        text = sweep._renewal_study_jit.lower(
            stacked, jax.random.PRNGKey(0), 24 * 3600.0, proc, n_runs=8,
            max_failures=6, engine="scan", survivor_tile=512).as_text(
            dialect="hlo", debug_info=True)
    comps, entry = _hlo_computations(text)
    scan, = [line for lines in comps.values() for line in lines
             if " while(" in line and "renewal_scan" in line]
    loop = _reached(comps, [re.search(r"body=([\w.-]+)", scan).group(1)])
    assert _f64_of_width(comps, loop, 1023)       # the survivors' own state
    assert not _f64_of_width(comps, loop, 1024)
    outside = _reached(comps, [entry]) - loop
    assert _f64_of_width(comps, outside, 1024, scope="renewal_scan")


def _dispatch_args(trace_dir, n_runs):
    """``(survivors, survivor_tile, tiles)`` of every ``sweep.dispatch``
    span in the trace of one warm Weibull study."""
    process, topology = _family("weibull")
    kw = dict(KW, n_runs=n_runs, process=process, topology=topology,
              makespan_s=MAKESPAN)
    sweep.renewal_monte_carlo_scenarios(CFGS, jax.random.PRNGKey(1), **kw)
    jax.profiler.start_trace(str(trace_dir))
    sweep.renewal_monte_carlo_scenarios(CFGS, jax.random.PRNGKey(1), **kw)
    jax.profiler.stop_trace()
    path, = glob.glob(str(trace_dir / "**" / "*.xplane.pb"), recursive=True)
    return {(s["survivors"], s["survivor_tile"], s["tiles"])
            for s in (dict(ev.stats) for plane in
                      ProfileData.from_file(path).planes
                      for line in plane.lines for ev in line.events
                      if ev.name == "sweep.dispatch")}


def test_the_dispatch_span_names_the_tiles(tmp_path, monkeypatch):
    # the stacked fold: all survivors in one piece
    assert _dispatch_args(tmp_path / "whole", 64) == {(N_SURVIVORS,
                                                      N_SURVIVORS, 1)}
    # over a smaller budget the fold runs per epoch: 16 epochs, one tile
    monkeypatch.setattr(sweep, "_SURVIVOR_TILE_ELEMENTS", 10_000)
    assert _dispatch_args(tmp_path / "tiled", 32) == {(N_SURVIVORS,
                                                      N_SURVIVORS, 16)}


def test_the_pallas_kernel_refuses_a_whole_machine():
    cfg = _wide(paper_scenarios()["scenario1_short_reexec"], 1023)
    with pytest.raises(ValueError, match="engine='scan'"):
        sweep.renewal_monte_carlo_scenarios(
            [cfg], jax.random.PRNGKey(0), n_runs=128, max_failures=48,
            makespan_s=MAKESPAN, process=F.Weibull.from_mtbf(0.7, MTBF),
            engine="pallas")
