"""On-chip smoke test: the system's main paths, end to end, on a TPU.

    python chip_smoke.py              # one chip: phases A-C
    python chip_smoke.py --chips 4    # four chips: phase D only

Phases (one process; nothing here starts a subprocess):

  A. Engine — ``sweep.renewal_monte_carlo_scenarios`` over the six
     Table-4 scenarios at 4096 runs x 64 epochs, one key, for exponential,
     Weibull(k=0.7) and rack-topology histories, on ``engine="scan"``
     (float64 geometry) and ``engine="pallas"`` (the compiled float32
     kernel).  Both are held to 1e-4 of the float64 host oracle
     ``renewal_compose`` on a slice of runs.  The kernel's integer stats
     equal the scan's: those of the failure geometry on every run, the
     decision counts on every run but the few with a decision tie (see
     ``TIE_S``).  The Kahan ledger is reported against naive float32
     summation.
  B. Served path — ``fleet.FleetAdvisor`` answers a 256-cluster
     ``synthetic_fleet`` in two shape buckets; two answers must equal
     standalone ``optimize.optimize_policy`` calls bit for bit, and a
     repeated fleet must trace nothing new.
  C. FT training — ``FTTrainer`` runs mamba2-370m at its published widths
     (depth cut to ``TRAIN_LAYERS``) through the compiled Pallas SSD kernel:
     one checkpoint, one injected pod failure (rollback, re-execution and
     an Algorithm-1 decision), finite losses.
  D. (``--chips 4``) the cross-chip paths against their one-chip results:
     the sharded advisor bit for bit, and the sharded train step's loss and
     gradients within ``LOSS_TOL`` and ``GRAD_TOL``; the gradient check must
     also reject a planted fault (the step on half the batch).

Each phase prints its compile and run seconds and its checks.  The last
line of standard output is one JSON object naming the device.  The script
exits non-zero, printing no such line, when JAX finds no TPU or any check
fails.  JAX's persistent compilation cache lives in
``$JAX_COMPILATION_CACHE_DIR`` when that is set, else in ``.jax_cache``
beside this file.
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import shutil
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", str(ROOT / ".jax_cache"))
sys.path.insert(0, str(ROOT / "src"))

import jax                                                    # noqa: E402
import jax.numpy as jnp                                       # noqa: E402
import numpy as np                                            # noqa: E402

from repro import fleet                                       # noqa: E402
from repro.core import energy_model as em                     # noqa: E402
from repro.core import failures, optimize, strategies, sweep  # noqa: E402
from repro.core import topology as node_topology              # noqa: E402
from repro.core.scenarios import paper_scenarios              # noqa: E402

SEED = 0
DAY = 24 * 3600.0
# phase A: the --full shape of benchmarks/failure_sweep.py
N_RUNS, MAX_FAILURES = 4096, 64
MAKESPAN_S, MTBF_S = 30 * DAY, 7 * DAY
ORACLE_RUNS = 64                 # runs re-composed by the float64 host oracle
TOL = 1e-4                       # the repo's cross-validation bar
GEOMETRY_STATS = ("n_failures", "truncated", "n_points", "failed_counts")
DECISION_STATS = ("n_sleep", "n_min_freq", "n_comp_changed", "n_infeasible")
# The kernel's float32 geometry is off the float64 one by up to ~1e-2 s at
# 1e5 s gaps (a few ulps of the gap), so an Algorithm-1 comparison that close
# to its boundary may fall either way.  Scenario 1 holds an exact tie: a
# failure snapped to a checkpoint end leaves a wait of t_D + t_R - dur =
# 180 s = mu1 x the sleep transition time, and rounding decides the side of
# the sleep gate.  Each run whose decision counts differ must hold a
# comparison within TIE_S of its boundary (``strategies.decision_margins``).
TIE_S = 0.02
MAX_TIE_RUNS = 8                 # per history, of 6 x 4096 runs
# phase B
FLEET_SIZE = 256
# phase C: mamba2-370m widths, depth cut so a checkpoint stays ~2 GB
TRAIN_LAYERS = 8
TRAIN_BATCH, TRAIN_SEQ = 4, 2048
TRAIN_STEPS = 4
TRAIN_LR = 3e-4
# phase D: sharded vs one-device train step.  The loss, relative; the
# gradients through the AdamW first moment the step returns (float32,
# 0.1 x the clipped gradient): the worst leaf's ||m4 - m1|| / ||m1||.  The
# parameters are no test of the gradients: a first Adam step moves each by
# lr g / (|g| + eps), about lr whatever g is.  GRAD_TOL sits between the
# sound runs (bfloat16 gradients; 1.2e-2 and 1.8e-2 on a 4-device CPU mesh
# at 1 and 2 layers) and the planted fault (0.79 and 0.88 there).
LOSS_TOL = 1e-3
GRAD_TOL = 0.2


def _clock(fn):
    t = time.perf_counter()
    out = jax.block_until_ready(fn())
    return out, time.perf_counter() - t


def _check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(what)


def _rel_err(a, b, denom=None) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    d = np.abs(b) if denom is None else denom
    return float(np.max(np.abs(a - b) / d))


# ---------------------------------------------------------------------------
# A. renewal engine: scan and pallas vs the float64 host oracle
# ---------------------------------------------------------------------------

def _histories(n_nodes: int) -> dict:
    weibull = failures.Weibull.from_mtbf(0.7, MTBF_S)
    return {
        "exponential": dict(mtbf_s=MTBF_S),
        "weibull": dict(process=weibull),
        "topology": dict(process=weibull, topology=node_topology.rack_topology(
            n_nodes, 3, shock_mtbs_s=10 * DAY, p_kill=0.6,
            age_boost_s=3600.0)),
    }


def _oracle(cfgs, key, hist: dict, n_nodes: int) -> list:
    got = sweep.renewal_failure_gaps(key, N_RUNS, n_nodes, MAX_FAILURES,
                                     **hist)
    gaps, failed = got[0][:ORACLE_RUNS], got[1][:ORACLE_RUNS]
    felled = None
    if "topology" in hist:
        felled = np.asarray(node_topology.survivor_slot_mask(
            jnp.asarray(got[2]), jnp.asarray(got[1])))[:ORACLE_RUNS]
    return [sweep.renewal_compose(cfg, gaps, MAKESPAN_S, failed_node=failed,
                                  felled=felled) for cfg in cfgs]


def _oracle_error(stats, hosts) -> float:
    """Max relative error of the whole-run energies over the oracle slice
    (saving relative to max(|saving|, 1e-4 * energy_ref), as the tests)."""
    err = 0.0
    for s, host in enumerate(hosts):
        for field in ("energy_ref", "energy_int", "balanced_energy",
                      "end_time"):
            got = np.asarray(getattr(stats, field))[s, :ORACLE_RUNS]
            err = max(err, _rel_err(got, getattr(host, field)))
        denom = np.maximum(np.abs(host.saving), 1e-4 * host.energy_ref)
        err = max(err, _rel_err(
            np.asarray(stats.saving)[s, :ORACLE_RUNS], host.saving, denom))
        _check(np.array_equal(np.asarray(stats.n_failures)[s, :ORACLE_RUNS],
                              host.n_failures), "oracle n_failures")
    return err


def _decision_margin_s(cfg, host, felled) -> float:
    """Distance, in seconds, of the run's closest Algorithm-1 comparison
    from its boundary (``strategies.decision_margins``), on the float64
    oracle's geometry cast to float32 as the engines cast it.  Valid,
    non-felled epochs only (``felled`` marks the survivors an epoch took
    down too)."""
    ladder = em.LadderArrays.from_table(cfg.profile.power_table)
    margin = np.asarray(strategies.decision_margins(
        host.exec_rem, host.t_failed,
        [host.n_ckpt[..., f] for f in range(ladder.num_levels)],
        cfg.ckpt_duration, ladder, em.SleepArrays.from_spec(cfg.profile.sleep),
        int(cfg.wait_mode), cfg.profile.p_idle_wait, mu1=cfg.mu1,
        mu2=cfg.mu2))                                            # (R, K, N)
    keep = host.valid[..., None] & ~np.asarray(
        False if felled is None else felled, bool)
    return float(np.min(np.where(keep, margin, np.inf)))


def _compare_integer_stats(cfgs, key, hist, n_nodes, scan, pallas) -> int:
    """The kernel's integer stats against the scan's.  Those fixed by the
    failure geometry must be equal on every run.  Those counting Algorithm-1
    decisions must be equal on every run but the few whose decisions sit
    within ``TIE_S`` of a boundary, where the float32 and the float64
    geometry may round to different sides.  Returns how many runs differ."""
    for field in GEOMETRY_STATS:
        _check(np.array_equal(np.asarray(getattr(pallas, field)),
                              np.asarray(getattr(scan, field))),
               f"pallas {field} != scan")
    differ = np.zeros(np.shape(scan.n_sleep), bool)
    for field in DECISION_STATS:
        differ |= (np.asarray(getattr(pallas, field))
                   != np.asarray(getattr(scan, field)))
    runs = np.argwhere(differ)
    _check(len(runs) <= MAX_TIE_RUNS, f"{len(runs)} runs differ in decisions")
    if len(runs):
        gaps, failed, *rest = sweep.renewal_failure_gaps(
            key, N_RUNS, n_nodes, MAX_FAILURES, **hist)
        felled = None
        if rest:
            felled = np.asarray(node_topology.survivor_slot_mask(
                jnp.asarray(rest[0]), jnp.asarray(failed)))
    for s, r in runs:
        host = sweep.renewal_compose(
            cfgs[s], gaps[r:r + 1], MAKESPAN_S, failed_node=failed[r:r + 1],
            felled=None if felled is None else felled[r:r + 1])
        margin = _decision_margin_s(
            cfgs[s], host, None if felled is None else felled[r:r + 1])
        print(f"A   decision tie: scenario {s} run {r} margin_s={margin:.3e}")
        _check(margin <= TIE_S, f"scenario {s} run {r}: decisions differ "
                                f"{margin} s from any boundary")
    return len(runs)


def phase_engine(key) -> dict:
    cfgs = [paper_scenarios()[n] for n in sorted(paper_scenarios())]
    n_nodes = len(cfgs[0].survivors) + 1
    kw = dict(n_runs=N_RUNS, max_failures=MAX_FAILURES, makespan_s=MAKESPAN_S)
    _check(not sweep._pallas_interpret(), "pallas would run interpreted")
    report = {}
    for name, hist in _histories(n_nodes).items():
        stats_by = {}
        hosts = _oracle(cfgs, key, hist, n_nodes)
        for engine in ("scan", "pallas"):
            summ, first = _clock(lambda: sweep.renewal_monte_carlo_scenarios(
                cfgs, key, engine=engine, **kw, **hist))
            stats, run_s = _clock(lambda: jax.device_get(
                sweep.renewal_monte_carlo_device(
                    cfgs, key, stats=True, engine=engine, **kw, **hist)))
            for s, cfg in enumerate(cfgs):
                _check(summ[cfg.name].mean_failures == float(
                    np.mean(stats.n_failures[s])), "summary vs stats")
                _check(np.isfinite(summ[cfg.name].mean_energy_int_j),
                       "non-finite summary")
            err = _oracle_error(stats, hosts)
            print(f"A {engine:6s} {name:11s} compile_s={first - run_s:.3f} "
                  f"run_s={run_s:.3f} oracle_max_rel_err={err:.3e}")
            _check(err <= TOL, f"{engine} {name}: oracle error {err}")
            stats_by[engine] = stats
            report[f"{engine}/{name}"] = err
            if name == "exponential" and engine == "scan":
                scan_exp = stats
        n_tie = _compare_integer_stats(cfgs, key, hist, n_nodes,
                                       stats_by["scan"], stats_by["pallas"])
        print(f"A integer stats pallas == scan ({name}): geometry stats on "
              f"all runs, decision counts on all but {n_tie} of "
              f"{len(cfgs) * N_RUNS} runs, each a decision tie")
        report[f"ties/{name}"] = n_tie
    _kahan_report(cfgs, key, scan_exp)
    return report


def _kahan_report(cfgs, key, scan) -> None:
    """Compensated vs naive float32 ledgers against the x64 scan at the
    exponential history (all 6 x 4096 runs)."""
    proc = failures.as_process(None, MTBF_S)
    _, stacked = sweep._renewal_device_inputs(cfgs, jnp.float32)
    args = (stacked, key, jnp.float32(MAKESPAN_S), proc)
    static = dict(n_runs=N_RUNS, max_failures=MAX_FAILURES)
    t = time.perf_counter()
    naive_fn = sweep._renewal_pallas_mc_jit.lower(
        *args, compensated=False, **static).compile()
    compile_s = time.perf_counter() - t
    _check("tpu_custom_call" in naive_fn.as_text(),
           "pallas engine did not compile to a TPU kernel")
    naive, run_s = _clock(lambda: jax.device_get(naive_fn(*args)))
    comp = jax.device_get(sweep._renewal_pallas_mc_jit(
        *args, compensated=True, **static))
    parts = []
    for field in ("energy_ref", "energy_int", "saving", "balanced_energy"):
        ref = np.asarray(getattr(scan, field), np.float64)
        e_c = float(np.sum(np.abs(np.asarray(comp[field], np.float64) - ref)))
        e_n = float(np.sum(np.abs(np.asarray(naive[field], np.float64) - ref)))
        parts.append(f"{field}={e_c:.6g}/{e_n:.6g}")
        if field in ("energy_ref", "saving"):
            _check(e_c < e_n, f"Kahan ledger lost to naive on {field}")
    print(f"A kahan compiled_kernel=tpu_custom_call compile_s={compile_s:.3f} "
          f"run_s={run_s:.3f} sum_abs_err_J compensated/naive: "
          + " ".join(parts))


# ---------------------------------------------------------------------------
# B. fleet advisory service
# ---------------------------------------------------------------------------

def _same_optimum(a, b) -> bool:
    return (a.best == b.best and a.knee == b.knee
            and np.array_equal(a.grid.mean_energy_j, b.grid.mean_energy_j)
            and np.array_equal(a.grid.mean_makespan_s, b.grid.mean_makespan_s))


def _fleet():
    return fleet.synthetic_fleet(FLEET_SIZE, seed=SEED, node_buckets=(4, 8),
                                 weibull_frac=0.0)


def phase_fleet(key) -> None:
    profiles = _fleet()
    buckets = sorted({p.bucket_key() for p in profiles})
    _check(len(buckets) == 2, f"expected two shape buckets, got {buckets}")
    advisor = fleet.FleetAdvisor(key=key)
    t = time.perf_counter()
    answers = advisor.advise(profiles)
    first = time.perf_counter() - t
    before = advisor.cache_stats()
    again, run_s = _clock(lambda: advisor.advise(profiles))
    after = advisor.cache_stats()
    _check(after.traces == before.traces, "repeated fleet retraced")
    _check(all(_same_optimum(a.optimum, b.optimum)
               for a, b in zip(answers, again)), "repeat answers differ")
    print(f"B fleet {len(profiles)} clusters {len(buckets)} buckets "
          f"n_runs={advisor.n_runs} compile_s={first - run_s:.3f} "
          f"run_s={run_s:.3f} advisories_per_s={len(profiles) / run_s:.1f} "
          f"traces={after.traces} repeat_new_traces=0")
    # one standalone check per bucket
    picks = [next(a for a in answers if a.profile.bucket_key() == b)
             for b in buckets]
    for a in picks:
        p = a.profile
        solo, solo_s = _clock(lambda: optimize.optimize_policy(
            p.scenario(), key, table=advisor.table,
            process=p.failure_process(), work_s=p.work_s,
            n_runs=advisor.n_runs, max_failures=advisor.max_failures))
        _check(_same_optimum(a.optimum, solo), f"{p.name}: batched != solo")
        print(f"B {p.name} (n={p.n_nodes}) batched == standalone "
              f"optimize_policy bit for bit (standalone {solo_s:.3f} s)")


# ---------------------------------------------------------------------------
# C. FT training through the compiled SSD kernel
# ---------------------------------------------------------------------------

def _train_model(use_kernel: bool):
    from repro.configs import get_config
    from repro.models import build_model
    cfg = get_config("mamba2-370m", num_layers=TRAIN_LAYERS,
                     use_flash_kernel=use_kernel)
    return cfg, build_model(cfg)


def phase_train(key) -> None:
    from repro.checkpoint.manager import CheckpointConfig
    from repro.data.pipeline import SyntheticLM
    from repro.ft.runtime import ClusterSpec, FailureInjector, FTTrainer
    from repro.launch.steps import make_train_step
    from repro.optim.adamw import AdamWConfig, adamw

    cfg, model = _train_model(use_kernel=True)
    params = model.init(key)
    opt = adamw(AdamWConfig(learning_rate=TRAIN_LR))
    state = (params, opt.init(params))
    pipe = SyntheticLM(vocab_size=cfg.vocab_size, seq_len=TRAIN_SEQ,
                       global_batch=TRAIN_BATCH, seed=SEED)
    t = time.perf_counter()
    step = jax.jit(make_train_step(model, opt)).lower(
        *state, pipe.batch_at(0)).compile()
    compile_s = time.perf_counter() - t
    _check("tpu_custom_call" in step.as_text(),
           "train step does not run the Pallas SSD kernel")
    ckpt_dir = ROOT / ".smoke_ckpt"
    shutil.rmtree(ckpt_dir, ignore_errors=True)
    try:
        # pods first save after step 1; pod 1 fails before step 3 -> roll
        # back to step 1, re-execute step 2, resync, run step 3
        trainer = FTTrainer(
            step_fn=step, pipeline=pipe, state=state,
            cluster=ClusterSpec(n_pods=2),
            ckpt_cfg=CheckpointConfig(root=str(ckpt_dir), interval_steps=2,
                                      phase_offset_steps=1),
            injector=FailureInjector({3: 1}))
        t = time.perf_counter()
        hist = trainer.run(TRAIN_STEPS)
        run_s = time.perf_counter() - t
    finally:
        shutil.rmtree(ckpt_dir, ignore_errors=True)
    losses = [h["loss"] for h in hist]
    _check(all(np.isfinite(losses)), f"non-finite loss {losses}")
    _check(len(trainer.events) == 1, "expected one failure event")
    ev = trainer.events[0]
    _check(ev["rollback_to"] == 1 and ev["reexec_steps"] == 1,
           f"unexpected rollback {ev}")
    _check(len(ev["decisions"]) == 1, "no Algorithm-1 decision")
    n_params = sum(x.size for x in jax.tree.leaves(params))
    mem = jax.devices()[0].memory_stats() or {}
    print(f"C {cfg.name} layers={cfg.num_layers} d_model={cfg.d_model} "
          f"params={n_params} batch={TRAIN_BATCH}x{TRAIN_SEQ} "
          f"compile_s={compile_s:.3f} run_s={run_s:.3f} "
          f"({TRAIN_STEPS} steps + {ev['reexec_steps']} re-executed, "
          f"{sum(m.saves for m in trainer.managers)} checkpoint saves) "
          f"loss {losses[0]:.4f} -> {losses[-1]:.4f} "
          f"peak_bytes_in_use={mem.get('peak_bytes_in_use')}")
    print(f"C failure@{ev['step']} pod{ev['pod']} rollback_to={ev['rollback_to']} "
          f"reexec_steps={ev['reexec_steps']} "
          f"saving_j={ev['saving_j']:.1f} decisions={ev['decisions']}")


# ---------------------------------------------------------------------------
# D. four chips: the sharded paths against their one-chip results
# ---------------------------------------------------------------------------

def _peaks() -> list:
    return [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
            for d in jax.local_devices()]


def phase_sharded_fleet(key) -> None:
    profiles = _fleet()
    plain, plain_s = _clock(
        lambda: fleet.FleetAdvisor(key=key).advise(profiles))
    before = _peaks()
    sharded, sharded_s = _clock(
        lambda: fleet.FleetAdvisor(key=key, shard=True).advise(profiles))
    after = _peaks()
    _check(all(_same_optimum(a.optimum, b.optimum)
               for a, b in zip(plain, sharded)), "sharded != unsharded")
    grew = [a > b for a, b in zip(after, before)]
    _check(all(grew[1:]), f"sharded advisor left devices idle: {grew}")
    print(f"D fleet sharded over {jax.local_device_count()} devices == "
          f"unsharded bit for bit ({len(profiles)} clusters; "
          f"unsharded {plain_s:.3f} s, sharded {sharded_s:.3f} s incl. "
          f"compile; peak bytes per device {after})")


def _worst_leaf_rel_err(tree, ref) -> tuple:
    """The largest ||a - b|| / ||b|| over the leaves, and that leaf's path."""
    flat = jax.tree_util.tree_flatten_with_path(jax.device_get(tree))[0]
    worst = (0.0, "")
    for (path, a), b in zip(flat, jax.tree.leaves(jax.device_get(ref))):
        a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
        err = float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))
        worst = max(worst, (err, jax.tree_util.keystr(path)))
    return worst


def phase_sharded_train(key) -> None:
    from repro.launch.mesh import make_mesh
    from repro.launch.steps import make_train_step
    from repro.optim.adamw import AdamWConfig, adamw
    from repro.parallel import sharding as shd
    from repro.data.pipeline import SyntheticLM

    # GSPMD cannot partition a Mosaic kernel, so both sides run the
    # reference SSD; the comparison is of the sharding, not the kernel
    cfg, model = _train_model(use_kernel=False)
    opt = adamw(AdamWConfig(learning_rate=TRAIN_LR))
    params = model.init(key)
    opt_state = opt.init(params)
    batch = SyntheticLM(vocab_size=cfg.vocab_size, seq_len=TRAIN_SEQ,
                        global_batch=TRAIN_BATCH, seed=SEED).batch_at(0)
    step = jax.jit(make_train_step(model, opt))

    mesh = make_mesh((2, 2), ("data", "model"), devices=jax.devices()[:4])
    pspecs = shd.param_specs(cfg, mesh, jax.eval_shape(lambda: params))
    put = lambda tree, specs: jax.device_put(tree, shd.named_tree(mesh, specs))
    sharded_in = (put(params, pspecs), put(opt_state, shd.opt_specs(pspecs)),
                  put(batch, shd.batch_specs(cfg, mesh, batch)))
    with mesh:
        (p4, s4, m4), t4 = _clock(lambda: step(*sharded_in))
    peaks = _peaks()
    devs = {d for leaf in jax.tree.leaves(p4) for d in leaf.sharding.device_set}
    _check(len(devs) == 4, f"sharded step ran on {len(devs)} devices")

    one = jax.device_put((params, opt_state, batch), jax.devices()[0])
    (_, s1, m1), t1 = _clock(lambda: step(*one))
    # planted fault: the gradient of half the batch, what one data shard
    # holds when the data-axis reduction is left out
    half = jax.tree.map(lambda x: x[:TRAIN_BATCH // 2], one[2])
    _, s_half, _ = step(one[0], one[1], half)

    l1, l4 = float(m1["total_loss"]), float(m4["total_loss"])
    loss_rel = abs(l4 - l1) / abs(l1)
    grad_err, grad_leaf = _worst_leaf_rel_err(s4["mu"], s1["mu"])
    fault_err, fault_leaf = _worst_leaf_rel_err(s_half["mu"], s1["mu"])
    print(f"D train step 2x2 mesh vs one device: loss {l1:.6f} vs {l4:.6f} "
          f"(rel {loss_rel:.3e}, tol {LOSS_TOL}); gradient worst leaf rel "
          f"err {grad_err:.3e} at {grad_leaf} (tol {GRAD_TOL}); planted "
          f"fault (half the batch) {fault_err:.3e} at {fault_leaf}; "
          f"one-device {t1:.3f} s, sharded {t4:.3f} s incl. compile; "
          f"peak bytes per device after the sharded step {peaks}")
    _check(loss_rel <= LOSS_TOL, "sharded loss out of tolerance")
    _check(grad_err <= GRAD_TOL, "sharded gradients out of tolerance")
    _check(fault_err > GRAD_TOL, "gradient check passes a planted fault")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform}",
              file=sys.stderr)
        return 1
    if len(jax.devices()) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(jax.devices())} devices", file=sys.stderr)
        return 1
    print(f"device {dev.device_kind} x{len(jax.devices())} jax {jax.__version__} "
          f"cache {os.environ['JAX_COMPILATION_CACHE_DIR']}")
    key = jax.random.PRNGKey(SEED)
    phases = ([phase_sharded_fleet, phase_sharded_train] if args.chips == 4
              else [phase_engine, phase_fleet, phase_train])
    t0 = time.perf_counter()
    for phase in phases:
        t = time.perf_counter()
        phase(key)
        print(f"{phase.__name__} wall_s={time.perf_counter() - t:.3f}")
    print(f"total wall_s={time.perf_counter() - t0:.3f}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
