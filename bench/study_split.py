"""Where a study's time goes, by the program's own spans and scopes:

    python3 bench/study_split.py --workload <name> --seed <n> --seconds <s>

Runs the cell's driver as ``bench/run.py --trace 1`` does (set-up, warm-up,
one traced closed-loop window) on the chip it finds, and prints one JSON
object of milliseconds per study (``bench/spans.py``): the device's self
time under each scope of the renewal engines and outside them, and the
device-idle time inside a study by the program span, the JAX event or the
driver that held the host.  Exits 2 without a TPU.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parent.parent


def study_hlo(driver) -> str:
    """Compiled text of the program each study of an ``mc_study`` driver
    runs, which maps a trace's operation names to their scopes: neither a
    TPU's nor a CPU's operation events carry the metadata name."""
    import jax
    from repro.core import sweep
    with jax.enable_x64():
        _, stacked = sweep._renewal_device_inputs(driver.cfgs)
        return sweep._renewal_mc_jit.lower(
            stacked, driver.keys[0], float(driver.makespan_s),
            driver.process, n_runs=driver.n_runs,
            max_failures=driver.max_failures, stats=True,
            topology=driver.topology).compile().as_text()


def split(driver, seconds: float) -> dict:
    """One traced window of ``driver`` (warmed up), reduced to
    milliseconds per study."""
    from bench import harness, spans
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
    try:
        t0, t1, n_calls, _, _, _ = harness.window(driver, seconds, trace_dir)
        got = spans.reduce(trace_dir, study_hlo(driver))
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    ms = lambda s: 1e3 * s / got["studies"]
    return dict(
        studies=got["studies"], calls=n_calls, window_s=t1 - t0,
        busy_ms=ms(got["busy_s"]),
        scope_ms={k: ms(v) for k, v in got["scopes_s"].items()},
        unscoped_ms=ms(got["unscoped_s"]),
        program_idle_ms={k: ms(v) for k, v in got["program_idle_s"].items()},
        jax_idle_ms={k: ms(v) for k, v in got["jax_idle_s"].items()},
        driver_idle_ms=ms(got["driver_idle_s"]))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args(argv)
    sys.path[:1] = [str(ROOT), str(ROOT / "src")]
    from bench import harness
    harness.use_checkout_cache()
    spec = harness.load_cell(args.workload)
    try:
        harness.check_chips(spec["cell"]["chips"])
    except harness.NoChip as e:
        print(f"study_split: {e}", file=sys.stderr)
        return 2
    driver = harness.make_driver(spec, args.seed)
    driver.warmup()
    out = dict(workload=args.workload, seed=args.seed,
               setup_s=time.perf_counter() - T_PROCESS,
               **split(driver, args.seconds))
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
