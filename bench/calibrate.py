"""Readings that a cell's correctness limits are set from, on the chip:

    python3 bench/calibrate.py --workload <name> --seeds 1,2,3 \\
        --control-seeds 4,5,6 --seconds 3

For every ``--seeds`` seed: a window of the program at the cell's own size
and load, and the numbers its check compares (the lower readings).  For
every ``--control-seeds`` seed: the same numbers with the plain reference
computed in float32 put in the program's place (the control, whose least
reading is the upper one).  One process, so the programs compile once;
one JSON line per reading on standard output.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parent.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args(argv)
    sys.path[:1] = [str(ROOT), str(ROOT / "src")]
    from bench import harness
    harness.use_checkout_cache()
    spec = harness.load_cell(args.workload)
    try:
        harness.check_chips(spec["cell"]["chips"])
    except harness.NoChip as e:
        print(f"calibrate: {e}", file=sys.stderr)
        return 2
    parse = lambda s: [int(x) for x in s.split(",") if x]
    for kind, seeds in (("program", parse(args.seeds)),
                        ("control", parse(args.control_seeds))):
        for seed in seeds:
            driver = harness.make_driver(spec, seed)
            driver.warmup()
            t0, t1, n_calls, items, failed, _ = harness.window(
                driver, args.seconds)
            t = time.perf_counter()
            found = driver.check(n_calls, spec["traffic"]["checks"],
                                 control=kind == "control")
            print(json.dumps(dict(
                workload=args.workload, kind=kind, seed=seed, calls=n_calls,
                failed=failed, window_s=t1 - t0,
                check_s=time.perf_counter() - t,
                **{k: float(v) for k, v in found.items()})), flush=True)
            driver.release()
    return 0


if __name__ == "__main__":
    sys.exit(main())
