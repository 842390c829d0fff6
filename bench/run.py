"""Run one benchmark cell once on the chip this process finds:

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is the result as one JSON object.  Exits
non-zero, printing no result, when JAX finds no TPU or fewer chips than
the cell asks for.  JAX's persistent compilation cache lives in
``.bench_cache/jax`` of the checkout, so only a checkout's first run of a
cell compiles.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parent.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    # the checkout's root and the program, in place of this script's own
    # directory (whose module names would shadow the standard library's)
    sys.path[:1] = [str(ROOT), str(ROOT / "src")]
    from bench import harness
    harness.use_checkout_cache()
    return harness.main(args, T_PROCESS)


if __name__ == "__main__":
    sys.exit(main())
