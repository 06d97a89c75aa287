"""Benchmark entry: ``python3 benchmark/run.py --workload <cell> --seed <n>
--seconds <s> --trace <0|1>`` from the root of a checkout.

Prints the numbers compared with their limits as the last lines of
standard error, and the result as the last line of standard output:
``{"correct", "attempted", "failed", "metrics", "device"[, "breakdown"],
"checks"}``. Exits non-zero, and prints no result, where JAX finds no
TPU or fewer chips than the cell asks for.
"""

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    # The script's own directory holds packages (trace, metrics) that
    # must not shadow top-level modules: import them as benchmark.*.
    here = os.path.dirname(os.path.abspath(__file__))
    sys.path[:] = [p for p in sys.path if os.path.abspath(p or ".") != here]
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    from benchmark import harness

    try:
        out = harness.measure(ROOT, args.workload, args.seed, args.seconds,
                              bool(args.trace), T_START)
    except harness.NoAccelerator as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 2
    for name, c in out["checks"].items():
        print(f"{name} {c['value']} limit {c['limit']}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
