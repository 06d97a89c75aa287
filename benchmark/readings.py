"""Readings for the limits of ``correct``: the numbers a run compares,
for the program or for one of the configuration's controls, over many
seeds in one process (so that set-up and compilation are paid once).

``python3 benchmark/readings.py --workload <cell> --seeds 1,2,3
--seconds 10 [--control crashes_as_failed]`` prints one JSON line per
seed: the run's checks, ``ops_per_s``, ``attempted`` and ``failed``. A
control names a transform of the cell's generator module, which rewrites
each history's events before the checker sees them. The benchmark's own
runs never run a control.
"""

import time

T0 = time.monotonic()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", default=None)
    args = ap.parse_args(argv)
    here = os.path.dirname(os.path.abspath(__file__))
    sys.path[:] = [p for p in sys.path if os.path.abspath(p or ".") != here]
    sys.path.insert(0, ROOT)
    from benchmark import harness

    transform = None
    if args.control:
        cell = harness.find_cell(ROOT, args.workload, False)
        transform = getattr(harness.generator(ROOT, cell.config),
                            args.control)
    t_start = T0
    for seed in (int(s) for s in args.seeds.split(",")):
        out = harness.measure(ROOT, args.workload, seed, args.seconds,
                              False, t_start, transform=transform)
        print(json.dumps({"seed": seed, "control": args.control,
                          "correct": out["correct"],
                          "attempted": out["attempted"],
                          "failed": out["failed"],
                          "checks": {k: v["value"] for k, v in
                                     out["checks"].items()},
                          "metrics": {k: v["value"] for k, v in
                                      out["metrics"].items()}}),
              flush=True)
        gc.unfreeze()
        t_start = time.monotonic()
    return 0


if __name__ == "__main__":
    sys.exit(main())
