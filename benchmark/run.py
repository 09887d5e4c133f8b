"""Entry point: one run of one cell.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up (data from the seed, the program's precomputed views, compile or
cache load, one warm-up of every shape) is timed from here, the process's
first line, to the window's start. The last line of standard output is the
result. With no TPU, or fewer chips than the cell asks for, the run exits
non-zero and prints no result. ``--rehearse 1`` is the CPU rehearsal: the
caller pins ``JAX_PLATFORMS=cpu``, sizes come from the traffic file's
``rehearsal`` block, and no device metric is printed.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=[0, 1], default=0)
    p.add_argument("--rehearse", type=int, choices=[0, 1], default=0)
    args = p.parse_args(argv)

    from benchmark import harness

    return harness.run_cell(ROOT, args.workload, args.seed, args.seconds,
                            bool(args.trace), bool(args.rehearse), T_START)


if __name__ == "__main__":
    sys.exit(main())
