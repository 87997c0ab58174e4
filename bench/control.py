#!/usr/bin/env python3
"""Run a cell with its control in the program's place, on the chip.

    python3 bench/control.py --workload <cell> --seed <n> --seconds <s>

Runs the cell as ``bench/run.py --trace 0`` does, then compares the
program's outputs with the plain reference (printed under ``program``),
and then the control's outputs in their place through the same
comparison: the reference one precision step below the configuration's
(for ``dave2``, one bfloat16 pass per product where the configuration
states ``high``). The line's ``correct`` and ``checks`` are the control's, and
``correct`` has to read false. The benchmark's own runs never run this;
the limits in each configuration's ``check`` group lie between the
program's readings and the control's.
"""
import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args()
    sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
    from bench import harness
    try:
        r = harness.run_cell(args.workload, args.seed, args.seconds, False,
                             control=True, t_start=T_START,
                             log=lambda m: print(m, file=sys.stderr))
    except harness.NoChip as e:
        print(e, file=sys.stderr)
        return 3
    print(json.dumps(dict(r, workload=args.workload, seed=args.seed)),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
