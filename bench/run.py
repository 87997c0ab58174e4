#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json``. Its inputs and
weights come from ``--seed``. After set-up (building, compiling or loading
from the compile cache, warming every shape the cell uses) the RT gang and
its best-effort co-runner run on ``GangExecutor`` for ``--seconds``. With
``--trace 0`` the result holds the end-to-end metrics, with ``--trace 1``
the per-layer metrics read from a profiler trace of the window.

The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1`` also
``breakdown``, and last ``checks``: each number compared with its limit).
The same numbers close standard error. A host without a TPU, or with fewer
chips than the cell needs, exits non-zero and prints no result.
"""
import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        log(f"no program sources (src/repro) beside {BENCH}")
        return 2
    sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
    from bench import harness

    try:
        result = harness.run_cell(args.workload, args.seed, args.seconds,
                                  bool(args.trace), t_start=T_START, log=log)
    except harness.NoChip as e:
        log(str(e))
        return 3
    for name, c in result["checks"].items():
        ok = harness.passes(c["value"], c["limit"], c["op"])
        log(f"check {name}: {c['value']} {c['op']} {c['limit']} "
            f"{'ok' if ok else 'FAILED'}")
    log(f"correct: {result['correct']}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
