#!/usr/bin/env python3
"""Run one cell of the benchmark on the chips of this machine.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

Prints progress as JSON lines, then, as the last line of standard output,
the result object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the
cell's end-to-end metrics, or with ``--trace 1`` its per-layer metrics,
which profile a sub-window), ``device`` and ``checks``.  Exits non-zero,
printing no result, where JAX finds no TPU or fewer chips than the cell
asks for.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    import harness
    cell = harness.find_cell(args.workload)
    try:
        result, _ = harness.run_cell(cell, args.seed, args.seconds,
                                     bool(args.trace), T_START)
    except harness.NoChip as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 3
    harness.report(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
