#!/usr/bin/env python3
"""Calibration runs on the chip, several in one process (so compiles are
shared); not part of a benchmark run.

    python3 benchmarks/chip/calibrate.py readings --workload <cell> \\
        --seeds 101-112 --control-seeds 201-203 --seconds 30
    python3 benchmarks/chip/calibrate.py knee --workload <cell> \\
        --rates 0.2,0.3,0.4 --seed 7 --seconds 51

``readings`` runs the cell's served path on each seed, then the control
on each control seed (the cell served with the configuration's
``correct.control`` settings, such as the program's int8 path one
precision below the configuration's), and prints the output check's
numbers of each run beside its ``correct`` at the configuration's limits:
for each number, the lower reading of its limit is the largest over the
program's seeds, the upper reading the smallest over the control's.
``knee`` runs an open-loop cell at each offered rate and prints how its
queue and first-token times behave over the window.
"""
import argparse
import contextlib
import io
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))


def seeds(text: str):
    if not text:
        return []
    lo, _, hi = text.partition("-")
    return list(range(int(lo), int(hi or lo) + 1))


def quiet(fn, *a, **k):
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        return fn(*a, **k)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("mode", choices=("readings", "knee"))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", default="101-112")
    ap.add_argument("--control-seeds", default="201-203")
    ap.add_argument("--rates", default="")
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args()

    import harness
    import stats
    cell = harness.find_cell(args.workload)
    if args.mode == "readings":
        out = {"program": [], "control": []}
        control = cell.config["correct"]["control"]
        runs = [("program", s, None) for s in seeds(args.seeds)] + \
               [("control", s, control) for s in seeds(args.control_seeds)]
        for side, seed, control in runs:
            t = time.perf_counter()
            r, run = quiet(harness.run_cell, cell, seed, args.seconds, False,
                           time.perf_counter(), control=control)
            out[side].append(run.check)
            print(json.dumps({"side": side, "seed": seed, **run.check,
                              "correct": r["correct"],
                              "checks": r["checks"],
                              "attempted": r["attempted"],
                              "metrics": r["metrics"],
                              "memory_peak_bytes":
                                  r["device"]["memory_peak_bytes"],
                              "seconds": time.perf_counter() - t}),
                  flush=True)
        for name in (out["program"] or out["control"])[0]:
            lower = max((c[name] for c in out["program"]), default=None)
            upper = min((c[name] for c in out["control"]), default=None)
            print(json.dumps({"number": name, "lower": lower,
                              "upper": upper,
                              "ratio": upper / lower if upper and lower
                              else None}), flush=True)
        return 0

    for rate in [float(r) for r in args.rates.split(",")]:
        cell.traffic = {**cell.traffic, "arrivals": {
            **cell.traffic["arrivals"], "rate_per_s": rate}}
        r, w = quiet(harness.run_cell, cell, args.seed, args.seconds, False,
                     time.perf_counter())
        due = w.due_in_window()
        half = (w.start + w.end) / 2
        ttft = stats.ttft_s(due, w.cutoff)
        first = [v for x, v in zip(due, ttft) if x.due < half]
        second = [v for x, v in zip(due, ttft) if x.due >= half]
        print(json.dumps({
            "rate_per_s": rate, "requests": len(due),
            "ttft_p50_first_half_s": stats.percentile(first, 50),
            "ttft_p50_second_half_s": stats.percentile(second, 50),
            "no_first_token_at_close": sum(
                x.first_token is None or x.first_token > w.end for x in due),
            "unadmitted_at_close": sum(
                x.admitted is None or x.admitted > w.end for x in due),
            "metrics": r["metrics"], "correct": r["correct"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
