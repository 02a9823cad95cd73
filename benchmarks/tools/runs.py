"""Run a cell several times, one process each, and print every metric's
spread — how the bounds in ``BENCHMARK.json`` were measured.

    python benchmarks/tools/runs.py --workload <cell> --seeds 11,12,13 \
        [--seconds N] [--trace 0|1] [--sets 2] [--tag name]

Each run's full output goes to ``chiprun_out/<tag>_s<set>_<seed>.log``.
A spread is the distance between the first and third quartile
(``statistics.quantiles(values, n=4)``) as a share of the median. This
process never imports jax: a chip belongs to one process at a time.
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def spread(values) -> float:
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def main(argv) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--sets", type=int, default=1)
    ap.add_argument("--tag", default="runs")
    args = ap.parse_args(argv)
    bench = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    seconds = args.seconds if args.seconds is not None \
        else bench["run_seconds"]
    out_dir = os.path.join(REPO, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    seeds = [int(s) for s in args.seeds.split(",")]
    sets, bad = [], 0
    for k in range(args.sets):
        rows = []
        for seed in seeds:
            cmd = [sys.executable] + bench["command"][1:] + [
                "--workload", args.workload, "--seed", str(seed),
                "--seconds", str(seconds), "--trace", str(args.trace)]
            t = time.monotonic()
            proc = subprocess.run(cmd, cwd=REPO, capture_output=True,
                                  text=True)
            wall = time.monotonic() - t
            log = os.path.join(out_dir, f"{args.tag}_s{k}_{seed}.log")
            with open(log, "w") as f:
                f.write(proc.stdout + "\n--- stderr ---\n" + proc.stderr)
            lines = proc.stdout.strip().splitlines()
            try:
                res = json.loads(lines[-1]) if proc.returncode == 0 else None
            except (ValueError, IndexError):
                res = None
            if res is None:
                bad += 1
                print(f"set {k} seed {seed}: rc {proc.returncode}, no "
                      f"result ({wall:.0f}s); see {log}\n"
                      + proc.stderr[-1500:], flush=True)
                continue
            bad += 0 if res["correct"] else 1
            vals = {m: v["value"] for m, v in res["metrics"].items()}
            print(f"set {k} seed {seed}: correct {res['correct']} failed "
                  f"{res['failed']}/{res['attempted']} wall {wall:.0f}s "
                  f"mem {res['device'].get('memory_peak_bytes')} "
                  + json.dumps(vals), flush=True)
            for line in lines[:-1]:
                if line.startswith("check ") or "NOT OK" in line:
                    print("    " + line)
            rows.append(vals)
        sets.append(rows)
    for k, rows in enumerate(sets):
        if len(rows) < 2:
            continue
        for m in rows[0]:
            vals = [r[m] for r in rows if m in r]
            # the first run of the first set compiles: its set-up apart
            if m == "setup_s" and k == 0:
                print(f"set {k} {m}: first run {vals[0]} (compiles)")
                vals = vals[1:]
            if len(vals) >= 2:
                print(f"set {k} {m}: median {statistics.median(vals)} "
                      f"spread {spread(vals):.5f} min {min(vals)} "
                      f"max {max(vals)} n {len(vals)}")
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
