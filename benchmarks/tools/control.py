"""Run a cell's control — the comparison behind ``correct`` with the
reference, computed one precision step lower, in the program's place —
on several seeds in one process. It has to come out NOT correct.

    python benchmarks/tools/control.py --workload <cell> --seeds 1,2,3 \
        [--seconds 10]

The benchmark's own runs never run this; a tier-1 test keeps it at a
size a test run can hold.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmarks import harness  # noqa: E402


def main(argv) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    escaped = 0
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.monotonic()
        ctx, driver, _info = harness.context(args.workload, seed,
                                             args.seconds)
        out = driver.control(ctx)
        checks = out.pop("checks")
        print(f"seed {seed} ({time.monotonic() - t:.0f}s): "
              + json.dumps(out, default=str), flush=True)
        for c in checks:
            print(f"seed {seed} control {c['name']}: value {c['value']} "
                  f"limit {c['limit']} -> "
                  f"{'passes (BAD)' if c['ok'] else 'fails, as it must'}"
                  + (f" (worst leaf {c['leaf']})" if "leaf" in c else ""),
                  flush=True)
        if all(c["ok"] for c in checks):
            escaped += 1
    print(f"control escaped the comparison on {escaped} seeds")
    return 1 if escaped else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
