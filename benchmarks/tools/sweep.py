"""The knee sweep of a serving cell: one engine, one window per offered
rate, everything else as in the cell's file.

    python benchmarks/tools/sweep.py --workload <cell> \
        --rates 1,1.5,2,2.5,3 --seconds 40 --seed 7

The knee is the highest swept rate at which no request is refused or
fails and the requests in flight at the window's end are no more than
``max_batch`` above those at its start (none: the engine is drained
between windows). The cell's ``rate_rps`` is 0.8 of it.
"""
from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmarks import harness, traffic  # noqa: E402


def main(argv) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args(argv)
    ctx, driver, _info = harness.context(args.workload, args.seed,
                                         args.seconds)
    cell, config = ctx.cell, ctx.config
    _params, eng = driver._set_up(ctx)
    max_batch = int(cell["engine"]["max_batch"])
    print("rate_rps attempted failed in_flight_end tokens/s ttft_p50 "
          "ttft_p95 gap_p50 gap_p95 late_p95 sustained", flush=True)
    for rate in (float(r) for r in args.rates.split(",")):
        mix = dict(cell["traffic"], rate_rps=rate)
        reqs = traffic.open_loop_requests(mix, config["vocab_size"],
                                          args.seconds, args.seed)
        t = time.monotonic()
        win = driver.serve_window(eng, reqs, args.seconds,
                                  drain_s=float(cell["drain_s"]))
        red = driver.reduce_window(win, args.seconds)
        p = harness.percentile
        ok = red["failed"] == 0 and red["in_flight_end"] <= max_batch
        print(f"{rate} {red['attempted']} {red['failed']} "
              f"{red['in_flight_end']} "
              f"{red['tokens_in_window'] / args.seconds:.1f} "
              f"{p(red['ttft_ms'], 50):.1f} {p(red['ttft_ms'], 95):.1f} "
              f"{p(red['gap_ms'], 50):.2f} {p(red['gap_ms'], 95):.2f} "
              f"{p(red['late_ms'], 95):.2f} {ok} "
              f"(window+drain {time.monotonic() - t:.0f}s)", flush=True)
    eng.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
