"""Where one benchmark cell's train step first makes a non-finite value:
which layer, in which pass, and how the magnitudes grew in the steps
before. The one operator's reader of the record that a ``jit.TrainStep``
built under ``FLAGS_check_nan_inf`` computes beside its loss
(``paddle_tpu/framework/nan_inf.py``), as ``tools/profile_step.py`` is
the reader of the step's scopes: that tool for times, this one for
values.

    python tools/find_nonfinite.py --workload <cell> --seed N[,N...]
        [--steps 100] [--flag 1|0] [--history 10] [--out DIR]

Builds the cell's ``Loop`` through ``benchmarks.harness.context`` and the
cell's driver with the flag set, drives it from the seed through the
window's own ``feed_and_step`` (the first steps are the ones the
benchmark checks, the rest its window's), fetches the record after every
step and stops at the first step whose record holds a non-finite value.
It prints the step, ``first_nonfinite`` and its pass, the keys that are
non-finite in that step and in the step before, and every probe's and
every changing key's ``absmax`` over the last ``--history`` steps (growth
step over step is an overflow; a sudden NaN behind small maxima is 0/0 or
inf - inf); the whole table goes to ``DIR/<cell>.<seed>.json``. A run
that stays finite prints each probe's largest ``absmax`` (smallest
``absmin``) over all its steps. ``--flag 0`` drives the same steps with
the flag off and watches the loss alone: whether the seed reads NaN in
the program the benchmark runs, and what the record costs (both modes
fetch once a step and print tokens/s, the first step's seconds and the
device's peak memory). Chip only, like the benchmark; :func:`render` is
plain arithmetic and is tested on a small recorded history.
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import os
import sys
import time
from collections import deque
from typing import Dict, List, Optional, Sequence, Tuple

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [REPO, os.path.join(REPO, "tools")]      # profile_step

#: a record as this tool keeps it: (step, {key: {"pass", "nonfinite",
#: "absmax"[, "absmin"]}}); steps count from 1
Step = Tuple[int, Dict[str, dict]]


def _num(x: float) -> str:
    return f"{x:.3g}"


def nonfinite_keys(record: Dict[str, dict]) -> List[str]:
    return [f"{k} ({v['pass']}, {v['nonfinite']} elements)"
            for k, v in record.items() if v["nonfinite"]]


def render(history: Sequence[Step], first: Optional[str],
           first_pass: Optional[str], grew: float = 4.0) -> str:
    """The report of a run that stopped at ``history[-1]`` (or ran out of
    steps there, ``first`` None): see the module docstring. A key is
    listed with its ``absmax`` over the history if it is a probe (its
    name holds ``/``), is or was non-finite, or its largest reading is
    more than ``grew`` times its smallest."""
    step, last = history[-1]
    lines = []
    if first is None:
        lines.append(f"finite through step {step}")
    else:
        lines.append(f"step {step}: first non-finite value at {first!r}, "
                     f"{first_pass} pass")
        lines.append("non-finite in that step: "
                     + "; ".join(nonfinite_keys(last)))
        if len(history) > 1:
            before = nonfinite_keys(history[-2][1])
            lines.append(f"non-finite in step {history[-2][0]}: "
                         + ("; ".join(before) if before else "nothing"))
    steps = [s for s, _ in history]
    lines.append(f"\nabsmax by step ({steps[0]}..{steps[-1]}; ! marks a "
                 "non-finite element; ~ the smallest finite |x|)")
    lines.append("| key | pass | " + " | ".join(str(s) for s in steps) + " |")
    lines.append("| --- | --- |" + " --- |" * len(steps))
    for key, row in last.items():
        cells = [rec.get(key) for _, rec in history]
        seen = [c["absmax"] for c in cells if c is not None]
        bad = any(c is not None and c["nonfinite"] for c in cells)
        low = min(seen)
        if not ("/" in key or bad or max(seen) > grew * low):
            continue
        lines.append(f"| {key} | {row['pass']} | " + " | ".join(
            "" if c is None else _num(c["absmax"])
            + ("!" if c["nonfinite"] else "")
            + (f" ~{_num(c['absmin'])}" if "absmin" in c else "")
            for c in cells) + " |")
    return "\n".join(lines)


def extremes(largest: Dict[str, float], smallest: Dict[str, float]) -> str:
    """Of a run that stayed finite: each probe's largest ``absmax`` (and
    smallest ``absmin``) over all its steps."""
    lines = ["largest absmax over the run, by probe:"]
    for key, big in largest.items():
        if "/" in key:
            lines.append(f"| {key} | {_num(big)} |" + (
                f" smallest |x| {_num(smallest[key])} |"
                if key in smallest else ""))
    return "\n".join(lines)


def find(workload: str, seed: int, steps: int = 100, flag: bool = True,
         history: int = 10, out: Optional[str] = None,
         root: Optional[str] = None, check_device: bool = True) -> dict:
    """Drive the cell's loop from ``seed`` for up to ``steps`` steps and
    report: see the module docstring."""
    import numpy as np

    from benchmarks import harness
    from paddle_tpu.framework import flags
    from profile_step import loop_and_batches

    ctx, driver, info = harness.context(
        workload, seed, 0.0, root or harness.ROOT, check_device)
    was = flags.get_flag("check_nan_inf")
    # read when the step is built, at the loop's first call
    flags.set_flags({"check_nan_inf": bool(flag)})
    try:
        loop, batches = loop_and_batches(ctx, driver)
        tokens = int(np.asarray(batches[0][0]).size)
        kept: deque = deque(maxlen=history)
        largest: Dict[str, float] = {}
        smallest: Dict[str, float] = {}
        first = first_pass = None
        losses, first_step_s, t_rest = [], None, None
        for _ in range(steps):
            t0 = time.monotonic()
            loss = loop.feed_and_step(batches[loop.steps % len(batches)])
            if flag:
                record = loop.step.numerics()
                kept.append((loop.steps, dict(record)))
                for k, v in record.items():
                    largest[k] = max(largest.get(k, 0.0), v["absmax"])
                    if "absmin" in v:
                        smallest[k] = min(smallest.get(k, math.inf),
                                          v["absmin"])
                losses.append(float(record["loss"]["absmax"])
                              if not record["loss"]["nonfinite"]
                              else math.nan)
                first, first_pass = (record.first_nonfinite,
                                     record.first_pass)
            else:
                losses.append(float(loss))      # the fetch, a barrier
                if not math.isfinite(losses[-1]):
                    first, first_pass = "loss", "forward"
            if first_step_s is None:
                first_step_s = time.monotonic() - t0
                t_rest = time.monotonic()
            if first is not None:
                break
        timed = loop.steps - 1
        rate = tokens * timed / (time.monotonic() - t_rest) if timed else None
    finally:
        flags.set_flags({"check_nan_inf": was})
    peak = harness.memory_peak_bytes(ctx.chips)
    result = {
        "workload": workload, "seed": seed, "flag": bool(flag),
        "device": info, "steps": loop.steps, "first_nonfinite": first,
        "pass": first_pass, "losses": losses,
        "first_step_s": first_step_s, "tokens_per_s": rate,
        "memory_peak_bytes": peak,
        "history": [{"step": s, "record": r} for s, r in kept],
        "largest_absmax": largest, "smallest_absmin": smallest}
    print(f"\n== {workload} seed {seed} flag {'on' if flag else 'off'} on "
          f"{info}: {loop.steps} steps, first step {first_step_s:.1f} s, "
          f"then {rate and round(rate, 1)} tokens/s with a fetch a step; "
          f"peak memory {peak} bytes")
    shown = losses if len(losses) <= 12 else losses[:3] + ["..."] + losses[-8:]
    print(f"loss by step: {shown}")
    if flag:
        print(render(list(kept), first, first_pass))
        if first is None:
            print(extremes(largest, smallest))
    elif first is None:
        print(f"every loss finite through step {loop.steps}")
    else:
        print(f"step {loop.steps}: the loss is {losses[-1]} (the flag is "
              "off: no record says where)")
    if out:
        os.makedirs(out, exist_ok=True)
        path = os.path.join(
            out, f"{workload}.{seed}.{'on' if flag else 'off'}.json")
        with open(path, "w") as f:
            json.dump(result, f, indent=1)
        print(f"kept at {path}")
    del loop
    gc.collect()                # the next seed needs the device's memory
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True,
                    help="a training cell of BENCHMARK.json")
    ap.add_argument("--seed", required=True,
                    help="one seed, or several separated by commas")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--flag", type=int, default=1, choices=(0, 1),
                    help="0: FLAGS_check_nan_inf off, watch the loss alone")
    ap.add_argument("--history", type=int, default=10,
                    help="steps of the record kept and printed")
    ap.add_argument("--out", default=None,
                    help="directory for <cell>.<seed>.<on|off>.json")
    args = ap.parse_args(argv)
    from benchmarks import harness

    try:
        for seed in args.seed.split(","):
            find(args.workload, int(seed), args.steps, bool(args.flag),
                 args.history, args.out)
    except harness.Refused as e:
        print(f"REFUSED: {e}", file=sys.stderr)
        return 2
    return 0


if __name__ == "__main__":
    sys.exit(main())
