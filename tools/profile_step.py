"""Capture an XPlane/TensorBoard profile of one bench config's train
step on the live chip (jax.profiler), for offline bottleneck analysis —
the resnet config sits at ~20% MFU vs BERT's 41%, and only a hardware
trace can say where the time goes.

Usage: python tools/profile_step.py [--config resnet] [--out DIR]
"""
from __future__ import annotations

import argparse
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def summarize_trace(out_dir: str, config: str, row: dict,
                    summary_path: str, top_k: int = 25) -> bool:
    """Aggregate the chrome-trace events jax.profiler wrote under
    `out_dir` into a committed markdown table: total device time by op
    name, top offenders first — the offline 'where does the non-MXU
    time go' answer VERDICT r4 #2 asks for, without needing the
    tensorboard profile plugin in the image."""
    import glob
    import gzip
    import json
    from collections import defaultdict

    traces = sorted(glob.glob(
        os.path.join(out_dir, "**", "*.trace.json.gz"), recursive=True))
    if not traces:
        print(f"no .trace.json.gz under {out_dir}; summary skipped")
        return False
    with gzip.open(traces[-1], "rt") as f:
        data = json.load(f)
    events = data.get("traceEvents", [])
    pid_names = {e.get("pid"): e.get("args", {}).get("name", "")
                 for e in events if e.get("name") == "process_name"}
    device_pids = {p for p, n in pid_names.items()
                   if "TPU" in str(n) or "/device" in str(n).lower()}
    # a device pid carries several thread lines ("XLA Modules", "Steps",
    # "XLA Ops"); module/step spans equal the SUM of the op events below
    # them, so summing across tids double-counts — keep op-level only
    tid_names = {(e.get("pid"), e.get("tid")):
                 str(e.get("args", {}).get("name", ""))
                 for e in events if e.get("name") == "thread_name"}
    # explicit op-line match: a substring like "op" also hits
    # "TensorFlow Name Scope" (sc-op-e), whose hierarchical spans
    # already contain every op under them — double counting
    op_tids = {k for k, n in tid_names.items()
               if k[0] in device_pids and "xla ops" in n.lower()}

    per_tid = defaultdict(lambda: defaultdict(float))
    counts = defaultdict(int)
    for e in events:
        if e.get("ph") != "X" or e.get("pid") not in device_pids:
            continue
        key = (e.get("pid"), e.get("tid"))
        if op_tids and key not in op_tids:
            continue
        dur = float(e.get("dur", 0.0))   # microseconds
        name = str(e.get("name", "?"))
        # fold fusion instances: fusion.123 -> fusion; keep op kind
        base = name.split(".")[0] if name.split(".")[-1].isdigit() else name
        per_tid[key][base] += dur
        counts[key] += 1
    if not per_tid:
        print("trace had no device events; summary skipped")
        return False
    if op_tids:
        # merge the explicit op-level threads (one per core)
        agg = defaultdict(float)
        for t in per_tid.values():
            for k, v in t.items():
                agg[k] += v
    else:
        # no thread_name metadata: the op line has by far the most
        # events (module/step lines have a handful of giant spans)
        agg = per_tid[max(counts, key=counts.get)]
    total = sum(agg.values())
    rows = sorted(agg.items(), key=lambda kv: -kv[1])[:top_k]
    from tools._captures import git_sha

    with open(summary_path, "a") as f:
        f.write(f"\n## {config} @ {row.get('device_kind', '?')} "
                f"(sha {git_sha()}, {row.get('value')} {row.get('unit')}"
                f", mfu {row.get('mfu')})\n\n")
        f.write("| op | device ms | % of device time |\n|---|---|---|\n")
        for name, us in rows:
            f.write(f"| {name} | {us / 1e3:.2f} | "
                    f"{100.0 * us / total:.1f}% |\n")
        f.write(f"| TOTAL (all ops) | {total / 1e3:.2f} | 100% |\n")
    print(f"summary appended to {summary_path} "
          f"({len(rows)} rows, total {total / 1e3:.1f} ms device time)")
    return True


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="resnet")
    ap.add_argument("--out", default="/tmp/paddle_tpu_profile")
    ap.add_argument("--steps", type=int, default=5)
    ap.add_argument("--summary", default=None,
                    help="markdown file to append a device-time-by-op "
                         "table to (e.g. XPLANE_SUMMARY.md)")
    args = ap.parse_args()

    import jax

    from paddle_tpu.framework.bringup import TPU_PLATFORMS

    backend = jax.default_backend()
    if backend not in TPU_PLATFORMS:
        print(f"backend {backend!r}: profiling a CPU run is not useful")
        return 1

    import bench

    os.environ.setdefault("BENCH_STEPS", str(args.steps))
    os.makedirs(args.out, exist_ok=True)
    with jax.profiler.trace(args.out):
        row = bench.CONFIGS[args.config](False)
    bench.attach_mfu(row)
    print({k: row.get(k) for k in ("value", "unit", "dt", "steps", "mfu")})
    print(f"trace written under {args.out} (tensorboard --logdir {args.out})")
    if args.summary:
        summarize_trace(args.out, args.config, row, args.summary)
    return 0


if __name__ == "__main__":
    sys.exit(main())
