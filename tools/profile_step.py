"""Where one benchmark cell's train step spends its device time, by the
program's own names: phase (``loss`` forward, backward, ``optimizer``),
module scope (``loss/bert/encoder/layer/self_attn``) and Pallas kernel
role (``fused_xent_fwd``). The one operator's reader of the scopes that
``nn.Layer.__call__`` and ``jit.TrainStep`` put on the compiled step.

    python tools/profile_step.py --workload <cell> --out DIR
        [--seed N] [--steps 10] [--depth 5] [--within SCOPE[,SCOPE...]]

Builds the cell's ``Loop`` through ``benchmarks.harness.context`` and the
cell's driver, warms it, traces ``--steps`` steps on the chip and keeps
the ``.xplane.pb`` under DIR. The ``XLA Ops`` events of a v5e profile
name an instruction and carry no ``op_name`` (looked for in their stats,
PR 24), so an event's scope comes from joining its instruction name with
the ``metadata={op_name=...}`` of the compiled step's text
(``TrainStep.lower(...).compile().as_text()``). A fusion has the op_name
of one of the operations fused into it: a weight's AdamW update that XLA
fused into the matmul of its gradient counts under that layer's backward.
Chip only, like the benchmark; :func:`summarize` is plain arithmetic and
is tested on a small recorded event list. This tool reads times; for
VALUES (which layer, in which pass, first makes a non-finite value, and
each layer's magnitudes step by step) see ``tools/find_nonfinite.py``.
"""
from __future__ import annotations

import argparse
import json
import os
import re
import sys
from typing import Dict, Iterable, List, Optional, Tuple

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

#: an event with no op_name: copies and transfers the compiler added
NO_SCOPE = "(no scope)"
_TRANSFORM = re.compile(r"^(jvp|transpose|vmap|remat|checkpoint|"
                        r"custom_jvp|custom_vjp|shard_map)\((.*)\)$")
_JIT = re.compile(r"^p?jit\(.*\)$")
_INSTRUCTION = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*?op_name=\"([^\"]+)\"")
_RESULT = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = (\(?[a-z]\w*\[[\d,]*\])")
_NUM_SUFFIX = re.compile(r"(\.\d+)+$")


def scopes_from_text(hlo_text: str) -> Dict[str, str]:
    """instruction name -> op_name, from a compiled module's text."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if m:
            out[m.group(1)] = m.group(2)
    return out


def shapes_from_text(hlo_text: str) -> Dict[str, str]:
    """instruction name -> its result's type and shape (a tuple's
    first), from a compiled module's text."""
    out = {}
    for line in hlo_text.splitlines():
        m = _RESULT.match(line)
        if m:
            out[m.group(1)] = m.group(2).lstrip("(")
    return out


def within(events: Iterable[Tuple[str, Optional[str], float]],
           names: Iterable[str], shapes: Optional[Dict[str, str]] = None
           ) -> Dict[str, dict]:
    """For each scope name: the seconds of the operations whose scope
    path holds it, by phase and by (operation family, result shape) —
    which instructions a layer's part is made of."""
    shapes = shapes or {}
    out = {n: {"total_s": 0.0, "by_phase": {}, "by_op": {}} for n in names}
    for name, op_name, seconds in events:
        phase, path = split_op_name(op_name)
        for n, row in out.items():
            if n not in path:
                continue
            key = (f"{_NUM_SUFFIX.sub('', name)} "
                   f"{shapes.get(name.removeprefix('kernel:'), '')}").strip()
            row["total_s"] += seconds
            row["by_phase"][phase] = row["by_phase"].get(phase, 0.0) + seconds
            row["by_op"][key] = row["by_op"].get(key, 0.0) + seconds
    for row in out.values():
        for k in ("by_phase", "by_op"):
            row[k] = sorted(row[k].items(), key=lambda kv: -kv[1])
    return out


def render_within(table: Dict[str, dict], top: int = 12) -> str:
    lines = []
    for name, row in table.items():
        phases = ", ".join(f"{p} {s:.4f}" for p, s in row["by_phase"])
        lines.append(f"\nwithin {name}: {row['total_s']:.4f} s ({phases})")
        lines += [f"| {op} | {s:.4f} |" for op, s in row["by_op"][:top]]
    return "\n".join(lines)


def split_op_name(op_name: Optional[str]) -> Tuple[str, List[str]]:
    """(phase, scope path) of an op_name such as
    ``jit(train_step)/transpose(jvp(loss))/bert/encoder/layer/mul``:
    jax wraps the first scope inside a differentiated region in
    ``jvp(...)`` and, for the backward ops, ``transpose(jvp(...))``. The
    path drops the module, inner ``jit(...)`` calls, the ``pallas`` guard
    above a kernel's role and the primitive at the end."""
    if not op_name:
        return "unattributed", []
    # an instruction XLA merged from several carries their op_names
    # joined by ';': the first stands for it
    parts = op_name.split(";")[0].split("/")
    if parts and _JIT.match(parts[0]):
        parts = parts[1:]
    parts = parts[:-1]                       # the primitive
    transforms, path = set(), []
    for part in parts:
        m = _TRANSFORM.match(part)
        while m:
            transforms.add(m.group(1))
            part = m.group(2)
            m = _TRANSFORM.match(part)
        if part and not _JIT.match(part) and part != "pallas":
            path.append(part)
    if "transpose" in transforms:
        phase = "backward"
    elif "jvp" in transforms:
        phase = "forward"
    elif path and path[0] == "optimizer":
        phase = "optimizer"
    else:
        phase = "other"
    return phase, path


def summarize(events: Iterable[Tuple[str, Optional[str], float]],
              depth: int = 5, top: int = 12) -> dict:
    """``events``: (instruction name, op_name or None, seconds) of one
    device's operations. Seconds by phase, by module scope cut at
    ``depth``, by Pallas kernel role (``kernel:`` names, numeric suffix
    merged), and for the ``top`` operation families the scopes that own
    them."""
    by_phase: Dict[str, float] = {}
    by_scope: Dict[str, float] = {}
    kernels: Dict[str, float] = {}
    by_op: Dict[str, Dict[str, float]] = {}
    total = 0.0
    for name, op_name, seconds in events:
        phase, path = split_op_name(op_name)
        scope = "/".join(path[:depth]) or NO_SCOPE
        family = _NUM_SUFFIX.sub("", name)
        total += seconds
        by_phase[phase] = by_phase.get(phase, 0.0) + seconds
        by_scope[scope] = by_scope.get(scope, 0.0) + seconds
        owners = by_op.setdefault(family, {})
        owners[scope] = owners.get(scope, 0.0) + seconds
        if family.startswith("kernel:"):
            role = family[len("kernel:"):]
            kernels[role] = kernels.get(role, 0.0) + seconds

    def ranked(table):
        return sorted(table.items(), key=lambda kv: -kv[1])

    ops = sorted(by_op.items(), key=lambda kv: -sum(kv[1].values()))[:top]
    return {"total_s": total, "by_phase": ranked(by_phase),
            "by_scope": ranked(by_scope), "kernels": ranked(kernels),
            "owners": [[op, sum(t.values()), ranked(t)] for op, t in ops]}


def self_seconds(events: Iterable[Tuple[str, float, float]]
                 ) -> List[Tuple[str, float]]:
    """``events``: (name, start, duration) of one device line, in the
    trace's whole nanoseconds (sums of seconds round, and an operation
    would then seem to start inside the one before it). An operation
    that encloses others (the ``cond`` around the branch it ran) keeps
    only the time its direct children do not cover, so the seconds add
    up to the device's busy time."""
    out: List[List] = []
    open_: List[Tuple[int, float]] = []     # (index in out, end)
    for name, start, duration in sorted(events,
                                        key=lambda e: (e[1], -e[2])):
        while open_ and open_[-1][1] <= start:
            open_.pop()
        if open_:
            out[open_[-1][0]][1] -= duration
        open_.append((len(out), start + duration))
        out.append([name, duration])
    return [(name, ns / 1e9) for name, ns in out]


def device_events(xplane_path: str) -> List[Tuple[str, float]]:
    """[(instruction name, self seconds)] of the first TPU plane's
    ``XLA Ops`` line, named as ``benchmarks/trace_reduce.py`` names
    them."""
    from jax.profiler import ProfileData

    from benchmarks import trace_reduce

    for plane in ProfileData.from_file(xplane_path).planes:
        if trace_reduce._DEVICE_PLANE.match(plane.name):
            return self_seconds(
                (trace_reduce.short_name(ev.name), ev.start_ns,
                 ev.duration_ns)
                for line in plane.lines
                if line.name == trace_reduce._OP_LINE
                for ev in line.events)
    return []


def render(summary: dict, steps: int) -> str:
    total = summary["total_s"]
    lines = [f"device seconds in {steps} traced steps: {total:.4f}"]

    def table(title, rows):
        lines.append(f"\n{title}")
        lines.append("| | s | share |")
        lines.append("| --- | --- | --- |")
        for name, s in rows:
            lines.append(f"| {name} | {s:.4f} | {100 * s / total:.1f}% |")

    table("by phase", summary["by_phase"])
    table("by module scope", summary["by_scope"])
    table("Pallas kernel roles", summary["kernels"])
    lines.append("\nwho owns the largest operation families")
    for op, s, owners in summary["owners"]:
        own = ", ".join(f"{scope} {100 * t / s:.0f}%"
                        for scope, t in owners[:4])
        lines.append(f"| {op} | {s:.4f} | {100 * s / total:.1f}% | {own} |")
    return "\n".join(lines)


def loop_and_batches(ctx, driver) -> tuple:
    """(the cell's ``Loop`` from ``ctx.seed``, its host batches): what
    this tool and ``tools/find_nonfinite.py`` drive."""
    from benchmarks import harness, traffic

    if not hasattr(driver, "Loop"):
        raise harness.Refused(f"the driver of {ctx.name} has no Loop: only "
                              "training cells have a step to drive")
    if hasattr(driver, "loop_and_batches"):     # a driver with a feed of
        return driver.loop_and_batches(ctx)                 # its own
    cfg, cell = ctx.config, ctx.cell
    batches = traffic.train_batches(cell["traffic"], cfg["vocab_size"],
                                    ctx.seed)
    return driver.Loop(cfg, cell, driver.make_params(cfg, ctx.seed),
                       ctx.seed), batches


def profile(workload: str, out: str, seed: int = 1, steps: int = 10,
            depth: int = 5, warm: int = 3, root: Optional[str] = None,
            check_device: bool = True, scopes_within: Tuple[str, ...] = ()
            ) -> dict:
    """Trace ``steps`` steps of the cell's loop and reduce the trace."""
    import jax

    from benchmarks import harness, trace_reduce

    ctx, driver, info = harness.context(
        workload, seed, 0.0, root or harness.ROOT, check_device)
    loop, batches = loop_and_batches(ctx, driver)
    loss = None
    for _ in range(warm):
        loss = loop.feed_and_step(batches[loop.steps % len(batches)])
    float(loss)                              # a fetch: the device is done
    os.makedirs(out, exist_ok=True)
    jax.profiler.start_trace(out)
    for _ in range(steps):
        loss = loop.feed_and_step(batches[loop.steps % len(batches)])
    float(loss)
    jax.profiler.stop_trace()
    path = trace_reduce.find_xplane(out)
    if path is None:
        raise harness.Refused(f"the profiler wrote no .xplane.pb under {out}")
    events = device_events(path)
    if not events:
        raise harness.Refused("the trace holds no device operation")
    import paddle_tpu as paddle

    batch = [paddle.to_tensor(a) for a in batches[0]]
    text = loop.step.lower(*batch).compile().as_text()
    scopes = scopes_from_text(text)
    events = [(n, scopes.get(n.removeprefix("kernel:")), s)
              for n, s in events]
    summary = summarize(events, depth)
    summary.update(workload=workload, steps=steps, device=info, xplane=path)
    print(f"{workload} on {info}: trace kept at {path} "
          f"({os.path.getsize(path)} bytes)")
    print(render(summary, steps))
    if scopes_within:
        summary["within"] = within(events, scopes_within,
                                   shapes_from_text(text))
        print(render_within(summary["within"]))
    return summary


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True,
                    help="a training cell of BENCHMARK.json")
    ap.add_argument("--out", required=True,
                    help="directory for the trace (.xplane.pb) and "
                         "summary.json")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--depth", type=int, default=5,
                    help="module scopes are cut at this depth")
    ap.add_argument("--within", default="",
                    help="scope names, comma-separated: for each, the "
                         "operations under it by family and result shape")
    args = ap.parse_args(argv)
    from benchmarks import harness

    try:
        summary = profile(
            args.workload, args.out, args.seed, args.steps, args.depth,
            scopes_within=tuple(n for n in args.within.split(",") if n))
    except harness.Refused as e:
        print(f"REFUSED: {e}", file=sys.stderr)
        return 2
    with open(os.path.join(args.out, "summary.json"), "w") as f:
        json.dump(summary, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
