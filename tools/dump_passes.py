"""Per-pass op-count / timing table for a static Program.

The CLI face of static/passes.py (reference: the --print_ir flavor of
build_strategy + graph_viz_pass): run the IR pass pipeline over a
program and print what each pass removed and how long it took, without
executing anything.

Usage:
    # serialized program (static.save_program output, e.g. the
    # main_program file save_train_program writes)
    python tools/dump_passes.py path/to/main_program --fetch loss_name

    # save_inference_model directory (feed/fetch read from the blob)
    python tools/dump_passes.py path/to/inference_dir

    # built-in demo program (no artifact needed)
    python tools/dump_passes.py --demo

    # graphviz dump of the optimized block, viz.py style
    python tools/dump_passes.py --demo --dot /tmp/optimized.dot

Knobs off by name: --disable fuse_elewise_add_act_ops,cse

Mixed precision: --amp [bf16|fp16] enables the auto_mixed_precision
pass and prints a per-op dtype table (inserted/elided casts, f32-pinned
ops, low-precision ops) after the usual per-pass report.

Rematerialization: --remat [N] enables the recompute_segmentation pass
(N segments; omit N for the automatic sqrt split, or pass checkpoint
var names via --checkpoints a,b) and prints the per-segment table: ops
per segment, stashed (boundary) vs recomputed (interior) var counts and
estimated bytes.

Sharding: --sharding [dp=2,tp=2] enables the shard_propagation pass
over that mesh shape and prints the per-var PartitionSpec table (hint
vs propagated vs conflict-replicated). Seed specs ride --shard-hints
"w0=-,tp;w1=tp,-" (dims comma-separated, '-' = replicated,
'dp+sp' = multi-axis dim); without hints the demo auto-hints the first
divisible 2-D parameters column-/row-parallel so the psum accounting
shows up. No devices are touched — the pass is pure annotation.

Quantized collectives: --comm [int8|bf16] enables the comm_bucketing
pass over a pure-dp mesh (--sharding dp=N, default dp=8) and prints
the per-bucket size/order/codec table: the gradient buckets in
backward-completion order with their f32 vs encoded ring bytes.
Bucket size rides --comm-bucket-bytes (default 1 MiB).

Pipelining: --pipeline [S] stamps pipeline_stages=S (with
--microbatches M as gradient_merge_k) and prints the tick-by-tick
schedule timeline grid for --schedule [gpipe|1f1b|interleaved] plus
the modeled bubble fractions of all three schedules at (S, M) — the
same parallel.pipeline generators the compiled step replays.

ZeRO: --zero [2|3] plans the sharded-optimizer decomposition over the
comm buckets (implies --comm int8 over dp=8) and prints the per-bucket
state-bytes table: replicated vs per-device (g, chunk) row bytes and
the saved fraction — or the counted refusal reason when the build
falls back to the replicated step.
"""
from __future__ import annotations

import argparse
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def _demo_program():
    """A small training program with food for every pass."""
    import paddle_tpu.static as static

    main, startup = static.Program(), static.Program()
    with static.program_guard(main, startup):
        x = static.data("x", [-1, 16])
        label = static.data("label", [-1, 1], dtype="int64")
        h = static.nn.fc(x, 32, act="relu")
        h = static.scale(h, scale=1.0)
        a = static.reduce_mean(h, dim=[1], keep_dim=True)
        b = static.reduce_mean(h, dim=[1], keep_dim=True)
        h = static.elementwise_add(static.elementwise_sub(h, a),
                                   static.elementwise_sub(h, b))
        c = static.elementwise_mul(
            static.fill_constant([1], "float32", 0.5),
            static.fill_constant([1], "float32", 4.0))
        h = static.elementwise_mul(h, c)
        static.nn.fc(h, 8)  # dead branch
        logits = static.nn.fc(h, 4)
        loss = static.mean(
            static.softmax_with_cross_entropy(logits, label))
        static.SGD(0.01).minimize(loss)
    return main, ["x", "label"], [loss.name]


def _load_target(path):
    """Resolve (program, feeds, fetches) from a serialized program file
    or a save_inference_model directory."""
    import paddle_tpu.static as static

    if os.path.isdir(path):
        from paddle_tpu.io.serialization import _load_pickle

        blob = _load_pickle(os.path.join(path, "__model__"))
        program = static.Program.from_dict(blob["program"])
        meta = blob["meta"]
        return program, meta["feed_names"], meta["fetch_names"]
    program = static.load_program(path)
    return program, [], []


def _amp_table(program, report):
    """Per-op dtype table of the optimized block: which ops run low
    precision, which are f32-pinned, where casts were inserted."""
    from paddle_tpu.static.passes import _LOW_PRECISION, _amp_lists

    _, black = _amp_lists()
    blk = program.global_block
    lines = [f"{'#':>3} {'op':<26}{'out dtype':<12}{'amp':<12}outputs"]
    for i, op in enumerate(blk.ops):
        outs = op.output_names()
        dts = {str(getattr(blk.vars.get(n), "dtype", "?")) for n in outs}
        if op.type == "cast":
            note = ("cast" if not any(
                "@amp." in n for n in outs + op.input_names())
                else "cast(amp)")
        elif op.type in black:
            note = "f32-pinned"
        elif dts & _LOW_PRECISION:
            note = "lowprec"
        else:
            note = "-"
        lines.append(f"{i:>3} {op.type:<26}"
                     f"{','.join(sorted(dts)) or '-':<12}{note:<12}"
                     f"{','.join(outs)[:44]}")
    if report.amp:
        lines.append("amp counters: " + "  ".join(
            f"{k}={v}" for k, v in sorted(report.amp.items())))
    return "\n".join(lines)


def _parse_shard_hints(spec, program, mesh_shape):
    """'w0=-,tp;w1=tp,-' -> {name: spec tuple}. With no spec given,
    auto-hint: the first 2-D trainable params whose dims divide the
    'tp' axis get column-/row-parallel seeds, so the demo's propagation
    (and the psum on the row-parallel contraction) is visible without
    memorizing parameter names."""
    if spec:
        hints = {}
        for entry in spec.split(";"):
            entry = entry.strip()
            if not entry:
                continue
            name, _, dims = entry.partition("=")
            parsed = []
            for d in dims.split(","):
                d = d.strip()
                if d in ("", "-", "None"):
                    parsed.append(None)
                elif "+" in d:
                    parsed.append(tuple(a for a in d.split("+") if a))
                else:
                    parsed.append(d)
            hints[name.strip()] = tuple(parsed)
        return hints
    tp = mesh_shape.get("tp", 0)
    if tp <= 1:
        return {}
    hints, want = {}, [(1, (None, "tp")), (0, ("tp", None))]
    for p in program.all_parameters():
        if not want:
            break
        shape = p.shape or ()
        if len(shape) != 2:
            continue
        dim, spec_t = want[0]
        if shape[dim] and shape[dim] % tp == 0:
            hints[p.name] = spec_t
            want.pop(0)
    return hints


def _timeline_table(schedule, s_count, m_count, interleave):
    """Tick-by-tick grid of the compiled schedule (rows = stages,
    columns = ticks, F<m>/B<m> slots) + the modeled bubble comparison
    across all three schedules at the same (S, M)."""
    from paddle_tpu.parallel.pipeline import (pipeline_timeline,
                                              schedule_bubble_fraction)

    grid, ticks = {}, 0
    for t, slots in pipeline_timeline(schedule, s_count, m_count,
                                      interleave):
        ticks = max(ticks, t + 1)
        for kind, s, m in slots:
            grid[(s, t)] = f"{kind}{m}"
    w = max(2, len(str(m_count - 1)) + 1)
    head = f"{schedule} timeline: S={s_count} M={m_count}"
    if schedule == "interleaved":
        head += f" v={interleave}"
    lines = [head,
             "stage " + " ".join(f"{t:>{w}}" for t in range(ticks))]
    for s in range(s_count):
        lines.append(f"{s:>5} " + " ".join(
            f"{grid.get((s, t), '.'):>{w}}" for t in range(ticks)))
    lines.append("modeled bubble fraction: " + "  ".join(
        f"{name}={schedule_bubble_fraction(name, s_count, m_count, interleave):.4f}"
        for name in ("gpipe", "1f1b", "interleaved")))
    return "\n".join(lines)


def _zero_state_table(program, strategy, stage):
    """Per-bucket replicated vs per-device optimizer-state bytes under
    the ZeRO plan — or the counted refusal reason on fallback."""
    from paddle_tpu.static import passes as passes_mod
    from paddle_tpu.static.stepplan import (zero_eligibility,
                                            zero_state_layout)

    comm = passes_mod.resolve_comm(strategy)
    shard_cfg = passes_mod.resolve_sharding(strategy)
    axis = passes_mod.comm_data_axis(shard_cfg)
    block = program.global_block
    comm_plan = None
    if comm is not None and axis is not None:
        cplan = passes_mod.comm_bucket_plan(block, comm, axis[1])
        if cplan:
            comm_plan = (axis[0], axis[1], cplan)
    reasons = []

    def bump(cat, kind, reason=None):
        if reason:
            reasons.append(reason)

    _, plan = zero_eligibility(
        program, block, stage, comm, comm_plan, shard_cfg,
        passes_mod.resolve_gradient_merge(strategy),
        passes_mod.resolve_pipeline(strategy), (), bump=bump)
    if plan is None:
        return ("zero refused (replicated fallback): "
                + (reasons[0] if reasons else "(no reason recorded)"))
    g = plan["group"]
    lines = [f"zero stage {plan['stage']} over axis {plan['axis']!r} "
             f"(g={g}): one (g, chunk) f32 row per (bucket, role)",
             f"{'bucket':>6}  {'opt':<10}{'params':>7}{'elems':>10}"
             f"{'chunk':>9}{'rows':>5}{'repl B':>12}{'/dev B':>12}"
             f"{'saved':>8}"]
    for i, b in enumerate(plan["buckets"]):
        nrows = len(b["roles"]) + (1 if plan["stage"] >= 3 else 0)
        rep = b["elems"] * 4 * nrows
        sh = b["chunk"] * 4 * nrows
        saved = 1 - sh / rep if rep else 0.0
        lines.append(f"{i:>6}  {b['op_type']:<10}{len(b['params']):>7}"
                     f"{b['elems']:>10}{b['chunk']:>9}{nrows:>5}"
                     f"{rep:>12}{sh:>12}{saved:>7.1%}")
    rows = zero_state_layout(plan)
    if rows:
        lines.append("state rows: " + ", ".join(
            f"{n}{list(shape)}" for n, _role, _bi, shape in rows))
    tot_r, tot_s = plan["bytes_replicated"], plan["bytes_sharded"]
    pct = 100.0 * (1 - tot_s / tot_r) if tot_r else 0.0
    lines.append(f"total optimizer-state bytes: replicated {tot_r} -> "
                 f"per-device {tot_s} ({pct:.1f}% saved)")
    return "\n".join(lines)


def _moe_demo_program(ep):
    """Demo program with an expert-parallel MoE block (2*ep experts so
    the ep axis divides them, capacity_factor 1.25 so overflow drops
    show up in the route table)."""
    import paddle_tpu.static as static

    e = 2 * max(2, ep)
    main, startup = static.Program(), static.Program()
    with static.program_guard(main, startup):
        x = static.data("x", [64, 16])
        label = static.data("label", [64, 1], dtype="int64")
        h = static.nn.fc(x, 16, act="relu")
        m, aux = static.nn.moe(h, num_experts=e, d_hidden=32,
                               capacity_factor=1.25)
        logits = static.nn.fc(m, 4)
        loss = static.mean(
            static.softmax_with_cross_entropy(logits, label)) \
            + static.mean(aux) * 0.01
        static.SGD(0.01).minimize(loss)
    return main, ["x", "label"], [loss.name]


def _moe_table(optimized, ep):
    """Per-moe-op routing/exchange table: the __moe_ep stamp (or why it
    is absent), the per-expert capacity-kept/dropped counts from one
    synthetic untrained-gate evaluation, and the explicit all_to_all
    wire bytes the cost model charges."""
    import numpy as np

    from paddle_tpu.nn.moe import moe_a2a_nbytes, moe_route_stats

    blk = optimized.global_block
    moes = [(i, op) for i, op in enumerate(blk.ops) if op.type == "moe"]
    if not moes:
        return "(no moe ops in the optimized block)"
    lines = []
    for i, op in moes:
        w1 = blk.vars[op.inputs["W1"][0]]
        x = blk.vars[op.inputs["X"][0]]
        e = int(w1.shape[0])
        t = abs(int(x.shape[0] or 1))
        d = int(x.shape[-1])
        cf = float(op.attrs.get("capacity_factor", 2.0))
        cap = max(1, int(cf * t / e))
        stamp = op.attrs.get("__moe_ep")
        head = (f"moe op #{i}: tokens={t} d={d} experts={e} "
                f"capacity={cap} (factor {cf})")
        if stamp:
            axis, n = str(stamp[0]), int(stamp[1])
            head += (f"  [stamped __moe_ep: {axis}={n}, explicit "
                     f"all_to_all x2, "
                     f"{moe_a2a_nbytes(e, cap, d, n)} B/device f32 / "
                     f"{moe_a2a_nbytes(e, cap, d, n, 'int8')} B int8]")
        else:
            head += (f"  [not stamped: needs an 'ep' mesh axis >1 "
                     f"dividing experts={e} (asked ep={ep}) -> dense]")
        lines.append(head)
        rng = np.random.RandomState(0)
        stats = moe_route_stats(
            rng.randn(t, e).astype("float32"), cap)
        lines.append(f"{'expert':>6}{'kept':>7}{'dropped':>9}  "
                     "(one synthetic untrained-gate eval)")
        for j, (k, dr) in enumerate(zip(stats["kept_per_expert"],
                                        stats["dropped_per_expert"])):
            lines.append(f"{j:>6}{k:>7}{dr:>9}")
        lines.append(f"capacity drop: {stats['drop_pct']}% of 2t "
                     f"token-choices, aux_loss="
                     f"{stats['aux_loss']:.4f}")
    return "\n".join(lines)


def main():
    ap = argparse.ArgumentParser(
        description="print per-pass op-count/timing table for a program")
    ap.add_argument("target", nargs="?",
                    help="serialized program file or inference-model dir")
    ap.add_argument("--demo", action="store_true",
                    help="use a built-in demo program")
    ap.add_argument("--feed", default=None,
                    help="comma-separated feed names (override)")
    ap.add_argument("--fetch", default=None,
                    help="comma-separated fetch names (override)")
    ap.add_argument("--disable", default=None,
                    help="comma-separated BuildStrategy knobs to turn off")
    ap.add_argument("--amp", nargs="?", const="bf16", default=None,
                    choices=("bf16", "bfloat16", "fp16", "float16"),
                    help="run the auto_mixed_precision pass (default "
                         "bf16) and print the per-op dtype table")
    ap.add_argument("--remat", nargs="?", const=0, default=None, type=int,
                    metavar="N",
                    help="run the recompute_segmentation pass (N "
                         "segments, 0/omitted = sqrt heuristic) and "
                         "print the per-segment stash/recompute table")
    ap.add_argument("--checkpoints", default=None,
                    help="comma-separated checkpoint var names marking "
                         "remat segment boundaries (implies --remat)")
    ap.add_argument("--sharding", nargs="?", const="dp=2,tp=2",
                    default=None, metavar="MESH",
                    help="run the shard_propagation pass over this mesh "
                         "shape (axis=size pairs, default dp=2,tp=2) and "
                         "print the per-var PartitionSpec table")
    ap.add_argument("--shard-hints", default=None, metavar="HINTS",
                    help="seed PartitionSpecs: 'w0=-,tp;w1=tp,-' "
                         "(';'-separated vars, ','-separated dims, '-' = "
                         "replicated, '+' joins multi-axis dims); "
                         "implies --sharding")
    ap.add_argument("--comm", nargs="?", const="int8", default=None,
                    choices=("int8", "bf16"),
                    help="run the comm_bucketing pass (quantized DP "
                         "all-reduce planning, default int8) and print "
                         "the per-bucket size/order/codec table; uses "
                         "--sharding's mesh (default dp=8)")
    ap.add_argument("--comm-bucket-bytes", type=int, default=1 << 20,
                    help="target f32 payload bytes per gradient bucket")
    ap.add_argument("--pipeline", nargs="?", const=4, default=None,
                    type=int, metavar="S",
                    help="stamp pipeline_stages=S (default 4) and print "
                         "the schedule timeline grid + modeled bubble "
                         "fractions")
    ap.add_argument("--schedule", default="gpipe",
                    choices=("gpipe", "1f1b", "interleaved"),
                    help="which schedule the --pipeline grid prints "
                         "(bubbles always compare all three)")
    ap.add_argument("--microbatches", type=int, default=8, metavar="M",
                    help="gradient_merge_k microbatch count for "
                         "--pipeline (default 8)")
    ap.add_argument("--interleave", type=int, default=2,
                    help="virtual chunks per worker for "
                         "--schedule interleaved (default 2)")
    ap.add_argument("--zero", nargs="?", const=2, default=None,
                    type=int, choices=(2, 3), metavar="STAGE",
                    help="plan ZeRO sharded optimizer states (implies "
                         "--comm int8 over dp=8) and print the "
                         "per-bucket state-bytes table or the counted "
                         "refusal reason")
    ap.add_argument("--moe", nargs="?", const=4, default=None,
                    type=int, metavar="EP",
                    help="run over an expert-parallel mesh (ep=EP, "
                         "default 4; demo swaps in an MoE program) and "
                         "print the per-expert capacity/route table + "
                         "the explicit all_to_all wire bytes")
    ap.add_argument("--dot", default=None,
                    help="write the optimized block as graphviz dot")
    args = ap.parse_args()

    import paddle_tpu.static as static

    if args.demo or not args.target:
        program, feeds, fetches = (_moe_demo_program(args.moe)
                                   if args.moe else _demo_program())
    else:
        program, feeds, fetches = _load_target(args.target)
    if args.feed:
        feeds = [s for s in args.feed.split(",") if s]
    if args.fetch:
        fetches = [s for s in args.fetch.split(",") if s]
    if not fetches:
        # default: every leaf output (no consumer) of the global block
        blk = program.global_block
        consumed = {n for op in blk.ops for n in op.input_names()}
        fetches = sorted({n for op in blk.ops for n in op.output_names()}
                         - consumed)
        print(f"(no --fetch given; using leaf outputs: {fetches})",
              file=sys.stderr)

    strategy = static.BuildStrategy()
    for knob in (args.disable or "").split(","):
        knob = knob.strip()
        if knob:
            if not hasattr(strategy, knob):
                ap.error(f"unknown BuildStrategy knob {knob!r}")
            setattr(strategy, knob, False)
    if args.amp:
        strategy.amp = True
        strategy.amp_dtype = args.amp
    if args.remat is not None or args.checkpoints:
        strategy.recompute = True
        strategy.recompute_segments = args.remat or 0
        if args.checkpoints:
            strategy.recompute_checkpoints = tuple(
                s for s in args.checkpoints.split(",") if s)
    if args.sharding or args.shard_hints:
        mesh_shape = {}
        for part in (args.sharding or "dp=2,tp=2").split(","):
            part = part.strip()
            if not part:
                continue
            axis, _, size = part.partition("=")
            mesh_shape[axis.strip()] = int(size or 2)
        strategy.mesh_shape = mesh_shape
        strategy.sharding_hints = _parse_shard_hints(
            args.shard_hints, program, mesh_shape)
    if args.zero and not args.comm:
        args.comm = "int8"   # ZeRO rides the engaged comm plan
    if args.comm:
        if not strategy.mesh_shape:
            strategy.mesh_shape = {"dp": 8}   # pure-dp planning mesh
        strategy.comm_quant = args.comm
        strategy.comm_bucket_bytes = args.comm_bucket_bytes
    if args.pipeline:
        strategy.pipeline_stages = args.pipeline
        strategy.gradient_merge_k = max(int(args.microbatches), 2)
        strategy.pipeline_schedule = args.schedule
        strategy.pipeline_interleave = args.interleave
    if args.zero:
        strategy.zero_stage = args.zero
    if args.moe:
        mesh = dict(strategy.mesh_shape or {})
        mesh.setdefault("ep", args.moe)
        strategy.mesh_shape = mesh

    optimized, report = static.apply_passes(program, feeds, fetches,
                                            strategy)
    print(report.table())
    if args.amp:
        print()
        print(_amp_table(optimized, report))
    if args.remat is not None or args.checkpoints:
        print()
        print(report.remat_segment_table())
    if args.sharding or args.shard_hints:
        print()
        print(report.shard_spec_table())
    if args.comm:
        print()
        print(report.comm_bucket_table())
    if args.pipeline:
        print()
        print(_timeline_table(args.schedule, args.pipeline,
                              strategy.gradient_merge_k,
                              args.interleave))
    if args.zero:
        print()
        print(_zero_state_table(optimized, strategy, args.zero))
    if args.moe:
        print()
        print(_moe_table(optimized, args.moe))
    if args.dot:
        static.save_dot(optimized, args.dot)
        print(f"optimized block dot -> {args.dot}")


if __name__ == "__main__":
    main()
