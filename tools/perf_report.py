#!/usr/bin/env python
"""Performance report over step-trace JSONL and/or a ``/metrics``
scrape: MFU trend, phase breakdown, top-K ops by flops/bytes, compute-
vs bandwidth-bound roofline buckets, and a before/after regression
delta — the reading side of the graph-derived cost model
(paddle_tpu/static/cost_model.py + the executor's live gauges).

Usage::

    python tools/perf_report.py trace.jsonl [--top 8]
    python tools/perf_report.py --compare before.jsonl after.jsonl
    python tools/perf_report.py --metrics 127.0.0.1:8321
    python tools/perf_report.py --metrics scrape.txt   # saved scrape

Traces come from ``PADDLE_STEP_TRACE=<file-or-dir>`` (or
``enable_step_trace``): per-step records carry measured phases plus the
cost-model gauges (step_model_flops/step_hbm_bytes/step_comm_bytes/
mfu/arith_intensity), and one ``kind="cost"`` record per compiled
executable carries the per-op breakdown this report's top-K/roofline
sections read. Records are schema-versioned (``"schema"``, see
MIGRATION.md): unknown versions fail loudly here instead of misparsing.

Exit codes: 0 ok, 1 empty/unreadable input, 2 unknown schema.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Dict, List, Optional, Tuple

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)

from paddle_tpu.observability.step_trace import (  # noqa: E402
    UnknownTraceSchema, read_trace_records,
)


class PerfReportError(Exception):
    """Typed failure: unreadable trace or unknown schema version."""


# ---------------------------------------------------------------------------
# loading
# ---------------------------------------------------------------------------
def load_trace(path: str) -> Tuple[List[dict], List[dict]]:
    """Parse one step-trace JSONL file into (step records, cost
    records) through the shared schema-gated loader
    (``step_trace.read_trace_records``). Raises PerfReportError on an
    unknown ``schema`` version — a reader silently misparsing a future
    format is how perf regressions hide."""
    try:
        records = read_trace_records(path, reader="tools/perf_report.py")
    except UnknownTraceSchema as e:
        raise PerfReportError(str(e))
    except OSError as e:
        raise PerfReportError(f"cannot read trace {path!r}: {e}")
    steps: List[dict] = []
    costs: List[dict] = []
    for rec in records:
        if rec.get("kind") == "cost":
            costs.append(rec)
        elif rec.get("phases", {}).get("dispatch") is not None:
            steps.append(rec)
    return steps, costs


# ---------------------------------------------------------------------------
# formatting
# ---------------------------------------------------------------------------
def _fmt_count(v) -> str:
    """Engineering notation with 2 decimals (golden-stable)."""
    v = float(v)
    for div, suf in ((1e12, "T"), (1e9, "G"), (1e6, "M"), (1e3, "K")):
        if abs(v) >= div:
            return f"{v / div:.2f}{suf}"
    return f"{v:.0f}"


def _mean(vals) -> float:
    vals = list(vals)
    return sum(vals) / len(vals) if vals else 0.0


def _roofline_bound(ai: float, balance: Optional[float]) -> str:
    if balance is None:
        return "?"
    return "compute" if ai >= balance else "bandwidth"


# ---------------------------------------------------------------------------
# report
# ---------------------------------------------------------------------------
def render_report(steps: List[dict], costs: List[dict],
                  top: int = 8) -> str:
    lines: List[str] = []
    n = len(steps)
    lines.append("== step summary ==")
    if not n:
        lines.append("no step records (phases.dispatch missing on "
                     "every row)")
    else:
        durs = [s.get("dur_ms", 0.0) for s in steps]
        lines.append(f"steps {n}   total {sum(durs):.1f} ms   "
                     f"mean {_mean(durs):.2f} ms/step")
        mean_dur = _mean(durs) or 1.0
        for phase in ("feed", "dispatch", "fetch"):
            ms = _mean(s.get("phases", {}).get(phase, 0.0)
                       for s in steps)
            lines.append(f"  phase {phase:<9}{ms:>10.2f} ms  "
                         f"{100.0 * ms / mean_dur:>5.1f}%")
        hits = sum(1 for s in steps if s.get("cache_hit"))
        lines.append(f"  cache hits {hits}/{n}")

    lines.append("")
    lines.append("== mfu trend ==")
    # mfu=0 rows are published when the peak is unknown or a step did
    # no model flops — they carry no utilization signal, so an all-zero
    # trace gets the guidance message, not a flat 0.0000 trend
    mfu_steps = [s for s in steps if s.get("mfu")]
    if not mfu_steps:
        lines.append("no nonzero mfu samples — device peak unknown "
                     "(run on a known TPU or set PADDLE_PEAK_FLOPS), "
                     "or every step was matmul-free")
    else:
        nb = min(8, len(mfu_steps))
        per = -(-len(mfu_steps) // nb)  # ceil
        lines.append(f"{'steps':<14}{'mean_mfu':>10}{'mean_ms':>10}"
                     f"{'model_flops':>13}")
        for b in range(0, len(mfu_steps), per):
            chunk = mfu_steps[b:b + per]
            label = f"{chunk[0]['step']}..{chunk[-1]['step']}"
            lines.append(
                f"{label:<14}"
                f"{_mean(c['mfu'] for c in chunk):>10.4f}"
                f"{_mean(c.get('dur_ms', 0.0) for c in chunk):>10.2f}"
                f"{_fmt_count(_mean(c.get('step_model_flops', 0) for c in chunk)):>13}")

    lines.append("")
    lines.append("== cost model (per compiled step) ==")
    if not costs:
        lines.append("no cost records in trace (pre-cost-model trace, "
                     "or the program could not be costed)")
        return "\n".join(lines) + "\n"
    cost = costs[-1]  # the latest compiled executable's breakdown
    balance = None
    peak_fl = cost.get("peak_flops")
    peak_bw = cost.get("peak_hbm_bytes_per_s")
    if peak_fl and peak_bw:
        balance = peak_fl / peak_bw
    lines.append(
        f"model_flops {_fmt_count(cost.get('model_flops', 0))}   "
        f"hbm_bytes {_fmt_count(cost.get('hbm_bytes', 0))}   "
        f"comm_bytes {_fmt_count(cost.get('comm_bytes', 0))}   "
        f"arith_intensity {cost.get('arith_intensity', 0.0)}")
    lines.append(
        f"batch {cost.get('batch', 1)}   gm_k {cost.get('gm_k', 1)}   "
        f"pp_stages {cost.get('pp_stages', 1)}   "
        f"n_shards {cost.get('n_shards', 1)}   "
        f"device {cost.get('device_kind', 'unknown')}")
    if balance is not None:
        step_bound = _roofline_bound(
            float(cost.get("arith_intensity", 0.0)), balance)
        lines.append(f"machine balance {balance:.1f} flops/byte -> "
                     f"step is {step_bound}-bound")
    # kernel MFU push (ISSUE 19): the two places step time hides from
    # the matmul roofline — optimizer-region HBM traffic (now one fused
    # Pallas pass per ZeRO chunk instead of 5-8 elementwise ops) and
    # the MoE expert exchange (explicit all_to_all, charged into
    # comm_bytes by the cost model)
    moe_b = int(cost.get("moe_a2a_bytes", 0) or 0)
    if moe_b:
        comm_b = int(cost.get("comm_bytes", 0) or 1)
        lines.append("")
        lines.append("-- kernel MFU push --")
        lines.append(
            f"moe_a2a_bytes {_fmt_count(moe_b)} "
            f"({100.0 * moe_b / comm_b:.1f}% of comm_bytes) — the "
            f"explicit expert-parallel dispatch/combine exchange")
    for field, title in (("top_flops", "top ops by model flops"),
                         ("top_bytes", "top ops by hbm bytes")):
        rows = cost.get(field) or []
        if not rows:
            continue
        lines.append("")
        lines.append(f"-- {title} --")
        lines.append(f"{'op':<26}{'out':<26}{'flops':>9}{'bytes':>9}"
                     f"{'AI':>8}  bound")
        for o in rows[:top]:
            ai = float(o.get("arith_intensity", 0.0))
            lines.append(
                f"{o.get('type', '?'):<26}"
                f"{str(o.get('out', ''))[:24]:<26}"
                f"{_fmt_count(o.get('flops', 0)):>9}"
                f"{_fmt_count(o.get('hbm_bytes', 0)):>9}"
                f"{ai:>8.2f}  {_roofline_bound(ai, balance)}")
    # roofline buckets over the per-op tables (dedup by op index)
    seen: Dict[int, dict] = {}
    for o in (cost.get("top_flops") or []) + (cost.get("top_bytes")
                                              or []):
        seen[o.get("index", id(o))] = o
    if balance is not None and seen:
        comp = [o for o in seen.values()
                if float(o.get("arith_intensity", 0.0)) >= balance]
        band = [o for o in seen.values()
                if float(o.get("arith_intensity", 0.0)) < balance]
        cf = sum(o.get("flops", 0) for o in comp)
        bf = sum(o.get("flops", 0) for o in band)
        tot = (cf + bf) or 1
        lines.append("")
        lines.append("-- roofline buckets (costed ops) --")
        lines.append(f"compute-bound   {len(comp):>4} ops  "
                     f"{100.0 * cf / tot:>5.1f}% of flops")
        lines.append(f"bandwidth-bound {len(band):>4} ops  "
                     f"{100.0 * bf / tot:>5.1f}% of flops")
    return "\n".join(lines) + "\n"


# ---------------------------------------------------------------------------
# compare
# ---------------------------------------------------------------------------
def _trace_metrics(steps: List[dict], costs: List[dict]
                   ) -> Dict[str, float]:
    out = {
        "mean_step_ms": round(_mean(s.get("dur_ms", 0.0)
                                    for s in steps), 3),
        "mean_dispatch_ms": round(_mean(
            s.get("phases", {}).get("dispatch", 0.0) for s in steps), 3),
        # zeros mean "no utilization signal" (unknown peak /
        # matmul-free), not a measured 0% — exclude them like the trend
        "mean_mfu": round(_mean(s["mfu"] for s in steps
                                if s.get("mfu")), 4),
    }
    src = costs[-1] if costs else {}
    for key in ("model_flops", "hbm_bytes", "comm_bytes"):
        out[key] = src.get(key, 0)
    return out


def render_compare(before: Tuple[List[dict], List[dict]],
                   after: Tuple[List[dict], List[dict]]) -> str:
    b = _trace_metrics(*before)
    a = _trace_metrics(*after)
    lines = ["== regression delta (before -> after) ==",
             f"{'metric':<20}{'before':>14}{'after':>14}{'delta':>10}"]
    for key in ("mean_step_ms", "mean_dispatch_ms", "mean_mfu",
                "model_flops", "hbm_bytes", "comm_bytes"):
        bv, av = b.get(key, 0), a.get(key, 0)
        if key.startswith("mean_"):
            bs, as_ = f"{bv:.4g}", f"{av:.4g}"
        else:
            bs, as_ = _fmt_count(bv), _fmt_count(av)
        delta = (f"{100.0 * (av - bv) / bv:+.1f}%" if bv else "n/a")
        lines.append(f"{key:<20}{bs:>14}{as_:>14}{delta:>10}")
    return "\n".join(lines) + "\n"


# ---------------------------------------------------------------------------
# decode overlap view (async tick pipelining + host KV tier)
# ---------------------------------------------------------------------------
def _phase_sum(samples: Dict[str, float], phase: str) -> float:
    """Cumulative ms for one decode tick phase, summed across any
    instance labels a federated scrape injected."""
    total = 0.0
    for key, v in samples.items():
        if key.startswith("decode_tick_phase_ms_sum") \
                and f'phase="{phase}"' in key:
            total += v
    return total


def decode_overlap_metrics(samples: Dict[str, float]
                           ) -> Dict[str, float]:
    """The decode-overlap scorecard from one parsed scrape: the tick
    wall split by phase (dispatch / host / fetch — fetch is the time
    the host sat blocked on device tokens, the thing async pipelining
    exists to hide), the engine's cumulative ``decode_overlap_frac``
    gauge, and the host-tier counters."""
    out: Dict[str, float] = {}
    for ph in ("dispatch", "host", "fetch"):
        out[f"tick_{ph}_ms"] = round(_phase_sum(samples, ph), 3)
    total = sum(out.values())
    out["tick_total_ms"] = round(total, 3)
    if total:
        out["overlap_frac"] = round(
            (total - out["tick_fetch_ms"]) / total, 4)
    for g in ("decode_overlap_frac", "kv_pages_host",
              "kv_offload_bytes", "kv_page_restores",
              "kv_sessions_parked", "kv_sessions_resumed",
              "kv_restore_fallbacks"):
        if g in samples:
            out[g] = samples[g]
    return out


def render_decode_overlap(samples: Dict[str, float]) -> str:
    m = decode_overlap_metrics(samples)
    if not m.get("tick_total_ms") and "decode_overlap_frac" not in m:
        return ""   # scrape has no decode tick phase data
    lines = ["-- decode overlap --"]
    for key in ("tick_dispatch_ms", "tick_host_ms", "tick_fetch_ms",
                "tick_total_ms", "overlap_frac",
                "decode_overlap_frac", "kv_pages_host",
                "kv_offload_bytes", "kv_page_restores",
                "kv_sessions_parked", "kv_sessions_resumed",
                "kv_restore_fallbacks"):
        if key in m:
            lines.append(f"{key:<22}{m[key]:>12g}")
    return "\n".join(lines) + "\n"


def render_metrics_compare(before: Dict[str, float],
                           after: Dict[str, float]) -> str:
    """``--compare`` over two SAVED SCRAPES instead of step traces:
    the decode-overlap deltas (sync baseline vs async run is the
    intended pairing — fetch wall should collapse and overlap_frac
    rise while token counts match)."""
    b, a = decode_overlap_metrics(before), decode_overlap_metrics(after)
    lines = ["== decode overlap delta (before -> after) ==",
             f"{'metric':<22}{'before':>14}{'after':>14}{'delta':>10}"]
    keys = [k for k in (
        "tick_dispatch_ms", "tick_host_ms", "tick_fetch_ms",
        "tick_total_ms", "overlap_frac", "decode_overlap_frac",
        "kv_pages_host", "kv_offload_bytes", "kv_page_restores",
        "kv_sessions_parked", "kv_sessions_resumed",
        "kv_restore_fallbacks") if k in b or k in a]
    for key in keys:
        bv, av = b.get(key, 0.0), a.get(key, 0.0)
        delta = (f"{100.0 * (av - bv) / bv:+.1f}%" if bv else "n/a")
        lines.append(f"{key:<22}{bv:>14g}{av:>14g}{delta:>10}")
    return "\n".join(lines) + "\n"


# ---------------------------------------------------------------------------
# /metrics scrape view
# ---------------------------------------------------------------------------
def render_metrics(samples: Dict[str, float]) -> str:
    """Utilization view of one parsed ``/metrics`` scrape: the cost
    gauges plus bucket-derived phase percentiles, and — when the
    scrape carries the decode-serving plane — the token-economics
    section (speculation accept rate, KV page occupancy/sharing,
    prefix-cache hits)."""
    from tools.metrics_watch import (format_percentile_table,
                                     histogram_percentile_deltas)

    lines = ["== /metrics utilization =="]
    for g in ("mfu", "arith_intensity", "step_model_flops",
              "step_hbm_bytes", "step_comm_bytes", "executor_steps"):
        if g in samples:
            v = samples[g]
            fmt = _fmt_count(v) if g.startswith("step_") else f"{v:g}"
            lines.append(f"{g:<20}{fmt:>14}")
    decode = [(g, samples[g]) for g in (
        "decode_requests", "decode_tokens", "decode_prefills",
        "decode_steps", "decode_batch_fill_pct", "spec_proposed",
        "spec_accepted", "spec_accept_rate", "kv_pages_in_use",
        "kv_pages_shared", "kv_pages_cached", "kv_prefix_hits",
        "kv_page_evictions", "kv_cow_copies") if g in samples]
    if decode:
        lines.append("")
        lines.append("-- decode token economics --")
        for g, v in decode:
            lines.append(f"{g:<22}{v:>12g}")
    overlap = render_decode_overlap(samples)
    if overlap:
        lines.append("")
        lines.append(overlap.rstrip("\n"))
    pct = histogram_percentile_deltas(samples, None)
    phase = {k: v for k, v in pct.items()
             if k.startswith("executor_step_phase_ms")}
    if phase:
        lines.append("")
        lines.append(format_percentile_table(
            phase, title="executor phase percentiles (cumulative)"))
    return "\n".join(lines) + "\n"


def _is_metrics_file(path: str) -> bool:
    """True when ``path`` reads as Prometheus text exposition rather
    than step-trace JSONL (whose every line is a JSON object)."""
    if not os.path.exists(path):
        return False
    try:
        with open(path) as fh:
            for line in fh:
                line = line.strip()
                if not line:
                    continue
                try:
                    json.loads(line)
                    return False
                except ValueError:
                    return True
    except OSError:
        return False
    return False


def _load_metrics(target: str) -> Dict[str, float]:
    from paddle_tpu.observability.metrics import parse_prometheus_text
    from tools.metrics_watch import scrape

    if os.path.exists(target):
        with open(target) as fh:
            return parse_prometheus_text(fh.read())
    return scrape(target)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="MFU / roofline report over step-trace JSONL "
                    "and/or a /metrics scrape")
    ap.add_argument("trace", nargs="?", help="step-trace JSONL file")
    ap.add_argument("--top", type=int, default=8,
                    help="rows per top-ops table")
    ap.add_argument("--compare", nargs=2,
                    metavar=("BEFORE", "AFTER"),
                    help="two traces; print the regression delta")
    ap.add_argument("--metrics", default=None,
                    help="host:port to scrape, or a saved scrape file")
    args = ap.parse_args(argv)
    try:
        wrote = False
        if args.compare:
            if all(_is_metrics_file(p) for p in args.compare):
                # two saved /metrics scrapes: decode-overlap deltas
                # (the async-vs-sync pairing)
                b, a = (_load_metrics(p) for p in args.compare)
                sys.stdout.write(render_metrics_compare(b, a))
            else:
                before, after = (load_trace(p) for p in args.compare)
                sys.stdout.write(render_compare(before, after))
            wrote = True
        elif args.trace:
            steps, costs = load_trace(args.trace)
            if not steps and not costs:
                print(f"no usable records in {args.trace}",
                      file=sys.stderr)
                return 1
            sys.stdout.write(render_report(steps, costs, top=args.top))
            wrote = True
        if args.metrics:
            try:
                samples = _load_metrics(args.metrics)
            except (OSError, RuntimeError, ValueError) as e:
                # ValueError: a typo'd filename with no colon reaches
                # scrape()'s int(port)
                print(f"perf_report: cannot scrape "
                      f"{args.metrics!r}: {e}", file=sys.stderr)
                return 1
            sys.stdout.write(render_metrics(samples))
            wrote = True
        if not wrote:
            ap.print_usage(sys.stderr)
            return 1
    except PerfReportError as e:
        print(f"perf_report: {e}", file=sys.stderr)
        return 2
    return 0


if __name__ == "__main__":
    sys.exit(main())
