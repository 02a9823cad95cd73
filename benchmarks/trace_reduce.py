"""From a profiler trace to numbers: device busy and idle time, the
device operations that took most of it, and the idle gaps named by what
the host was doing. Part of the yardstick: every PR reduces its trace
with this code, and a small recorded example under ``tests_data/`` pins
its arithmetic.

A trace, as this module sees it, is

    {"devices": {plane name: [[op name, start_ns, duration_ns], ...]},
     "host_spans": [[span name, start_ns, duration_ns], ...]}

:func:`load_xplane` builds that from the ``.xplane.pb`` file jax's
profiler writes (``jax.profiler.ProfileData``); the host spans are the
benchmark's own ``TraceAnnotation`` spans, whose names start ``bench.``.
On a v5e the plane ``/device:TPU:0`` carries the lines ``Scalar Unit``,
``XLA Modules``, ``XLA Ops``, ``Async XLA Ops`` and ``TC Overlay`` (seen
in PR 23's traces); operations are read from ``XLA Ops`` alone, the others
repeat or overlap them.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

#: the span the harness wraps around the traced slice of the window
WINDOW_SPAN = "bench.trace_window"
#: the line of a device plane that holds its operations
_OP_LINE = "XLA Ops"
_DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
_NUM_SUFFIX = re.compile(r"(\.\d+)+$")
#: a gap shorter than this is the device's own turn-around between two
#: operations, not the host's doing; such gaps are summed under one name
SHORT_GAP_NS = 20_000.0
BETWEEN_OPS = "device.between_ops"

Interval = Tuple[float, float]


def short_name(name: str) -> str:
    """The profiler names a device event by its whole HLO line
    (``%fusion.12 = (...) fusion(...)``); keep the result's name, and mark
    a Pallas kernel (a ``tpu_custom_call``) as such."""
    head = name.split(" = ", 1)[0].lstrip("%")
    return "kernel:" + head if "tpu_custom_call" in name else head


def find_xplane(trace_dir: str) -> Optional[str]:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def load_xplane(path: str, span_prefix: str = "bench.") -> dict:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: Dict[str, list] = {}
    host: List[list] = []
    for plane in data.planes:
        lines = list(plane.lines)
        if _DEVICE_PLANE.match(plane.name):
            devices[plane.name] = [
                [short_name(ev.name), float(ev.start_ns),
                 float(ev.duration_ns)]
                for ln in lines if ln.name == _OP_LINE
                for ev in ln.events]
        elif plane.name.startswith("/host:"):
            for ln in lines:
                for ev in ln.events:
                    if ev.name.startswith(span_prefix):
                        host.append([ev.name, float(ev.start_ns),
                                     float(ev.duration_ns)])
    return {"devices": devices, "host_spans": host}


def union(intervals: Sequence[Interval]) -> List[Interval]:
    """Merged, ascending, non-overlapping intervals."""
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def _clip(intervals: Sequence[Interval], lo: float, hi: float):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def _gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    out, at = [], lo
    for a, b in busy:
        if a > at:
            out.append((at, a))
        at = max(at, b)
    if hi > at:
        out.append((at, hi))
    return out


def op_family(name: str) -> str:
    """``fusion.123`` -> ``fusion``: the same operation in each of a
    model's layers carries a different numeric suffix."""
    return _NUM_SUFFIX.sub("", name)


def reduce(trace: dict, top: int = 10) -> Optional[dict]:
    """Busy/idle over the traced window, averaged over the device planes.

    The window is the ``bench.trace_window`` host span when the trace has
    one, else the extent of the device events. Each idle gap of each
    device goes, whole, to the benchmark span that overlaps it most
    (``host.other`` when none does; gaps under 20 us are summed as
    ``device.between_ops``). Returns None for a trace in which no
    operation ran on a device."""
    devices = {k: v for k, v in trace["devices"].items() if v}
    if not devices:
        return None
    spans = [(n, s, s + d) for n, s, d in trace["host_spans"]]
    windows = [(s, e) for n, s, e in spans if n == WINDOW_SPAN]
    if windows:
        lo, hi = min(s for s, _ in windows), max(e for _, e in windows)
    else:
        every = [ev for evs in devices.values() for ev in evs]
        lo = min(s for _, s, _ in every)
        hi = max(s + d for _, s, d in every)
    named = sorted((s, e, n) for n, s, e in spans if n != WINDOW_SPAN)
    starts = [s for s, _, _ in named]
    longest = max((e - s for s, e, _ in named), default=0.0)
    busy_ns = 0.0
    by_op: Dict[str, float] = {}
    by_gap: Dict[str, float] = {}
    for events in devices.values():
        busy = union(_clip([(s, s + d) for _, s, d in events], lo, hi))
        busy_ns += sum(b - a for a, b in busy)
        for name, s, d in events:
            inside = min(s + d, hi) - max(s, lo)
            if inside > 0:
                fam = op_family(name)
                by_op[fam] = by_op.get(fam, 0.0) + inside
        for a, b in _gaps(busy, lo, hi):
            if b - a < SHORT_GAP_NS:
                owner = BETWEEN_OPS
            else:
                owner, best = "host.other", 0.0
                first = bisect.bisect_left(starts, a - longest)
                last = bisect.bisect_right(starts, b)
                for s, e, n in named[first:last]:
                    over = min(b, e) - max(a, s)
                    if over > best:
                        owner, best = n, over
            by_gap[owner] = by_gap.get(owner, 0.0) + (b - a)
    n_dev = len(devices)

    def ranked(table):
        rows = sorted(table.items(), key=lambda kv: -kv[1])[:top]
        return [[k, v / n_dev / 1e9] for k, v in rows]

    window_s = (hi - lo) / 1e9
    busy_s = busy_ns / n_dev / 1e9
    return {"busy_s": busy_s, "window_s": window_s,
            "idle_pct": 100.0 * (1.0 - busy_s / window_s),
            "device_ops": ranked(by_op), "idle_gaps": ranked(by_gap),
            "n_devices": n_dev}
