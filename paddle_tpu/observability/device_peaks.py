"""Device peak-performance registry: bf16 peak FLOP/s and HBM bandwidth
per TPU generation — the ONE home of the numbers every utilization
metric divides by (the executor's live ``mfu`` / ``arith_intensity``
gauges, tools/perf_report.py's roofline buckets).

Bandwidth entries make the roofline position derivable:
``machine_balance`` (peak FLOP/s divided by HBM byte/s) is the
arithmetic-intensity threshold separating bandwidth-bound from
compute-bound ops.

Matching is by lowercased substring, first hit wins — "v5 lite" must
stay ahead of the bare "v5" family entries. Unknown chips resolve to
``None`` rather than a guess (the ``mfu`` gauge is then left unset),
unless the operator pins peaks explicitly:

- ``PADDLE_PEAK_FLOPS``: peak FLOP/s override (any backend, including
  CPU runs — lets a dev box exercise the whole MFU plane)
- ``PADDLE_PEAK_HBM_GBPS``: HBM bandwidth override, GB/s

stdlib-only on purpose, like the rest of the observability package.
"""
from __future__ import annotations

import os
from typing import NamedTuple, Optional

__all__ = ["DevicePeak", "DEVICE_PEAKS", "peaks_for",
           "peak_flops", "hbm_bandwidth", "machine_balance"]


class DevicePeak(NamedTuple):
    """Per-chip peaks: bf16 FLOP/s and HBM bytes/s."""

    kind: str
    flops: float        # peak bf16 FLOP/s per chip
    hbm_bytes_per_s: float  # HBM bandwidth, bytes/s per chip


# (device_kind substring, bf16 peak FLOP/s, HBM GB/s) — lowercased
# substring match, first hit wins ("v5 lite" before the bare "v5").
# FLOP/s and bandwidths are the published per-chip numbers.
DEVICE_PEAKS = (
    ("v5 lite", 197e12, 819.0),
    ("v5e", 197e12, 819.0),
    ("v5p", 459e12, 2765.0),
    ("v6", 918e12, 1640.0),
    ("trillium", 918e12, 1640.0),
    ("v4", 275e12, 1228.0),
    ("v3", 123e12, 900.0),
    ("v2", 45e12, 700.0),
)


def _env_float(name: str) -> Optional[float]:
    raw = os.environ.get(name)
    if not raw:
        return None
    try:
        v = float(raw)
    except ValueError:
        return None
    return v if v > 0 else None


def peaks_for(kind: str) -> Optional[DevicePeak]:
    """Resolve ``kind`` (a PJRT ``device_kind`` string) to its peaks.

    Env pins win over the table — with ``PADDLE_PEAK_FLOPS`` set the
    result is never None (bandwidth falls back to the table entry or
    0.0 when unknown), so a CPU box can exercise the MFU plane."""
    k = (kind or "").lower()
    row = next((DevicePeak(sub, fl, bw * 1e9)
                for sub, fl, bw in DEVICE_PEAKS if sub in k), None)
    env_fl = _env_float("PADDLE_PEAK_FLOPS")
    env_bw = _env_float("PADDLE_PEAK_HBM_GBPS")
    if env_fl is None and env_bw is None:
        return row
    base = row or DevicePeak(k or "unknown", 0.0, 0.0)
    return DevicePeak(
        base.kind,
        env_fl if env_fl is not None else base.flops,
        env_bw * 1e9 if env_bw is not None else base.hbm_bytes_per_s)


def peak_flops(kind: str) -> Optional[float]:
    """Peak bf16 FLOP/s for ``kind``; None when unknown (never a
    guess — the ``mfu`` gauge is left unset instead)."""
    p = peaks_for(kind)
    return p.flops if p is not None and p.flops > 0 else None


def hbm_bandwidth(kind: str) -> Optional[float]:
    """HBM bandwidth in bytes/s for ``kind``; None when unknown."""
    p = peaks_for(kind)
    return (p.hbm_bytes_per_s
            if p is not None and p.hbm_bytes_per_s > 0 else None)


def machine_balance(kind: str) -> Optional[float]:
    """Roofline ridge point, FLOPs per HBM byte: ops whose arithmetic
    intensity sits below this are bandwidth-bound on ``kind``."""
    fl, bw = peak_flops(kind), hbm_bandwidth(kind)
    if fl is None or bw is None:
        return None
    return fl / bw
