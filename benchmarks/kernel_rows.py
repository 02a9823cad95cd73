"""What the per-layer metrics of the Pallas kernels share: a kernel
family's seconds in the traced slice (the ``kernel:`` rows of the trace
reduction whose name holds the family, e.g. ``fused_xent``), its share of
the device's busy time, and its share of its roofline — the program's own
work ledger (``paddle_tpu.ops.pallas.counters.step_work``) over those
seconds. A reader gets None wherever a row or a counter is not there: on
a CPU, on a commit whose program has no ledger, in a cell whose step
does not launch the kernel.
"""
from __future__ import annotations

from typing import Dict, List, Optional

#: the compiled step whose ledger the training cells read
TRAIN_STEP = "train_step"
_printed: List[str] = []


def kernel_rows(run: dict, token: str) -> Optional[float]:
    """Seconds of the ``kernel:`` rows whose name holds ``token``; None
    when the reduction has no such row (``trace_reduce`` has already
    merged ``name.3`` into ``name`` and keeps the ten largest rows)."""
    rows = [s for n, s in (run.get("trace") or {}).get("device_ops", [])
            if n.startswith("kernel:") and token in n]
    return sum(rows) if rows else None


def device_share_pct(run: dict, family: str) -> Optional[float]:
    seconds = kernel_rows(run, family)
    if seconds is None:
        return None
    return 100.0 * seconds / run["trace"]["busy_s"]


def step_work(step: str = TRAIN_STEP) -> Optional[Dict[str, dict]]:
    """The program's ledger for one execution of ``step``, printed once
    into the run's log; None where the program keeps none."""
    from paddle_tpu.ops.pallas import counters

    read = getattr(counters, "step_work", None)
    work = read(step) if read is not None else None
    if work and step not in _printed:
        _printed.append(step)
        print(f"step_work({step!r}) = {work}", flush=True)
    return work or None


def roofline_pct(run: dict, family: str) -> Optional[float]:
    """The least time the chip could take for the family's work in the
    traced steps — per role the larger of FLOPs over the bf16 peak and
    bytes over the HBM peak — over the seconds its rows took. A role
    whose own row is not among the reduction's rows is left out of both
    sides."""
    work = step_work()
    if not work:
        return None
    peaks = run["peaks"]
    steps = int(run["cell"]["traffic"]["loss_fetch_every"])
    least = took = 0.0
    for role, w in work.items():
        seconds = kernel_rows(run, role) if family in role else None
        if seconds is None:
            continue
        least += steps * max(w["flops"] / peaks["bf16_flops_per_s"],
                             w["bytes"] / peaks["hbm_bytes_per_s"])
        took += seconds
    return 100.0 * least / took if took else None
