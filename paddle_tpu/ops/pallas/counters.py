"""Trace-time dispatch counters, kernel names and the work ledger of the
custom Pallas kernels.

Every kernel dispatch site bumps a counter — ``<kernel>.pallas`` when
the custom kernel runs, ``<kernel>.xla`` (with a reason) when the XLA
path is taken — and ``FLAGS_log_pallas_fallback=True`` additionally
writes each fallback to stderr. A silent try/except fallback once hid a
real lowering bug for a whole round; nothing falls back silently now.

Counts are per DISPATCH DECISION (trace time under jit — once per
compilation, not per step; every call in eager mode). The benchmark
prints ``snapshot()`` in the log of every run (``benchmarks/run.py``,
the ``pallas counters`` line), and ``chip_smoke.py`` reports deltas.

**Names.** A kernel is launched through :func:`kernel_call` under its
ROLE name (``fused_xent_fwd``, ``flash_attention_short_bwd``,
``kda_chunk_fwd``, ...). That name is what a device trace shows
(``kernel:<role>``) and what the benchmark's readers key on, so it holds
the kernel's family and never a layer index.

**Work.** The dispatch site also declares the work the call requires:
matmul FLOPs of the mathematical operation (recomputation not counted,
every row the kernel is handed counted) and the bytes it must read and
write once, per role, from the shapes in hand. While a compiled step is
traced inside :func:`capture`, the declared work is summed by role and
kept as the work of ONE execution of that step:
``step_work("train_step")`` -> ``{role: {"calls", "flops", "bytes"}}``.
Where a dispatch picks among kernels on the device (the row-capacity
ladder of ``fused_xent``), every role it may run is listed with its own
work and one of them runs in an execution; a device trace shows which.
Kernel time from a device trace over that work is the kernel's roofline
share (``benchmarks/kernel_rows.py``).
"""
from __future__ import annotations

import collections
import contextlib
import sys
from typing import Dict, Mapping, Optional, Tuple

from ...framework.flags import define_flag, get_flag

define_flag("log_pallas_fallback", False,
            "Log every Pallas-kernel fallback to the XLA path with its "
            "reason (dispatch decisions are trace-time)")

#: {role: (flops, bytes)} of one call
Work = Mapping[str, Tuple[float, float]]

_COUNTS: collections.Counter = collections.Counter()
#: the capture in progress: role -> [calls, flops, bytes]; None outside
_capture: Optional[Dict[str, list]] = None
#: whether the trace being captured is differentiated right now
_differentiated = False
#: whether the trace in progress is a segment that
#: ``optimizer.meta.recompute`` runs again in the backward
_recomputed = False
#: step name -> the work of one execution, from its latest trace
_STEP_WORK: Dict[str, Dict[str, Dict[str, float]]] = {}


def bump(kernel: str, path: str, reason: str = "",
         work: Optional[Work] = None,
         grad_work: Optional[Work] = None, times: int = 1) -> None:
    """Count one dispatch decision (or ``times`` of a thing that is
    counted in bulk: a looped model's passes, its block applications).
    ``work`` is what the call requires, ``grad_work`` what its backward
    requires on top when the enclosing trace differentiates it
    (:func:`differentiated`); both only count inside a :func:`capture`."""
    _COUNTS[f"{kernel}.{path}"] += times
    if path == "xla" and get_flag("log_pallas_fallback"):
        msg = f"pallas-fallback: {kernel} -> {path}"
        if reason:
            msg += f" ({reason})"
        sys.stderr.write(msg + "\n")
    if _capture is None:
        return
    for part in (work, grad_work if _differentiated else None):
        for role, (flops, moved) in (part or {}).items():
            row = _capture.setdefault(role, [0, 0.0, 0.0])
            row[0] += 1
            row[1] += flops
            row[2] += moved


@contextlib.contextmanager
def capture(step: Optional[str]):
    """Sum the work that dispatches declare while ``step`` is traced and
    keep it as the work of one execution of it (the latest trace wins).
    ``capture(None)`` drops what is declared inside: the autotuner times
    its candidates on the side of whichever trace asked for a verdict."""
    global _capture
    outer, _capture = _capture, {}
    try:
        yield
        if step is not None:
            _STEP_WORK[step] = {
                role: {"calls": c, "flops": f, "bytes": b}
                for role, (c, f, b) in _capture.items()}
    finally:
        _capture = outer


@contextlib.contextmanager
def differentiated():
    """Inside, a dispatch's ``grad_work`` counts: the caller takes the
    gradient of what is traced here."""
    global _differentiated
    outer, _differentiated = _differentiated, True
    try:
        yield
    finally:
        _differentiated = outer


@contextlib.contextmanager
def recomputed():
    """Inside, ``optimizer.meta.recompute`` traces a segment on the jit
    path: its checkpoint keeps what a kernel's forward rule names for it
    (:func:`in_recomputed`), and the dispatch counts that."""
    global _recomputed
    outer, _recomputed = _recomputed, True
    try:
        yield
    finally:
        _recomputed = outer


def in_recomputed() -> bool:
    return _recomputed


def step_work(step: str) -> Dict[str, Dict[str, float]]:
    """{role: {"calls", "flops", "bytes"}} of ONE execution of the
    compiled step ``step``; empty when it was never traced or launched
    no kernel that declares work. Roles under a data-dependent branch
    are all listed, and one of them runs."""
    return {role: dict(row) for role, row in _STEP_WORK.get(step, {}).items()}


def nbytes(*arrays) -> int:
    """Bytes of the arrays (or shape structs) as they are."""
    return sum(int(a.size) * a.dtype.itemsize for a in arrays)


def kernel_call(role: str, kernel, **kwargs):
    """``pl.pallas_call`` under the kernel's role name.

    XLA names a Mosaic custom call after the innermost scope of its
    ``op_name``, and jax folds the first scope inside ``jvp(...)`` /
    ``transpose(...)`` into the transform's own name, so a kernel called
    straight under ``jax.grad`` would show as ``jvp_<role>_``. The plain
    ``pallas`` scope above the role keeps the row ``<role>`` wherever
    the call sits (seen in the HLO compiled for a v5e, PR 24)."""
    import jax
    from jax.experimental import pallas as pl

    call = pl.pallas_call(kernel, name=role, **kwargs)

    def named(*operands):
        with jax.named_scope("pallas"):
            return call(*operands)

    return named


def snapshot() -> Dict[str, int]:
    return dict(_COUNTS)


def delta(before: Dict[str, int]) -> Dict[str, int]:
    return {k: v - before.get(k, 0) for k, v in _COUNTS.items()
            if v - before.get(k, 0)}


def reset() -> None:
    _COUNTS.clear()
    _STEP_WORK.clear()
