"""A run with the ``kernel:`` rows that its driver's tracer kept from
under the reduction's ten largest (``drivers/conv_hybrid_lm_step.
KernelRowTracer``, the observation ``kernel_rows_under_top``) put back
behind them, for the readers of ``kernel_rows.py``: a kernel whose row is
the eleventh is then read like one whose row is the ninth. The run itself
is left as it is."""
from __future__ import annotations


def with_rows_under_top(run: dict) -> dict:
    trace = run.get("trace")
    under = (run.get("observations") or {}).get("kernel_rows_under_top")
    if not (trace and under):
        return run
    return dict(run, trace=dict(
        trace, device_ops=list(trace["device_ops"]) + list(under)))
