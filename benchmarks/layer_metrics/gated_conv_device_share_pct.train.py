from benchmarks import kernel_rows


def read(run):
    """None unless this process dispatched a gated short convolution to
    its fused stage (the program's own counter: absent on a commit that
    has no such mixer, and in a cell whose model has none). The kernel's
    row is a small one: where the reduction's ten largest rows leave it
    out, the cell's driver hands it on as the observation
    ``kernel_rows_under_top`` (``drivers/conv_hybrid_lm_step.py``)."""
    from paddle_tpu.ops.pallas import counters

    if not counters.snapshot().get("gated_conv.fused"):
        return None
    trace = run.get("trace")
    under = (run.get("observations") or {}).get(
        "kernel_rows_under_top")
    if trace and under:
        run = dict(run, trace=dict(
            trace, device_ops=trace["device_ops"] + under))
    return kernel_rows.device_share_pct(run, "gated_conv")
