from benchmarks import kernel_rows


def read(run):
    """None unless this process dispatched the fused cross-entropy for a
    loss a row (the program's own counter ``fused_xent.per_row``: absent
    on a commit that has no such entry, zero in a cell whose head returns
    the mean)."""
    from paddle_tpu.ops.pallas import counters

    if not counters.snapshot().get("fused_xent.per_row"):
        return None
    return kernel_rows.roofline_pct(run, "fused_xent")
