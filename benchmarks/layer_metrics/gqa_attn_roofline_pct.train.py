from benchmarks import kernel_rows


def read(run):
    """None unless this process dispatched grouped-query attention to the
    Pallas kernels (the program's own counter: absent on a commit that
    has no such path, zero in a cell whose model has one key head a
    query head, as the Kimi cell's MLA layer on the same stream roles)."""
    from paddle_tpu.ops.pallas import counters

    if not counters.snapshot().get("flash_attention.grouped"):
        return None
    return kernel_rows.roofline_pct(run, "flash_attention")
