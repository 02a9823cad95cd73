from benchmarks import kernel_rows


def read(run):
    """None unless this process dispatched attention with a value width
    of its own to the stream kernels (the program's own counter: absent
    on a commit that does not count it, zero in a cell whose attention
    has one width)."""
    from paddle_tpu.ops.pallas import counters

    if not counters.snapshot().get("flash_attention.latent"):
        return None
    return kernel_rows.roofline_pct(run, "flash_attention_stream")
