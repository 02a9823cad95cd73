from benchmarks import kernel_rows


def read(run):
    """None unless this process built a looped model (the program's own
    counter ``causal_lm.ut_steps``, the passes of one build: absent on a
    commit that walks its layers once, and in every cell of a one-pass
    model)."""
    from paddle_tpu.ops.pallas import counters

    if counters.snapshot().get("causal_lm.ut_steps", 0) <= 1:
        return None
    return kernel_rows.device_share_pct(run, "flash_attention_stream")
