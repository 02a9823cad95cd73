from benchmarks import kernel_rows
from benchmarks.rows_under_top import with_rows_under_top


def read(run):
    """None unless this process's program built an output-gated
    attention layer (``gqa.output_gate``, the program's own counter:
    absent on a commit without that argument, and in the cells whose
    grouped attention has no gate, which have their own two readings)."""
    from paddle_tpu.ops.pallas import counters

    if not counters.snapshot().get("gqa.output_gate"):
        return None
    return kernel_rows.roofline_pct(with_rows_under_top(run),
                                "flash_attention")
