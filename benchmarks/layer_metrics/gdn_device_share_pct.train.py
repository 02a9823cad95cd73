from benchmarks import kernel_rows
from benchmarks.rows_under_top import with_rows_under_top


def read(run):
    """None unless this process's program counted a scalar-decay delta
    rule (``gdn.scalar_decay``, the program's own counter: absent on a
    commit without that mixer, and in the Kimi cell, which runs the same
    kernels on a decay per channel and has its own two readings)."""
    from paddle_tpu.ops.pallas import counters

    if not counters.snapshot().get("gdn.scalar_decay"):
        return None
    return kernel_rows.device_share_pct(with_rows_under_top(run), "kda_chunk")
