from benchmarks import kernel_rows


def read(run):
    """None where the reduction has no ``kernel:ssd_chunk`` row: on a
    CPU, on a commit whose program has no such kernel, in a cell whose
    model has no state-space layer."""
    return kernel_rows.device_share_pct(run, "ssd_chunk")
