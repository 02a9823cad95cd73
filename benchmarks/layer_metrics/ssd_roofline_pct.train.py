from benchmarks import kernel_rows


def read(run):
    """None where the program keeps no ledger or the reduction has no
    ``kernel:ssd_chunk`` row."""
    return kernel_rows.roofline_pct(run, "ssd_chunk")
