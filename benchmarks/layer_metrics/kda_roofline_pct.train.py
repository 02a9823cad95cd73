from benchmarks import kernel_rows


def read(run):
    return kernel_rows.roofline_pct(run, "kda_chunk")
