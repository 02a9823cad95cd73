from benchmarks import kernel_rows


def read(run):
    return kernel_rows.device_share_pct(run, "kda_chunk")
