def read(run):
    obs = run["observations"]
    if "train_tokens_per_s" not in obs or "flops_per_token" not in obs:
        return None
    peak = run["chips"] * run["peaks"]["bf16_flops_per_s"]
    return 100.0 * obs["train_tokens_per_s"] * obs["flops_per_token"] / peak
