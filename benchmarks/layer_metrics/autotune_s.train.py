def read(run):
    from paddle_tpu.ops.pallas import autotune

    return autotune.stats().get("timed_s")
