def read(run):
    from paddle_tpu.static import compile_cache

    seconds = getattr(compile_cache, "seconds_by_function", None)
    return seconds().get("train_step") if seconds is not None else None
