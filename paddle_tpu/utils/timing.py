"""Shared micro-benchmark timing for the Pallas autotuner,
tools/op_bench.py and tools/tune_flash.py."""
from __future__ import annotations

import time

import jax


def timeit(fn, *args, iters=20):
    """ms/iteration of ``fn(*args)``: one warm-up call (compile + first
    run) outside the clock, then ``iters`` dispatches closed by
    ``block_until_ready`` — dispatch is asynchronous, so the barrier is
    what makes the window cover the device work. (chip_smoke.py checks
    on every run that the barrier really waits.)"""
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e3
