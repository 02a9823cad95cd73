"""Attention dispatch autotune (FLAGS_cudnn_exhaustive_search parity):
selection, caching, failure, and dispatch wiring. Real on-device
timing is exercised by chip_smoke.py; here the timer is stubbed and
kernels run in interpret mode."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu.framework.bringup as bringup
from paddle_tpu.ops.pallas import autotune, counters
from paddle_tpu.ops.pallas import flash_attention as fa


@pytest.fixture(autouse=True)
def _reset(monkeypatch, tmp_path):
    # point the persistent verdict cache at a per-test dir so a warm
    # disk cache from a previous run can't satisfy a lookup the test
    # expects to re-time
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    autotune.reset()
    counters.reset()
    yield
    autotune.reset()
    counters.reset()


@pytest.fixture
def interpret_pallas(monkeypatch):
    from jax.experimental import pallas as pl

    real = pl.pallas_call
    monkeypatch.setattr(pl, "pallas_call",
                        functools.partial(real, interpret=True))
    yield


def _q(l=128, b=2, h=2, d=64):
    rng = np.random.RandomState(0)
    return jnp.asarray(rng.randn(b, l, h, d), jnp.float32)


def test_choice_none_off_tpu():
    q = _q()
    assert autotune.short_window_choice(q, q, False, 0.0) is None


def test_selection_picks_min_and_caches(monkeypatch, interpret_pallas):
    import paddle_tpu.utils.timing as timing

    monkeypatch.setattr(bringup, "pallas_enabled", lambda: True)
    monkeypatch.setattr(bringup, "TPU_PLATFORMS", ("cpu", "tpu"))
    calls = []
    # candidate order at seq 128 (stream ineligible below its floor):
    # short, xla
    times = iter([3.0, 1.0])

    def fake_timeit(fn, *args, iters=0):
        calls.append(fn)
        return next(times)

    monkeypatch.setattr(timing, "timeit", fake_timeit)
    q = _q(l=128)
    choice = autotune.short_window_choice(q, q, False, 0.0)
    assert choice == "xla"
    assert len(calls) == 2
    # memoized: no more timing on the same shape
    assert autotune.short_window_choice(q, q, False, 0.0) == "xla"
    assert len(calls) == 2
    # different shape -> fresh tuning
    times2 = iter([1.0, 9.0, 9.0])
    monkeypatch.setattr(timing, "timeit",
                        lambda fn, *a, **k: next(times2))
    q2 = _q(l=256)
    assert autotune.short_window_choice(q2, q2, False, 0.0) == "short"


def test_probe_hands_every_candidate_the_projections_layout(
        monkeypatch, interpret_pallas):
    """What the probe times: forward and backward from three distinct
    (B, L, H*D) arrays, as a model's projections write them, under a
    cotangent with values of its own. Every candidate gets the same
    operands and returns the same three gradients."""
    import paddle_tpu.utils.timing as timing

    monkeypatch.setattr(bringup, "pallas_enabled", lambda: True)
    monkeypatch.setattr(bringup, "TPU_PLATFORMS", ("cpu", "tpu"))
    grads = []

    def run_once(fn, *args, iters=0):
        grads.append(fn(*args))
        return float(len(grads))            # the first candidate wins

    monkeypatch.setattr(timing, "timeit", run_once)
    q = _q(l=256, b=1, h=2, d=64)
    assert autotune.short_window_choice(q, q, False, 0.0) == "short"
    assert len(grads) == 3                  # short, stream, xla
    for got in grads:
        assert [g.shape for g in got] == [(1, 256, 128)] * 3
        assert not np.allclose(got[0], got[1], atol=1e-3)   # dq is not dk
        for a, b in zip(got, grads[-1]):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-4)


def test_failed_candidate_raises_and_pins_nothing(monkeypatch,
                                                  interpret_pallas):
    """Every candidate passed its shape gate, so one that fails to
    compile is an error — not a loss to XLA — and no verdict is cached
    in memory or on disk."""
    import paddle_tpu.utils.timing as timing

    monkeypatch.setattr(bringup, "pallas_enabled", lambda: True)
    monkeypatch.setattr(bringup, "TPU_PLATFORMS", ("cpu", "tpu"))

    def exploding_timeit(fn, *args, iters=0):
        if exploding_timeit.n == 0:
            exploding_timeit.n += 1
            raise RuntimeError("mosaic says no")
        return 1.0

    exploding_timeit.n = 0
    monkeypatch.setattr(timing, "timeit", exploding_timeit)
    q = _q(l=128)
    with pytest.raises(RuntimeError, match="mosaic says no"):
        autotune.short_window_choice(q, q, False, 0.0)
    assert autotune.cached_choices() == {}
    import os

    assert not os.path.exists(autotune._disk_path())


def test_dispatch_routes_on_choice(monkeypatch, interpret_pallas):
    monkeypatch.setattr(bringup, "pallas_enabled", lambda: True)
    q = _q(l=128)

    monkeypatch.setattr(autotune, "short_window_choice",
                        lambda *a: "short")
    out = fa._local_attention(q, q, q, False)
    assert counters.snapshot().get("flash_attention.pallas", 0) == 1
    ref = fa._xla_attention(q, q, q, None, 0.0, False, None)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)

    counters.reset()
    monkeypatch.setattr(autotune, "short_window_choice",
                        lambda *a: "xla")
    out2 = fa._local_attention(q, q, q, False)
    snap = counters.snapshot()
    assert snap.get("flash_attention.pallas", 0) == 0
    assert snap.get("flash_attention.xla", 0) == 1
    np.testing.assert_allclose(np.asarray(out2), np.asarray(ref),
                               rtol=1e-6)


def test_disk_persistence_skips_retiming(monkeypatch, interpret_pallas):
    """A warm disk cache means a fresh 'process' (reset() simulates one)
    pays zero on-chip timings for a known shape — VERDICT r4 weak #5."""
    import paddle_tpu.utils.timing as timing

    monkeypatch.setattr(bringup, "pallas_enabled", lambda: True)
    monkeypatch.setattr(bringup, "TPU_PLATFORMS", ("cpu", "tpu"))
    times = iter([3.0, 1.0])
    monkeypatch.setattr(timing, "timeit", lambda fn, *a, **k: next(times))
    q = _q(l=128)
    assert autotune.short_window_choice(q, q, False, 0.0) == "xla"
    assert autotune.stats()["timed"] == 1

    # simulate a new process: in-memory state gone, disk cache kept
    autotune.reset()
    monkeypatch.setattr(
        timing, "timeit",
        lambda *a, **k: (_ for _ in ()).throw(
            AssertionError("warm shape must not re-time")))
    assert autotune.short_window_choice(q, q, False, 0.0) == "xla"
    st = autotune.stats()
    assert st["disk_hits"] == 1 and st["timed"] == 0
    # and a third lookup in the same process hits memory, not disk
    assert autotune.short_window_choice(q, q, False, 0.0) == "xla"
    assert autotune.stats()["mem_hits"] == 1

    # reset(disk=True) wipes the persisted verdicts too
    autotune.reset(disk=True)
    times2 = iter([1.0, 2.0])
    monkeypatch.setattr(timing, "timeit", lambda fn, *a, **k: next(times2))
    assert autotune.short_window_choice(q, q, False, 0.0) == "short"
    assert autotune.stats()["timed"] == 1


def test_disk_cache_survives_corruption(monkeypatch, interpret_pallas,
                                        tmp_path):
    """A truncated/garbage cache file must not break dispatch."""
    import paddle_tpu.utils.timing as timing

    monkeypatch.setattr(bringup, "pallas_enabled", lambda: True)
    monkeypatch.setattr(bringup, "TPU_PLATFORMS", ("cpu", "tpu"))
    path = autotune._disk_path()
    import os

    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        f.write("{not json")
    times = iter([3.0, 1.0])
    monkeypatch.setattr(timing, "timeit", lambda fn, *a, **k: next(times))
    q = _q(l=128)
    assert autotune.short_window_choice(q, q, False, 0.0) == "xla"


def test_tuner_runs_under_an_outer_jit_trace(monkeypatch, interpret_pallas):
    """Dispatch decisions are taken at trace time: the tuner must run
    its candidates eagerly on concrete inputs even while the dispatch
    site is being traced (a ConcretizationTypeError here used to be
    swallowed into 'static dispatch keeps')."""
    monkeypatch.setattr(bringup, "pallas_enabled", lambda: True)
    monkeypatch.setattr(bringup, "TPU_PLATFORMS", ("cpu", "tpu"))
    seen = []

    @jax.jit
    def f(q):
        seen.append(autotune.short_window_choice(q, q, False, 0.0))
        return q

    f(_q(l=128))
    assert seen and seen[0] in ("short", "xla")
    assert autotune.stats()["timed"] == 1


def test_compile_cache_dir_colocates_and_counts(monkeypatch,
                                               interpret_pallas,
                                               tmp_path):
    """Verdicts persist under <compile cache dir>/autotune — tuned
    configs relaunch alongside the compiled steps, under
    JAX_COMPILATION_CACHE_DIR when set and the fixed in-checkout
    directory otherwise — and a disk hit bumps the process-global
    autotune_disk_hits counter (COMPILE_COUNTER_NAMES slice)."""
    import os

    import paddle_tpu.utils.timing as timing
    from paddle_tpu import profiler

    monkeypatch.setattr(bringup, "pallas_enabled", lambda: True)
    monkeypatch.setattr(bringup, "TPU_PLATFORMS", ("cpu", "tpu"))
    from paddle_tpu.static import compile_cache

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    assert autotune._cache_dir() == os.path.join(
        compile_cache._CHECKOUT_DIR, "autotune")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                       str(tmp_path / "xla_cache"))
    autotune.reset()
    assert autotune._cache_dir() == str(tmp_path / "xla_cache" /
                                        "autotune")
    times = iter([3.0, 1.0])
    monkeypatch.setattr(timing, "timeit", lambda fn, *a, **k: next(times))
    q = _q(l=128)
    assert autotune.short_window_choice(q, q, False, 0.0) == "xla"
    assert os.path.exists(autotune._disk_path())
    # fresh "process": the verdict reloads from the co-located cache and
    # the counter records the saved timing round
    before = profiler.counters_snapshot().get("autotune_disk_hits", 0)
    autotune.reset()
    monkeypatch.setattr(
        timing, "timeit",
        lambda *a, **k: (_ for _ in ()).throw(
            AssertionError("warm shape must not re-time")))
    assert autotune.short_window_choice(q, q, False, 0.0) == "xla"
    assert autotune.stats()["disk_hits"] == 1
    assert profiler.counters_snapshot()["autotune_disk_hits"] == \
        before + 1
