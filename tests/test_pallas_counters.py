"""Pallas dispatch counters (VERDICT r3 weak #4/#8): fallbacks to the
XLA path are counted with reasons and optionally logged — never silent.
On the CPU test backend every dispatch is a fallback, which is exactly
what the counters must report."""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.framework.flags import set_flags
from paddle_tpu.ops.pallas import counters


@pytest.fixture(autouse=True)
def _fresh_counters():
    counters.reset()
    yield
    counters.reset()


def test_attention_dispatch_counted():
    import jax
    import jax.numpy as jnp

    from paddle_tpu.nn import functional as F

    before = counters.snapshot()
    q = jnp.zeros((2, 64, 4, 64), jnp.float32)
    F.scaled_dot_product_attention(q, q, q, is_causal=True,
                                   training=False)
    d = counters.delta(before)
    assert d.get("flash_attention.xla", 0) >= 1, d
    assert d.get("flash_attention.pallas", 0) == 0


@pytest.mark.parametrize("kind, kv_heads, length, kwargs, want", [
    ("stream", 2, 256, {"is_causal": True}, 1),
    ("grouped", 1, 256, {"is_causal": True}, 1),
    ("windowed", 2, 256, {"is_causal": True, "window": 128}, 1),
    ("masked", 2, 256, {"mask": True}, 1),
    # the short kernels' backward always was one launch: not counted
    ("short", 2, 128, {"is_causal": True}, 0),
])
def test_stream_backward_counts_its_one_launch(monkeypatch, kind, kv_heads,
                                               length, kwargs, want):
    """``flash_attention.bwd_one_launch``: once a dispatch that took the
    streaming kernels, beside ``flash_attention.pallas``; a dispatch to
    the short kernels or to XLA does not count it."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu.framework.bringup as bringup
    from paddle_tpu.ops.pallas import flash_attention as fa

    q = jnp.zeros((2, length, 2, 64), jnp.float32)
    k = jnp.zeros((2, length, kv_heads, 64), jnp.float32)
    if kwargs.get("mask"):
        kwargs = {"mask": jnp.ones((2, 1, 1, length), bool)}

    def trace():
        # a function of its own each time: jax keeps a function's trace
        jax.eval_shape(lambda q, k: fa.flash_attention_or_fallback(
            q, k, k, **kwargs), q, k)

    trace()                                       # the gate shut: XLA
    assert "flash_attention.bwd_one_launch" not in counters.snapshot()
    monkeypatch.setattr(bringup, "pallas_enabled", lambda: True)
    monkeypatch.setattr(fa, "_get_flag_short", lambda: kind == "short")
    before = counters.snapshot()
    trace()
    d = counters.delta(before)
    assert d.get("flash_attention.pallas") == 1, d
    assert d.get("flash_attention.bwd_one_launch", 0) == want, d


def test_fused_embedding_dispatch_counted():
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas.fused_embedding import \
        fused_embedding_seq_pool

    before = counters.snapshot()
    table = jnp.ones((64, 128), jnp.float32)
    ids = jnp.zeros((8, 8), jnp.int32)
    fused_embedding_seq_pool(table, ids, combiner="sum")
    d = counters.delta(before)
    assert d.get("fused_embedding.xla", 0) >= 1, d


def test_fallback_logging_flag(capfd):
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas.fused_embedding import \
        fused_embedding_seq_pool

    set_flags({"log_pallas_fallback": True})
    try:
        table = jnp.ones((64, 128), jnp.float32)
        ids = jnp.zeros((8, 8), jnp.int32)
        fused_embedding_seq_pool(table, ids, combiner="sum")
    finally:
        set_flags({"log_pallas_fallback": False})
    err = capfd.readouterr().err
    assert "pallas-fallback: fused_embedding -> xla" in err


def test_counters_shape():
    counters.bump("flash_attention", "pallas")
    counters.bump("flash_attention", "xla", "why")
    snap = counters.snapshot()
    assert snap["flash_attention.pallas"] == 1
    assert snap["flash_attention.xla"] == 1
    assert counters.delta(snap) == {}
