"""Fused sampling (ops/pallas/sampling.py) in interpret mode
(CPU-hermetic): kernel parity against the XLA reference, greedy
short-circuit, top-k/top-p truncation semantics, dispatch counters,
the PADDLE_FUSED_SAMPLING=0 escape leg, and the autotune cache keys —
the same coverage contract the paged_attention kernel carries."""
import functools

import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu.framework.bringup as bringup
from paddle_tpu.ops.pallas import autotune, counters
from paddle_tpu.ops.pallas import sampling as sm


@pytest.fixture(autouse=True)
def interpret_pallas(monkeypatch):
    """Run pallas_call in interpret mode so kernels execute on CPU."""
    from jax.experimental import pallas as pl

    real = pl.pallas_call
    monkeypatch.setattr(pl, "pallas_call",
                        functools.partial(real, interpret=True))
    yield


@pytest.fixture(autouse=True)
def _reset_counters():
    counters.reset()
    yield
    counters.reset()


def _rows(b=4, v=128, seed=0):
    rng = np.random.RandomState(seed)
    logits = jnp.asarray(rng.randn(b, v) * 3, jnp.float32)
    noise = jnp.asarray(-np.log(-np.log(
        rng.uniform(1e-9, 1.0, (b, v)))), jnp.float32)
    return logits, noise


def test_temperature_zero_is_pure_argmax():
    """T <= 0 short-circuits to greedy and IGNORES the noise — the
    spec-decode-compatible leg."""
    logits, noise = _rows()
    out = np.asarray(sm.fused_sample(logits, noise, 0.0))
    assert (out == np.asarray(jnp.argmax(logits, -1))).all()
    out2 = np.asarray(sm.fused_sample(logits, noise * 100, 0.0))
    assert (out == out2).all()


@pytest.mark.parametrize("top_k", [0, 1, 4, 8])
def test_kernel_matches_xla_reference(top_k):
    logits, noise = _rows(seed=top_k)
    ref = np.asarray(sm._xla_sample(logits, noise, 0.7, top_k, 1.0))
    out = np.asarray(sm._fused_sample_pallas(logits, noise, 0.7, top_k))
    assert (out == ref).all()
    assert ((0 <= out) & (out < logits.shape[1])).all()


def test_top_k_truncates_support():
    """With top_k=2 the draw must land on one of the two largest
    logits no matter how hard the noise pulls elsewhere."""
    logits, _ = _rows(b=2, seed=3)
    order = np.argsort(-np.asarray(logits), axis=-1)
    # noise that screams for the WORST token
    noise = np.zeros(logits.shape, np.float32)
    for r in range(2):
        noise[r, order[r, -1]] = 1e4
    noise = jnp.asarray(noise)
    for fn in (lambda: sm._xla_sample(logits, noise, 1.0, 2, 1.0),
               lambda: sm._fused_sample_pallas(logits, noise, 1.0, 2)):
        out = np.asarray(fn())
        for r in range(2):
            assert out[r] in order[r, :2], (r, out[r], order[r, :4])


def test_top_p_truncates_support():
    """A peaked distribution under small top_p keeps only the head."""
    logits = jnp.asarray([[10.0, 9.9, -10.0, -10.0] + [-30.0] * 124],
                         jnp.float32)
    noise = jnp.zeros_like(logits).at[0, 2].set(1e4)
    out = np.asarray(sm._xla_sample(logits, noise, 1.0, 0, 0.9))
    assert out[0] in (0, 1)


def test_gumbel_max_matches_softmax_frequencies():
    """The Gumbel-max draw really samples softmax(logits/T): empirical
    frequencies over many iid noise rows track the analytic
    probabilities."""
    rng = np.random.RandomState(0)
    logits = jnp.asarray(np.tile([[2.0, 1.0, 0.0, -1.0] + [-30.0] * 124],
                                 (512, 1)), jnp.float32)
    noise = jnp.asarray(-np.log(-np.log(
        rng.uniform(1e-9, 1.0, (512, 128)))), jnp.float32)
    out = np.asarray(sm._xla_sample(logits, noise, 1.0, 0, 1.0))
    z = np.exp([2.0, 1.0, 0.0, -1.0])
    p = z / z.sum()
    freq = np.bincount(out, minlength=128)[:4] / 512
    np.testing.assert_allclose(freq, p, atol=0.08)


# ---------------------------------------------------------------------------
# dispatch: counters, gate, escape, autotune keys
# ---------------------------------------------------------------------------
def test_dispatch_pallas_bumps_counter(monkeypatch):
    monkeypatch.setattr(bringup, "pallas_enabled", lambda: True)
    logits, noise = _rows()
    out = np.asarray(sm.fused_sample(logits, noise, 0.8, top_k=4))
    ref = np.asarray(sm._xla_sample(logits, noise, 0.8, 4, 1.0))
    assert (out == ref).all()
    assert counters.snapshot().get("fused_sample.pallas", 0) == 1


def test_top_p_routes_to_xla_with_reason(monkeypatch):
    monkeypatch.setattr(bringup, "pallas_enabled", lambda: True)
    logits, noise = _rows()
    sm.fused_sample(logits, noise, 0.8, top_k=0, top_p=0.9)
    snap = counters.snapshot()
    assert snap.get("fused_sample.xla", 0) == 1
    assert snap.get("fused_sample.pallas", 0) == 0


def test_ineligible_vocab_falls_back(monkeypatch):
    monkeypatch.setattr(bringup, "pallas_enabled", lambda: True)
    logits, noise = _rows(v=100)                   # V % 128 != 0
    sm.fused_sample(logits, noise, 0.8)
    assert counters.snapshot().get("fused_sample.xla", 0) == 1


def test_kernel_error_propagates(monkeypatch):
    monkeypatch.setattr(bringup, "pallas_enabled", lambda: True)

    def boom(*a, **k):
        raise RuntimeError("mosaic said no")

    monkeypatch.setattr(sm, "_fused_sample_pallas", boom)
    logits, noise = _rows()
    with pytest.raises(RuntimeError, match="mosaic said no"):
        sm.fused_sample(logits, noise, 0.8, top_k=2)
    assert counters.snapshot().get("fused_sample.xla", 0) == 0


def test_escape_env_pins_xla_bitwise(monkeypatch):
    monkeypatch.setattr(bringup, "pallas_enabled", lambda: True)
    monkeypatch.setenv("PADDLE_FUSED_SAMPLING", "0")
    logits, noise = _rows()
    out = np.asarray(sm.fused_sample(logits, noise, 0.8, top_k=4))
    ref = np.asarray(sm._xla_sample(logits, noise, 0.8, 4, 1.0))
    assert out.tobytes() == ref.tobytes()
    snap = counters.snapshot()
    assert snap.get("fused_sample.pallas", 0) == 0
    assert snap.get("fused_sample.xla", 0) == 1


def test_sample_ok_gate(monkeypatch):
    monkeypatch.setattr(bringup, "pallas_enabled", lambda: True)
    logits, _ = _rows()
    assert sm._sample_ok(logits, 0, 1.0)
    assert sm._sample_ok(logits, sm._KERNEL_TOPK_MAX, 1.0)
    assert not sm._sample_ok(logits, sm._KERNEL_TOPK_MAX + 1, 1.0)
    assert not sm._sample_ok(logits, 0, 0.95)
    big, _ = _rows(b=1, v=128 * 256)               # past the VMEM cap
    assert not sm._sample_ok(big, 0, 1.0)
    monkeypatch.setattr(bringup, "pallas_enabled", lambda: False)
    assert not sm._sample_ok(logits, 0, 1.0)


def test_sample_cache_key_namespaced():
    key = autotune.sample_cache_key(4, 128, jnp.float32, 4)
    assert "sample" in str(key)
    assert key != autotune.sample_cache_key(4, 128, jnp.float32, 8)
    assert key != autotune.sample_cache_key(8, 128, jnp.float32, 4)
