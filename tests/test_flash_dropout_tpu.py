"""In-kernel dropout flash attention — TPU-only checks (the Pallas PRNG
has no CPU interpreter path). Self-gates; runs on a chip with
`JAX_PLATFORMS=tpu python -m pytest tests/test_flash_dropout_tpu.py`
(tests/conftest.py forces the CPU otherwise).
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

pytestmark = pytest.mark.skipif(
    jax.default_backend() != "tpu",
    reason="Pallas TPU PRNG kernel needs a real TPU backend")


def _arrs(rng, B, L, H, D):
    return (jnp.asarray(rng.randn(B, L, H, D), jnp.float32)
            for _ in range(3))


def test_dropout_statistics_and_determinism():
    from paddle_tpu.ops.pallas.flash_attention import (
        _flash_attention_pallas, _flash_attention_pallas_dropout)
    rng = np.random.RandomState(0)
    q, k, v = _arrs(rng, 2, 128, 2, 64)
    base = _flash_attention_pallas(q, k, v)
    outs = [_flash_attention_pallas_dropout(
        q, k, v, jnp.asarray([[s]], jnp.int32), 0.1) for s in range(32)]
    mean = jnp.mean(jnp.stack(outs), axis=0)
    rel = float(jnp.abs(mean - base).mean() / jnp.abs(base).mean())
    assert rel < 0.08, rel
    seed = jnp.asarray([[11]], jnp.int32)
    a = _flash_attention_pallas_dropout(q, k, v, seed, 0.1)
    b = _flash_attention_pallas_dropout(q, k, v, seed, 0.1)
    c = _flash_attention_pallas_dropout(q, k, v, seed + 1, 0.1)
    assert bool(jnp.all(a == b)) and bool(jnp.any(a != c))


def test_dropout_fraction_exact():
    """With q=0 probs are uniform, so dropped entries of the recovered
    probability matrix are exactly zero; their fraction ~ dropout_p."""
    from paddle_tpu.ops.pallas.flash_attention import (
        _flash_attention_pallas_dropout)
    rng = np.random.RandomState(1)
    B, L, H, D = 1, 128, 1, 64
    q = jnp.zeros((B, L, H, D), jnp.float32)
    k = jnp.asarray(rng.randn(B, L, H, D), jnp.float32)
    seed = jnp.asarray([[5]], jnp.int32)
    pd = 0.25
    probs = np.zeros((L, L), np.float32)
    for blk in range(2):
        v = np.zeros((B, L, H, D), np.float32)
        for d in range(64):
            v[0, blk * 64 + d, 0, d] = 1.0
        out = _flash_attention_pallas_dropout(q, k, jnp.asarray(v), seed, pd)
        probs[:, blk * 64:(blk + 1) * 64] = np.asarray(out[0, :, 0, :])
    frac = float((probs == 0).mean())
    assert abs(frac - pd) < 0.03, frac


@pytest.mark.parametrize("L,causal", [(128, False), (512, True)])
def test_dropout_grads_directional(L, causal):
    """Directional derivative check; the keep mask is a pure function of
    (seed, tile), so f is smooth in q/k/v. Random cotangent weighting
    keeps the check sensitive (see optimization_barrier note in the bwd)."""
    from paddle_tpu.ops.pallas.flash_attention import (
        _flash_attention_pallas_dropout)
    rng = np.random.RandomState(2)
    q, k, v = _arrs(rng, 2, L, 2, 64)
    do = jnp.asarray(rng.randn(*q.shape), jnp.float32)
    seed = jnp.asarray([[9]], jnp.int32)
    pd = 0.2

    for name, fn, arr, t in [
        ("dq", lambda a: jnp.sum(_flash_attention_pallas_dropout(
            a, k, v, seed, pd, causal=causal) * do), q, 0.01),
        ("dk", lambda a: jnp.sum(_flash_attention_pallas_dropout(
            q, a, v, seed, pd, causal=causal) * do), k, 0.01),
        ("dv", lambda a: jnp.sum(_flash_attention_pallas_dropout(
            q, k, a, seed, pd, causal=causal) * do), v, 1.0),
    ]:
        g = jax.grad(fn)(arr)
        d = jnp.asarray(rng.randn(*arr.shape), jnp.float32)
        num = (float(fn(arr + t * d)) - float(fn(arr - t * d))) / (2 * t)
        ana = float(jnp.sum(g * d))
        assert abs(ana - num) / max(abs(num), 1e-6) < 0.05, (name, ana, num)


def test_dropout_constant_cotangent():
    """grad of plain sum(out): the cotangent is a broadcast constant —
    regression test for the Mosaic constant-folding mis-lowering that the
    optimization_barrier in the dropout bwd guards against."""
    from paddle_tpu.ops.pallas.flash_attention import (
        _flash_attention_pallas_dropout)
    rng = np.random.RandomState(3)
    q, k, v = _arrs(rng, 2, 128, 2, 64)
    seed = jnp.asarray([[21]], jnp.int32)
    fn = lambda a: jnp.sum(_flash_attention_pallas_dropout(q, k, a, seed, 0.2))
    g = jax.grad(fn)(v)
    d = jnp.asarray(rng.randn(*v.shape), jnp.float32)
    num = (float(fn(v + d)) - float(fn(v - d))) / 2.0   # linear in v
    ana = float(jnp.sum(g * d))
    assert abs(ana - num) / max(abs(num), 1e-6) < 0.05, (ana, num)
