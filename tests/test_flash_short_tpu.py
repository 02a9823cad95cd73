"""Short single-block flash kernel — TPU-only hardware checks (the
in-kernel PRNG dropout has no CPU interpreter path, and real-Mosaic
lowering is exactly what the r3 fused-embedding bug showed interpret
mode cannot vouch for). Self-gates; runs on a chip with
`JAX_PLATFORMS=tpu python -m pytest tests/test_flash_short_tpu.py`.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

pytestmark = pytest.mark.skipif(
    jax.default_backend() != "tpu",
    reason="Mosaic lowering + TPU PRNG need a real TPU backend")


def _arrs(rng, B, L, H, D, dtype=jnp.float32):
    return tuple(jnp.asarray(rng.randn(B, L, H, D), dtype)
                 for _ in range(3))


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("l", [128, 256])
def test_short_fwd_lowers_and_matches_xla(causal, l):
    from paddle_tpu.ops.pallas.flash_attention import (
        _flash_attention_pallas_short, _xla_attention)

    rng = np.random.RandomState(0)
    q, k, v = _arrs(rng, 2, l, 4, 64)
    out = _flash_attention_pallas_short(q, k, v, causal=causal)
    ref = _xla_attention(q, k, v, None, 0.0, causal, None)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_short_fused_bwd_matches_xla_on_hw():
    from paddle_tpu.ops.pallas.flash_attention import (
        _flash_attention_core_short, _xla_attention)

    rng = np.random.RandomState(1)
    q, k, v = _arrs(rng, 2, 128, 2, 64)

    def loss_s(q, k, v):
        return jnp.sum(_flash_attention_core_short(
            q, k, v, None, True, 0.0) ** 2)

    def loss_x(q, k, v):
        return jnp.sum(_xla_attention(q, k, v, None, 0.0, True,
                                      None) ** 2)

    gs = jax.grad(loss_s, argnums=(0, 1, 2))(q, k, v)
    gx = jax.grad(loss_x, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gs, gx):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-3, atol=1e-3)


def test_short_dropout_statistics_and_determinism():
    from paddle_tpu.ops.pallas.flash_attention import (
        _flash_attention_pallas_short)

    rng = np.random.RandomState(2)
    q, k, v = _arrs(rng, 2, 128, 2, 64)
    base = _flash_attention_pallas_short(q, k, v)
    outs = [_flash_attention_pallas_short(
        q, k, v, seed=jnp.asarray([[s]], jnp.int32), dropout_p=0.1)
        for s in range(32)]
    mean = jnp.mean(jnp.stack(outs), axis=0)
    rel = float(jnp.abs(mean - base).mean() / jnp.abs(base).mean())
    assert rel < 0.08, rel
    seed = jnp.asarray([[7]], jnp.int32)
    a = _flash_attention_pallas_short(q, k, v, seed=seed, dropout_p=0.1)
    b = _flash_attention_pallas_short(q, k, v, seed=seed, dropout_p=0.1)
    c = _flash_attention_pallas_short(q, k, v, seed=seed + 1,
                                      dropout_p=0.1)
    assert bool(jnp.all(a == b)) and bool(jnp.any(a != c))
