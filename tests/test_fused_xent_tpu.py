"""Fused linear+cross-entropy — TPU-only hardware checks: real Mosaic
lowering of the 2D-grid reduction idiom (output-ref accumulators
revisited across the inner vocab axis) and fwd+bwd numerics at the
real MLM-head scale. Self-gates; runs on a chip with
`JAX_PLATFORMS=tpu python -m pytest tests/test_fused_xent_tpu.py`."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

pytestmark = pytest.mark.skipif(
    jax.default_backend() != "tpu",
    reason="Mosaic lowering needs a real TPU backend")


def _data(n, h, v, seed=0):
    rng = np.random.RandomState(seed)
    return (jnp.asarray(rng.randn(n, h) * 0.2, jnp.float32),
            jnp.asarray(rng.randn(v, h) * 0.2, jnp.float32),
            jnp.asarray(rng.randn(v) * 0.1, jnp.float32),
            jnp.asarray(rng.randint(0, v, n), jnp.int32))


def _ref_loss(h, w, b, lab):
    logits = h @ w.T + b
    logp = jax.nn.log_softmax(logits, axis=-1)
    ll = jnp.take_along_axis(logp, lab[:, None], axis=1)[:, 0]
    return jnp.mean(-ll)


def test_fused_xent_lowers_and_matches_xla():
    from paddle_tpu.ops.pallas import counters
    from paddle_tpu.ops.pallas.fused_xent import (
        fused_linear_cross_entropy,
    )

    h, w, b, lab = _data(1024, 768, 30592)
    counters.reset()
    out = fused_linear_cross_entropy(h, w, b, lab)
    assert counters.snapshot().get("fused_xent.pallas", 0) == 1, (
        counters.snapshot())
    ref = _ref_loss(h, w, b, lab)
    np.testing.assert_allclose(float(out), float(ref), rtol=5e-4)


def test_fused_xent_bwd_lowers_and_matches_xla():
    from paddle_tpu.ops.pallas.fused_xent import (
        fused_linear_cross_entropy,
    )

    h, w, b, lab = _data(512, 768, 30592, seed=1)
    gf = jax.grad(lambda *a: fused_linear_cross_entropy(*a, lab),
                  argnums=(0, 1, 2))(h, w, b)
    gr = jax.grad(lambda *a: _ref_loss(*a, lab), argnums=(0, 1, 2))(h, w,
                                                                    b)
    for a, r in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                   rtol=2e-3, atol=2e-4)
