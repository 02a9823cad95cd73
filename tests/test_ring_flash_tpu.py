"""Flash-ring attention — TPU-only hardware checks. Interpret mode
cannot vouch for Mosaic lowering (the r3 fused-embedding lesson), and
the flash-ring composition is novel on the chip: pallas_call inside
lax.switch inside fori_loop inside shard_map, with vma-typed out_shapes.

One real chip cannot rotate a >1 ring, so the shard_map here is a
1-device mesh: the custom_vjp, the switch diagonal branch, and both
backward kernels still lower and execute for real; multi-device
numerics are pinned by tests/test_ring_flash.py on the 8-device CPU
mesh. Self-gates; runs on a chip with
`JAX_PLATFORMS=tpu python -m pytest tests/test_ring_flash_tpu.py`.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

pytestmark = pytest.mark.skipif(
    jax.default_backend() != "tpu",
    reason="Mosaic lowering needs a real TPU backend")


def _mesh1():
    from jax.sharding import Mesh

    return Mesh(np.array(jax.devices()[:1]), ("sp",))


def _qkv(l=256, b=2, h=4, d=64, seed=0):
    rng = np.random.RandomState(seed)
    return tuple(jnp.asarray(rng.randn(b, l, h, d) * 0.5, jnp.float32)
                 for _ in range(3))


@pytest.mark.parametrize("causal", [False, True])
def test_ring_flash_lowers_and_matches_xla(causal):
    from jax.sharding import PartitionSpec

    from paddle_tpu.ops.pallas.flash_attention import _xla_attention
    from paddle_tpu.parallel.ring import _ring_flash

    q, k, v = _qkv()
    spec = PartitionSpec(None, "sp", None, None)

    def local(q_, k_, v_):
        bias = jnp.zeros((), jnp.float32)
        return _ring_flash(q_, k_, v_, bias, "sp", 1, causal, False)

    out = jax.shard_map(local, mesh=_mesh1(), in_specs=(spec,) * 3,
                        out_specs=spec)(q, k, v)
    ref = _xla_attention(q, k, v, None, 0.0, causal, None)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_ring_flash_bwd_lowers_and_matches_xla():
    from jax.sharding import PartitionSpec

    from paddle_tpu.ops.pallas.flash_attention import _xla_attention
    from paddle_tpu.parallel.ring import _ring_flash

    q, k, v = _qkv(seed=1)
    spec = PartitionSpec(None, "sp", None, None)

    def loss_ring(q_, k_, v_):
        def local(a, b_, c):
            bias = jnp.zeros((), jnp.float32)
            return _ring_flash(a, b_, c, bias, "sp", 1, True, False)

        out = jax.shard_map(local, mesh=_mesh1(), in_specs=(spec,) * 3,
                            out_specs=spec)(q_, k_, v_)
        return jnp.sum(out ** 2)

    def loss_x(q_, k_, v_):
        return jnp.sum(_xla_attention(q_, k_, v_, None, 0.0, True,
                                      None) ** 2)

    gr = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
    gx = jax.grad(loss_x, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gr, gx):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-3, atol=1e-3)


def test_ring_flash_masked_lowers():
    from jax.sharding import PartitionSpec

    from paddle_tpu.ops.pallas.flash_attention import _xla_attention
    from paddle_tpu.parallel.ring import _ring_flash

    q, k, v = _qkv(seed=2)
    b, l = q.shape[0], q.shape[1]
    mask = np.random.RandomState(3).rand(b, l) > 0.3
    mask[:, :32] = True
    bias = jnp.where(jnp.asarray(mask), 0.0, -1e30).astype(jnp.float32)
    spec = PartitionSpec(None, "sp", None, None)
    bspec = PartitionSpec(None, "sp")

    def local(q_, k_, v_, bias_):
        return _ring_flash(q_, k_, v_, bias_, "sp", 1, False, True)

    out = jax.shard_map(local, mesh=_mesh1(),
                        in_specs=(spec, spec, spec, bspec),
                        out_specs=spec)(q, k, v, bias)
    ref = _xla_attention(q, k, v, jnp.asarray(mask)[:, None, None, :],
                         0.0, False, None)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)
