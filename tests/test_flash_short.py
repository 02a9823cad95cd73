"""Short-sequence single-block flash kernels in interpret mode
(CPU-hermetic): fwd and the fused one-launch bwd must match the XLA
reference in the packed (B, L, H*D) layout, one head or two to a block,
and behind the transposing wrapper. What Mosaic makes of them, and the
dropout masks, are `chip_smoke.py kernels`' to check."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops.pallas import flash_attention as fa


@pytest.fixture(autouse=True)
def interpret_pallas(monkeypatch):
    from jax.experimental import pallas as pl

    real = pl.pallas_call
    monkeypatch.setattr(pl, "pallas_call",
                        functools.partial(real, interpret=True))
    yield


def _qkv(b=2, l=128, h=2, d=64, seed=0, dtype=jnp.float32):
    rng = np.random.RandomState(seed)
    return tuple(jnp.asarray(rng.randn(b, l, h, d), dtype)
                 for _ in range(3))


#: (heads, head width): two heads a 128-lane block (BERT's twelve, and the
#: least), one 128-wide head a block, and an odd count at 64 that keeps
#: the transposing wrapper
HEADS = [(12, 64), (2, 64), (4, 128), (3, 64)]


@pytest.mark.parametrize("h, d, width", [
    (12, 64, 128), (2, 64, 128), (4, 128, 128), (2, 256, 256),
    (3, 64, None), (2, 192, None), (1, 64, None)])
def test_short_block_width_holds_whole_heads(h, d, width):
    assert fa._short_block_width(h, d) == width


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("l", [128, 256, 512])
@pytest.mark.parametrize("h, d", HEADS)
def test_short_fwd_matches_xla(causal, l, h, d):
    q, k, v = _qkv(b=1 if h == 12 else 2, l=l, h=h, d=d)
    ref = fa._xla_attention(q, k, v, None, 0.0, causal, None)
    out = fa._flash_attention_core_short(q, k, v, None, causal, 0.0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def _grads(attention, q, k, v, w):
    return jax.grad(lambda q, k, v: jnp.sum(attention(q, k, v) * w),
                    argnums=(0, 1, 2))(q, k, v)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("l", [128, 512])
@pytest.mark.parametrize("h, d", HEADS)
def test_short_fused_bwd_matches_xla(causal, l, h, d):
    q, k, v = _qkv(b=1 if h == 12 else 2, l=l, h=h, d=d)
    w = _qkv(b=q.shape[0], l=l, h=h, d=d, seed=1)[0]  # a cotangent
    gs = _grads(lambda q, k, v: fa._flash_attention_core_short(
        q, k, v, None, causal, 0.0), q, k, v, w)
    gx = _grads(lambda q, k, v: fa._xla_attention(
        q, k, v, None, 0.0, causal, None), q, k, v, w)
    for a, b in zip(gs, gx):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("changed", [0, 1])
def test_two_heads_of_one_block_stay_apart(changed):
    """Heads 0 and 1 share a 128-lane block. New q, k, v and cotangent for
    one of them must change that head's output and gradients and leave
    the other's bit for bit: a kernel that swapped the two, computed one
    twice or let one leak into the other's sums fails here."""
    l, h, d = 128, 2, 64
    q, k, v = _qkv(l=l, h=h, d=d)
    w = _qkv(l=l, h=h, d=d, seed=1)[0]
    other = _qkv(l=l, h=h, d=d, seed=2) + _qkv(l=l, h=h, d=d, seed=3)[:1]
    q2, k2, v2, w2 = (x.at[:, :, changed].set(o[:, :, changed])
                      for x, o in zip((q, k, v, w), other))

    def run(q, k, v, w):
        short = lambda q, k, v: fa._flash_attention_core_short(  # noqa: E731
            q, k, v, None, False, 0.0)
        return (short(q, k, v),) + _grads(short, q, k, v, w)

    kept = 1 - changed
    for a, b in zip(run(q, k, v, w), run(q2, k2, v2, w2)):
        a, b = np.asarray(a), np.asarray(b)
        np.testing.assert_array_equal(a[:, :, kept], b[:, :, kept])
        assert np.abs(a[:, :, changed] - b[:, :, changed]).max() > 1e-2
    ref = fa._xla_attention(q2, k2, v2, None, 0.0, False, None)
    np.testing.assert_allclose(np.asarray(run(q2, k2, v2, w2)[0]),
                               np.asarray(ref), rtol=2e-5, atol=2e-5)


def _primitives(jaxpr):
    """Names of the primitives of a jaxpr and of every jaxpr nested in it,
    the bodies of ``pallas_call``s left out."""
    for eqn in jaxpr.eqns:
        yield eqn.primitive.name
        if eqn.primitive.name != "pallas_call":
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from _primitives(sub)


def test_packed_layout_moves_no_data_outside_the_kernels(monkeypatch):
    """At BERT's shape the gradient through the short kernels holds both
    kernels and no transpose beside them (the (B, L, H, D) <-> (B, L, H*D)
    reshapes move nothing); with an odd head count the transposing
    wrapper is still there. The dispatch says which it took."""
    import paddle_tpu.framework.bringup as bringup
    from paddle_tpu.framework.flags import set_flags
    from paddle_tpu.ops.pallas import counters

    def grad_primitives(shape):
        arg = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
        jaxpr = jax.make_jaxpr(jax.grad(
            lambda q, k, v: jnp.sum(fa._flash_attention_core_short(
                q, k, v, None, False, 0.0).astype(jnp.float32)),
            argnums=(0, 1, 2)))(arg, arg, arg)
        return list(_primitives(jaxpr.jaxpr))

    packed = grad_primitives((2, 512, 12, 64))
    assert packed.count("pallas_call") == 2
    assert "transpose" not in packed
    assert "transpose" in grad_primitives((2, 512, 3, 64))

    monkeypatch.setattr(bringup, "pallas_enabled", lambda: True)
    set_flags({"flash_short_seq": True})
    try:
        for h, want in ((12, 1), (3, 0)):
            counters.reset()
            fa._local_attention(*_qkv(b=1, l=128, h=h), False)
            got = counters.snapshot()
            assert got.get("flash_attention.pallas", 0) == 1
            assert got.get("flash_attention.short_packed", 0) == want
    finally:
        set_flags({"flash_short_seq": False})


def test_short_matches_streaming_kernel():
    """Same math as the streaming online-softmax kernel (including the
    lse side output used by the bwd)."""
    q, k, v = _qkv(l=256)
    out_s, res_s = fa._flash_attention_core_short_fwd(
        q, k, v, None, False, 0.0)
    out_f, res_f = fa._flash_attention_core_fwd(q, k, v, False, 128, 128)
    np.testing.assert_allclose(np.asarray(out_s), np.asarray(out_f),
                               rtol=2e-5, atol=2e-5)
    # lse: (B, H, 1, L) beside the packed layout, (B*H, 1, L) merged
    np.testing.assert_allclose(
        np.asarray(res_s[4]).reshape(res_f[4].shape), np.asarray(res_f[4]),
        rtol=2e-5, atol=2e-5)


def test_short_ok_eligibility():
    q, k, _ = _qkv(l=128)
    import paddle_tpu.framework.bringup as bringup
    orig = bringup.pallas_enabled
    bringup.pallas_enabled = lambda: True
    try:
        assert fa._short_ok(q, k, False)
        q2, k2, _ = _qkv(l=1024)
        assert not fa._short_ok(q2, k2, False), "beyond short max"
        assert not fa._short_ok(q, k2, False), "cross attention"
    finally:
        bringup.pallas_enabled = orig


def test_short_dispatch_flag_gates(monkeypatch):
    """flash_short_seq off (default): the short kernel is NOT entered
    at seq 128; on: it is (counter shows pallas engagement)."""
    import paddle_tpu.framework.bringup as bringup
    from paddle_tpu.framework.flags import set_flags
    from paddle_tpu.ops.pallas import counters

    monkeypatch.setattr(bringup, "pallas_enabled", lambda: True)
    q, k, v = _qkv(l=128)
    counters.reset()
    fa._local_attention(q, k, v, False)
    assert counters.snapshot().get("flash_attention.pallas", 0) == 0
    set_flags({"flash_short_seq": True})
    try:
        counters.reset()
        out = fa._local_attention(q, k, v, False)
        assert counters.snapshot().get("flash_attention.pallas", 0) == 1
        ref = fa._xla_attention(q, k, v, None, 0.0, False, None)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)
    finally:
        set_flags({"flash_short_seq": False})
