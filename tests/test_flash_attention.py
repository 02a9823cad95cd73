"""Flash-attention Pallas kernels in interpret mode (CPU-hermetic): the
forward/backward math must match the XLA reference. On-chip speed is
the BERT cells' (benchmarks/run.py)."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops.pallas import flash_attention as fa


@pytest.fixture(autouse=True)
def interpret_pallas(monkeypatch):
    """Run pallas_call in interpret mode so kernels execute on CPU."""
    from jax.experimental import pallas as pl
    import functools

    real = pl.pallas_call
    monkeypatch.setattr(pl, "pallas_call",
                        functools.partial(real, interpret=True))
    yield


def _qkv(b=2, l=256, h=2, d=64, seed=0, dtype=jnp.float32, hkv=None,
         dv=None):
    """q, k, v; ``hkv`` key heads (default ``h``), values ``dv`` wide
    (default ``d``)."""
    rng = np.random.RandomState(seed)
    return tuple(jnp.asarray(rng.randn(b, l, heads, width), dtype)
                 for heads, width in ((h, d), (hkv or h, d),
                                      (hkv or h, dv or d)))


@pytest.mark.parametrize("causal", [False, True])
def test_flash_fwd_matches_xla(causal):
    q, k, v = _qkv()
    ref = fa._xla_attention(q, k, v, None, 0.0, causal, None)
    out = fa._flash_attention_core(q, k, v, causal, 128, 128)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def _grads(f, *args):
    """Gradients of ``sum(f(q, k, v) * w)`` in q, k, v, ``w`` fixed."""
    q, v = args[0], args[2]
    w = jnp.asarray(np.random.RandomState(7).randn(
        *q.shape[:3], v.shape[-1]), jnp.float32)
    return jax.grad(lambda *a: jnp.sum(f(*a) * w), argnums=(0, 1, 2))(*args)


#: id -> (length, heads, key heads, key width, value width, causal,
#: window, block_q, block_kv): the one-launch backward at the widths and
#: groups the benchmark's cells hand it
BWD_CASES = {
    "64-full": (256, 2, 2, 64, 64, False, None, 128, 128),
    "64-causal": (256, 2, 2, 64, 64, True, None, 128, 128),
    # latent attention: keys 192, values 128
    "mla-192-128-full": (512, 2, 2, 192, 128, False, None, 128, 128),
    "mla-192-128-causal": (512, 2, 2, 192, 128, True, None, 128, 256),
    "128-full": (512, 2, 2, 128, 128, False, None, 256, 128),
    "128-causal": (512, 2, 2, 128, 128, True, None, 128, 128),
    "128-group8-full": (512, 8, 1, 128, 128, False, None, 128, 128),
    "128-group8-causal": (512, 8, 1, 128, 128, True, None, 128, 128),
    # a window of 1,024 is four kv blocks of 256 wide
    "128-group8-window1024": (2048, 8, 1, 128, 128, True, 1024, 512, 256),
    "128-window1024": (2048, 2, 2, 128, 128, True, 1024, 256, 256),
}


@pytest.mark.parametrize("case", sorted(BWD_CASES))
def test_flash_bwd_matches_xla(case):
    length, h, hkv, d, dv, causal, window, bq, bkv = BWD_CASES[case]
    q, k, v = _qkv(1, length, h, d, hkv=hkv, dv=dv)
    got = _grads(lambda q, k, v: fa._flash_attention_core(
        q, k, v, causal, bq, bkv, window), q, k, v)
    want = _grads(lambda q, k, v: fa._xla_attention(
        q, k, v, None, 0.0, causal, None, window=window), q, k, v)
    for a, b in zip(got, want):
        assert a.shape == b.shape
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


def _two_launch_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                          dq_ref, *, kv_len, block_kv, sm_scale, causal,
                          q_block, window):
    """The dQ launch the streaming backward had until PR 42 (grid heads x
    q blocks, K and V resident), kept here as the reference of ORDER: the
    one-launch kernel forms the same products and adds them over kv
    blocks in the same ascending order, so its dQ has the same bits."""
    from jax.experimental import pallas as pl

    q = q_ref[...].astype(jnp.float32) * sm_scale
    do = do_ref[...].astype(jnp.float32)
    lse, delta = lse_ref[0, :], delta_ref[0, :]
    bq = q.shape[0]
    qi = pl.program_id(1)

    def body(j, dq):
        k = k_ref[pl.dslice(j * block_kv, block_kv), :].astype(jnp.float32)
        v = v_ref[pl.dslice(j * block_kv, block_kv), :].astype(jnp.float32)
        s = fa._dot(q, k, trans_b=True)
        if causal:
            q_pos = qi * q_block + jax.lax.broadcasted_iota(
                jnp.int32, (bq, block_kv), 0)
            k_pos = j * block_kv + jax.lax.broadcasted_iota(
                jnp.int32, (bq, block_kv), 1)
            s = fa._band(s, q_pos, k_pos, window)
        p = jnp.exp(s - lse[:, None])
        dp = fa._dot(do, v, trans_b=True)
        ds = p * (dp - delta[:, None])
        return dq + fa._dot(ds, k)

    last = kv_len // block_kv
    if causal:
        last = jnp.minimum(((qi + 1) * q_block - 1) // block_kv + 1, last)
    dq = jax.lax.fori_loop(
        fa._first_kv_block(qi, q_block, block_kv, window), last, body,
        jnp.zeros_like(q))
    dq_ref[...] = (dq * sm_scale).astype(dq_ref.dtype)


def _two_launch_dq(qm, km, vm, dom, lse, delta, causal, bq, bkv, sm_scale,
                   window):
    from jax.experimental import pallas as pl

    bh, ql, d = qm.shape
    kl, dv = km.shape[1], vm.shape[2]
    group = bh // km.shape[0]

    def rows(i, j):
        return (i, j, 0)

    def stat(i, j):
        return (i, 0, j)

    def whole(i, j):
        return (i // group, 0, 0)

    return pl.pallas_call(
        functools.partial(_two_launch_dq_kernel, kv_len=kl, block_kv=bkv,
                          sm_scale=sm_scale, causal=causal, q_block=bq,
                          window=window),
        grid=(bh, ql // bq),
        in_specs=[pl.BlockSpec((None, bq, d), rows),
                  pl.BlockSpec((None, kl, d), whole),
                  pl.BlockSpec((None, kl, dv), whole),
                  pl.BlockSpec((None, bq, dv), rows),
                  pl.BlockSpec((None, 1, bq), stat),
                  pl.BlockSpec((None, 1, bq), stat)],
        out_specs=pl.BlockSpec((None, bq, d), rows),
        out_shape=jax.ShapeDtypeStruct((bh, ql, d), qm.dtype),
    )(qm, km, vm, dom, lse, delta)


@pytest.mark.parametrize("case", ["mla-192-128-causal", "128-group8-full",
                                  "128-window1024"])
def test_one_launch_dq_is_the_two_launch_dq_bit_for_bit(case):
    length, h, hkv, d, dv, causal, window, bq, bkv = BWD_CASES[case]
    q, k, v = _qkv(1, length, h, d, seed=5, hkv=hkv, dv=dv)
    dout = jnp.asarray(np.random.RandomState(6).randn(1, length, h, dv),
                       jnp.float32)
    sm_scale = 1.0 / np.sqrt(d)
    qm, km, vm, dom = (fa._mergeheads(a) for a in (q, k, v, dout))
    out_m, lse = fa._fwd_call(qm, km, vm, causal, bq, bkv, sm_scale,
                              window=window)
    delta = jnp.sum(dom * out_m, axis=-1)[:, None, :]
    dq, _, _ = fa._bwd_call(qm, km, vm, dom, lse, delta, causal, bq, bkv,
                            sm_scale, window=window)
    want = _two_launch_dq(qm, km, vm, dom, lse, delta, causal, bq, bkv,
                          sm_scale, window)
    assert bool(jnp.all(jnp.isfinite(dq))) and float(jnp.abs(dq).max()) > 0
    assert np.array_equal(np.asarray(dq), np.asarray(want))


def _hashed_keep_mask(seed, row, qi, j, shape, dropout_p):
    """A keep mask that is a pure function of (seed, row, q tile, kv tile,
    position in the tile) like the chip generator's, in plain integer
    arithmetic the interpreter runs."""
    r = jax.lax.broadcasted_iota(jnp.uint32, shape, 0)
    c = jax.lax.broadcasted_iota(jnp.uint32, shape, 1)
    tile = (jnp.asarray(seed).astype(jnp.uint32) * jnp.uint32(40503)
            + jnp.asarray(row).astype(jnp.uint32) * jnp.uint32(9973)
            + jnp.asarray(qi).astype(jnp.uint32) * jnp.uint32(613)
            + jnp.asarray(j).astype(jnp.uint32) * jnp.uint32(149))
    x = (r * jnp.uint32(2654435761) + c * jnp.uint32(40499) + tile
         ) * jnp.uint32(2246822519)
    x = x ^ (x >> 15)
    return (x % jnp.uint32(1000)) >= jnp.uint32(int(dropout_p * 1000))


@pytest.mark.parametrize("causal", [False, True])
def test_flash_dropout_bwd_regenerates_the_forwards_mask(monkeypatch,
                                                         causal):
    """The one-launch backward seeds a tile's keep mask by (row, q tile,
    kv tile) as the forward does: with a generator the interpreter can
    run in the chip's place, dQ, dK, dV are those of XLA attention
    under the same mask laid out whole."""
    monkeypatch.setattr(fa, "_keep_mask", _hashed_keep_mask)
    length, h, d, bq, bkv, p_drop = 256, 2, 64, 128, 128, 0.25
    q, k, v = _qkv(1, length, h, d, seed=8)
    seed = jnp.asarray([[11]], jnp.int32)
    keep = np.zeros((1, h, length, length), bool)
    for row in range(h):
        for i in range(length // bq):
            for j in range(length // bkv):
                keep[0, row, i * bq:(i + 1) * bq, j * bkv:(j + 1) * bkv] = \
                    np.asarray(_hashed_keep_mask(11, row, i, j, (bq, bkv),
                                                 p_drop))
    assert 0.2 < 1 - keep.mean() < 0.3

    def reference(q, k, v):
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(d)
        if causal:
            s = jnp.where(jnp.tril(jnp.ones((length, length), bool)), s,
                          -1e30)
        p = jnp.where(keep, jax.nn.softmax(s, axis=-1) / (1 - p_drop), 0.0)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v)

    got = _grads(lambda q, k, v: fa._flash_attention_core_dropout(
        q, k, v, seed, causal, bq, bkv, p_drop), q, k, v)
    want = _grads(reference, q, k, v)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


def test_flash_uneven_blocks():
    """kv blocks smaller than q blocks and vice versa."""
    q, k, v = _qkv(l=512)
    ref = fa._xla_attention(q, k, v, None, 0.0, True, None)
    out = fa._flash_attention_core(q, k, v, True, 256, 128)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def _padding_mask(b, l, lens):
    m = np.zeros((b, l), bool)
    for i, n in enumerate(lens):
        m[i, :n] = True
    return jnp.asarray(m)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_masked_fwd_matches_xla(causal):
    q, k, v = _qkv(b=2, l=256)
    mask = _padding_mask(2, 256, [256, 192])
    bias = fa._kv_mask_bias(mask, 2, 256)
    assert bias is not None
    got = fa._flash_attention_pallas_masked(q, k, v, bias, causal=causal)
    # XLA reference consumes the (B,1,1,L) bool form
    ref = fa._xla_attention(q, k, v, mask[:, None, None, :], 0.0,
                            causal, None)
    valid = np.asarray(mask)
    np.testing.assert_allclose(np.asarray(got)[valid],
                               np.asarray(ref)[valid], rtol=2e-3,
                               atol=2e-3)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_masked_bwd_matches_xla(causal):
    q, k, v = _qkv(b=2, l=256)
    mask = _padding_mask(2, 256, [224, 160])
    bias = fa._kv_mask_bias(mask, 2, 256)
    valid = np.asarray(mask)

    def loss_pallas(q, k, v):
        out = fa._flash_attention_pallas_masked(q, k, v, bias,
                                                causal=causal)
        return jnp.sum(jnp.where(mask[:, :, None, None], out, 0.0) ** 2)

    def loss_xla(q, k, v):
        out = fa._xla_attention(q, k, v, mask[:, None, None, :], 0.0,
                                causal, None)
        return jnp.sum(jnp.where(mask[:, :, None, None], out, 0.0) ** 2)

    gp = jax.grad(loss_pallas, argnums=(0, 1, 2))(q, k, v)
    gx = jax.grad(loss_xla, argnums=(0, 1, 2))(q, k, v)
    for a, b_, name in zip(gp, gx, "qkv"):
        np.testing.assert_allclose(np.asarray(a)[valid],
                                   np.asarray(b_)[valid], rtol=5e-3,
                                   atol=5e-3,
                                   err_msg=f"d{name} mismatch")


def test_kv_mask_bias_shapes():
    m = jnp.ones((2, 1, 1, 256), bool)
    assert fa._kv_mask_bias(m, 2, 256).shape == (2, 256)
    # per-query mask is rejected (stays on the XLA path)
    per_q = jnp.ones((2, 1, 256, 256), bool)
    assert fa._kv_mask_bias(per_q, 2, 256) is None
    # float additive masks stay on XLA (their gradient is real there)
    add = jnp.zeros((2, 256), jnp.float32)
    assert fa._kv_mask_bias(add, 2, 256) is None


def test_pallas_ok_floor_vs_modulus(monkeypatch):
    """seq_floor is a perf floor; 128 is the hard tile modulus. Lengths
    >= floor but not multiples of 256 (384, 640) must stay eligible —
    the wrappers fall back to 128-wide blocks for them."""
    monkeypatch.setattr(
        "paddle_tpu.framework.bringup.pallas_enabled", lambda: True)

    def ok(l):
        q = jnp.zeros((1, l, 2, 64), jnp.float32)
        return fa._pallas_ok(q, q, False)

    assert not ok(128)       # below floor: XLA wins there (measured)
    assert ok(256) and ok(384) and ok(512) and ok(640)
    assert not ok(192)       # not a multiple of the 128 tile
    assert not ok(8192 + 128)  # above the VMEM ceiling


def test_flash_wrappers_128_block_fallback_at_384():
    """Non-multiple-of-256 lengths must produce correct output (the
    grid would silently drop tail tiles if 256 blocks were kept)."""
    q, k, v = _qkv(l=384)
    ref = fa._xla_attention(q, k, v, None, 0.0, False, None)
    out = fa._flash_attention_pallas(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    mask = _padding_mask(2, 384, [300, 384])
    bias = fa._kv_mask_bias(mask, 2, 384)
    ref_m = fa._xla_attention(q, k, v, mask[:, None, None, :], 0.0,
                              False, None)
    out_m = fa._flash_attention_pallas_masked(q, k, v, bias)
    valid = np.asarray(mask)
    np.testing.assert_allclose(np.asarray(out_m)[valid],
                               np.asarray(ref_m)[valid], rtol=2e-5,
                               atol=2e-5)
