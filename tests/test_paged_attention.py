"""Ragged paged attention (ops/pallas/paged_attention.py) in interpret
mode (CPU-hermetic): kernel parity against the XLA gather fallback and
a dense reference, page-write scatter semantics, dispatch counters,
the PADDLE_PAGED_ATTENTION=0 escape leg, and the autotune cache keys —
the same coverage contract the flash_attention kernel carries."""
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu.framework.bringup as bringup
from paddle_tpu.ops.pallas import autotune, counters
from paddle_tpu.ops.pallas import paged_attention as pa


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


def _pool(b=3, h=2, d=16, s=8, pages=12, t=3, seed=0):
    rng = np.random.RandomState(seed)
    q = jnp.asarray(rng.randn(b, h, d), jnp.float32)
    kp = jnp.asarray(rng.randn(pages, s, h, d), jnp.float32)
    vp = jnp.asarray(rng.randn(pages, s, h, d), jnp.float32)
    return q, kp, vp


def _dense_ref(q, kp, vp, table, lens):
    """Plain-softmax reference over the gathered pages."""
    B, H, D = q.shape
    S = kp.shape[1]
    T = table.shape[1]
    k = kp[jnp.maximum(table, 0)].reshape(B, T * S, H, D)
    v = vp[jnp.maximum(table, 0)].reshape(B, T * S, H, D)
    s = jnp.einsum("bhd,bkhd->bhk", q, k) / math.sqrt(D)
    pos = jnp.arange(T * S)
    s = jnp.where(pos[None, None, :] < lens[:, None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhk,bkhd->bhd", p, v)


def test_kernel_matches_fallback_and_dense_ragged():
    """Mixed lengths, partially filled tables, a part-filled tail
    page: the kernel, the XLA gather fallback, and the dense reference
    agree."""
    q, kp, vp = _pool()
    table = jnp.asarray([[1, 2, 3], [4, 5, -1], [6, -1, -1]], jnp.int32)
    lens = jnp.asarray([20, 11, 5], jnp.int32)
    ref = pa._xla_paged_attention(q, kp, vp, table, lens)
    out = pa._paged_attention_pallas(q, kp, vp, table, lens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    dense = _dense_ref(q, kp, vp, table, lens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(dense),
                               rtol=2e-5, atol=2e-5)


def test_kernel_single_token_and_full_table():
    q, kp, vp = _pool(b=2, t=4, pages=16, seed=3)
    table = jnp.asarray([[1, 2, 3, 4], [5, 6, 7, 8]], jnp.int32)
    for lens in ([1, 32], [32, 1], [17, 9]):
        lens = jnp.asarray(lens, jnp.int32)
        ref = pa._xla_paged_attention(q, kp, vp, table, lens)
        out = pa._paged_attention_pallas(q, kp, vp, table, lens)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)


def test_kernel_ignores_dead_page_contents():
    """Pages past ceil(len/S) and -1 table slots must not leak into the
    output whatever garbage they hold."""
    q, kp, vp = _pool(seed=5)
    table = jnp.asarray([[1, 2, -1], [3, -1, -1], [4, 5, 6]], jnp.int32)
    lens = jnp.asarray([10, 3, 24], jnp.int32)
    out1 = pa._paged_attention_pallas(q, kp, vp, table, lens)
    # poison every page the tables don't reach live
    kp2 = kp.at[7:].set(1e4).at[0].set(-1e4)
    vp2 = vp.at[7:].set(1e4).at[0].set(-1e4)
    out2 = pa._paged_attention_pallas(q, kp2, vp2, table, lens)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2),
                               rtol=1e-6, atol=1e-6)


def test_paged_write_scatter_and_trash_page():
    """paged_write lands each sequence's token at page_table[pos//S],
    offset pos%S; inactive lanes land on the reserved page 0."""
    _, kp, vp = _pool(b=2, pages=6, t=2)
    table = jnp.asarray([[3, 4], [5, -1]], jnp.int32)
    positions = jnp.asarray([9, 2], jnp.int32)   # page 4 off 1, page 5 off 2
    new_k = jnp.full((2, 2, 16), 7.0, jnp.float32)
    new_v = jnp.full((2, 2, 16), -7.0, jnp.float32)
    k2, v2 = pa.paged_write(kp, vp, table, positions, new_k, new_v,
                            jnp.asarray([True, True]))
    np.testing.assert_allclose(np.asarray(k2[4, 1]), 7.0)
    np.testing.assert_allclose(np.asarray(v2[5, 2]), -7.0)
    # untouched elsewhere
    np.testing.assert_allclose(np.asarray(k2[3]), np.asarray(kp[3]))
    # inactive lane routes at the trash page 0 and clobbers nothing live
    k3, _ = pa.paged_write(kp, vp, table, positions, new_k, new_v,
                           jnp.asarray([False, False]))
    np.testing.assert_allclose(np.asarray(k3[1:]), np.asarray(kp[1:]))


def test_paged_prefill_write_roundtrip():
    _, kp, vp = _pool(pages=8)
    page_ids = jnp.asarray([2, 5], jnp.int32)
    new_k = jnp.arange(2 * 8 * 2 * 16, dtype=jnp.float32
                       ).reshape(16, 2, 16)
    k2, _ = pa.paged_prefill_write(kp, vp, page_ids, new_k, new_k)
    np.testing.assert_allclose(np.asarray(k2[2]),
                               np.asarray(new_k[:8]))
    np.testing.assert_allclose(np.asarray(k2[5]),
                               np.asarray(new_k[8:]))


# ---------------------------------------------------------------------------
# dispatch: counters, eligibility gate, escape leg, kernel-error fallback
# ---------------------------------------------------------------------------
def _eligible_shapes(seed=0):
    # S=128, D=64: inside the _paged_ok contract
    rng = np.random.RandomState(seed)
    q = jnp.asarray(rng.randn(2, 2, 64), jnp.float32)
    kp = jnp.asarray(rng.randn(5, 128, 2, 64), jnp.float32)
    vp = jnp.asarray(rng.randn(5, 128, 2, 64), jnp.float32)
    table = jnp.asarray([[1, 2], [3, -1]], jnp.int32)
    lens = jnp.asarray([200, 70], jnp.int32)
    return q, kp, vp, table, lens


def test_dispatch_pallas_bumps_counter(monkeypatch):
    monkeypatch.setattr(bringup, "pallas_enabled", lambda: True)
    q, kp, vp, table, lens = _eligible_shapes()
    out = pa.paged_attention(q, kp, vp, table, lens)
    ref = pa._xla_paged_attention(q, kp, vp, table, lens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    assert counters.snapshot().get("paged_attention.pallas", 0) == 1


def test_dispatch_ineligible_falls_back_with_counter(monkeypatch):
    monkeypatch.setattr(bringup, "pallas_enabled", lambda: True)
    q, kp, vp = _pool()          # S=8: outside the page-size contract
    table = jnp.asarray([[1, 2, 3], [4, 5, -1], [6, -1, -1]], jnp.int32)
    lens = jnp.asarray([20, 11, 5], jnp.int32)
    out = pa.paged_attention(q, kp, vp, table, lens)
    ref = pa._xla_paged_attention(q, kp, vp, table, lens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    assert counters.snapshot().get("paged_attention.xla", 0) == 1
    assert counters.snapshot().get("paged_attention.pallas", 0) == 0


def test_dispatch_kernel_error_propagates(monkeypatch):
    """A kernel that was chosen and then fails raises — it must not be
    counted as an XLA dispatch and served from the gather path."""
    monkeypatch.setattr(bringup, "pallas_enabled", lambda: True)

    def boom(*a, **k):
        raise RuntimeError("mosaic said no")

    monkeypatch.setattr(pa, "_paged_attention_pallas", boom)
    q, kp, vp, table, lens = _eligible_shapes()
    with pytest.raises(RuntimeError, match="mosaic said no"):
        pa.paged_attention(q, kp, vp, table, lens)
    assert counters.snapshot().get("paged_attention.xla", 0) == 0


def test_escape_env_pins_xla_bitwise(monkeypatch):
    """PADDLE_PAGED_ATTENTION=0 pins the gather path even on an
    eligible shape — and its output is bitwise the fallback's."""
    monkeypatch.setattr(bringup, "pallas_enabled", lambda: True)
    monkeypatch.setenv("PADDLE_PAGED_ATTENTION", "0")
    q, kp, vp, table, lens = _eligible_shapes()
    out = pa.paged_attention(q, kp, vp, table, lens)
    ref = pa._xla_paged_attention(q, kp, vp, table, lens)
    assert np.asarray(out).tobytes() == np.asarray(ref).tobytes()
    assert counters.snapshot().get("paged_attention.pallas", 0) == 0
    assert counters.snapshot().get("paged_attention.xla", 0) == 1


def test_paged_ok_gate():
    class _Arr:
        def __init__(self, shape):
            self.shape = shape

    import paddle_tpu.ops.pallas.paged_attention as mod

    real = bringup.pallas_enabled
    try:
        bringup.pallas_enabled = lambda: True

        def ok(h, d, s):
            return mod._paged_ok(_Arr((2, h, d)), _Arr((4, s, h, d)))

        assert ok(4, 64, 128) and ok(8, 128, 256)
        assert not ok(4, 48, 128)       # head_dim % 64
        assert not ok(4, 64, 100)       # page_size % 128
        assert not ok(4, 512, 128)      # D ceiling
        assert not ok(4, 64, 2048)      # page VMEM ceiling
    finally:
        bringup.pallas_enabled = real


# ---------------------------------------------------------------------------
# autotune: paged verdict keys, memoization, disk persistence
# ---------------------------------------------------------------------------
@pytest.fixture
def _autotune_tmp(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    autotune.reset()
    yield tmp_path
    autotune.reset()


def test_paged_cache_key_namespaced():
    key = autotune.paged_cache_key(4, 8, 128, 2, 64, jnp.float32)
    assert key[0] == "paged"
    assert key == ("paged", 4, 8, 128, 2, 64, str(jnp.float32))
    # distinct from any flash key shape and from other paged shapes
    assert autotune.paged_cache_key(4, 8, 128, 2, 64, jnp.bfloat16) != key
    assert autotune.paged_cache_key(8, 8, 128, 2, 64, jnp.float32) != key


def test_paged_choice_none_off_tpu(_autotune_tmp):
    q, kp, _, table, _ = _eligible_shapes()
    assert autotune.paged_attention_choice(q, kp, table) is None


def test_paged_selection_memoizes_and_persists(monkeypatch,
                                               _autotune_tmp):
    import paddle_tpu.utils.timing as timing

    monkeypatch.setattr(bringup, "pallas_enabled", lambda: True)
    monkeypatch.setattr(bringup, "TPU_PLATFORMS", ("cpu", "tpu"))
    times = iter([5.0, 1.0])    # pallas, xla -> xla wins
    calls = []

    def fake_timeit(fn, *a, **k):
        calls.append(fn)
        return next(times)

    monkeypatch.setattr(timing, "timeit", fake_timeit)
    q, kp, _, table, _ = _eligible_shapes()
    assert autotune.paged_attention_choice(q, kp, table) == "xla"
    assert len(calls) == 2
    # memoized: same shape re-queries pay nothing
    assert autotune.paged_attention_choice(q, kp, table) == "xla"
    assert len(calls) == 2
    # a fresh process (reset memo, keep disk) reads the persisted
    # verdict instead of re-timing
    autotune._cache.clear()
    autotune._disk = None
    monkeypatch.setattr(timing, "timeit",
                        lambda *a, **k: pytest.fail("re-timed a "
                                                    "persisted verdict"))
    assert autotune.paged_attention_choice(q, kp, table) == "xla"
    assert autotune.stats()["disk_hits"] >= 1


def test_paged_autotuned_xla_choice_drives_dispatch(monkeypatch,
                                                    _autotune_tmp):
    monkeypatch.setattr(bringup, "pallas_enabled", lambda: True)
    monkeypatch.setattr(bringup, "TPU_PLATFORMS", ("cpu", "tpu"))
    import paddle_tpu.utils.timing as timing

    monkeypatch.setattr(timing, "timeit",
                        lambda fn, *a, **k: {0: 9.0}.get(id(fn) % 1, 1.0))
    # force the verdict directly: dispatch must honor it with the
    # autotuned-xla counter reason
    q, kp, vp, table, lens = _eligible_shapes()
    key = autotune.paged_cache_key(q.shape[0], table.shape[1],
                                   kp.shape[1], q.shape[1], q.shape[2],
                                   q.dtype)
    autotune._cache[key] = "xla"
    out = pa.paged_attention(q, kp, vp, table, lens)
    ref = pa._xla_paged_attention(q, kp, vp, table, lens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    assert counters.snapshot().get("paged_attention.xla", 0) == 1
    assert counters.snapshot().get("paged_attention.pallas", 0) == 0


# ---------------------------------------------------------------------------
# int8 KV pages (kv_codec="int8"): quant parity, writes, dispatch
# ---------------------------------------------------------------------------
from paddle_tpu.ps.codec import jnp_encode_kv_rows  # noqa: E402


def _quant_pool(**kw):
    q, kp, vp = _pool(**kw)
    kq, ks = jnp_encode_kv_rows(kp)
    vq, vs = jnp_encode_kv_rows(vp)
    return q, kq, vq, ks, vs, kp, vp


def test_quant_xla_tracks_f32_reference():
    """Dequantized attention stays within int8-roundoff of the f32
    pool — the kv_quant_loss gate at unit scale."""
    q, kq, vq, ks, vs, kp, vp = _quant_pool()
    table = jnp.asarray([[1, 2, 3], [4, 5, -1], [6, -1, -1]], jnp.int32)
    lens = jnp.asarray([20, 11, 5], jnp.int32)
    ref = pa._xla_paged_attention(q, kp, vp, table, lens)
    out = pa._xla_paged_attention_quant(q, kq, vq, ks, vs, table, lens)
    assert float(jnp.max(jnp.abs(out - ref))) <= 5e-2


def test_quant_kernel_matches_quant_xla():
    """The quant kernel and the quant gather fallback are the same
    function of the encoded pool."""
    q, kq, vq, ks, vs, _, _ = _quant_pool(seed=9)
    table = jnp.asarray([[1, 2, 3], [4, 5, -1], [6, -1, -1]], jnp.int32)
    lens = jnp.asarray([20, 11, 5], jnp.int32)
    ref = pa._xla_paged_attention_quant(q, kq, vq, ks, vs, table, lens)
    out = pa._paged_attention_pallas_quant(q, kq, vq, ks, vs, table,
                                           lens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_paged_write_quant_roundtrip_and_trash_page():
    """paged_write_quant encodes the row in place: payload at
    [page, off], its scale on the (P, S) plane, and inactive lanes
    land on the reserved page 0."""
    _, kp, vp = _pool(b=2, pages=6, t=2)
    kq, ks = jnp_encode_kv_rows(kp)
    vq, vs = jnp_encode_kv_rows(vp)
    table = jnp.asarray([[3, 4], [5, -1]], jnp.int32)
    positions = jnp.asarray([9, 2], jnp.int32)
    new_k = jnp.full((2, 2, 16), 7.0, jnp.float32)
    new_v = jnp.full((2, 2, 16), -7.0, jnp.float32)
    k2, v2, ks2, vs2 = pa.paged_write_quant(
        kq, vq, ks, vs, table, positions, new_k, new_v,
        jnp.asarray([True, True]))
    # dequant lands back on the written constant
    np.testing.assert_allclose(
        np.asarray(k2[4, 1].astype(jnp.float32) * ks2[4, 1]),
        7.0, rtol=1e-2)
    np.testing.assert_allclose(
        np.asarray(v2[5, 2].astype(jnp.float32) * vs2[5, 2]),
        -7.0, rtol=1e-2)
    # untouched elsewhere (payload AND scale planes)
    np.testing.assert_array_equal(np.asarray(k2[3]), np.asarray(kq[3]))
    np.testing.assert_array_equal(np.asarray(ks2[3]), np.asarray(ks[3]))
    # inactive lanes route to the trash page
    k3, _, ks3, _ = pa.paged_write_quant(
        kq, vq, ks, vs, table, positions, new_k, new_v,
        jnp.asarray([False, False]))
    np.testing.assert_array_equal(np.asarray(k3[1:]), np.asarray(kq[1:]))
    np.testing.assert_array_equal(np.asarray(ks3[1:]),
                                  np.asarray(ks[1:]))


def test_paged_prefill_write_quant_roundtrip():
    _, kp, vp = _pool(pages=8)
    kq, ks = jnp_encode_kv_rows(kp)
    vq, vs = jnp_encode_kv_rows(vp)
    page_ids = jnp.asarray([2, 5], jnp.int32)
    new_k = jnp.asarray(np.random.RandomState(4).randn(16, 2, 16),
                        jnp.float32)
    k2, _, ks2, _ = pa.paged_prefill_write_quant(kq, vq, ks, vs,
                                                 page_ids, new_k, new_k)
    deq = np.asarray(k2[2].astype(jnp.float32)) * \
        np.asarray(ks2[2])[:, None, None]
    np.testing.assert_allclose(deq, np.asarray(new_k[:8]), atol=0.05)
    deq5 = np.asarray(k2[5].astype(jnp.float32)) * \
        np.asarray(ks2[5])[:, None, None]
    np.testing.assert_allclose(deq5, np.asarray(new_k[8:]), atol=0.05)


def test_quant_dispatch_counters_and_escape(monkeypatch):
    monkeypatch.setattr(bringup, "pallas_enabled", lambda: True)
    rng = np.random.RandomState(2)
    q = jnp.asarray(rng.randn(2, 2, 64), jnp.float32)
    kp = jnp.asarray(rng.randn(5, 128, 2, 64), jnp.float32)
    vp = jnp.asarray(rng.randn(5, 128, 2, 64), jnp.float32)
    kq, ks = jnp_encode_kv_rows(kp)
    vq, vs = jnp_encode_kv_rows(vp)
    table = jnp.asarray([[1, 2], [3, -1]], jnp.int32)
    lens = jnp.asarray([200, 70], jnp.int32)
    out = pa.paged_attention(q, kq, vq, table, lens, k_scales=ks,
                             v_scales=vs)
    ref = pa._xla_paged_attention_quant(q, kq, vq, ks, vs, table, lens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    assert counters.snapshot().get("paged_attention.pallas", 0) == 1
    # the escape env pins the quant gather path bitwise
    monkeypatch.setenv("PADDLE_PAGED_ATTENTION", "0")
    out2 = pa.paged_attention(q, kq, vq, table, lens, k_scales=ks,
                              v_scales=vs)
    assert np.asarray(out2).tobytes() == np.asarray(ref).tobytes()
    assert counters.snapshot().get("paged_attention.xla", 0) == 1
