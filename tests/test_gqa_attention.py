"""Grouped-query attention with rotary positions and a sliding window:
the rotary op and YaRN's frequencies by hand, the band's mask, the
streaming Pallas kernels with groups and windows against the XLA path
(interpret mode, CPU-hermetic), the dispatch counters, and the layer —
loss and every gradient — against the benchmark's plain reference
(``benchmarks/reference/mellum2.py``, written from the equations and
sharing no code with ``paddle_tpu.nn``)."""
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from benchmarks.reference import mellum2 as ref
from paddle_tpu import nn
from paddle_tpu.nn import functional as F
from paddle_tpu.ops.pallas import counters
from paddle_tpu.ops.pallas import flash_attention as fa

#: the published rope_parameters of Mellum2-12B-A2.5B
YARN = {"rope_type": "yarn", "rope_theta": 500000, "factor": 16,
        "original_max_position_embeddings": 8192, "beta_fast": 32,
        "beta_slow": 1, "attention_factor": 1.2772588722239782}
PLAIN = {"rope_type": "default", "rope_theta": 500000}


@pytest.fixture
def interpret(monkeypatch):
    from jax.experimental import pallas as pl

    monkeypatch.setattr(pl, "pallas_call",
                        functools.partial(pl.pallas_call, interpret=True))


def _qkv(b, l, h, hkv, d, seed=0):
    rng = np.random.RandomState(seed)
    return (jnp.asarray(rng.randn(b, l, h, d), jnp.float32),
            jnp.asarray(rng.randn(b, l, hkv, d), jnp.float32),
            jnp.asarray(rng.randn(b, l, hkv, d), jnp.float32))


# ---------------------------------------------------------------------------
# rotary positions
# ---------------------------------------------------------------------------
def test_yarn_inv_freq_by_hand_at_the_published_parameters():
    inv, low, high = F.yarn_inv_freq(128, 500000, 16, 8192, 32, 1)
    # c(r) = 128 ln(8192 / (2 pi r)) / (2 ln 500000): c(32) = 18.08,
    # c(1) = 34.98
    assert (low, high) == (18, 35)
    assert inv.shape == (64,) and inv.dtype == np.float64
    # i = 0 (below low): extrapolated, untouched
    assert inv[0] == pytest.approx(1.0)
    # i = 10 (below low): 500000 ** (-20 / 128)
    assert inv[10] == pytest.approx(500000 ** (-20 / 128), rel=1e-12)
    # i = 26 (inside the ramp, (26 - 18) / 17 of the way): blended
    base = 500000 ** (-52 / 128)
    ramp = 8 / 17
    assert inv[26] == pytest.approx(base / 16 * ramp + base * (1 - ramp),
                                    rel=1e-12)
    # i = 50 (past high): interpolated, a sixteenth
    assert inv[50] == pytest.approx(500000 ** (-100 / 128) / 16, rel=1e-12)
    # the reference computes the same from its own code
    want, scale = ref.rope_inv_freq(128, YARN)
    np.testing.assert_allclose(inv, want, rtol=1e-12)
    assert scale == YARN["attention_factor"] == pytest.approx(
        0.1 * math.log(16) + 1)


def test_plain_inv_freq_and_the_default_attention_factor():
    np.testing.assert_allclose(
        F.rope_inv_freq(128, 500000),
        500000.0 ** (-np.arange(64) / 64), rtol=1e-12)
    from paddle_tpu.nn.grouped_query_attention import rope_tables

    _, scale = rope_tables(128, {k: v for k, v in YARN.items()
                                 if k != "attention_factor"})
    assert scale == pytest.approx(YARN["attention_factor"], rel=1e-12)
    assert rope_tables(128, PLAIN)[1] == 1.0
    with pytest.raises(NotImplementedError, match="yarn_inv_freq"):
        rope_tables(128, {"rope_type": "llama3", "rope_theta": 1e4})


@pytest.mark.parametrize("rope", [PLAIN, YARN], ids=["plain", "yarn"])
def test_rotary_embedding_is_the_references_rotation(rope):
    x = np.random.RandomState(0).randn(2, 40, 3, 16).astype(np.float32)
    inv, scale = ref.rope_inv_freq(16, rope)
    got = F.rotary_embedding(paddle.to_tensor(x), inv, scale).numpy()
    want = np.stack([np.asarray(ref.rope(jnp.asarray(row), inv, scale))
                     for row in x])
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    # position 0 is only scaled; the rotation keeps each pair's norm
    np.testing.assert_allclose(got[:, 0], x[:, 0] * scale, rtol=1e-6)
    pairs = lambda a: a[..., :8] ** 2 + a[..., 8:] ** 2  # noqa: E731
    np.testing.assert_allclose(pairs(got), pairs(x) * scale ** 2,
                               rtol=1e-4, atol=1e-5)


def test_rotary_embedding_applies_in_the_activations_type_under_its_scope():
    x = jnp.ones((1, 8, 2, 16), jnp.bfloat16)
    inv = F.rope_inv_freq(16, 1e4)
    assert F.rotary_embedding.raw_fn(x, inv).dtype == jnp.bfloat16
    text = jax.jit(lambda a: F.rotary_embedding.raw_fn(a, inv)).lower(
        x).as_text(debug_info=True)
    assert "rotary_embedding" in text


# ---------------------------------------------------------------------------
# the band
# ---------------------------------------------------------------------------
def _support(attend, length=128, d=128):
    """Which keys each query reads: zero scores make the softmax uniform
    over the allowed keys, one-hot values show them."""
    q = jnp.zeros((1, length, 1, d), jnp.float32)
    v = jnp.eye(length, d, dtype=jnp.float32)[None, :, None, :]
    return np.asarray(attend(q, q, v))[0, :, 0, :] > 0


@pytest.mark.parametrize("window", [1, 5, 37, 128])
def test_allowed_is_exactly_the_band_in_the_xla_path(window):
    i, j = np.arange(128)[:, None], np.arange(128)[None, :]
    want = (i - j >= 0) & (i - j < window)
    got = _support(lambda q, k, v: fa._xla_attention(
        q, k, v, None, 0.0, True, None, window=window))
    assert np.array_equal(got, want)
    assert np.array_equal(np.asarray(ref.allowed(
        "sliding_attention", window, jnp.arange(128), 128)), want)


@pytest.mark.parametrize("window", [1, 37, 200])
def test_allowed_is_exactly_the_band_in_the_kernel(interpret, window):
    i, j = np.arange(256)[:, None], np.arange(256)[None, :]
    got = _support(lambda q, k, v: fa._flash_attention_core(
        q, k, v, True, 128, 128, window), length=256, d=256)
    assert np.array_equal(got, (i - j >= 0) & (i - j < window))


def test_band_pairs_counts_the_mask():
    for length, window in ((128, 37), (8192, 1024), (64, 64), (64, 100)):
        i, j = np.arange(length)[:, None], np.arange(length)[None, :]
        assert fa.band_pairs(length, window) == int(
            ((i - j >= 0) & (i - j < window)).sum())
    assert fa.band_pairs(8192, 1024) == 7_864_832


# ---------------------------------------------------------------------------
# the kernels with groups and windows against the XLA path
# ---------------------------------------------------------------------------
CASES = [
    # heads, kv heads, window, block_q, block_kv
    (4, 2, None, 128, 128),
    (4, 1, 200, 128, 128),
    (2, 2, 130, 128, 128),          # group 1, a window alone
    (8, 2, 300, 256, 128),
    (4, 2, 128, 128, 256),
    (4, 4, 1, 128, 128),            # every query reads itself alone
    # the Mellum cell's group of 8: since PR 42 the launch that sums a
    # key head's dK, dV over the group also holds each query head's dQ
    (8, 1, None, 128, 128),
    (8, 1, 300, 128, 128),          # a band over four kv blocks
]


@pytest.mark.parametrize("h, hkv, window, bq, bkv", CASES)
def test_grouped_windowed_forward_matches_xla(interpret, h, hkv, window,
                                              bq, bkv):
    q, k, v = _qkv(2, 512, h, hkv, 64)
    want = fa._xla_attention(q, k, v, None, 0.0, True, None, window=window)
    got = fa._flash_attention_core(q, k, v, True, bq, bkv, window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("h, hkv, window, bq, bkv", CASES)
def test_grouped_windowed_backward_matches_xla(interpret, h, hkv, window,
                                               bq, bkv):
    q, k, v = _qkv(2, 512, h, hkv, 64, seed=1)
    w = jnp.asarray(np.random.RandomState(2).randn(2, 512, h, 64),
                    jnp.float32)

    def grads(f):
        return jax.grad(lambda q, k, v: jnp.sum(f(q, k, v) * w),
                        argnums=(0, 1, 2))(q, k, v)

    got = grads(lambda q, k, v: fa._flash_attention_core(
        q, k, v, True, bq, bkv, window))
    want = grads(lambda q, k, v: fa._xla_attention(
        q, k, v, None, 0.0, True, None, window=window))
    for g, r in zip(got, want):
        assert g.shape == r.shape        # dK, dV come out Hkv heads wide
        np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                   rtol=1e-4, atol=1e-4)


def test_group_one_without_a_window_is_todays_call_bit_for_bit(interpret):
    """The kernels MHA runs today are the group-1, window-less launches.
    A window as long as the sequence walks the band's code and must give
    the same bits; a group that reads replicated keys through the block
    index map must give the forward and dQ the same bits as today's call
    on K and V copied to every query head, and dK, dV their sum."""
    q, k, v = _qkv(1, 256, 4, 2, 64, seed=3)
    kk, vv = (jnp.repeat(a, 2, axis=2) for a in (k, v))
    w = jnp.asarray(np.random.RandomState(4).randn(1, 256, 4, 64),
                    jnp.float32)

    def run(q, k, v, window=None):
        return jax.value_and_grad(
            lambda q, k, v: jnp.sum(fa._flash_attention_core(
                q, k, v, True, 128, 128, window) * w),
            argnums=(0, 1, 2))(q, k, v)

    today, (dq, dkk, dvv) = run(q, kk, vv)
    banded, g_banded = run(q, kk, vv, window=256)
    assert float(today) == float(banded)
    for a, b in zip((dq, dkk, dvv), g_banded):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    grouped, (gq, gk, gv) = run(q, k, v)
    assert float(today) == float(grouped)
    assert np.array_equal(np.asarray(dq), np.asarray(gq))
    for whole, mine in ((dkk, gk), (dvv, gv)):
        np.testing.assert_allclose(
            np.asarray(whole).reshape(1, 256, 2, 2, 64).sum(axis=3),
            np.asarray(mine), rtol=1e-5, atol=1e-5)
    out_today = fa._flash_attention_core(q, kk, vv, True, 128, 128)
    out_grouped = fa._flash_attention_core(q, k, v, True, 128, 128)
    assert np.array_equal(np.asarray(out_today), np.asarray(out_grouped))


def test_windowed_launches_have_roles_of_their_own(interpret):
    q, k, v = _qkv(1, 1024, 2, 1, 64)
    text = str(jax.make_jaxpr(lambda q, k, v: fa._flash_attention_pallas(
        q, k, v, causal=True, window=512))(q, k, v))
    assert "flash_attention_window" in text
    assert "flash_attention_grouped" not in text
    assert "flash_attention_stream_fwd" not in text
    full = str(jax.make_jaxpr(lambda q, k, v: fa._flash_attention_pallas(
        q, k, v, causal=True))(q, k, v))
    assert "flash_attention_grouped" in full
    assert "flash_attention_window" not in full
    # one key head a query head and no window: the roles MHA always had
    mha = str(jax.make_jaxpr(lambda q, v: fa._flash_attention_pallas(
        q, q, v, causal=True))(q, q))
    assert "flash_attention_stream_fwd" in mha
    assert "flash_attention_grouped" not in mha


# ---------------------------------------------------------------------------
# dispatch: counters, roles, declared work
# ---------------------------------------------------------------------------
@pytest.fixture
def on_chip_gate(monkeypatch, interpret):
    import paddle_tpu.framework.bringup as bringup

    monkeypatch.setattr(bringup, "pallas_enabled", lambda: True)
    counters.reset()
    yield
    counters.reset()


def test_dispatch_takes_the_kernels_and_declares_the_bands_work(
        on_chip_gate):
    q, k, v = _qkv(1, 512, 4, 2, 64)
    with counters.capture("t"), counters.differentiated():
        full = fa.flash_attention_or_fallback(q, k, v, is_causal=True)
        band = fa.flash_attention_or_fallback(q, k, v, is_causal=True,
                                              window=128)
    snap = counters.snapshot()
    assert snap["flash_attention.pallas"] == 2
    assert snap["flash_attention.grouped"] == 2
    assert snap["flash_attention.windowed"] == 1
    assert "flash_attention.xla" not in snap
    work = counters.step_work("t")
    # one role a layer kind: its forward and its backward add up under it
    assert set(work) == {"flash_attention_grouped", "flash_attention_window"}
    assert {k: w["calls"] for k, w in work.items()} == {
        "flash_attention_grouped": 2, "flash_attention_window": 2}
    pairs = fa.band_pairs(512, 128)
    assert work["flash_attention_window"]["flops"] == \
        (4.0 + 8.0) * 4 * pairs * 64
    assert work["flash_attention_grouped"]["flops"] == \
        (4.0 + 8.0) * 4 * (512 * 512 / 2) * 64
    # q and the output 4 heads wide, K and V 2: once per key head
    q_bytes, kv_bytes = 512 * 4 * 64 * 4, 2 * 512 * 2 * 64 * 4
    lse = 4 * 4 * 512
    assert work["flash_attention_window"]["bytes"] == \
        (2 * q_bytes + kv_bytes + lse) + (4 * q_bytes + 2 * kv_bytes + lse)
    for out in (full, band):
        assert out.shape == q.shape
    np.testing.assert_allclose(
        np.asarray(band), np.asarray(fa._xla_attention(
            q, k, v, None, 0.0, True, None, window=128)),
        rtol=2e-5, atol=2e-5)


def test_a_window_as_long_as_the_row_is_the_full_layers_launch(
        on_chip_gate):
    q, k, v = _qkv(1, 256, 2, 2, 64)
    fa.flash_attention_or_fallback(q, k, v, is_causal=True, window=256)
    snap = counters.snapshot()
    assert snap["flash_attention.pallas"] == 1
    assert "flash_attention.windowed" not in snap


def test_nothing_falls_back_silently(on_chip_gate):
    q, k, v = _qkv(2, 512, 4, 2, 64)
    # a length off the 128 modulus: XLA, counted with its reason
    fa.flash_attention_or_fallback(q[:, :500], k[:, :500], v[:, :500],
                                   is_causal=True, window=64)
    assert counters.snapshot()["flash_attention.xla"] == 1
    # a key-padding mask with groups: the masked kernel on repeated keys
    keep = jnp.arange(512)[None, :] < jnp.asarray([[512], [400]])
    got = fa.flash_attention_or_fallback(q, k, v, mask=keep)
    snap = counters.snapshot()
    assert snap["flash_attention.grouped_replicated_kv"] == 1
    assert snap["flash_attention.pallas"] == 1
    want = fa._xla_attention(q, k, v, keep[:, None, None, :], 0.0, False,
                             None)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    # a window with a mask: XLA carries both
    fa.flash_attention_or_fallback(q, k, v, mask=keep[:, None, None, :],
                                   is_causal=True, window=64)
    assert counters.snapshot()["flash_attention.xla"] == 2


def test_dispatch_refuses_what_is_no_band_and_no_group():
    q, k, v = _qkv(1, 128, 4, 3, 64)
    with pytest.raises(ValueError, match="no multiple"):
        fa.flash_attention_or_fallback(q, k, v, is_causal=True)
    q, k, v = _qkv(1, 128, 2, 2, 64)
    with pytest.raises(ValueError, match="is_causal=True"):
        fa.flash_attention_or_fallback(q, k, v, window=16)


def test_sdpa_takes_fewer_key_heads_and_a_window_on_the_cpu():
    counters.reset()
    q, k, v = _qkv(2, 48, 4, 2, 16)
    got = F.scaled_dot_product_attention(
        paddle.to_tensor(np.asarray(q)), paddle.to_tensor(np.asarray(k)),
        paddle.to_tensor(np.asarray(v)), is_causal=True, window=9)
    i, j = np.arange(48)[:, None], np.arange(48)[None, :]
    mask = jnp.asarray((i - j >= 0) & (i - j < 9))[None, None]
    want = fa._xla_attention(q, jnp.repeat(k, 2, axis=2),
                             jnp.repeat(v, 2, axis=2), mask, 0.0, False, None)
    np.testing.assert_allclose(got.numpy(), np.asarray(want), rtol=1e-5,
                               atol=1e-5)
    assert counters.snapshot()["flash_attention.xla"] == 1
    counters.reset()


# ---------------------------------------------------------------------------
# the layer against the reference: loss and every gradient
# ---------------------------------------------------------------------------
LAYER_CFG = {"num_attention_heads": 4, "num_key_value_heads": 2,
             "head_dim": 16, "rms_norm_eps": 1e-6, "sliding_window": 11,
             "rope_parameters": {"full_attention": YARN,
                                 "sliding_attention": PLAIN}}


@pytest.mark.parametrize("qk_norm", [True, False], ids=["qknorm", "bare"])
@pytest.mark.parametrize("kind", ["full_attention", "sliding_attention"])
def test_layer_matches_the_reference_loss_and_every_gradient(kind, qk_norm):
    """(full, YaRN) and (sliding, plain rope); the window (11) is shorter
    than the rows (40), so both edges of the band are crossed."""
    hidden, t = 32, 40
    paddle.seed(5)
    layer = nn.GroupedQueryAttention(
        hidden, 4, 2, 16,
        window=11 if kind == "sliding_attention" else None,
        rope=LAYER_CFG["rope_parameters"][kind], qk_norm=qk_norm)
    rng = np.random.RandomState(6)
    # scales that are not one, so that the norms' gradients are checked
    for name, p in layer.named_parameters():
        if name.endswith("norm.weight"):
            p._value = jnp.asarray(1.0 + 0.3 * rng.randn(*p.shape),
                                   jnp.float32)
    x = rng.randn(2, t, hidden).astype(np.float32)
    w = rng.randn(2, t, hidden).astype(np.float32)
    params = {"m." + k: p.value for k, p in layer.named_parameters()}
    cfg = dict(LAYER_CFG, qk_norm=qk_norm)

    def ref_loss(p, x):
        out = jnp.stack([ref.attention(p, "m.", row, cfg, kind,
                                       ref.F32_MATMULS, 8) for row in x])
        return jnp.sum(out * w)

    want, (want_p, want_x) = jax.value_and_grad(ref_loss, argnums=(0, 1))(
        params, jnp.asarray(x))

    xt = paddle.to_tensor(x, stop_gradient=False)
    with jax.default_matmul_precision("highest"):
        loss = (layer(xt) * paddle.to_tensor(w)).sum()
        loss.backward()
    assert float(loss) == pytest.approx(float(want), rel=2e-5, abs=1e-4)
    np.testing.assert_allclose(xt.grad.numpy(), np.asarray(want_x),
                               rtol=1e-4, atol=1e-5)
    got = dict(layer.named_parameters())
    assert set("m." + k for k in got) == set(want_p)
    for k, p in got.items():
        np.testing.assert_allclose(p.grad.numpy(), np.asarray(want_p["m." + k]),
                                   rtol=1e-4, atol=2e-5, err_msg=k)


def test_layer_keeps_keys_and_values_kv_heads_wide_and_names_its_scopes():
    layer = nn.GroupedQueryAttention(32, 4, 2, 16, window=8, rope=PLAIN)
    shapes = {k: tuple(p.shape) for k, p in layer.named_parameters()}
    assert shapes == {
        "q_proj.weight": (32, 64), "k_proj.weight": (32, 32),
        "v_proj.weight": (32, 32), "o_proj.weight": (64, 32),
        "q_norm.weight": (16,), "k_norm.weight": (16,)}
    with pytest.raises(ValueError, match="no multiple"):
        nn.GroupedQueryAttention(32, 4, 3, 16)

    class Block(nn.Layer):
        def __init__(self):
            super().__init__()
            self.mixer = layer

        def forward(self, x):
            return self.mixer(x)

    block = Block()
    text = jax.jit(lambda a: block(paddle.to_tensor(a)).value).lower(
        jnp.zeros((1, 16, 32), jnp.float32)).as_text(debug_info=True)
    for scope in ("mixer/q_proj", "mixer/k_norm", "rotary_embedding",
                  "mixer/o_proj"):
        assert scope in text, scope


def test_mla_rotates_with_the_same_op_and_refuses_a_scaled_rotation():
    """Until PR 39 ``MLAttention(rotary=True)`` was refused and named the
    op it lacked; it is wired now (``rope=``), through that op."""
    layer = nn.MLAttention(64, 4, 16, 8, 16, 32,
                           rope={"rope_theta": 1e4, "interleave": False})
    text = jax.jit(lambda a: layer(paddle.to_tensor(a)).value).lower(
        jnp.zeros((1, 16, 64), jnp.float32)).as_text(debug_info=True)
    assert "rotary_embedding" in text
    with pytest.raises(NotImplementedError, match="rope_inv_freq"):
        nn.MLAttention(64, 4, 16, 8, 16, 32,
                       rope={"rope_theta": 1e4, "rope_type": "yarn"})


# ---------------------------------------------------------------------------
# an output gate from a doubled q_proj, and rotary positions on a part of
# each head (PR 49): against hand-written formulas
# ---------------------------------------------------------------------------
def _rotate_by_hand(x, rotary_dim, theta):
    """x (T, H, D) numpy: pair i of the first ``rotary_dim`` entries is
    (x_i, x_{i + R/2}), turned by position * theta^(-2i/R); the rest of
    the head is left as it is."""
    t, half = x.shape[0], rotary_dim // 2
    out = x.copy()
    for i in range(half):
        angle = np.arange(t) * theta ** (-2.0 * i / rotary_dim)
        c, s = np.cos(angle)[:, None], np.sin(angle)[:, None]
        a, b = x[..., i], x[..., i + half]
        out[..., i], out[..., i + half] = a * c - b * s, b * c + a * s
    return out


def _gated_attention_by_hand(p, x, heads, kv_heads, d, rotary_dim, theta,
                             gate, zero_centered):
    """float64 numpy, one row x (T, hidden)."""
    t = x.shape[0]
    qg = (x @ p["q_proj.weight"]).reshape(t, heads, (2 if gate else 1) * d)
    q, g = qg[..., :d], qg[..., d:].reshape(t, -1)
    k = (x @ p["k_proj.weight"]).reshape(t, kv_heads, d)
    v = (x @ p["v_proj.weight"]).reshape(t, kv_heads, d)

    def norm(a, w):
        a = a / np.sqrt((a * a).mean(-1, keepdims=True) + 1e-6)
        return a * ((1.0 + w) if zero_centered else w)

    q = _rotate_by_hand(norm(q, p["q_norm.weight"]), rotary_dim, theta)
    k = _rotate_by_hand(norm(k, p["k_norm.weight"]), rotary_dim, theta)
    out = np.zeros((t, heads, d))
    for h in range(heads):
        kv = h // (heads // kv_heads)
        s = q[:, h] @ k[:, kv].T / math.sqrt(d)
        s = np.where(np.tril(np.ones((t, t), bool)), s, -np.inf)
        w = np.exp(s - s.max(-1, keepdims=True))
        out[:, h] = (w / w.sum(-1, keepdims=True)) @ v[:, kv]
    out = out.reshape(t, heads * d)
    if gate:
        out = out / (1.0 + np.exp(-g))
    return out @ p["o_proj.weight"]


@pytest.mark.parametrize("gate,rotary_dim,zero_centered", [
    (True, 8, True), (True, None, False), (False, 8, False)],
    ids=["gate+partial+1w", "gate", "partial"])
def test_output_gate_and_partial_rotary_against_a_hand_written_layer(
        gate, rotary_dim, zero_centered):
    """16 query heads on 2 key heads would be the cell's; here 4 on 2,
    heads of 32, a quarter of each head rotated (``rotary_dim`` 8: the
    inverse frequencies of a head of 8), the gate a head's second half of
    the doubled ``q_proj``."""
    hidden, t, heads, kv, d, theta = 24, 12, 4, 2, 32, 1e7
    paddle.seed(3)
    counters.reset()
    layer = nn.GroupedQueryAttention(
        hidden, heads, kv, d, rope={"rope_type": "default",
                                    "rope_theta": theta},
        output_gate=gate, rotary_dim=rotary_dim,
        zero_centered_norm=zero_centered)
    snap = counters.snapshot()
    assert snap.get("gqa.output_gate", 0) == int(gate)
    assert snap.get("gqa.partial_rotary", 0) == int(rotary_dim is not None)
    shapes = {k: tuple(p.shape) for k, p in layer.named_parameters()}
    assert shapes["q_proj.weight"] == (hidden, (2 if gate else 1) * heads * d)
    rng = np.random.RandomState(4)
    for name, p in layer.named_parameters():
        if name.endswith("norm.weight"):
            assert float(jnp.max(jnp.abs(p.value))) == (
                0.0 if zero_centered else 1.0)
            p._value = jnp.asarray(0.3 * rng.randn(*p.shape)
                                   + (0.0 if zero_centered else 1.0),
                                   jnp.float32)
    x = rng.randn(t, hidden)
    params = {k: np.asarray(p.value, np.float64)
              for k, p in layer.named_parameters()}
    want = _gated_attention_by_hand(params, x, heads, kv, d,
                                    rotary_dim or d, theta, gate,
                                    zero_centered)
    with jax.default_matmul_precision("highest"):
        got = layer(paddle.to_tensor(x[None].astype(np.float32))).numpy()[0]
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
    with pytest.raises(ValueError, match="rotary_dim"):
        nn.GroupedQueryAttention(hidden, heads, kv, d, rotary_dim=7)


def test_the_gate_has_its_scope_and_type_and_absent_arguments_change_nothing():
    """``gated_attn`` is in the HLO round the gate's product, which runs
    in the attention output's (autocast) type; a layer built without the
    three new arguments lowers, differentiated, to the text it lowered to
    before they existed (sha256 from the parent commit, 2c186de)."""
    import hashlib

    from paddle_tpu import amp

    paddle.seed(0)
    layer = nn.GroupedQueryAttention(32, 4, 2, 16, rope=PLAIN,
                                     output_gate=True, rotary_dim=4)

    def run(a):
        with amp.auto_cast(level="O1", dtype="bfloat16"):
            return layer(paddle.to_tensor(a)).value

    text = jax.jit(run).lower(jnp.zeros((1, 16, 32), jnp.float32)).as_text(
        debug_info=True)
    lines = text.splitlines()
    names = [ln.split(" = ")[0] for ln in lines
             if ln.startswith("#loc") and "gated_attn" in ln
             and ln.split('"')[1].endswith("/mul")]
    gate = [ln for ln in lines if "stablehlo.multiply" in ln
            and any(ln.rstrip().endswith(f"loc({n})") for n in names)]
    assert gate and all("bf16" in ln and "f32" not in ln for ln in gate), \
        (names, gate)
    paddle.seed(0)
    plain = nn.GroupedQueryAttention(32, 4, 2, 16, window=8, rope=PLAIN)

    def loss(a):
        return plain(paddle.to_tensor(a)).value.sum()

    text = jax.jit(jax.grad(loss)).lower(
        jnp.zeros((1, 16, 32), jnp.float32)).as_text()
    assert "gated_attn" not in text
    assert hashlib.sha256(text.encode()).hexdigest() == (
        "875e50c14b473cdf59832e5efd3a745bb5d2ceee33b5fc7486f2e2733b6f6a60")


def test_gated_heads_of_256_dispatch_to_the_grouped_kernels(on_chip_gate,
                                                            interpret):
    """K and V stay 2 heads wide to the kernel at heads of 256: the
    dispatch counts ``flash_attention.grouped`` and no ``xla``."""
    paddle.seed(1)
    layer = nn.GroupedQueryAttention(64, 4, 2, 256, rope=PLAIN,
                                     output_gate=True, rotary_dim=64)
    counters.reset()
    x = np.random.RandomState(2).randn(1, 256, 64).astype(np.float32)
    got = layer(paddle.to_tensor(x)).numpy()
    snap = counters.snapshot()
    assert snap.get("flash_attention.grouped") == 1, snap
    assert "flash_attention.xla" not in snap, snap
    assert np.isfinite(got).all()
