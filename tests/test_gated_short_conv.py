"""The gated short convolution: ``nn.GatedShortConv`` and the fused stage
under it (ops/pallas/gated_conv.py: a third form of
``mamba2_stages``' convolution launches), kernels in interpret mode,
against the LITERAL formula ``y = C * sum_j w_j (B * X)_{t-(W-1)+j}`` of
``[B | C | X] = proj`` written out below with a loop over the taps: the
value and every gradient, float32 to rounding and bfloat16 to two
roundings of its 8 bits; blocks small enough that a row is several of
them (the halo crosses block borders), a length that is no whole number
of blocks, a batch whose rows must not see each other, the chunk order,
and the shapes the stage refuses. Real Mosaic lowering is
``tests/test_tpu_compile.py``'s and ``chip_smoke.py kernels``'."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.framework.bringup as bringup
from paddle_tpu import nn
from paddle_tpu.ops.pallas import counters, gated_conv
from paddle_tpu.ops.pallas import mamba2_stages as stages

F32, BF16 = jnp.float32, jnp.bfloat16
#: relative to the norm: float32 rounding; two roundings of bfloat16
TOL = {F32: 2e-6, BF16: 2 * 2.0 ** -8}


@pytest.fixture
def interp(monkeypatch):
    from jax.experimental import pallas as pl

    monkeypatch.setattr(pl, "pallas_call",
                        functools.partial(pl.pallas_call, interpret=True))
    monkeypatch.setattr(bringup, "pallas_enabled", lambda: True)
    # blocks of 64 rows forward and 32 backward at 128 lanes
    monkeypatch.setattr(stages, "BLOCK", {"fwd": 64 * 128, "bwd": 32 * 128})
    counters.reset()
    jax.clear_caches()      # the launches are jitted: no trace of another
    yield                   # block size or of a compiled kernel is reused
    jax.clear_caches()
    counters.reset()


def literal(proj, taps):
    """The formula with nothing shared with the code under test: chunks
    in the order B, C, X; a sum over the taps of shifted copies, zeros
    before a row's start; float32."""
    d = proj.shape[-1] // 3
    proj = proj.astype(F32)
    b, c, x = proj[..., :d], proj[..., d:2 * d], proj[..., 2 * d:]
    z = b * x
    width, t = taps.shape[0], proj.shape[1]
    conv = jnp.zeros_like(z)
    for j in range(width):
        back = width - 1 - j
        shifted = jnp.concatenate(
            [jnp.zeros_like(z[:, :back]), z[:, :t - back]], axis=1)
        conv = conv + taps[j].astype(F32) * shifted
    return c * conv


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def _inputs(b, t, d, dtype, taps=3, seed=0):
    ks = jax.random.split(jax.random.key(seed), 3)
    return (jax.random.normal(ks[0], (b, t, 3 * d)).astype(dtype),
            0.5 * jax.random.normal(ks[1], (taps, d)),
            jax.random.normal(ks[2], (b, t, d)))


def _run(form, proj, taps, w):
    def loss(proj, taps):
        out = form(proj, taps)
        return jnp.sum(out.astype(F32) * w), out

    (_, out), (dproj, dtaps) = jax.value_and_grad(
        loss, argnums=(0, 1), has_aux=True)(proj, taps)
    d = taps.shape[1]
    return {"y": out, "dB": dproj[..., :d], "dC": dproj[..., d:2 * d],
            "dX": dproj[..., 2 * d:], "dtaps": dtaps}


def _same(got, want, tol):
    assert sorted(got) == sorted(want)
    for name in want:
        assert got[name].shape == want[name].shape, name
        assert _rel(got[name], want[name]) < tol, name


#: (length, channels, taps, type): 96 and 200 rows are 1.5 and 3.1
#: forward blocks, 3 and 6.25 backward ones; 40 rows less than one
#: forward block; 256 channels are two tiles of 128 lanes, so X and C sit
#: two tiles and four tiles into the projection
CASES = [(96, 128, 3, F32), (200, 256, 3, F32), (40, 128, 3, F32),
         (96, 256, 3, BF16), (200, 128, 3, BF16), (96, 128, 4, F32),
         (200, 128, 2, F32)]


@pytest.mark.parametrize(
    "t,d,taps,dtype", CASES,
    ids=[f"{t}x{d}-{k}taps-{jnp.dtype(dt).name}" for t, d, k, dt in CASES])
def test_the_fused_stage_matches_the_literal_formula_and_every_gradient(
        interp, t, d, taps, dtype):
    proj, w_taps, w = _inputs(2, t, d, dtype, taps=taps)
    got = _run(gated_conv.gated_conv, proj, w_taps, w)
    want = _run(literal, proj, w_taps, w)
    assert got["y"].dtype == dtype       # written once, in the input's type
    _same(got, want, TOL[dtype])
    assert counters.snapshot() == {"gated_conv.fused": 1}


def test_the_xla_formula_is_the_literal_one():
    proj, taps, w = _inputs(2, 50, 24, F32)
    _same(_run(gated_conv.gated_conv, proj, taps, w),
          _run(literal, proj, taps, w), TOL[F32])


@pytest.mark.parametrize("path", ["fused", "xla"])
def test_nothing_leaks_across_a_rows_start_or_between_batch_rows(
        request, path):
    """Row 1's values move neither row 0's output nor row 0's gradient,
    and an output's first tokens see zeros before them, not the row
    before: blocks of one row end where the next row's begin."""
    if path == "fused":
        request.getfixturevalue("interp")
    proj, taps, w = _inputs(2, 64, 128, F32, seed=3)
    other = proj.at[1].set(7.0 * proj[1] + 1.0)
    a = _run(gated_conv.gated_conv, proj, taps, w)
    b = _run(gated_conv.gated_conv, other, taps, w)
    for name in ("y", "dB", "dC", "dX"):
        np.testing.assert_array_equal(np.asarray(a[name][0]),
                                      np.asarray(b[name][0]), err_msg=name)
    # the first token's output is the last tap alone
    d = taps.shape[1]
    first = proj[:, 0, d:2 * d] * taps[-1] * proj[:, 0, :d] * proj[:, 0, 2 * d:]
    assert _rel(a["y"][:, 0], first) < TOL[F32]


def test_the_chunks_are_b_then_c_then_x(interp):
    """C alone multiplies OUTSIDE the convolution: with one tap on an
    earlier token, swapping the groups changes the result unless the swap
    is of B and X."""
    proj, _, w = _inputs(1, 64, 128, F32, seed=5)
    taps = jnp.zeros((3, 128)).at[0].set(1.0)      # y_t = C_t (B X)_{t-2}
    d = 128
    b, c, x = proj[..., :d], proj[..., d:2 * d], proj[..., 2 * d:]
    y = gated_conv.gated_conv(proj, taps)
    want = c[:, 2:] * (b * x)[:, :-2]
    assert _rel(y[:, 2:], want) < TOL[F32]
    np.testing.assert_array_equal(np.asarray(y[:, :2]), 0.0)
    swapped = gated_conv.gated_conv(jnp.concatenate([x, c, b], -1), taps)
    np.testing.assert_allclose(np.asarray(swapped), np.asarray(y), rtol=1e-6)
    assert _rel(gated_conv.gated_conv(
        jnp.concatenate([c, b, x], -1), taps), y) > 0.5


#: (channels, taps, why the kernels do not take it)
REFUSED = [(96, 3, "channel offsets (96,): whole 128 lanes each"),
           (128, 10, "10 taps: at most 9")]


@pytest.mark.parametrize("d,taps,why", REFUSED,
                         ids=["offsets", "taps"])
def test_a_shape_outside_the_gate_takes_the_formula_and_says_why(
        interp, capsys, d, taps, why):
    paddle.set_flags({"log_pallas_fallback": True})
    try:
        proj, w_taps, w = _inputs(1, 32, d, F32, taps=taps)
        _same(_run(gated_conv.gated_conv, proj, w_taps, w),
              _run(literal, proj, w_taps, w), TOL[F32])
    finally:
        paddle.set_flags({"log_pallas_fallback": False})
    assert counters.snapshot() == {"gated_conv.xla": 1}
    assert f"gated_conv -> xla ({why})" in capsys.readouterr().err


def test_on_the_cpu_the_fallback_is_counted_as_the_backends():
    counters.reset()
    proj, taps, _ = _inputs(1, 16, 128, F32)
    assert gated_conv.gated_conv(proj, taps).dtype == F32
    assert counters.snapshot() == {"gated_conv.xla": 1}
    counters.reset()


def test_a_projection_that_is_not_three_groups_is_refused():
    with pytest.raises(ValueError, match="three groups"):
        gated_conv.gated_conv(jnp.zeros((1, 8, 256)), jnp.zeros((3, 128)))


def test_the_declared_work_is_four_arrays_forward_and_seven_backward(interp):
    """What ``counters.step_work`` holds for a differentiated trace: 4 +
    7 arrays of tokens x D in the projection's type under ONE role."""
    proj, taps, w = _inputs(2, 64, 128, BF16)
    with counters.capture("probe"), counters.differentiated():
        gated_conv.gated_conv(proj, taps)
    moved = 2 * 64 * 128 * 2
    assert counters.step_work("probe") == {
        "gated_conv": {"calls": 2, "flops": 0.0, "bytes": 11.0 * moved}}


# ---------------------------------------------------------------------------
# the layer
# ---------------------------------------------------------------------------
def _layer_and_literal(hidden, taps, x):
    paddle.seed(11)
    layer = nn.GatedShortConv(hidden, taps=taps)
    w_in, w_out = (np.asarray(layer.in_proj.weight.numpy()),
                   np.asarray(layer.out_proj.weight.numpy()))
    conv = np.asarray(layer.conv_weight.numpy())        # (hidden, taps)
    want = literal(jnp.asarray(x) @ w_in, jnp.asarray(conv.T)) @ w_out
    return layer, want


@pytest.mark.parametrize("path", ["fused", "xla"])
def test_the_layer_is_out_proj_of_the_gated_convolution_of_in_proj(
        request, path):
    if path == "fused":
        request.getfixturevalue("interp")
    x = np.random.default_rng(0).standard_normal((2, 48, 128)).astype(
        "float32")
    layer, want = _layer_and_literal(128, 3, x)
    assert sorted(n for n, _ in layer.named_parameters()) == [
        "conv_weight", "in_proj.weight", "out_proj.weight"]
    assert tuple(layer.conv_weight.shape) == (128, 3)
    assert tuple(layer.in_proj.weight.shape) == (128, 384)
    got = layer(paddle.to_tensor(x))
    assert _rel(got.numpy(), want) < 1e-5
    assert counters.snapshot() == {f"gated_conv.{path}": 1}
    counters.reset()


def test_the_layers_gradients_reach_all_three_parameters():
    x = np.random.default_rng(1).standard_normal((2, 24, 32)).astype(
        "float32")
    layer, _ = _layer_and_literal(32, 3, x)
    loss = (layer(paddle.to_tensor(x)) ** 2).sum()
    loss.backward()
    names = [n for n, p in layer.named_parameters() if p.grad is not None
             and float(np.abs(p.grad.numpy()).max()) > 0.0]
    assert sorted(names) == ["conv_weight", "in_proj.weight",
                             "out_proj.weight"]

    def value(w_in, conv, w_out):
        return jnp.sum((literal(jnp.asarray(x) @ w_in, conv.T) @ w_out) ** 2)

    want = jax.grad(value, argnums=(0, 1, 2))(
        *(jnp.asarray(p.numpy()) for p in (
            layer.in_proj.weight, layer.conv_weight, layer.out_proj.weight)))
    for p, g in zip((layer.in_proj.weight, layer.conv_weight,
                     layer.out_proj.weight), want):
        assert _rel(p.grad.numpy(), g) < 1e-5


def test_the_layers_scope_is_in_the_lowered_text():
    layer = nn.GatedShortConv(32, taps=3)

    def f(x):
        return layer(paddle.to_tensor(x))._value

    text = jax.jit(f).lower(jnp.zeros((1, 8, 32))).as_text(debug_info=True)
    assert "gated_conv" in text


def test_a_bias_is_refused_by_its_key():
    with pytest.raises(NotImplementedError, match="conv_bias"):
        nn.GatedShortConv(32, taps=3, bias=True)
