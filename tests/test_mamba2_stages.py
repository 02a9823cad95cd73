"""The Mamba-2 mixer's two fused element-wise stages
(ops/pallas/mamba2_stages.py), kernels in interpret mode, against the
formulas they replace (``conv_silu_xla``, ``gate_norm_xla``: float32
arrays, jax's own transpose): the value and every gradient, float32 to
rounding and bfloat16 to two roundings of its 8 bits; blocks small enough
that a row is several of them (the halo rows cross block borders), a
length that is no whole number of blocks, a batch whose rows must not
see each other, and the shapes the stages refuse. Real Mosaic lowering
is ``tests/test_tpu_compile.py``'s and ``chip_smoke.py kernels``'."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.framework.bringup as bringup
from paddle_tpu import nn
from paddle_tpu.ops.pallas import counters
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


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def _conv_inputs(b, t, start, widths, rest, dtype, taps=4, seed=0):
    ks = jax.random.split(jax.random.key(seed), 4)
    c = sum(widths)
    args = (jax.random.normal(ks[0], (b, t, start + c + rest)).astype(dtype),
            0.5 * jax.random.normal(ks[1], (taps, c)),
            0.3 * jax.random.normal(ks[2], (c,)))
    return args, [jax.random.normal(k, (b, t, w))
                  for k, w in zip(jax.random.split(ks[3], len(widths)),
                                  widths)]


def _conv(form, args, ws, start, widths):
    def loss(proj, taps, bias):
        outs = form(proj, taps, bias, start, widths)
        return sum(jnp.sum(o.astype(F32) * w) for o, w in zip(outs, ws)), outs

    (_, outs), grads = jax.value_and_grad(loss, argnums=(0, 1, 2),
                                          has_aux=True)(*args)
    return dict(zip(("u", "B", "C"), outs),
                **dict(zip(("dx", "dtaps", "dbias"), grads)))


def _norm_inputs(b, t, heads, p, rest, dtype, seed=1):
    ks = jax.random.split(jax.random.key(seed), 6)
    inner = heads * p
    args = (jax.random.normal(ks[0], (b, t, inner)).astype(dtype),
            jax.random.normal(ks[1], (b, t, inner)).astype(dtype),
            jax.random.normal(ks[2], (b, t, inner + rest)).astype(dtype),
            1.0 + 0.5 * jax.random.normal(ks[3], (heads,)),
            1.0 + 0.2 * jax.random.normal(ks[4], (inner,)))
    return args, jax.random.normal(ks[5], (b, t, inner))


def _norm(form, args, w, groups):
    def loss(*a):
        out = form(*a, groups, 1e-5)
        return jnp.sum(out.astype(F32) * w), out

    (_, out), grads = jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4),
                                         has_aux=True)(*args)
    return dict(zip(("dy", "du", "dz", "dD", "dweight"), grads), out=out)


def _same(got, want, tol):
    assert sorted(got) == sorted(want)
    for name in want:
        assert got[name].shape == want[name].shape, name
        assert _rel(got[name], want[name]) < tol, name


#: (stage, length, type): 96 and 200 rows are 1.5 and 3.1 forward blocks,
#: 3 and 6.25 backward ones; 40 rows are less than one forward block
CASES = [("conv", 96, F32), ("conv", 200, F32), ("conv", 40, F32),
         ("conv", 96, BF16), ("conv", 200, BF16),
         ("norm", 96, F32), ("norm", 200, F32), ("norm", 40, F32),
         ("norm", 96, BF16), ("norm", 200, BF16)]


@pytest.mark.parametrize(
    "stage,t,dtype", CASES,
    ids=[f"{s}-{t}-{jnp.dtype(d).name}" for s, t, d in CASES])
def test_a_fused_stage_matches_its_formula_and_every_gradient(
        interp, stage, t, dtype):
    """The projection is wider than the stage's slice on both sides, as
    ``[z | xBC | dt]`` is: the kernels find their channels by block
    index."""
    if stage == "conv":
        widths = (256, 128, 128)
        args, ws = _conv_inputs(2, t, 128, widths, 64, dtype)
        got = _conv(stages.conv_silu, args, ws, 128, widths)
        want = _conv(stages.conv_silu_xla, args, ws, 128, widths)
        assert all(got[k].dtype == dtype for k in ("u", "B", "C", "dx"))
    else:
        args, w = _norm_inputs(2, t, 4, 64, 192, dtype)
        got = _norm(stages.gate_norm, args, w, 2)
        want = _norm(stages.gate_norm_xla, args, w, 2)
        assert got["out"].dtype == dtype and want["out"].dtype == F32
        assert got["dz"][..., 256:].any() == False  # noqa: E712
    assert counters.snapshot() == {"mamba2_stage.fused": 1}
    _same(got, want, TOL[dtype])


def test_a_row_of_the_batch_does_not_see_the_row_before_it(interp):
    """The second row's first W - 1 outputs read zeros, not the first
    row's tail (which here would show: it is 1e4), and the first row's
    tail gets no gradient from them."""
    widths = (128,)
    (proj, taps, bias), ws = _conv_inputs(2, 96, 0, widths, 0, F32, seed=2)
    proj = proj.at[0, -3:].set(1e4)
    both = _conv(stages.conv_silu, (proj, taps, bias), ws, 0, widths)
    alone = _conv(stages.conv_silu, (proj[1:], taps, bias), [ws[0][1:]], 0,
                  widths)
    np.testing.assert_array_equal(np.asarray(both["u"][1]),
                                  np.asarray(alone["u"][0]))
    np.testing.assert_array_equal(np.asarray(both["dx"][1]),
                                  np.asarray(alone["dx"][0]))
    want = _conv(stages.conv_silu_xla, (proj, taps, bias), ws, 0, widths)
    assert _rel(both["dx"][0, -3:], want["dx"][0, -3:]) < TOL[F32]


REFUSED = {
    "conv_start": (lambda: stages.conv_silu(
        *_conv_inputs(1, 32, 64, (128,), 0, F32)[0], 64, (128,)),
        "convolution ineligible: channel offsets (64, 128)"),
    "conv_width": (lambda: stages.conv_silu(
        *_conv_inputs(1, 32, 0, (128, 96), 0, F32)[0], 0, (128, 96)),
        "convolution ineligible: channel offsets (0, 128, 96)"),
    "conv_taps": (lambda: stages.conv_silu(
        *_conv_inputs(1, 32, 0, (128,), 0, F32, taps=10)[0], 0, (128,)),
        "convolution ineligible: 10 taps: at most 9"),
    "norm_group": (lambda: stages.gate_norm(
        *_norm_inputs(1, 32, 4, 64, 0, F32)[0], 4, 1e-5),
        "gated norm ineligible: channel offsets (64,)"),
}


@pytest.mark.parametrize("case", sorted(REFUSED))
def test_a_refused_shape_takes_the_formula_and_is_counted_with_its_reason(
        interp, capsys, case):
    from paddle_tpu.framework.flags import set_flags

    call, reason = REFUSED[case]
    set_flags({"log_pallas_fallback": True})
    try:
        call()
    finally:
        set_flags({"log_pallas_fallback": False})
    assert counters.snapshot() == {"mamba2_stage.xla": 1}
    assert reason in capsys.readouterr().err


def test_off_the_tpu_both_stages_take_the_formulas():
    counters.reset()
    stages.conv_silu(*_conv_inputs(1, 32, 0, (128,), 0, F32)[0], 0, (128,))
    out = stages.gate_norm(*_norm_inputs(1, 32, 2, 64, 0, BF16)[0], 1, 1e-5)
    assert out.dtype == F32
    assert counters.snapshot() == {"mamba2_stage.xla": 2}
    counters.reset()


def _mixer():
    """A mixer the kernels take: 4 heads of 64 in 2 groups (a norm group
    and a group's heads 128 lanes), a state 128 wide."""
    paddle.seed(0)
    layer = nn.Mamba2Mixer(48, 4, 64, 128, groups=2, conv_size=4)
    rng = np.random.RandomState(0)
    layer.conv_bias._value = jnp.asarray(0.3 * rng.randn(768), F32)
    layer.D._value = jnp.asarray(1.0 + 0.5 * rng.randn(4), F32)
    layer.norm_weight._value = jnp.asarray(1.0 + 0.2 * rng.randn(256), F32)
    return layer


def _mixer_grads(x, w):
    layer = _mixer()
    xt = paddle.to_tensor(x, stop_gradient=False)
    out = layer(xt)
    (out * paddle.to_tensor(w)).sum().backward()
    grads = {k: p.grad.numpy() for k, p in layer.named_parameters()}
    return dict(grads, out=out.numpy(), x=xt.grad.numpy())


def test_the_mixer_on_its_kernels_is_the_mixer_on_the_formulas(monkeypatch):
    """``Mamba2Mixer`` end to end, 1.25 chunks of two rows: both stages
    and the scan on their kernels against all three on XLA, the output
    and the gradient of the input and of every parameter (the skip's D
    among them, which left the scan for the gated norm)."""
    rng = np.random.RandomState(1)
    x = rng.randn(2, 160, 48).astype("float32")
    w = rng.randn(2, 160, 48).astype("float32")
    counters.reset()
    with jax.default_matmul_precision("highest"):
        want = _mixer_grads(x, w)
        assert counters.snapshot() == {"mamba2_stage.xla": 2, "ssd.xla": 1}
        from jax.experimental import pallas as pl

        monkeypatch.setattr(pl, "pallas_call", functools.partial(
            pl.pallas_call, interpret=True))
        monkeypatch.setattr(bringup, "pallas_enabled", lambda: True)
        counters.reset()
        got = _mixer_grads(x, w)
    assert counters.snapshot() == {"mamba2_stage.fused": 2, "ssd.pallas": 1}
    counters.reset()
    jax.clear_caches()      # (interpreted launches: not for the next test)
    assert sorted(got) == sorted(want)
    for name in want:
        assert _rel(got[name], want[name]) < 2e-4, name


#: sha256 of the traced text (below) of the two stages at the Nemotron
#: cell's shapes on commit 784e939, PR 43's parent
NEMOTRON_STAGE_TEXT = {
    ("conv", "bfloat16"):
        "babff08f9f925adbd19b370316a3b6bf40d3ae62e3952f9583fc7f1513453eda",
    ("conv", "float32"):
        "3db52322047de330427aa23f0091927a883d08167e81eb90c475c0577fed5fb2",
    ("norm", "bfloat16"):
        "6df61399d5bf7ae51b253d4a8292884bf096a04a17edf281e6cc54935aed6050",
    ("norm", "float32"):
        "ac48aff18473cce2c2f9ee26048db25f0a85a5dba875f084c54844bb5de3ba31"}


@pytest.mark.parametrize("stage,dtype", sorted(NEMOTRON_STAGE_TEXT))
def test_the_nemotron_cells_stages_trace_to_their_pinned_text(
        monkeypatch, stage, dtype):
    """The mirror of ``test_kda.py``'s pin of ``kda_mix``: each stage,
    value and gradient, on the Nemotron cell's projection (2 x 8,192
    tokens, ``[z | xBC | dt]`` 10,304 wide, 64 heads of 64 in 8 groups)
    traces to one jaxpr text — the kernels' bodies, their grids and
    block maps, the launches' jits and the glue between them. PR 43 made
    the convolution take a role, an output type, no bias and a norm
    behind SiLU as static arguments for the KDA mixer; these digests are
    its PARENT's, so the Nemotron cell's kernels are the ones it ran
    before. (The lowered Mosaic text cannot be pinned: its bytecode
    holds the checkout's path and every line number.)"""
    import hashlib

    monkeypatch.setattr(bringup, "pallas_enabled", lambda: True)
    b, t, inner, gn, heads, groups = 2, 8192, 4096, 1024, 64, 8
    total, act = 2 * inner + 2 * gn + heads, jnp.dtype(dtype)
    if stage == "conv":
        def loss(proj, taps, bias):
            return sum(jnp.sum(o.astype(F32)) for o in stages.conv_silu(
                proj, taps, bias, inner, (inner, gn, gn)))

        shapes = [((b, t, total), act), ((4, inner + 2 * gn), F32),
                  ((inner + 2 * gn,), F32)]
    else:
        def loss(y, u, proj, d_skip, weight):
            return jnp.sum(stages.gate_norm(y, u, proj, d_skip, weight,
                                            groups, 1e-5).astype(F32))

        shapes = [((b, t, inner), act), ((b, t, inner), act),
                  ((b, t, total), act), ((heads,), F32), ((inner,), F32)]
    text = str(jax.make_jaxpr(jax.value_and_grad(
        loss, argnums=tuple(range(len(shapes)))))(
        *(jax.ShapeDtypeStruct(s, d) for s, d in shapes)))
    assert "mamba2_conv" in text or "mamba2_gate_norm" in text
    assert hashlib.sha256(text.encode()).hexdigest() \
        == NEMOTRON_STAGE_TEXT[stage, dtype]
