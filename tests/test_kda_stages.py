"""The KDA mixer's two fused element-wise stages
(ops/pallas/kda_stages.py), kernels in interpret mode, against the
float32 formulas they replace (``conv_norm_xla``, ``norm_gate_xla``:
float32 arrays in HBM, jax's own transpose): the values and every
gradient from float32 and bfloat16 projections, float32 out either way;
blocks small enough that a row is several of them (the halo rows cross
block borders), a length that is no whole number of blocks, a batch whose
rows must not see each other, and the shapes the stages refuse. Real
Mosaic lowering is ``tests/test_tpu_compile.py``'s and ``chip_smoke.py
kernels``'."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu.framework.bringup as bringup
from paddle_tpu.ops.pallas import counters
from paddle_tpu.ops.pallas import kda_stages as stages
from paddle_tpu.ops.pallas import mamba2_stages as shared

F32, BF16 = jnp.float32, jnp.bfloat16
#: relative to the norm: float32 rounding; the cotangents that leave in
#: bfloat16 (dx, dgate) are rounded to its 8 bits once on each side
TOL = {F32: 3e-6, BF16: 2 * 2.0 ** -8}
HEAD = 128


@pytest.fixture
def interp(monkeypatch):
    from jax.experimental import pallas as pl

    monkeypatch.setattr(pl, "pallas_call",
                        functools.partial(pl.pallas_call, interpret=True))
    monkeypatch.setattr(bringup, "pallas_enabled", lambda: True)
    # blocks of 64 rows forward and 32 backward at 128 lanes
    monkeypatch.setattr(shared, "BLOCK", {"fwd": 64 * 128, "bwd": 32 * 128})
    counters.reset()
    jax.clear_caches()      # the launches are jitted: no trace of another
    yield                   # block size or of a compiled kernel is reused
    jax.clear_caches()
    counters.reset()


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def _conv_inputs(b, t, heads, dtype, head=HEAD, taps=4, seed=0):
    ks = jax.random.split(jax.random.key(seed), 7)
    shape = (b, t, heads * head)
    args = tuple(jax.random.normal(k, shape).astype(dtype) for k in ks[:3]) \
        + tuple(0.5 * jax.random.normal(k, (taps, heads * head))
                for k in ks[3:6])
    return args, [jax.random.normal(k, shape)
                  for k in jax.random.split(ks[6], 3)]


def _conv(form, args, ws, head=HEAD):
    def loss(*a):
        outs = form(*a, head)
        return sum(jnp.sum(o * w) for o, w in zip(outs, ws)), outs

    (_, outs), grads = jax.value_and_grad(
        loss, argnums=tuple(range(6)), has_aux=True)(*args)
    return dict(zip(("q", "k", "v"), outs), **dict(zip(
        ("dxq", "dxk", "dxv", "dtaps_q", "dtaps_k", "dtaps_v"), grads)))


def _norm_inputs(b, t, heads, dtype, head=HEAD, seed=1):
    ks = jax.random.split(jax.random.key(seed), 4)
    shape = (b, t, heads * head)
    args = (jax.random.normal(ks[0], shape),
            jax.random.normal(ks[1], shape).astype(dtype),
            1.0 + 0.2 * jax.random.normal(ks[2], (head,)))
    return args, jax.random.normal(ks[3], shape)


def _norm(form, args, w):
    def loss(*a):
        out = form(*a, 1e-5)
        return jnp.sum(out * w), out

    (_, out), grads = jax.value_and_grad(loss, argnums=(0, 1, 2),
                                         has_aux=True)(*args)
    return dict(zip(("do", "dgate", "dweight"), grads), out=out)


def _same(got, want, tol):
    assert sorted(got) == sorted(want)
    for name in want:
        assert got[name].shape == want[name].shape, name
        assert got[name].dtype == want[name].dtype, name
        assert _rel(got[name], want[name]) < tol, name


#: (stage, length, heads, type): 96 and 200 rows are 1.5 and 3.1 forward
#: blocks, 3 and 6.25 backward ones; 40 rows are less than one forward
#: block; 4 heads are ONE 512-lane tile of four heads, 3 heads three
#: 128-lane tiles, 6 heads three tiles of two
CASES = [("conv", 96, 3, F32), ("conv", 200, 4, F32), ("conv", 40, 6, F32),
         ("conv", 96, 4, BF16), ("conv", 200, 3, BF16),
         ("norm", 96, 3, F32), ("norm", 200, 4, F32), ("norm", 40, 6, F32),
         ("norm", 96, 4, BF16), ("norm", 200, 3, BF16)]


@pytest.mark.parametrize(
    "stage,t,heads,dtype", CASES,
    ids=[f"{s}-{t}-{h}-{jnp.dtype(d).name}" for s, t, h, d in CASES])
def test_a_fused_stage_matches_its_formula_and_every_gradient(
        interp, stage, t, heads, dtype):
    """Values, dx, dtaps (stage A), do, dgate, dweight (stage B); what
    the stages hand the recurrence and the output projection is float32
    whatever the projections' type, and a gradient has its primal's."""
    if stage == "conv":
        args, ws = _conv_inputs(2, t, heads, dtype)
        got = _conv(stages.conv_norm, args, ws)
        want = _conv(stages.conv_norm_xla, args, ws)
        assert all(got[n].dtype == F32 for n in ("q", "k", "v"))
        assert all(got[n].dtype == dtype for n in ("dxq", "dxk", "dxv"))
        # q and k leave as unit heads (times D^-0.5 for q)
        lengths = jnp.linalg.norm(got["k"].reshape(2, t, heads, HEAD), axis=-1)
        np.testing.assert_allclose(np.asarray(lengths), 1.0, atol=1e-4)
    else:
        args, w = _norm_inputs(2, t, heads, dtype)
        got = _norm(stages.norm_gate, args, w)
        want = _norm(stages.norm_gate_xla, args, w)
        assert got["out"].dtype == got["do"].dtype == F32
        assert got["dgate"].dtype == dtype
    assert counters.snapshot() == {"kda_stage.fused": 1}
    _same(got, want, TOL[dtype])


@pytest.mark.parametrize("stage", ["conv", "norm"])
def test_heads_of_two_lane_tiles(interp, stage):
    """Heads of 256 channels, three of them: a channel tile is ONE head
    (768 lanes are no whole 512-lane tiles) and a head's length is
    summed over its two lane tiles."""
    if stage == "conv":
        args, ws = _conv_inputs(1, 96, 3, BF16, head=256)
        got = _conv(stages.conv_norm, args, ws, head=256)
        want = _conv(stages.conv_norm_xla, args, ws, head=256)
    else:
        args, w = _norm_inputs(1, 96, 3, BF16, head=256)
        got = _norm(stages.norm_gate, args, w)
        want = _norm(stages.norm_gate_xla, args, w)
    assert counters.snapshot() == {"kda_stage.fused": 1}
    _same(got, want, TOL[BF16])


def test_the_convolution_computes_in_float32_from_a_bfloat16_projection(
        interp):
    """bfloat16 in, float32 inside and out: the result is the float32
    formula's on the SAME bfloat16 values to float32 rounding (a
    convolution in bfloat16, or a result rounded to it, would be 2^-8
    away)."""
    args, ws = _conv_inputs(1, 96, 2, BF16, seed=3)
    got = stages.conv_norm(*args, HEAD)
    want = stages.conv_norm_xla(*(a.astype(F32) for a in args), HEAD)
    for g, w in zip(got, want):
        assert g.dtype == F32 and _rel(g, w) < TOL[F32]


def test_a_row_of_the_batch_does_not_see_the_row_before_it(interp):
    """The second row's first W - 1 outputs read zeros, not the first
    row's tail (which here would show: it is 1e4), and the first row's
    tail gets no gradient from them."""
    args, ws = _conv_inputs(2, 96, 1, F32, seed=2)
    args = tuple(a.at[0, -3:].set(1e4) for a in args[:3]) + args[3:]
    both = _conv(stages.conv_norm, args, ws)
    alone = _conv(stages.conv_norm, tuple(a[1:] for a in args[:3]) + args[3:],
                  [w[1:] for w in ws])
    for name in ("q", "k", "v", "dxq", "dxk", "dxv"):
        np.testing.assert_array_equal(np.asarray(both[name][1]),
                                      np.asarray(alone[name][0]))
    want = _conv(stages.conv_norm_xla, args, ws)
    assert _rel(both["dxv"][0, -3:], want["dxv"][0, -3:]) < TOL[F32]


def _multi_device_trace(monkeypatch):
    import paddle_tpu.parallel.mesh as mesh

    monkeypatch.setattr(mesh, "auto_partitioned_trace", lambda: True)


REFUSED = {
    "conv_head_64": (lambda mp: stages.conv_norm(
        *_conv_inputs(1, 32, 2, F32, head=64)[0], 64),
        "convolution ineligible: channel offsets (64,)"),
    "conv_head_384": (lambda mp: stages.conv_norm(
        *_conv_inputs(1, 32, 1, F32, head=384)[0], 384),
        "convolution ineligible: heads of 384 channels"),
    "conv_taps": (lambda mp: stages.conv_norm(
        *_conv_inputs(1, 32, 1, F32, taps=10)[0], HEAD),
        "convolution ineligible: 10 taps: at most 9"),
    "conv_multi_device": (lambda mp: (_multi_device_trace(mp),
                                      stages.conv_norm(*_conv_inputs(
                                          1, 32, 1, F32)[0], HEAD)),
                          "convolution ineligible: a multi-device trace"),
    "norm_head_64": (lambda mp: stages.norm_gate(
        *_norm_inputs(1, 32, 2, F32, head=64)[0], 1e-5),
        "gated norm ineligible: channel offsets (64,)"),
    "norm_multi_device": (lambda mp: (_multi_device_trace(mp),
                                      stages.norm_gate(*_norm_inputs(
                                          1, 32, 1, F32)[0], 1e-5)),
                          "gated norm ineligible: a multi-device trace"),
}


@pytest.mark.parametrize("case", sorted(REFUSED))
def test_a_refused_shape_takes_the_formula_and_is_counted_with_its_reason(
        interp, monkeypatch, capsys, case):
    from paddle_tpu.framework.flags import set_flags

    call, reason = REFUSED[case]
    set_flags({"log_pallas_fallback": True})
    try:
        call(monkeypatch)
    finally:
        set_flags({"log_pallas_fallback": False})
    assert counters.snapshot() == {"kda_stage.xla": 1}
    assert reason in capsys.readouterr().err


def test_off_the_tpu_both_stages_take_the_formulas():
    counters.reset()
    outs = stages.conv_norm(*_conv_inputs(1, 32, 1, BF16)[0], HEAD)
    out = stages.norm_gate(*_norm_inputs(1, 32, 1, BF16)[0], 1e-5)
    assert all(o.dtype == F32 for o in (*outs, out))
    assert counters.snapshot() == {"kda_stage.xla": 2}
    counters.reset()


def test_a_launch_is_traced_once_a_shape(interp, monkeypatch):
    """Each launch sits in a ``jax.jit`` of its own: three layers' calls
    at one shape trace a kernel's body once."""
    traced = []
    body = stages._norm_fwd_kernel
    monkeypatch.setattr(stages, "_norm_fwd_kernel",
                        lambda *a, **kw: traced.append(1) or body(*a, **kw))
    args, _ = _norm_inputs(1, 64, 1, F32)

    @jax.jit
    def three_layers(*a):
        return sum(stages.norm_gate(*a, 1e-5) for _ in range(3))

    three_layers(*args)
    assert len(traced) == 1


def test_declared_work_is_the_bytes_of_one_pass_each_way(interp):
    args, _ = _conv_inputs(2, 64, 2, BF16)
    o, gate, weight = _norm_inputs(2, 64, 2, BF16)[0]
    with counters.capture("s"), counters.differentiated():
        stages.conv_norm(*args, HEAD)
        stages.norm_gate(o, gate, weight, 1e-5)
    work = counters.step_work("s")
    n = 2 * 64 * 2 * HEAD
    # forward: three projections read, three float32 operands written;
    # backward on top: the projections and the float32 cotangents read,
    # dx written
    assert work["kda_conv"] == {"calls": 2, "flops": 0.0,
                                "bytes": 3.0 * n * ((2 + 4) + (2 + 4 + 2))}
    assert work["kda_gate_norm"] == {
        "calls": 2, "flops": 0.0,
        "bytes": 1.0 * n * ((4 + 2 + 4) + (4 + 2 + 4 + 4 + 2))}
    assert not [r for r in work if "kda_chunk" in r]
