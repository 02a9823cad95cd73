"""KDA (ops/pallas/kda.py): the chunked gated delta rule — its XLA form
and its Pallas kernels in interpret mode — against the token-by-token
recurrence it replaces, forward and all five gradients, at two chunk
sizes, a sequence that is no multiple of the chunk, and decays strong
enough to overflow a naive e^G / e^-G split. Real Mosaic lowering is
``chip_smoke.py kernels``' (and the v5e compile in the benchmark's
tests)."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu.framework.bringup as bringup
from paddle_tpu.ops.pallas import counters, kda


@pytest.fixture
def interp(monkeypatch):
    from jax.experimental import pallas as pl

    monkeypatch.setattr(pl, "pallas_call",
                        functools.partial(pl.pallas_call, interpret=True))
    monkeypatch.setattr(bringup, "pallas_enabled", lambda: True)
    counters.reset()
    yield
    counters.reset()


def recurrence(q, k, v, g, beta):
    """S' = Diag(e^g) S; S = S' + beta k (v - S'^T k)^T; o = S^T q."""
    b, _, h, kd = q.shape

    def step(state, x):
        q_t, k_t, v_t, g_t, b_t = x
        state = state * jnp.exp(g_t)[..., None]
        seen = jnp.einsum("bhkv,bhk->bhv", state, k_t, precision="highest")
        state = state + b_t[..., None, None] * k_t[..., None] \
            * (v_t - seen)[..., None, :]
        return state, jnp.einsum("bhkv,bhk->bhv", state, q_t,
                                 precision="highest")

    xs = tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta))
    _, o = jax.lax.scan(step, jnp.zeros((b, h, kd, v.shape[-1])), xs)
    return jnp.moveaxis(o, 0, 1)


def inputs(seed, b, t, h, kd, vd, decay):
    ks = jax.random.split(jax.random.key(seed), 6)

    def unit(x):
        return x / jnp.linalg.norm(x, axis=-1, keepdims=True)

    q = unit(jax.random.normal(ks[0], (b, t, h, kd))) * kd ** -0.5
    k = unit(jax.random.normal(ks[1], (b, t, h, kd)))
    v = jax.random.normal(ks[2], (b, t, h, vd))
    g = -jax.random.uniform(ks[3], (b, t, h, kd), minval=0.0, maxval=decay)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, t, h)))
    return (q, k, v, g, beta), jax.random.normal(ks[5], (b, t, h, vd))


def _rel(a, b):
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


def _agree(args, w, chunk, tol):
    o_ref = recurrence(*args)
    o = kda.chunk_kda(*args, chunk=chunk)
    assert o.shape == o_ref.shape
    assert _rel(o, o_ref) < tol
    want = jax.grad(lambda *a: jnp.sum(recurrence(*a) * w),
                    argnums=(0, 1, 2, 3, 4))(*args)
    got = jax.grad(lambda *a: jnp.sum(kda.chunk_kda(*a, chunk=chunk) * w),
                   argnums=(0, 1, 2, 3, 4))(*args)
    for name, a, b in zip(("q", "k", "v", "g", "beta"), got, want):
        assert _rel(a, b) < tol, name


@pytest.mark.parametrize("t,chunk,decay", [
    (96, 32, 0.2),       # whole chunks
    (100, 64, 0.2),      # a ragged tail: padded, then cut off
    (128, 64, 3.0),      # e^(3 * 64) overflows float32; group references
])
def test_chunked_form_matches_the_recurrence(t, chunk, decay):
    args, w = inputs(0, 2, t, 3, 16, 24, decay)
    _agree(args, w, chunk, 2e-5)
    assert counters.snapshot().get("kda_chunk.xla", 0) >= 1


@pytest.mark.parametrize("t,chunk", [(128, 64), (80, 32)])
def test_kernels_match_the_recurrence(interp, t, chunk):
    args, w = inputs(1, 1, t, 2, 128, 128, 0.5)
    _agree(args, w, chunk, 2e-5)
    assert counters.snapshot()["kda_chunk.pallas"] >= 1


def test_kernels_match_the_xla_form_on_the_same_chunks(interp):
    args, w = inputs(2, 2, 128, 2, 128, 128, 1.0)

    def run(kernel):
        return jax.value_and_grad(
            lambda *a: jnp.sum(kda._chunk_kda(*a, 64, kernel) * w),
            argnums=(0, 1, 2, 3, 4))(*args)

    (lk, gk), (lx, gx) = run(True), run(False)
    assert float(lk) == pytest.approx(float(lx), rel=1e-5)
    for a, b in zip(gk, gx):
        assert _rel(a, b) < 1e-5


def test_narrow_heads_take_the_xla_form(interp):
    args, _ = inputs(3, 1, 64, 2, 16, 16, 0.2)
    kda.chunk_kda(*args)
    snap = counters.snapshot()
    assert snap.get("kda_chunk.xla") == 1 and "kda_chunk.pallas" not in snap


def test_declared_work_is_the_recurrences(interp):
    args, _ = inputs(4, 2, 128, 2, 128, 128, 0.2)
    with counters.capture("s"), counters.differentiated():
        kda.chunk_kda(*args)
    work = counters.step_work("s")
    tokens = 2 * 128 * 2
    assert work["kda_chunk_fwd"] == {
        "calls": 1, "flops": 6.0 * tokens * 128 * 128,
        "bytes": 4.0 * tokens * (5 * 128 + 1)}
    assert work["kda_chunk_bwd"]["flops"] == 2 * work["kda_chunk_fwd"]["flops"]
    assert work["kda_chunk_bwd"]["bytes"] == 2 * work["kda_chunk_fwd"]["bytes"]


def test_unit_lower_inverse():
    n = np.tril(np.random.RandomState(0).randn(64, 64), -1).astype("float32")
    inv = kda._unit_lower_inverse(jnp.asarray(n * 0.3))
    np.testing.assert_allclose(np.asarray(inv) @ (np.eye(64) + n * 0.3),
                               np.eye(64), atol=2e-4)


#: sha256 of ``kda_mix``'s lowered text (below) on commit 4e54371, the
#: parent of PR 36
KDA_MIX_TEXT = {
    "bfloat16":
        "c5e4df10cb542d6e6fb898d83bd826e8b3d81ad0b6cf43ca2d0e256ca699c035",
    "float32":
        "533aaa3b7c18f053a85af416b27109aa4a2e02f19c54d0cf47d316dc4f25aa1a"}


@pytest.mark.parametrize("dtype", sorted(KDA_MIX_TEXT))
def test_kda_mix_lowers_to_the_text_it_lowered_to_before_pr_36(dtype):
    """PR 36 gave the Mamba-2 mixer fused stages of its own and left
    ``F.short_conv`` and ``kda_mix`` as they were: the KDA layer,
    differentiated under ``jax.checkpoint`` as a block of the Kimi cell
    is, lowers to the parent's StableHLO byte for byte (the projections
    in the step's autocast type and in float32)."""
    import hashlib

    from paddle_tpu.nn.linear_attention import kda_mix

    b, t, heads, d = 2, 64, 2, 16
    w, act, f32 = heads * d, jnp.dtype(dtype), jnp.float32
    shapes = ([((b, t, w), act)] * 3 + [((4, w), f32)] * 3
              + [((b, t, w), act), ((heads,), f32), ((w,), f32),
                 ((b, t, heads), act), ((b, t, w), act), ((d,), f32)])

    @jax.checkpoint
    def layer(*a):
        return jnp.sum(kda_mix.raw_fn(*a, num_heads=heads))

    text = jax.jit(jax.grad(layer, argnums=tuple(range(len(shapes))))).lower(
        *(jax.ShapeDtypeStruct(s, dt) for s, dt in shapes)).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == KDA_MIX_TEXT[dtype]
