"""KDA (ops/pallas/kda.py): the chunked gated delta rule — its XLA form
and its Pallas kernels in interpret mode — against the token-by-token
recurrence it replaces, forward and all five gradients, at two chunk
sizes, a sequence that is no multiple of the chunk, decays strong
enough to overflow a naive e^G / e^-G split, and keys correlated enough
to lose a triangular inverse formed from powers of the whole chunk's
matrix (PR 38); the kernels at every number of heads a grid step. Real
Mosaic lowering is
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


def correlated_inputs(seed, b, t, h, kd, vd, cosine=0.8, beta=0.99,
                      decay=0.05):
    """Unit keys that share a component a head (mean cosine ``cosine``
    between any two), a write strength near 1 and a weak decay: what the
    Kimi cell's last KDA layer reads after some fifty steps (PR 37)."""
    (q, k, v, g, _), w = inputs(seed, b, t, h, kd, vd, decay)
    common = jax.random.normal(jax.random.key(seed + 100), (b, 1, h, kd))
    common = common / jnp.linalg.norm(common, axis=-1, keepdims=True)
    k = cosine ** 0.5 * common + (1 - cosine) ** 0.5 * k
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    return (q, k, v, g, jnp.full((b, t, h), beta)), w


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


def test_correlated_keys_match_the_recurrence():
    """Keys at a mean cosine of 0.8, beta 0.99, decay <= 0.05, four
    chunks of 64: the recurrence's state stays under 10 and the chunked
    form must follow it. FAILS on the parent's ``_unit_lower_inverse``
    (the product form on the whole 64 x 64 matrix: its powers reach
    C(62, 31) 0.79^31 and their float32 rounding is larger than the
    inverse): there the output and all five gradients come out
    non-finite where the change reads 1.3e-5 to 1.8e-5; this is the
    fault that took the Kimi cell's state to 1e24 and NaN."""
    args, w = correlated_inputs(5, 1, 256, 2, 16, 24)
    _agree(args, w, 64, 2e-4)
    assert counters.snapshot().get("kda_chunk.xla", 0) >= 1


def test_kernels_match_the_recurrence_on_correlated_keys(interp):
    """The same case through the kernels (128-wide heads), which share
    the inverse with the XLA form; the parent's kernels fail it too."""
    args, w = correlated_inputs(6, 1, 256, 2, 128, 128)
    _agree(args, w, 64, 2e-4)
    assert counters.snapshot()["kda_chunk.pallas"] >= 1


@pytest.mark.parametrize("heads,taken", [(2, 2), (8, 4), (4, 4), (3, 1)])
def test_kernels_match_the_xla_form_on_the_same_chunks(interp, heads, taken):
    """G heads a grid step, G the largest of 8, 4, 2, 1 that divides the
    head count and fits the launch's VMEM (four 128-wide heads do, eight
    do not): every G runs the same chunks as the XLA form, and the
    dispatch says which it took."""
    assert kda._heads_a_step(heads, 128, 128, 64) == taken
    args, w = inputs(2, 2 if heads == 2 else 1, 128, heads, 128, 128, 1.0)

    def run(fn):
        return jax.value_and_grad(
            lambda *a: jnp.sum(fn(*a) * w), argnums=(0, 1, 2, 3, 4))(*args)

    (lk, gk) = run(lambda *a: kda.chunk_kda(*a, chunk=64))
    (lx, gx) = run(lambda *a: kda._chunk_kda(
        *(x.reshape(*x.shape[:2], -1) for x in a[:4]), a[4], 64,
        False).reshape(w.shape))
    assert float(lk) == pytest.approx(float(lx), rel=1e-5)
    for a, b in zip(gk, gx):
        assert _rel(a, b) < 1e-5
    snap = counters.snapshot()
    assert snap["kda_chunk.pallas"] == snap[f"kda_chunk.heads{taken}"] == 1
    assert [k for k in snap if k.startswith("kda_chunk.heads")] \
        == [f"kda_chunk.heads{taken}"] and "kda_chunk.xla" not in snap


def test_a_launch_is_traced_once_a_shape(interp, monkeypatch):
    """Each launch sits in a ``jax.jit`` of its own: three layers' calls
    at one shape trace the kernel's body once (a Kimi step has twelve
    launches; jax would trace and lower each on every process start)."""
    traced = []
    fwd = kda._chunk_fwd
    monkeypatch.setattr(kda, "_chunk_fwd",
                        lambda *a: traced.append(1) or fwd(*a))
    kda._launch_fwd.clear_cache()
    args, _ = inputs(7, 1, 128, 2, 128, 128, 0.2)

    @jax.jit
    def three_layers(*a):
        return sum(kda.chunk_kda(*a) for _ in range(3))

    three_layers(*args)
    assert len(traced) == 1
    kda._launch_fwd.clear_cache()


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


def _random_lower(c):
    n = np.tril(np.random.RandomState(0).randn(c, c), -1)
    return (n * 0.3).astype("float32")


def _gram_lower(c):
    """beta M of ``c`` unit keys at a mean cosine of 0.8, beta 0.99."""
    rng = np.random.RandomState(1)
    k = rng.randn(c, 128)
    k /= np.linalg.norm(k, axis=1, keepdims=True)
    common = rng.randn(128)
    k = 0.8 ** 0.5 * common / np.linalg.norm(common) + 0.2 ** 0.5 * k
    k /= np.linalg.norm(k, axis=1, keepdims=True)
    return (np.tril(k @ k.T, -1) * 0.99).astype("float32")


@pytest.mark.parametrize("c", [64, 32, 48])
@pytest.mark.parametrize("lower", [_random_lower, _gram_lower])
def test_unit_lower_inverse(lower, c):
    """Against float64, entry by entry; the Gram case is what a chunk of
    correlated keys hands it (the parent's product form errs by 1e8
    there at 64 rows; the true inverse's entries are under 1)."""
    n = lower(c)
    inv = kda._unit_lower_inverse(jnp.asarray(n))
    want = np.linalg.inv(np.eye(c) + n.astype("float64"))
    np.testing.assert_allclose(np.asarray(inv), want, atol=2e-4)
    np.testing.assert_allclose(np.asarray(inv) @ (np.eye(c) + n),
                               np.eye(c), atol=2e-4)


#: sha256 of ``kda_mix``'s lowered text (below) as PR 43 left it
KDA_MIX_TEXT = {
    "bfloat16":
        "51bc8271f0937ac105a03e215540bd6ed68614e5a9efb7e7437d00a02a1d0303",
    "float32":
        "7a872269091a173780fd930230986fa9113508d597fb51f96136b49f4f58cae3"}


@pytest.mark.parametrize("dtype", sorted(KDA_MIX_TEXT))
def test_kda_mix_lowers_to_its_pinned_text(dtype):
    """The KDA layer, differentiated under ``jax.checkpoint`` as a block
    of the Kimi cell is, lowers to one StableHLO text (the projections
    in the step's autocast type and in float32): a PR that means to
    leave ``F.short_conv``, ``kda_mix`` and the chunk formulas alone
    keeps these digests (PR 36 did, byte for byte). Re-pinned ON PURPOSE
    by PR 38 (the chunk formulas' ``_unit_lower_inverse`` solves by 16 x
    16 blocks) and by PR 43 (ROADMAP S15; before: 1a12e487... /
    b619b8b9..., from commit 784e939), which changed how the layer is
    LAID OUT and nothing of its arithmetic but the order of two sums:
    this is the CPU's path, the float32 formulas of
    ``ops/pallas/kda_stages.py`` (``conv_norm_xla``, ``norm_gate_xla``:
    the same convolution + SiLU + L2 norm and head norm x gate, each
    under its own ``jax.checkpoint``, where ``kda_mix`` had two
    checkpoints over all of one side), the decay and every array round
    the recurrence on (B, T, H * D) with ``A_log`` repeated to the width
    (``chunk_kda_flat``; no reshape to (B, T, H, D) left outside the XLA
    form's scan), and the chunk's cumulative decay as a product with a
    triangle of ones (its transpose the same with the triangle turned)
    where ``jnp.cumsum`` and two flips were."""
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
