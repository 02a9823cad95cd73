"""The state-space scan (ops/pallas/ssd.py): its chunked XLA formulation
and its Pallas kernels in interpret mode against the token-by-token
recurrence they replace — the output and every gradient (u, dt, A_log,
B, C, D, dt_bias) — at lengths that are several chunks and no whole
number of them, with decays near 1 and near 0, one group and several.
Real Mosaic lowering is ``tests/test_tpu_compile.py``'s and
``chip_smoke.py kernels``'."""
import functools

import jax
import jax.numpy as jnp
import pytest

import paddle_tpu.framework.bringup as bringup
from paddle_tpu.ops.pallas import counters, ssd

NAMES = ("u", "dt", "A_log", "B", "C", "D", "dt_bias")


@pytest.fixture
def interp(monkeypatch):
    from jax.experimental import pallas as pl

    monkeypatch.setattr(pl, "pallas_call",
                        functools.partial(pl.pallas_call, interpret=True))
    monkeypatch.setattr(bringup, "pallas_enabled", lambda: True)
    counters.reset()
    yield
    counters.reset()


def recurrence(u, dt, a_log, bm, cm, d_skip, dt_bias, groups):
    """S_t = e^(delta_t A) S_{t-1} + delta_t u_t (x) B_t; y_t = S_t C_t +
    D u_t, a token at a time."""
    b, t, hp = u.shape
    h = dt.shape[-1]
    p, n = hp // h, bm.shape[-1] // groups
    delta = jax.nn.softplus(dt + dt_bias)
    a = -jnp.exp(a_log)
    uh = u.reshape(b, t, h, p)

    def per_head(x):
        return jnp.repeat(x.reshape(b, t, groups, n), h // groups, axis=2)

    def step(state, x):
        u_t, d_t, b_t, c_t = x
        state = jnp.exp(d_t * a)[..., None, None] * state \
            + (d_t[..., None] * u_t)[..., None] * b_t[..., None, :]
        return state, jnp.einsum("bhpn,bhn->bhp", state, c_t,
                                 precision="highest")

    xs = tuple(jnp.moveaxis(x, 1, 0)
               for x in (uh, delta, per_head(bm), per_head(cm)))
    _, y = jax.lax.scan(step, jnp.zeros((b, h, p, n)), xs)
    y = jnp.moveaxis(y, 0, 1) + d_skip[:, None] * uh
    return y.reshape(b, t, hp)


def inputs(seed, b, t, h, p, groups, n, decay):
    """``decay``: (low, high) of delta * |A| a token."""
    ks = jax.random.split(jax.random.key(seed), 8)
    lo, hi = decay
    step = jnp.exp(jax.random.uniform(ks[0], (h,), minval=jnp.log(lo),
                                      maxval=jnp.log(hi)))
    args = (jax.random.normal(ks[1], (b, t, h * p)),
            0.5 * jax.random.normal(ks[2], (b, t, h)),
            jnp.log(jax.random.uniform(ks[3], (h,), minval=1.0, maxval=4.0)),
            jax.random.normal(ks[4], (b, t, groups * n)) * n ** -0.5,
            jax.random.normal(ks[5], (b, t, groups * n)),
            jax.random.normal(ks[6], (h,)),
            step + jnp.log(-jnp.expm1(-step)))           # inverse softplus
    return args, jax.random.normal(ks[7], (b, t, h * p))


def _rel(a, b):
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


def scan(u, dt, a_log, bm, cm, d_skip, dt_bias, groups, chunk):
    """``ssd.ssd_scan`` and the skip ``D_h u``, which is its caller's."""
    y = ssd.ssd_scan(u, dt, a_log, bm, cm, dt_bias, groups, chunk=chunk)
    return y + jnp.repeat(d_skip, u.shape[-1] // d_skip.shape[0]) * u


def _agree(args, w, groups, chunk, tol=2e-4):
    with jax.default_matmul_precision("highest"):
        y_ref = recurrence(*args, groups)
        y = scan(*args, groups, chunk)
        assert y.shape == y_ref.shape
        assert _rel(y, y_ref) < tol
        every = tuple(range(len(args)))
        want = jax.grad(lambda *a: jnp.sum(recurrence(*a, groups) * w),
                        argnums=every)(*args)
        got = jax.grad(lambda *a: jnp.sum(
            scan(*a, groups, chunk) * w), argnums=every)(*args)
    for name, a, b in zip(NAMES, got, want):
        assert _rel(a, b) < tol, name


#: decay a token near 1 (delta |A| ~ 1e-4) and near 0 (e^-30)
DECAYS = {"near1": (1e-4, 1e-3), "mixed": (1e-3, 1e-1), "near0": (5.0, 30.0)}


@pytest.mark.parametrize("decay", sorted(DECAYS))
@pytest.mark.parametrize("t,chunk,groups", [(48, 16, 2), (40, 16, 1),
                                            (16, 16, 4)])
def test_xla_formulation_matches_the_recurrence(t, chunk, groups, decay):
    counters.reset()
    args, w = inputs(1, 2, t, 4, 8, groups, 16, DECAYS[decay])
    _agree(args, w, groups, chunk)
    assert counters.snapshot() == {"ssd.xla": 2}


@pytest.mark.parametrize("decay", sorted(DECAYS))
@pytest.mark.parametrize("t,groups", [(384, 2), (300, 1)])
def test_pallas_kernels_match_the_recurrence(interp, t, groups, decay):
    """Heads of 32 in groups 128 lanes wide, a state 128 wide, chunks of
    128: the kernels' own shapes, forward and the hand-derived backward."""
    h = 4 * groups
    args, w = inputs(2, 2, t, h, 32, groups, 128, DECAYS[decay])
    _agree(args, w, groups, 128)
    assert counters.snapshot() == {"ssd.pallas": 2}


def test_pallas_kernels_agree_with_the_xla_formulation_on_bfloat16(interp):
    """The training step's types: bfloat16 operands, float32 decays and
    states. Both forms round the same products, so they agree far more
    closely than either does with float32."""
    args, w = inputs(3, 1, 256, 4, 32, 1, 128, DECAYS["mixed"])
    args = tuple(a.astype(jnp.bfloat16) if i in (0, 3, 4) else a
                 for i, a in enumerate(args))

    def loss(form):
        def f(u, dt, bm, cm):
            delta = jax.nn.softplus(dt + args[6])
            rows = ssd._rows(delta, -jnp.exp(args[2]), 1, 128)
            return jnp.sum(form(u, bm, cm, rows, 128).astype(jnp.float32)
                           * w)
        return jax.value_and_grad(f, argnums=(0, 1, 2, 3))(
            args[0], args[1], args[3], args[4])

    (y_k, g_k), (y_x, g_x) = loss(ssd._pallas_scan), loss(ssd._xla_scan)
    assert abs(float(y_k) - float(y_x)) < 2e-2 * abs(float(y_x)) + 1e-2
    for name, a, b in zip(("u", "dt", "B", "C"), g_k, g_x):
        assert _rel(a.astype(jnp.float32), b.astype(jnp.float32)) < 2e-2, name


def test_an_ineligible_shape_is_counted_with_its_reason(interp, capsys):
    from paddle_tpu.framework.flags import set_flags

    args, _ = inputs(4, 1, 128, 2, 16, 1, 128, DECAYS["mixed"])
    set_flags({"log_pallas_fallback": True})
    try:
        scan(*args, 1, 128)
    finally:
        set_flags({"log_pallas_fallback": False})
    assert counters.snapshot() == {"ssd.xla": 1}
    assert "a group's heads 32 wide" in capsys.readouterr().err


def test_declared_work_is_the_chunked_algorithms():
    """2 Q N a group + (2 Q P + 4 N P) a head a token: 3.41 MFLOP a token
    at the Nemotron-3-Nano layer's shapes."""
    work = ssd.ssd_work(1, 1, 64, 64, 8, 128, 128, 2)
    flops, moved = work["work"][ssd.ROLE]
    assert flops == 8 * 2 * 128 * 128 + 64 * (2 * 128 * 64 + 4 * 128 * 64)
    assert round(flops / 1e6, 2) == 3.41
    assert moved == 2 * (2 * 4096 + 2 * 1024) + 4 * 64
    assert work["grad_work"][ssd.ROLE] == (2 * flops, 2 * moved)
