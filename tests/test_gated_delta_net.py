"""Gated DeltaNet (``nn.GatedDeltaNet``, PR 49): the mixer against the
token-by-token recurrence of the benchmark's plain reference
(``benchmarks/reference/qwen3_next.py``), values and every gradient, with
fewer key heads than value heads; a scalar decay through
``chunk_kda_flat`` against the same decay written into every channel, in
the XLA form and with the kernels interpreted, at a mild decay and at the
family's strongest (where only the scalar form of the chunk body is
right); the SiLU gate through the ONE ``norm_gate`` stage; the declared
work; one forward launch of the recurrence in a recomputed block. CPU;
the chip's launches are ``tests/test_tpu_compile.py``'s and
``chip_smoke.py kernels``'."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.framework.bringup as bringup
from benchmarks.reference import qwen3_next as ref
from benchmarks.reference.kimi_linear import delta_rule_recurrence
from paddle_tpu import nn
from paddle_tpu.framework.tensor import Tensor
from paddle_tpu.ops.pallas import counters, kda
from paddle_tpu.ops.pallas import kda_stages as stages
from paddle_tpu.optimizer import meta
from paddle_tpu.optimizer.meta import recompute
from tests.test_recompute_keeps_flash import _holding, launches

F32 = jnp.float32


@pytest.fixture
def interp(monkeypatch):
    from jax.experimental import pallas as pl

    monkeypatch.setattr(pl, "pallas_call",
                        functools.partial(pl.pallas_call, interpret=True))
    monkeypatch.setattr(bringup, "pallas_enabled", lambda: True)
    counters.reset()
    jax.clear_caches()
    yield
    jax.clear_caches()
    counters.reset()


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


# ---------------------------------------------------------------------------
# the layer against the reference: loss and every gradient
# ---------------------------------------------------------------------------
CFG = {"linear_num_key_heads": 2, "linear_num_value_heads": 4,
       "linear_key_head_dim": 16, "linear_value_head_dim": 8,
       "linear_conv_kernel_dim": 4, "rms_norm_eps": 1e-6}


@pytest.mark.parametrize("tokens", [128, 72], ids=["chunks", "ragged"])
def test_layer_matches_the_recurrence_loss_and_every_gradient(tokens):
    """2 key heads of 16 under 4 value heads of 8 (value head j reads key
    head j // 2), decays from the family's start (A up to 16 under
    softplus(a + 1): up to 21 a token), 72 tokens no whole chunks."""
    hidden = 32
    paddle.seed(7)
    counters.reset()
    layer = nn.GatedDeltaNet(hidden, 2, 4, 16, 8, conv_size=4)
    shapes = {k: tuple(p.shape) for k, p in layer.named_parameters()}
    assert shapes == {
        "in_proj_qkvz.weight": (32, 2 * 32 + 2 * 32),
        "in_proj_ba.weight": (32, 8), "qkv_conv": (4, 96),
        "A_log": (4,), "dt_bias": (4,), "o_norm": (8,),
        "o_proj.weight": (32, 32)}
    assert np.all(layer.dt_bias.numpy() == 1.0)
    assert np.all(np.exp(layer.A_log.numpy()) <= 16.0)
    rng = np.random.RandomState(8)
    layer.o_norm._value = jnp.asarray(1.0 + 0.3 * rng.randn(8), F32)
    layer.A_log._value = jnp.log(jnp.asarray([0.05, 1.0, 6.0, 16.0], F32))
    x = rng.randn(2, tokens, hidden).astype(np.float32)
    w = rng.randn(2, tokens, hidden).astype(np.float32)
    params = {"m." + k: p.value for k, p in layer.named_parameters()}

    def ref_loss(p, x):
        out = jnp.stack([ref.gated_delta_net(p, "m.", row, CFG,
                                             ref.F32_MATMULS[0])
                         for row in x])
        return jnp.sum(out * w)

    want, (want_p, want_x) = jax.value_and_grad(ref_loss, argnums=(0, 1))(
        params, jnp.asarray(x))
    xt = paddle.to_tensor(x, stop_gradient=False)
    with jax.default_matmul_precision("highest"):
        loss = (layer(xt) * paddle.to_tensor(w)).sum()
        loss.backward()
    assert float(loss) == pytest.approx(float(want), rel=2e-5, abs=1e-4)
    assert _rel(xt.grad.numpy(), want_x) < 2e-5
    got = dict(layer.named_parameters())
    assert set("m." + k for k in got) == set(want_p)
    for k, p in got.items():
        assert _rel(p.grad.numpy(), want_p["m." + k]) < 5e-5, k
    snap = counters.snapshot()
    assert snap["gdn.scalar_decay"] == 1 and snap["kda_chunk.xla"] == 1


def test_layer_names_its_scopes_and_probes():
    from paddle_tpu.framework import nan_inf

    layer = nn.GatedDeltaNet(32, 2, 4, 16, 8)
    text = jax.jit(lambda a: layer(paddle.to_tensor(a)).value).lower(
        jnp.zeros((1, 64, 32), F32)).as_text(debug_info=True)
    for scope in ("gdn_before", "gdn_after"):
        assert scope in text, scope
    assert "kda_before" not in text
    seen = []
    with pytest.MonkeyPatch.context() as mp:
        import paddle_tpu.nn.linear_attention as la

        mp.setattr(la, "probe", lambda name, a, **kw: seen.append(name) or a)
        layer(paddle.to_tensor(np.zeros((1, 64, 32), np.float32)))
    assert seen == ["gdn_q", "gdn_k", "gdn_v", "gdn_g", "gdn_beta", "gdn_o"]
    assert nan_inf.record is None
    with pytest.raises(ValueError, match="no multiple"):
        nn.GatedDeltaNet(32, 3, 4, 16, 8)


# ---------------------------------------------------------------------------
# a scalar decay through chunk_kda_flat
# ---------------------------------------------------------------------------
def _operands(b, t, hk, hv, kd, vd, strength, seed=0):
    rng = np.random.default_rng(seed)

    def unit(x):
        return x / np.sqrt((x * x).sum(-1, keepdims=True) + 1e-6)

    q = unit(rng.standard_normal((b, t, hk, kd))) * kd ** -0.5
    k = unit(rng.standard_normal((b, t, hk, kd)))
    v = rng.standard_normal((b, t, hv * vd))
    g = -strength * rng.uniform(0.1, 1.0, (b, t, hv))
    beta = rng.uniform(0.0, 1.0, (b, t, hv))
    w = rng.standard_normal((b, t, hv * vd))
    return tuple(jnp.asarray(a, F32) for a in (
        q.reshape(b, t, -1), k.reshape(b, t, -1), v, g, beta, w))


def _flat(q, k, v, g, beta, w, key_heads, channels=None):
    """Loss, output and the five gradients through ``chunk_kda_flat``;
    ``channels``: the decay written into that many channels a head
    first (the per-channel form of the same call)."""
    def loss(q, k, v, g, beta):
        decay = g if channels is None else jnp.repeat(g, channels, axis=-1)
        o = kda.chunk_kda_flat(q, k, v, decay, beta, key_heads=key_heads)
        return jnp.sum(o * w), o

    (_, o), grads = jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4),
                                       has_aux=True)(q, k, v, g, beta)
    return (o,) + grads


def _recurrence(q, k, v, g, beta, w, hk, hv):
    def loss(q, k, v, g, beta):
        outs = []
        for n in range(q.shape[0]):
            t = q.shape[1]
            qq, kk = (jnp.repeat(a[n].reshape(t, hk, -1), hv // hk, axis=1)
                      for a in (q, k))
            kd = qq.shape[-1]
            outs.append(delta_rule_recurrence(
                qq, kk, v[n].reshape(t, hv, -1),
                jnp.broadcast_to(g[n][:, :, None], (t, hv, kd)),
                beta[n]).reshape(t, -1))
        o = jnp.stack(outs)
        return jnp.sum(o * w), o

    (_, o), grads = jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4),
                                       has_aux=True)(q, k, v, g, beta)
    return (o,) + grads


@pytest.mark.parametrize("strength", [0.05, 1.0, 20.0])
def test_a_scalar_decay_is_the_recurrence_at_any_strength(strength):
    """The XLA form, 2 key heads under 4 value heads: o and the five
    gradients against the token-by-token recurrence. At a mild decay the
    same decay written into every channel (the per-channel form) gives
    the same numbers; at 20 a token that form's groups of 16 rows span
    more than e^80 and it is wrong, which is why the chunk body has a
    scalar form and the broadcast alone would not do."""
    with jax.default_matmul_precision("highest"):
        ops = _operands(1, 128, 2, 4, 16, 16, strength)
        counters.reset()
        got = _flat(*ops, key_heads=2)
        assert counters.snapshot()["gdn.scalar_decay"] == 1
        want = _recurrence(*ops, 2, 4)
        for a, b in zip(got, want):
            assert a.shape == b.shape and _rel(a, b) < 2e-5
        counters.reset()
        wide = _flat(*ops, key_heads=2, channels=16)
        assert "gdn.scalar_decay" not in counters.snapshot()
    worst = max(_rel(a, b) for a, b in zip(wide, got))
    assert worst < 2e-5 if strength < 5 else worst > 1e-2


@pytest.mark.parametrize("shape", [(2, 128, 2, 2), (1, 96, 1, 2),
                                   (1, 64, 2, 4)],
                         ids=["equal", "ragged-2to1", "four-2to1"])
def test_the_kernels_scalar_form_is_the_xla_forms(interp, monkeypatch, shape):
    """Interpreted, heads of 128 x 128: what the two launches give on a
    scalar decay (from mild to 20 a token) against the XLA form of the
    same formulas, within the kernels' tolerance; the work declared is
    the scalar-decay recurrence's (q and k once a KEY head, g one float
    a head and token)."""
    b, t, hk, hv = shape
    ops = _operands(b, t, hk, hv, 128, 128, 20.0, seed=3)
    with counters.capture("t"), counters.differentiated():
        got = _flat(*ops, key_heads=hk)
    snap = counters.snapshot()
    assert snap["kda_chunk.pallas"] == 1 and "kda_chunk.xla" not in snap
    assert snap["gdn.scalar_decay"] == 1
    padded = -(-t // 64) * 64
    moved = 4.0 * b * padded * (2 * hk * 128 + hv + hv * (2 * 128 + 1))
    flops = 6.0 * b * padded * hv * 128 * 128
    assert counters.step_work("t") == {
        "kda_chunk_fwd": {"calls": 1, "flops": flops, "bytes": moved},
        "kda_chunk_bwd": {"calls": 1, "flops": 2 * flops,
                          "bytes": 2 * moved}}
    monkeypatch.setattr(bringup, "pallas_enabled", lambda: False)
    want = _flat(*ops, key_heads=hk)
    for a, b_ in zip(got, want):
        assert _rel(a, b_) < 2e-5


def test_kda_work_counts_a_channel_decay_as_before():
    """The per-channel call's declared work is what PR 48 left."""
    tokens = 2 * 8192 * 32
    assert kda.kda_work(2, 8192, 32, 128, 128) == {
        "work": {"kda_chunk_fwd": (
            6.0 * tokens * 128 * 128, 4 * tokens * (5 * 128 + 1))},
        "grad_work": {"kda_chunk_bwd": (
            12.0 * tokens * 128 * 128, 8 * tokens * (5 * 128 + 1))}}


# ---------------------------------------------------------------------------
# the gate's function through the ONE stage
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("dtype", [F32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("gate_fn", ["sigmoid", "silu"])
def test_norm_gate_takes_the_gates_function(interp, gate_fn, dtype):
    """The fused stage (interpreted) against its float32 formula, values
    and the three gradients, for both gates; the formula against numpy."""
    ks = jax.random.split(jax.random.key(1), 4)
    shape = (2, 96, 3 * 128)
    o = jax.random.normal(ks[0], shape)
    gate = (2.0 * jax.random.normal(ks[1], shape)).astype(dtype)
    weight = 1.0 + 0.2 * jax.random.normal(ks[2], (128,))
    w = jax.random.normal(ks[3], shape)

    def run(form):
        def loss(o, gate, weight):
            out = form(o, gate, weight, 1e-6, gate_fn)
            return jnp.sum(out * w), out

        (_, out), grads = jax.value_and_grad(
            loss, argnums=(0, 1, 2), has_aux=True)(o, gate, weight)
        return (out,) + grads

    got, want = run(stages.norm_gate), run(stages.norm_gate_xla)
    assert counters.snapshot() == {"kda_stage.fused": 1}
    tol = 3e-6 if dtype == F32 else 2 * 2.0 ** -8
    for a, b in zip(got, want):
        assert a.dtype == b.dtype and _rel(a, b) < tol
    heads = np.asarray(o, np.float64).reshape(2, 96, 3, 128)
    normed = heads / np.sqrt((heads ** 2).mean(-1, keepdims=True) + 1e-6) \
        * np.asarray(weight, np.float64)
    z = np.asarray(gate.astype(F32), np.float64)
    act = 1.0 / (1.0 + np.exp(-z))
    if gate_fn == "silu":
        act = z * act
    assert _rel(want[0], normed.reshape(shape) * act) < 1e-6
    with pytest.raises(ValueError, match="gate"):
        stages.norm_gate(o, gate, weight, 1e-6, "tanh")


# ---------------------------------------------------------------------------
# a recomputed block launches the recurrence's forward once
# ---------------------------------------------------------------------------
class Block(nn.Layer):
    def __init__(self):
        super().__init__()
        self.mixer = nn.GatedDeltaNet(64, 1, 2, 128, 128)

    def forward(self, x):
        return x + self.mixer(x)


@pytest.mark.parametrize("mode", ["kept", "plain"])
def test_a_recomputed_block_launches_the_forward_once(interp, monkeypatch,
                                                      mode):
    """Two blocks of 1 key head under 2 value heads of 128: with
    ``kda.KEPT`` in the policy ONE ``kda_chunk_fwd`` a layer in the
    gradient's jaxpr, two under a plain ``jax.checkpoint``; the dispatch
    counts ``kda_chunk.kept_across_recompute`` beside
    ``gdn.scalar_decay``."""
    paddle.seed(0)
    blocks = [Block() for _ in range(2)]
    params = [p for blk in blocks for p in blk.parameters()]
    x = jax.random.normal(jax.random.key(1), (1, 128, 64))
    if mode == "plain":
        monkeypatch.setattr(meta, "_kept_policy", lambda: None)

    def loss(xv, pv):
        h = Tensor(xv)
        with _holding(params, pv):
            for blk in blocks:
                h = recompute(blk, h)
        return jnp.sum(h.value.astype(F32) ** 2)

    calls = launches(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1)))(
        x, [p.value for p in params]).jaxpr)
    assert calls["kda_chunk_bwd"] == 2
    assert calls["kda_chunk_fwd"] == (2 if mode == "kept" else 4)
    snap = counters.snapshot()
    assert snap["kda_chunk.kept_across_recompute"] == 2
    assert snap["gdn.scalar_decay"] == snap["kda_chunk.pallas"] == 2
