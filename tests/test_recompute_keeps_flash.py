"""A segment that ``optimizer.meta.recompute`` runs again in the backward
keeps what its flash attention kernels wrote (PR 40): the gradient of a
recomputed stack holds ONE forward launch a layer, its numbers are those
of plain ``jax.checkpoint`` and of no recomputation bit for bit, the
checkpoint keeps the kernels' two outputs and nothing else the size of
the sequence, the dispatch counts ``kept_across_recompute``, and outside
a checkpoint the name lowers to nothing. CPU, ``pallas_call`` in
interpret mode; the chip's launches are ``tools/profile_step.py``'s to
show."""
import collections
import contextlib
import functools

import jax
import jax.numpy as jnp
import pytest

import paddle_tpu as paddle
import paddle_tpu.framework.bringup as bringup
from paddle_tpu import nn, ops
from paddle_tpu.framework import nan_inf
from paddle_tpu.framework import tape as tape_mod
from paddle_tpu.framework.random import rng_scope
from paddle_tpu.framework.tensor import Tensor
from paddle_tpu.nn import functional as F
from paddle_tpu.ops.pallas import counters
from paddle_tpu.ops.pallas import flash_attention as fa
from paddle_tpu.optimizer import meta
from paddle_tpu.optimizer.meta import recompute

B, L, HIDDEN = 2, 256, 64


@pytest.fixture
def interp(monkeypatch):
    """The Pallas path on the CPU: kernels interpreted, the gate open."""
    from jax.experimental import pallas as pl

    monkeypatch.setattr(pl, "pallas_call",
                        functools.partial(pl.pallas_call, interpret=True))
    monkeypatch.setattr(bringup, "pallas_enabled", lambda: True)
    counters.reset()
    yield
    counters.reset()


class Attn(nn.Layer):
    """``x + Attention(x Wq, x Wk, x Wv) Wo``: what a block keeps of its
    mixer, with the projections that bring q, k, v back."""

    def __init__(self, heads, kv_heads, dk, dv, **sdpa):
        super().__init__()
        self.dims, self.sdpa = (heads, kv_heads, dk, dv), sdpa
        self.wq = self.create_parameter([HIDDEN, heads * dk])
        self.wk = self.create_parameter([HIDDEN, kv_heads * dk])
        self.wv = self.create_parameter([HIDDEN, kv_heads * dv])
        self.wo = self.create_parameter([heads * dv, HIDDEN])

    def forward(self, x):
        h, hk, dk, dv = self.dims
        b, l, _ = x.shape
        q = ops.reshape(ops.matmul(x, self.wq), [b, l, h, dk])
        k = ops.reshape(ops.matmul(x, self.wk), [b, l, hk, dk])
        v = ops.reshape(ops.matmul(x, self.wv), [b, l, hk, dv])
        sdpa = dict(self.sdpa)
        if sdpa.pop("padded", False):
            sdpa["attn_mask"] = Tensor(jnp.arange(l)[None, :] < jnp.array(
                [[l], [l - 64]]))
        o = F.scaled_dot_product_attention(q, k, v, **sdpa)
        return x + ops.matmul(ops.reshape(o, [b, l, h * dv]), self.wo)


#: name -> (Attn arguments, (forward role, backward role), whether the
#: interpreter can run it)
KINDS = {
    # latent attention's widths: keys 192, values 128
    "stream": ((2, 2, 192, 128, dict(is_causal=True)),
               ("flash_attention_stream_fwd", "flash_attention_stream_bwd"),
               True),
    "grouped": ((4, 1, 64, 64, dict(is_causal=True)),
                ("flash_attention_grouped",) * 2, True),
    "windowed": ((2, 2, 64, 64, dict(is_causal=True, window=128)),
                 ("flash_attention_window",) * 2, True),
    "masked": ((2, 2, 64, 64, dict(padded=True)),
               ("flash_attention_stream_fwd", "flash_attention_stream_bwd"),
               True),
    # the keep mask comes from the TPU's generator: traced here, not run
    "dropout": ((2, 2, 64, 64, dict(dropout_p=0.1)),
                ("flash_attention_stream_fwd", "flash_attention_stream_bwd"),
                False),
    "short": ((2, 2, 64, 64, dict(is_causal=True)),
              ("flash_attention_short_fwd", "flash_attention_short_bwd"),
              True),
}
LAYERS = 2


def launches(jaxpr, acc=None):
    """{role: ``pallas_call``s} of a jaxpr and everything nested in it."""
    acc = collections.Counter() if acc is None else acc
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            acc[eqn.params["name"]] += 1
            continue                    # not into the kernel's body
        for sub in jax.core.jaxprs_in_params(eqn.params):
            launches(sub, acc)
    return acc


def _stack(kind):
    paddle.seed(0)
    args = KINDS[kind][0]
    layers = [Attn(*args[:4], **args[4]) for _ in range(LAYERS)]
    params = [p for layer in layers for p in layer.parameters()]
    values = [0.1 * jax.random.normal(
        jax.random.fold_in(jax.random.key(2), i), tuple(p.shape))
        for i, p in enumerate(params)]
    x = jax.random.normal(jax.random.key(1), (B, L, HIDDEN))
    return layers, params, x, values


@contextlib.contextmanager
def _holding(params, values):
    """The parameters hold ``values`` (tracers), the tape off: how
    ``TrainStep`` traces a model."""
    saved = [p._value for p in params]
    try:
        for p, v in zip(params, values):
            p._value = v
        with tape_mod.no_grad():
            yield
    finally:
        for p, v in zip(params, saved):
            p._value = v


def _value_and_grad(layers, params, mode, monkeypatch):
    """Loss and gradients (input, every weight) of the stack with each
    layer through ``recompute`` (``"kept"``), through ``recompute`` as
    the parent had it, ``jax.checkpoint`` with no policy (``"plain"``),
    or called as it is (``"none"``); traced as ``TrainStep`` traces."""
    if mode == "plain":
        monkeypatch.setattr(meta, "_kept_policy", lambda: None)

    def loss(xv, pv):
        x = Tensor(xv)
        with _holding(params, pv), rng_scope(jax.random.key(3)):
            for layer in layers:
                x = layer(x) if mode == "none" else recompute(layer, x)
        return jnp.sum(x.value.astype(jnp.float32) ** 2)

    return jax.value_and_grad(loss, argnums=(0, 1))


@pytest.mark.parametrize("kind", sorted(KINDS))
def test_a_recomputed_layer_launches_its_forward_kernel_once(
        interp, monkeypatch, kind):
    (_, (fwd, bwd), runs) = KINDS[kind]
    if kind == "short":
        monkeypatch.setattr(fa, "_get_flag_short", lambda: True)
    layers, params, x, values = _stack(kind)
    got, snaps = {}, {}
    for mode in ("kept", "plain", "none"):
        with monkeypatch.context() as mp:
            counters.reset()
            f = _value_and_grad(layers, params, mode, mp)
            got[mode] = launches(jax.make_jaxpr(f)(x, values).jaxpr)
            snaps[mode] = counters.snapshot()
            if runs:
                got[mode, "values"] = jax.jit(f)(x, values)
    # the backward is one launch a layer: the short kernels' always was,
    # the stream family's since dQ accumulates in the dK/dV kernel (PR 42)
    one, two = ({fwd: n + LAYERS} if fwd == bwd else {fwd: n, bwd: LAYERS}
                for n in (LAYERS, 2 * LAYERS))
    assert got["kept"] == got["none"] == one
    assert got["plain"] == two
    for mode in ("kept", "plain"):
        assert snaps[mode]["flash_attention.kept_across_recompute"] \
            == snaps[mode]["flash_attention.pallas"] == LAYERS
    assert "flash_attention.kept_across_recompute" not in snaps["none"]
    assert "flash_attention.xla" not in snaps["kept"]
    if not runs:
        return
    want = jax.tree_util.tree_leaves(got["kept", "values"])
    assert all(bool(jnp.all(jnp.isfinite(a))) for a in want)
    for mode in ("plain", "none"):
        for a, b in zip(want,
                        jax.tree_util.tree_leaves(got[mode, "values"])):
            assert bool(jnp.all(a == b)), (kind, mode)


def test_the_record_of_check_nan_inf_rides_the_same_policy(interp,
                                                           monkeypatch):
    """A step built with ``FLAGS_check_nan_inf`` hands the rows made in a
    segment out of its checkpoint (``nan_inf._carrying``): the policy
    goes with them. ``LAYERS`` backward launches, not the ``2 * LAYERS``
    this test pinned until PR 42, and on purpose: the streaming backward
    is ONE launch a layer since the kernel that sums dK, dV also holds
    the head's dQ (``flash_attention._bwd_call``)."""
    layers, params, x, values = _stack("stream")
    f = _value_and_grad(layers, params, "kept", monkeypatch)
    bare = jax.jit(f)(x, values)
    model = nn.LayerList(layers)

    def recorded(xv, pv):
        with nan_inf.recording(model) as rec:
            out = f(xv, pv)
            return out, rec.frames[0].stacked()

    counters.reset()
    jaxpr = jax.make_jaxpr(recorded)(x, values).jaxpr
    snap = counters.snapshot()
    out, rows = jax.jit(recorded)(x, values)
    assert launches(jaxpr) == {"flash_attention_stream_fwd": LAYERS,
                               "flash_attention_stream_bwd": LAYERS}
    assert snap["flash_attention.kept_across_recompute"] == LAYERS
    # one row a layer's output, none of them with a non-finite element
    assert rows.shape == (LAYERS, 3) and not bool(jnp.any(rows[:, 0]))
    for a, b in zip(jax.tree_util.tree_leaves(out),
                    jax.tree_util.tree_leaves(bare)):
        assert bool(jnp.all(a == b))


def test_the_xla_fallback_names_and_counts_nothing(monkeypatch):
    """On the CPU as it is the attention falls back: nothing is named,
    nothing is kept and nothing is counted."""
    counters.reset()
    layers, params, x, values = _stack("stream")
    f = _value_and_grad(layers, params, "kept", monkeypatch)
    text = str(jax.make_jaxpr(f)(x, values))
    snap = counters.snapshot()
    counters.reset()
    assert snap["flash_attention.xla"] == LAYERS
    assert "flash_attention.kept_across_recompute" not in snap
    assert fa.KEPT not in text


# ---------------------------------------------------------------------------
# what a recomputed block keeps
# ---------------------------------------------------------------------------
def _saved(layer, x):
    """What the checkpoint that ``recompute(layer, x)`` lowers to inside
    a trace keeps: [(aval, where from)], as
    ``jax.ad_checkpoint.print_saved_residuals`` lists it."""
    from jax._src.ad_checkpoint import saved_residuals

    params = list(layer.parameters())
    seen = {}
    real = jax.checkpoint

    def spy(fn, **kwargs):
        wrapped = real(fn, **kwargs)

        def call(*args):
            seen["residuals"] = saved_residuals(wrapped, *args)
            return wrapped(*args)
        return call

    def traced(xv, pv):
        with _holding(params, pv):
            return recompute(layer, Tensor(xv)).value

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax, "checkpoint", spy)
        jax.make_jaxpr(traced)(x, [p.value for p in params])
    return len(params), seen["residuals"]


@pytest.mark.parametrize("kind,heads", [("mla", 2), ("gqa", 4)])
def test_a_recomputed_block_keeps_its_inputs_the_output_and_the_logsumexp(
        interp, kind, heads):
    """Arguments (the input, the parameters), the kernel's output and
    logsumexp under their name, and nothing else as large as the
    sequence: the policy keeps no more than it says."""
    paddle.seed(0)
    layer = nn.MLAttention(128, heads, 64, 64, 64, 32,
                           rope={"rope_theta": 1e6, "interleave": True}) \
        if kind == "mla" else nn.GroupedQueryAttention(
            128, heads, 2, 64, rope=None, qk_norm=False)
    n_params, residuals = _saved(
        layer, jax.random.normal(jax.random.key(5), (1, L, 128)))
    args = [aval for aval, why in residuals if "argument" in why]
    assert len(args) == 1 + n_params
    # beside them, of anything as long as the sequence: the launch's two
    # outputs (listed as the outputs of the dispatcher's inner jit)
    large = [(aval.shape, why) for aval, why in residuals
             if "argument" not in why and aval.size >= L]
    assert sorted(shape for shape, _ in large) == [
        (heads, 1, L), (heads, L, 64)], residuals
    assert all("_flash_attention_pallas" in why or fa.KEPT in why
               for _, why in large), large
    assert counters.snapshot()["flash_attention.kept_across_recompute"] == 1


# ---------------------------------------------------------------------------
# outside a checkpoint the name is nothing
# ---------------------------------------------------------------------------
def _lowered(fn, *shapes, named):
    with pytest.MonkeyPatch.context() as mp:
        if not named:       # the forward rules as the parent had them
            mp.setattr(fa, "_kept", lambda x: x)
        return jax.jit(fn).lower(*shapes).as_text()


def test_a_nope_mla_outside_any_checkpoint_lowers_to_the_parents_text(
        interp):
    paddle.seed(0)
    layer = nn.MLAttention(128, 2, 64, 64, 64, 32, epsilon=1e-5)
    params = list(layer.parameters())

    def grads(xv, pv):
        def loss(xv, pv):
            with _holding(params, pv):
                return jnp.sum(layer(Tensor(xv)).value)
        return jax.grad(loss, argnums=(0, 1))(xv, pv)

    shapes = (jax.ShapeDtypeStruct((1, L, 128), jnp.float32),
              [jax.ShapeDtypeStruct(tuple(p.shape), jnp.float32)
               for p in params])
    named = _lowered(grads, *shapes, named=True)
    assert counters.snapshot()["flash_attention.latent"] == 1
    assert named == _lowered(grads, *shapes, named=False)


def test_a_bert_encoder_step_lowers_to_the_parents_text(interp, monkeypatch):
    """BERT's step asks for no recomputation: the name its short kernels'
    forward rule gives stands outside any checkpoint and leaves the
    lowered step as it was."""
    import numpy as np

    from paddle_tpu import optimizer
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.models.bert import BertConfig, BertForPretraining

    monkeypatch.setattr(fa, "_get_flag_short", lambda: True)
    rng = np.random.default_rng(0)
    ids = rng.integers(0, 512, (2, 128)).astype("int32")
    batch = [paddle.to_tensor(a) for a in (
        ids, np.zeros_like(ids),
        np.where(rng.random(ids.shape) < 0.15, ids, -100).astype("int32"),
        rng.integers(0, 2, (2,)).astype("int32"))]

    def text(named):
        paddle.seed(0)
        model = BertForPretraining(BertConfig(
            vocab_size=512, hidden_size=128, num_hidden_layers=2,
            num_attention_heads=2, intermediate_size=256,
            max_position_embeddings=128, hidden_dropout_prob=0.0,
            attention_probs_dropout_prob=0.0))
        model.eval()
        step = TrainStep(
            model, lambda m, ids, tt, mlm, nsp: m.loss(ids, tt, mlm, nsp),
            optimizer.AdamW(learning_rate=1e-4,
                            parameters=model.parameters()))
        with pytest.MonkeyPatch.context() as mp:
            if not named:
                mp.setattr(fa, "_kept", lambda x: x)
            return step.lower(*batch).as_text()

    named = text(True)
    snap = counters.snapshot()
    assert snap["flash_attention.short_packed"] == 2
    assert "flash_attention.kept_across_recompute" not in snap
    assert named == text(False)


@pytest.mark.parametrize("name", ["kimi", "mellum", "nemotron"])
def test_the_policy_alone_changes_no_number_of_a_decoder_step(
        monkeypatch, name):
    """On the CPU nothing is named, so the policy keeps what plain
    ``jax.checkpoint`` kept: two steps of a tiny decoder (the builders of
    ``test_step_numerics``, whose pinned digests say the lowered text is
    the parent's too: ``recompute`` hands every segment ONE policy
    object, so jax's caches part the helpers the blocks share once)
    give the same losses and the same parameters to the last bit."""
    import numpy as np

    from tests import test_step_numerics as cells

    def two_steps(plain):
        with monkeypatch.context() as mp:
            if plain:
                mp.setattr(meta, "_kept_policy", lambda: None)
            step, batch = cells._step(cells.BUILDERS[name])
            losses = [float(step(*batch)[0]) for _ in range(2)]
        return losses, {n: np.asarray(p.value)
                        for n, p in step.model.named_parameters()}

    (kept_losses, kept), (plain_losses, plain) = two_steps(False), \
        two_steps(True)
    assert kept_losses == plain_losses
    assert all(np.array_equal(kept[n], plain[n]) for n in kept)
