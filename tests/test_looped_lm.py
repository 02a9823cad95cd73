"""A looped causal LM (``total_ut_steps`` > 1, ``models/causal_lm.py``):
the program against the plain reference ``benchmarks/reference/ouro.py``
on the eager tape (``test_benchmark_ouro.py`` does the same through the
jitted ``TrainStep``), a parameter's gradient as the sum over its uses,
recomputation on and off, the fused cross-entropy's loss a row
(``reduction="none"``), the older spelling of the rotary keys, the
``FLAGS_check_nan_inf`` keys of a layer called once a pass, the spans and
counters of a traced step, and the one-pass models' lowered text."""
import functools
import hashlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.framework.bringup as bringup
from benchmarks.reference import ouro as ref
from paddle_tpu import amp, nn, optimizer
from paddle_tpu.framework import flags
from paddle_tpu.jit import TrainStep
from paddle_tpu.models.causal_lm import CausalLM, DecoderBlock
from paddle_tpu.nn import functional as F
from paddle_tpu.ops.pallas import counters
from paddle_tpu.ops.pallas import fused_xent as fx

CONFIGS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks", "configs")
PASSES, LAYERS = 4, 2
TINY = dict(hidden_size=64, num_attention_heads=4, num_key_value_heads=4,
            head_dim=16, intermediate_size=96, hidden_act="silu",
            layer_types=["full_attention"] * LAYERS,
            num_hidden_layers=LAYERS, rms_norm_eps=1e-6, rope_theta=1000000,
            rope_scaling=None, sliding_window=None, vocab_size=512,
            total_ut_steps=PASSES, tie_word_embeddings=False,
            sandwich_norm=True, qk_norm=False, exit_entropy_beta=0.1)


def _batch(seed=0, b=2, s=32, vocab=512):
    ids = np.random.default_rng(seed).integers(0, vocab, (b, s)).astype(
        "int32")
    labels = np.full_like(ids, -100)
    labels[:, :-1] = ids[:, 1:]
    return ids, labels


def _model(cfg=TINY, recompute=False, seed=7):
    """A model on seeded weights with scales and a gate bias that are
    not their initial one and zero, so that every leaf matters."""
    paddle.seed(seed)
    model = CausalLM.from_config(cfg, recompute=recompute)
    key = jax.random.key(seed)
    for i, (name, p) in enumerate(model.named_parameters()):
        k = jax.random.fold_in(key, i)
        if p.ndim >= 2:
            p._value = 0.05 * jax.random.normal(k, p.shape, jnp.float32)
        else:
            p._value = 1.0 + 0.1 * jax.random.normal(k, p.shape, jnp.float32)
    model.exit_gate.bias._value = jnp.asarray([0.3], jnp.float32)
    return model


def _params(model):
    return {n: p.value for n, p in model.named_parameters()}


def _eager_grads(model, ids, labels):
    model.clear_gradients()
    loss = model.loss(paddle.to_tensor(ids), paddle.to_tensor(labels))
    loss.backward()
    return float(loss), {n: np.asarray(p.grad.value)
                         for n, p in model.named_parameters()}


# ---------------------------------------------------------------------------
# the program against the reference
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("recompute", [False, True], ids=["plain", "remat"])
def test_eager_loss_and_every_leafs_gradient_match_the_reference(recompute):
    model = _model(recompute=recompute)
    ids, labels = _batch()
    want_loss, want = jax.value_and_grad(ref.loss)(
        _params(model), TINY, jnp.asarray(ids), jnp.asarray(labels),
        block_rows=16)
    loss, got = _eager_grads(model, ids, labels)
    assert loss == pytest.approx(float(want_loss), abs=2e-6)
    assert set(got) == set(want)
    for name in want:
        np.testing.assert_allclose(got[name], np.asarray(want[name]),
                                   atol=2e-6, rtol=2e-4, err_msg=name)
    # the gate and every norm of the sandwich get a gradient that is not 0
    for name in ("exit_gate.weight", "exit_gate.bias",
                 "layers.0.mixer_out_norm.weight",
                 "layers.1.ffn_out_norm.weight", "final_norm.weight"):
        assert np.abs(got[name]).max() > 1e-6, name


def test_three_eager_adamw_steps_match_the_reference():
    # a small rate: Adam's first steps move every element by the rate,
    # whatever its gradient's size, so an element whose gradient is near
    # zero turns a last-bit difference into a step of the other sign
    hyper = {"learning_rate": 1e-4, "warmup_steps": 1, "beta1": 0.9,
             "beta2": 0.95, "epsilon": 1e-8, "weight_decay": 0.1}
    batches = [_batch(seed) for seed in (1, 2, 3)]
    model = _model()
    # on the host: the reference's update and the eager optimizer's both
    # donate the arrays they are handed
    start = {n: np.asarray(v) for n, v in _params(model).items()}
    want = ref.train(lambda: {n: jnp.asarray(v) for n, v in start.items()},
                     TINY, batches, hyper, block_rows=16)
    opt = optimizer.AdamW(
        learning_rate=hyper["learning_rate"], beta1=0.9, beta2=0.95,
        epsilon=1e-8, weight_decay=0.1, parameters=model.parameters())
    losses = []
    for ids, labels in batches:
        loss = model.loss(paddle.to_tensor(ids), paddle.to_tensor(labels))
        loss.backward()
        opt.step()
        opt.clear_grad()
        losses.append(float(loss))
    np.testing.assert_allclose(losses, want["loss"], atol=1e-5)
    for name, p in model.named_parameters():
        moved = float(np.linalg.norm(np.asarray(p.value) - start[name]))
        assert moved == pytest.approx(want["delta_norm"][name], rel=2e-3,
                                      abs=1e-7), name


def test_the_exit_distribution_by_products_and_by_log_sigmoids_agree():
    rng = np.random.default_rng(3)
    a = jnp.asarray(rng.normal(0, 3, (PASSES - 1, 40)), jnp.float32)
    losses = jnp.asarray(rng.uniform(1, 8, (PASSES, 40)), jnp.float32)
    labels = jnp.asarray(rng.integers(0, 9, (40,)) - 100 * (
        rng.random(40) < 0.2), jnp.int32)
    labels = jnp.where(labels < 0, -100, labels)
    probs = ref.exit_distribution(list(a))
    np.testing.assert_allclose(np.asarray(sum(probs)), 1.0, atol=1e-6)
    at = sum(p * (l + 0.1 * jnp.log(p)) for p, l in zip(probs, losses))
    want = jnp.sum(jnp.where(labels != -100, at, 0.0)) / jnp.sum(
        labels != -100)
    got = F.expected_exit_loss.raw_fn(losses, a, labels, beta=0.1)
    assert float(got) == pytest.approx(float(want), rel=2e-6)
    # a gate driven to either end keeps the loss finite where the literal
    # products would take log(0)
    far = jnp.asarray([[60.0], [-60.0], [0.0]], jnp.float32)
    out, grad = jax.value_and_grad(
        lambda g: F.expected_exit_loss.raw_fn(
            jnp.ones((4, 1)), g, jnp.zeros((1,), jnp.int32), beta=0.1))(far)
    assert np.isfinite(float(out)) and np.all(np.isfinite(np.asarray(grad)))


# ---------------------------------------------------------------------------
# four uses of one parameter
# ---------------------------------------------------------------------------
def test_a_parameters_gradient_is_the_sum_over_its_four_uses():
    """T = 4 on L layers against an UNTIED model of 4 x L blocks whose
    copies hold equal weights: pass t of the untied model walks its own
    copy, so each copy's gradient is that use's alone."""
    ids, labels = _batch(5)
    looped = _model(recompute=True)
    _, tied = _eager_grads(looped, ids, labels)

    untied = _model()
    copies = [untied.layers] + [
        nn.LayerList([DecoderBlock(TINY, n + 1) for n in range(LAYERS)])
        for _ in range(PASSES - 1)]
    for blocks in copies[1:]:
        for mine, theirs in zip(blocks, untied.layers):
            for (_, p), (_, q) in zip(mine.named_parameters(),
                                      theirs.named_parameters()):
                p._value = q.value
    calls = []

    def walk(x):
        from paddle_tpu import ops

        blocks = copies[len(calls) % PASSES]
        calls.append(1)
        for block in blocks:
            x, _ = block(x)
        return untied.final_norm(x), ops.zeros([LAYERS, 2], "float32")

    untied._walk = walk
    loss = untied.loss(paddle.to_tensor(ids), paddle.to_tensor(labels))
    loss.backward()
    assert len(calls) == PASSES
    for n in range(LAYERS):
        per_use = [dict(blocks[n].named_parameters()) for blocks in copies]
        for name, first in per_use[0].items():
            uses = [np.asarray(u[name].grad.value) for u in per_use]
            assert all(np.abs(u).max() > 0 for u in uses), name
            np.testing.assert_allclose(
                tied[f"layers.{n}.{name}"], sum(uses), atol=2e-6, rtol=2e-4,
                err_msg=name)
            # no one use is the whole of it
            assert not np.allclose(tied[f"layers.{n}.{name}"], uses[0],
                                   rtol=1e-2, atol=1e-8), name


def _train_step(cfg, recompute, level="O0"):
    model = _model(cfg, recompute=recompute)
    opt = optimizer.AdamW(learning_rate=1e-3, parameters=model.parameters())

    def loss_fn(m, i, l):
        with amp.auto_cast(level=level, dtype="bfloat16"):
            return m.loss(i, l, return_routing=True)

    return model, TrainStep(model, loss_fn, opt)


def test_recomputation_on_and_off_agree_in_the_jitted_step():
    ids, labels = _batch(6)
    out = {}
    for recompute in (False, True):
        model, step = _train_step(TINY, recompute)
        losses = [float(step(paddle.to_tensor(ids),
                             paddle.to_tensor(labels))[0]) for _ in range(3)]
        out[recompute] = (losses, _params(model))
    np.testing.assert_allclose(out[True][0], out[False][0], atol=1e-6)
    for name, value in out[False][1].items():
        # AdamW divides by the gradient's size: an element whose
        # gradient is near zero amplifies a last-bit difference
        np.testing.assert_allclose(np.asarray(out[True][1][name]),
                                   np.asarray(value), atol=5e-5,
                                   err_msg=name)
    # and the eager tape gave the same first loss
    model = _model(recompute=True)
    assert _eager_grads(model, ids, labels)[0] == pytest.approx(
        out[True][0][0], abs=2e-6)


def test_routing_keeps_its_shape_and_forward_gives_the_last_pass():
    model = _model()
    ids, labels = _batch()
    loss, routing = model.loss(paddle.to_tensor(ids),
                               paddle.to_tensor(labels), return_routing=True)
    assert tuple(routing.shape) == (LAYERS, 2)
    assert float(jnp.abs(routing.value).max()) == 0.0
    passes, _ = model.hidden_passes(paddle.to_tensor(ids))
    assert len(passes) == PASSES
    want = ref.pass_states(_params(model), TINY, jnp.asarray(ids[0]),
                           block_rows=16)
    for got, h in zip(passes, want):
        np.testing.assert_allclose(np.asarray(got.value[0]), np.asarray(h),
                                   atol=2e-5)
    logits = model(paddle.to_tensor(ids))
    np.testing.assert_allclose(
        np.asarray(logits.value[0]),
        np.asarray(want[-1] @ model.head.value.T), atol=2e-4)


# ---------------------------------------------------------------------------
# the fused cross-entropy, a loss a row
# ---------------------------------------------------------------------------
@pytest.fixture
def interp(monkeypatch):
    from jax.experimental import pallas as pl

    monkeypatch.setattr(pl, "pallas_call",
                        functools.partial(pl.pallas_call, interpret=True))
    monkeypatch.setattr(bringup, "pallas_enabled", lambda: True)
    counters.reset()
    with jax.default_matmul_precision("highest"):
        yield
    counters.reset()


def _xent_data(n, h=128, v=512, seed=0, labelled=1.0):
    rng = np.random.default_rng(seed)
    hmat = jnp.asarray(rng.normal(0, 0.3, (n, h)), jnp.float32)
    w = jnp.asarray(rng.normal(0, 0.3, (v, h)), jnp.float32)
    b = jnp.asarray(rng.normal(0, 0.1, (v,)), jnp.float32)
    lab = rng.integers(0, v, n)
    lab[rng.random(n) >= labelled] = -100
    weight = jnp.asarray(rng.uniform(0.2, 2.0, (n,)), jnp.float32)
    return hmat, w, b, jnp.asarray(lab, jnp.int32), weight


def _xla_rows(h, w, b, lab):
    logp = jax.nn.log_softmax(
        jnp.matmul(h, w.T, precision="highest") + b, axis=-1)
    ll = jnp.take_along_axis(logp, jnp.maximum(lab, 0)[:, None], 1)[:, 0]
    return jnp.where(lab != -100, -ll, 0.0)


@pytest.mark.parametrize("labelled,rung", [
    (1.0, ""), (0.4, "rows4096_"), (0.2, "rows2048_"), (0.08, "rows1024_")],
    ids=["top", "half", "quarter", "eighth"])
def test_per_row_losses_and_both_cotangents_match_xla_logits(interp, labelled,
                                                             rung):
    """Values, and the cotangent in (a weight a row) with the cotangents
    out (dh, dW, db), on every rung of the ladder."""
    h, w, b, lab, weight = _xent_data(8192, labelled=labelled)
    count = int(jnp.sum(lab != -100))
    rungs = fx._ladder(8192, fx._blocks(h, w)[0])
    assert rungs == (1024, 2048, 4096, 8192)
    assert fx._tag(min(k for k in rungs if k >= count), 8192) == rung

    def fused(h, w, b):
        rows = fx.fused_linear_cross_entropy(h, w, b, lab, reduction="none")
        return jnp.sum(rows * weight), rows

    def plain(h, w, b):
        rows = _xla_rows(h, w, b, lab)
        return jnp.sum(rows * weight), rows

    (_, rows), got = jax.value_and_grad(fused, (0, 1, 2), has_aux=True)(h, w,
                                                                        b)
    (_, want_rows), want = jax.value_and_grad(plain, (0, 1, 2),
                                              has_aux=True)(h, w, b)
    assert rows.shape == lab.shape and rows.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(rows), np.asarray(want_rows),
                               atol=2e-5)
    assert float(jnp.abs(jnp.where(lab == -100, rows, 0.0)).max()) == 0.0
    for g, wnt, name in zip(got, want, ("dh", "dw", "db")):
        np.testing.assert_allclose(np.asarray(g), np.asarray(wnt),
                                   atol=3e-5, err_msg=name)
    snap = counters.snapshot()
    assert snap["fused_xent.per_row"] == snap["fused_xent.pallas"] >= 1


def test_per_row_keeps_the_labels_shape_pads_and_mean_is_unchanged(interp):
    h, w, b, lab, _ = _xent_data(4 * 100, labelled=0.7)
    h3, lab3 = h.reshape(4, 100, -1), lab.reshape(4, 100)
    rows = fx.fused_linear_cross_entropy(h3, w, b, lab3, reduction="none")
    assert rows.shape == (4, 100)                 # 400 rows padded to 512
    np.testing.assert_allclose(np.asarray(rows.reshape(-1)),
                               np.asarray(_xla_rows(h, w, b, lab)), atol=2e-5)
    mean = fx.fused_linear_cross_entropy(h3, w, b, lab3)
    assert mean.shape == ()
    assert float(mean) == pytest.approx(
        float(jnp.sum(rows) / jnp.sum(lab != -100)), rel=1e-6)
    with pytest.raises(ValueError, match="reduction"):
        fx.fused_linear_cross_entropy(h3, w, b, lab3, reduction="sum")


def test_per_row_on_the_xla_path_and_through_the_tape():
    """Off the chip the same entry takes XLA logits; ``F`` puts it on the
    eager tape, whose backward hands a cotangent a row in."""
    h, w, b, lab, weight = _xent_data(96, h=32, v=64, labelled=0.8)
    counters.reset()
    th, tw = paddle.to_tensor(h), paddle.to_tensor(w)
    th.stop_gradient = tw.stop_gradient = False
    rows = F.fused_linear_cross_entropy(th, tw, paddle.to_tensor(b),
                                        paddle.to_tensor(lab),
                                        reduction="none")
    (rows * paddle.to_tensor(weight)).sum().backward()
    want = jax.grad(lambda h, w: jnp.sum(_xla_rows(h, w, b, lab) * weight),
                    (0, 1))(h, w)
    np.testing.assert_allclose(np.asarray(rows.value),
                               np.asarray(_xla_rows(h, w, b, lab)), atol=1e-5)
    np.testing.assert_allclose(np.asarray(th.grad.value),
                               np.asarray(want[0]), atol=1e-5)
    np.testing.assert_allclose(np.asarray(tw.grad.value),
                               np.asarray(want[1]), atol=1e-5)
    snap = counters.snapshot()
    assert (snap["fused_xent.per_row"], snap["fused_xent.xla"]) == (1, 1)
    counters.reset()


def test_work_of_a_per_row_call_adds_a_loss_out_and_a_cotangent_in():
    h, w, b, lab, _ = _xent_data(2048)
    mean = fx._work(h, w, b, lab, (1024, 2048))
    rows = fx._work(h, w, b, lab, (1024, 2048), per_row=True)
    for part in ("work", "grad_work"):
        for (role, (flops, moved)), k in zip(rows[part].items(),
                                             (1024, 2048)):
            assert (flops, moved - 4 * k) == mean[part][role]


# ---------------------------------------------------------------------------
# the config's keys
# ---------------------------------------------------------------------------
def test_positions_are_read_from_either_spelling_and_never_dropped():
    from paddle_tpu.nn.functional import rope_inv_freq

    old = CausalLM.from_config(TINY)
    want = np.asarray(rope_inv_freq(16, 1000000))
    for block in old.layers:
        np.testing.assert_allclose(np.asarray(block.mixer.inv_freq), want)
        assert block.mixer.q_norm is None and block.mixer.window is None
    base = {k: v for k, v in TINY.items()
            if k not in ("rope_theta", "rope_scaling")}
    new = CausalLM.from_config(dict(base, rope_parameters={
        "rope_type": "default", "rope_theta": 1000000}))
    np.testing.assert_allclose(np.asarray(new.layers[0].mixer.inv_freq), want)
    # a scaling the older spelling carries is refused by name, a file with
    # no positions at all is refused, one that SAYS it has none is built
    with pytest.raises(NotImplementedError, match="rope_scaling"):
        CausalLM.from_config(dict(TINY, rope_scaling={"type": "linear",
                                                      "factor": 4.0}))
    with pytest.raises(ValueError, match="without positions"):
        CausalLM.from_config(base)
    assert CausalLM.from_config(dict(base, rope_parameters=None)
                                ).layers[0].mixer.inv_freq is None


@pytest.mark.parametrize("key,value", [
    ("d_rel", 16), ("use_sconv", True), ("log_scaling_alpha", 0.1)])
def test_an_attention_variant_no_mixer_builds_is_refused_by_its_key(key,
                                                                    value):
    with pytest.raises(NotImplementedError, match=key):
        CausalLM.from_config(dict(TINY, **{key: value}))
    CausalLM.from_config(dict(TINY, **{key: None}))     # said, and off


def test_a_block_is_built_from_the_keys_it_reads():
    model = CausalLM.from_config(TINY)
    names = {n for n, _ in model.named_parameters()}
    assert {"exit_gate.weight", "exit_gate.bias",
            "layers.0.mixer_out_norm.weight",
            "layers.1.ffn_out_norm.weight"} <= names
    assert model.ut_steps == PASSES and model.exit_entropy_beta == 0.1
    plain = CausalLM.from_config(dict(TINY, total_ut_steps=1,
                                      sandwich_norm=False))
    names = {n for n, _ in plain.named_parameters()}
    assert not [n for n in names if "exit_gate" in n or "out_norm" in n]
    with pytest.raises(KeyError, match="exit_entropy_beta"):
        CausalLM.from_config({k: v for k, v in TINY.items()
                              if k != "exit_entropy_beta"})
    with pytest.raises(ValueError, match="total_ut_steps"):
        CausalLM.from_config(dict(TINY, total_ut_steps=0))


# ---------------------------------------------------------------------------
# spans, counters, the numerics record
# ---------------------------------------------------------------------------
def test_a_traced_step_counts_its_passes_and_carries_their_scopes(
        monkeypatch):
    """Kernels in interpret mode at lane-dense widths: 4 x 2 block
    applications, each keeping its flash launch's output across the
    recomputation, one head call for all four passes."""
    from jax.experimental import pallas as pl

    monkeypatch.setattr(pl, "pallas_call",
                        functools.partial(pl.pallas_call, interpret=True))
    monkeypatch.setattr(bringup, "pallas_enabled", lambda: True)
    cfg = dict(TINY, hidden_size=256, num_attention_heads=2,
               num_key_value_heads=2, head_dim=128, intermediate_size=128)
    ids, labels = _batch(2, b=1, s=256)
    counters.reset()
    try:
        _, step = _train_step(cfg, recompute=True, level="O1")
        snap_built = counters.snapshot()
        batch = [paddle.to_tensor(ids), paddle.to_tensor(labels)]
        text = step.lower(*batch).as_text(debug_info=True)
        snap = counters.snapshot()
        work = counters.step_work("train_step")
    finally:
        counters.reset()
    assert snap_built == {"causal_lm.ut_steps": PASSES}
    assert snap["causal_lm.block_applications"] == PASSES * LAYERS
    assert snap["flash_attention.pallas"] == PASSES * LAYERS
    assert snap["flash_attention.kept_across_recompute"] == PASSES * LAYERS
    assert "flash_attention.xla" not in snap
    assert (snap["fused_xent.per_row"], snap["fused_xent.pallas"]) == (1, 1)
    assert work["flash_attention_stream_fwd"]["calls"] == PASSES * LAYERS
    assert work["flash_attention_stream_bwd"]["calls"] == PASSES * LAYERS
    assert work["fused_xent_fwd"]["calls"] == 1
    # K = 4 passes x 256 tokens on 512 columns at hidden 256
    assert work["fused_xent_fwd"]["flops"] == 2.0 * 1024 * 256 * 512
    for t in range(1, PASSES + 1):
        assert f"ut_step{t}" in text
    assert f"ut_step{PASSES + 1}" not in text and "ut_exit_loss" in text


def test_check_nan_inf_keys_a_looped_models_rows_by_pass():
    flags.set_flags({"check_nan_inf": True})
    try:
        _, step = _train_step(TINY, recompute=True)
        ids, labels = _batch(4)
        step(paddle.to_tensor(ids), paddle.to_tensor(labels))
        record = step.numerics()
    finally:
        flags.set_flags({"check_nan_inf": False})
    keys = list(record)
    for t in range(1, PASSES + 1):
        for n in range(LAYERS):
            assert f"layers.{n}@ut{t}" in keys
            assert f"layers.{n}.mixer@ut{t}" in keys
            assert f"layers.{n}.ffn_out_norm@ut{t}" in keys
        assert f"final_norm@ut{t}" in keys
    assert not [k for k in keys if "#" in k]        # no key written twice
    assert "embed" in keys and "exit_gate" in keys  # outside every pass
    # forward in execution order; a parameter's gradient where the
    # backward reaches its FIRST use, after every later pass's rows
    assert keys.index("layers.1@ut1") < keys.index("layers.0@ut2") \
        < keys.index("final_norm@ut4") < keys.index("loss")
    assert record["layers.0.mixer.q_proj.weight"]["pass"] == "backward"
    assert record.first_nonfinite is None
    assert all(v["nonfinite"] == 0 for v in record.values())


# ---------------------------------------------------------------------------
# one pass: the parent's program
# ---------------------------------------------------------------------------
def _kanana():
    with open(os.path.join(CONFIGS, "kanana-2-30b-a3b.json")) as f:
        cfg = json.load(f)
    cfg = {k: v for k, v in cfg.items() if k not in (
        "published", "program", "assumed", "departs", "reduced")}
    cfg.update(hidden_size=32, intermediate_size=48, moe_intermediate_size=16,
               kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=8,
               qk_head_dim=16, head_dim=8, v_head_dim=8,
               num_attention_heads=4, num_key_value_heads=4, vocab_size=256,
               n_routed_experts=32, experts_held=8, num_hidden_layers=3)
    return cfg


#: sha256 of the lowered text of one jitted step of a tiny one-pass
#: ``deepseek_v3`` model (below) on this PR's parent commit b4719d2
#: (``PYTHONPATH=. python tests/test_looped_lm.py`` prints it).
#: ``tests/test_step_numerics.py`` pins the Kimi, Mellum, Nemotron and
#: BERT steps the same way: with this one, all five configurations of the
#: benchmark lower to the parent's text.
KANANA_STEP_TEXT = \
    "ffd1d17077f4d3c4ef1d683495231255f5c2b88ebcf19edb66ecc51650f63cbd"


def kanana_digest():
    paddle.seed(5)
    model = CausalLM.from_config(_kanana(), recompute=True)
    opt = optimizer.AdamW(learning_rate=1e-3, parameters=model.parameters())

    def loss_fn(m, i, l):
        with amp.auto_cast(level="O1", dtype="bfloat16"):
            return m.loss(i, l, return_routing=True)

    ids, labels = _batch(0, s=64, vocab=256)
    step = TrainStep(model, loss_fn, opt)
    text = step.lower(paddle.to_tensor(ids), paddle.to_tensor(labels))
    return hashlib.sha256(text.as_text().encode()).hexdigest()


def test_a_one_pass_model_lowers_to_the_parents_text():
    counters.reset()
    assert kanana_digest() == KANANA_STEP_TEXT
    # and counts nothing of the loop
    assert not [k for k in counters.snapshot() if k.startswith("causal_lm")]
    counters.reset()


if __name__ == "__main__":
    print(kanana_digest())
