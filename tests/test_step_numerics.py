"""``FLAGS_check_nan_inf`` inside the compiled ``jit.TrainStep``
(``framework/nan_inf.py``): off it is absent from the traced step; on it
names the layer and the pass in which a value first stops being finite,
through ``recompute``, ``jax.checkpoint`` and beside ``lax.switch``."""
import hashlib
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import amp, nn, optimizer
from paddle_tpu.framework import flags
from paddle_tpu.jit import TrainStep

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIGS = os.path.join(REPO, "benchmarks", "configs")


def _config(name):
    with open(os.path.join(CONFIGS, name)) as f:
        cfg = json.load(f)
    return {k: v for k, v in cfg.items() if k not in (
        "published", "program", "assumed", "departs", "reduced")}


def _kimi():
    """kda, kda, mla; dense, moe, moe: at 2 x 128 tokens the expert
    layers' ladder has two sorted rungs (256 and 512 rows)."""
    return {
        "hidden_size": 64, "num_attention_heads": 4, "qk_nope_head_dim": 16,
        "qk_rope_head_dim": 8, "v_head_dim": 16, "kv_lora_rank": 32,
        "q_lora_rank": None, "mla_use_nope": True, "rms_norm_eps": 1e-5,
        "linear_attn_config": {"kda_layers": [1, 2], "full_attn_layers": [3],
                               "num_heads": 4, "head_dim": 16,
                               "short_conv_kernel_size": 4},
        "first_k_dense_replace": 1, "intermediate_size": 96,
        "hidden_act": "silu", "moe_intermediate_size": 32,
        "num_experts": 256, "experts_held": 8, "num_experts_per_token": 2,
        "num_shared_experts": 1, "moe_renormalize": True,
        "routed_scaling_factor": 2.446,
        "moe_router_activation_func": "sigmoid", "num_hidden_layers": 3,
        "vocab_size": 256, "tie_word_embeddings": False}


def _mellum():
    """gqa (three windowed layers to one full), softmax-routed experts."""
    cfg = _config("mellum2-12b-a2.5b.json")
    cfg.update(hidden_size=64, head_dim=16, num_attention_heads=4,
               num_key_value_heads=2, moe_intermediate_size=32,
               num_experts=16, experts_held=4, num_experts_per_tok=4,
               vocab_size=256, sliding_window=8)
    return cfg


def _nemotron():
    """mamba2, plain experts, gqa without positions: blocks of ONE
    sublayer."""
    cfg = _config("nemotron-3-nano-30b-a3b.json")
    cfg.update(hidden_size=32, head_dim=8, num_attention_heads=4,
               num_key_value_heads=2, mamba_num_heads=4, mamba_head_dim=8,
               ssm_state_size=16, n_groups=2, moe_intermediate_size=16,
               moe_shared_expert_intermediate_size=24, n_routed_experts=32,
               experts_held=8, vocab_size=256, num_hidden_layers=6,
               hybrid_override_pattern="MEMEM*")
    return cfg


def _causal_lm_step(cfg, seq=64):
    """A tiny cell of ``benchmarks/drivers/causal_lm_step.py``: block
    recomputation, bfloat16 autocast, AdamW, the routing beside the
    loss."""
    from paddle_tpu.models.causal_lm import CausalLM

    paddle.seed(5)
    model = CausalLM.from_config(cfg, recompute=True)
    ids = np.random.default_rng(0).integers(0, 256, (2, seq)).astype("int32")
    labels = np.full_like(ids, -100)
    labels[:, :-1] = ids[:, 1:]

    def loss_fn(m, i, l):
        with amp.auto_cast(level="O1", dtype="bfloat16"):
            return m.loss(i, l, return_routing=True)

    return model, loss_fn, (ids, labels)


def _bert_step():
    from paddle_tpu.models.bert import BertConfig, BertForPretraining

    paddle.seed(5)
    model = BertForPretraining(BertConfig(
        vocab_size=512, hidden_size=64, num_hidden_layers=2,
        num_attention_heads=2, intermediate_size=128,
        max_position_embeddings=64, hidden_dropout_prob=0.0,
        attention_probs_dropout_prob=0.0))
    model.eval()
    rng = np.random.default_rng(0)
    ids = rng.integers(0, 512, (2, 64)).astype("int32")
    mlm = np.where(rng.random((2, 64)) < 0.15, ids, -100).astype("int32")
    batch = (ids, np.zeros((2, 64), "int32"), mlm,
             rng.integers(0, 2, (2,)).astype("int32"))

    def loss_fn(m, i, tt, l, nsp):
        with amp.auto_cast(level="O1", dtype="bfloat16"):
            return m.loss(i, tt, l, nsp)

    return model, loss_fn, batch


BUILDERS = {"kimi": lambda: _causal_lm_step(_kimi(), seq=128),
            "mellum": lambda: _causal_lm_step(_mellum()),
            "nemotron": lambda: _causal_lm_step(_nemotron()),
            "bert": _bert_step}


def _step(build):
    model, loss_fn, batch = build()
    opt = optimizer.AdamW(learning_rate=1e-3, parameters=model.parameters())
    return (TrainStep(model, loss_fn, opt),
            [paddle.to_tensor(a) for a in batch])


def lowered_digest(name):
    step, batch = _step(BUILDERS[name])
    return hashlib.sha256(step.lower(*batch).as_text().encode()).hexdigest()


#: sha256 of each builder's lowered step text on PR 37's parent commit
#: 3f20322 (``PYTHONPATH=. python tests/test_step_numerics.py`` prints
#: them). ``kimi`` was re-pinned ON PURPOSE by PR 38, which changed the
#: KDA chunk formulas' arithmetic (``kda._unit_lower_inverse`` solves by
#: 16 x 16 blocks; before: 19df3056...) and by PR 43 (``kda_mix`` on the
#: flat (B, T, H * D) layout through ``ops/pallas/kda_stages.py``'s
#: formulas, see ``tests/test_kda.py``'s pin; before: 8600284c...); both
#: touched nothing the other three builders run: their digests are PR
#: 37's, byte for byte, which is the proof that no other cell's program
#: changed.
PARENT_TEXT = {
    "bert": "cc586cdb4418fd36d06f83fe638f348b7835e82c2ae1a579a405c563e55611bf",
    "kimi": "580eec3e589fcdf94d2891c7b497ac80f34b1d0e2672328b7feb1a42106a6345",
    "mellum":
        "9b230a9ff398b840b30a848609d18c6a5b923306d689e07f1df8d1b46c780912",
    "nemotron":
        "ccd42afc94cbe3483832bc4cf26cbb03d55302b8e2ad0d6c63a2fd7366d61866"}


@pytest.fixture
def flag_on():
    flags.set_flags({"check_nan_inf": True})
    yield
    flags.set_flags({"check_nan_inf": False})


def _run(build, plant=None):
    """One step of a builder's model with the flag set; ``plant`` edits
    the model first. Returns (step, what the call returned)."""
    flags.set_flags({"check_nan_inf": True})
    try:
        model, loss_fn, batch = build()
        if plant:
            plant(model)
        opt = optimizer.AdamW(learning_rate=1e-3,
                              parameters=model.parameters())
        step = TrainStep(model, loss_fn, opt)
        return step, step(*[paddle.to_tensor(a) for a in batch])
    finally:
        flags.set_flags({"check_nan_inf": False})


# -- (a) off means absent ---------------------------------------------------
@pytest.mark.parametrize("name", sorted(PARENT_TEXT))
def test_flag_off_the_step_lowers_to_the_parents_text(name):
    """Byte for byte, before the flag was ever set and after a step was
    built with it on; and such a step has no record."""
    assert lowered_digest(name) == PARENT_TEXT[name]
    if name in ("bert", "kimi"):
        flags.set_flags({"check_nan_inf": True})
        try:
            assert lowered_digest(name) != PARENT_TEXT[name]
        finally:
            flags.set_flags({"check_nan_inf": False})
        assert lowered_digest(name) == PARENT_TEXT[name]
    step, batch = _step(BUILDERS[name])
    assert step.numerics() is None


def test_flag_off_a_step_that_ran_has_no_record():
    step, batch = _step(BUILDERS["bert"])
    loss = step(*batch)
    assert np.isfinite(float(loss)) and step.numerics() is None
    assert step.numerics(check=True) is None


# -- what the record holds, on a sound step ---------------------------------
def test_the_record_of_a_sound_kimi_step():
    """Forward keys are the parameter-name paths WITH their container
    index, in execution order, through ``recompute`` and the mixer's
    inner ``jax.checkpoint``s; the probes under ``<layer>/<name>``; the
    loss; then every parameter's gradient, deepest layer first."""
    from paddle_tpu.nn.moe import _row_ladder

    assert _row_ladder(2 * 128 * 2, 8, 256) == (256, 512)
    step, (loss, routing) = _run(BUILDERS["kimi"])
    rec = step.numerics()
    assert rec.first_nonfinite is None and rec.first_pass is None
    keys = list(rec)
    forward = [k for k in keys if rec[k]["pass"] == "forward"]
    assert forward[0] == "embed" and forward[-1] == "loss"
    assert rec["loss"]["absmax"] == pytest.approx(float(loss), rel=1e-6)
    # a block's sublayers before the block, its second output after it
    want = ["layers.1.input_norm", "layers.1.mixer.q_proj",
            "layers.1.mixer/kda_q", "layers.1.mixer/kda_states",
            "layers.1.mixer/kda_o", "layers.1.mixer.o_proj",
            "layers.1.mixer", "layers.1.post_norm",
            "layers.1.ffn/renorm_denominator", "layers.1.ffn/hidden",
            "layers.1.ffn/routed", "layers.1.ffn.shared.down_proj",
            "layers.1.ffn.shared", "layers.1.ffn", "layers.1", "layers.1:1",
            "layers.2.input_norm"]
    assert [k for k in forward if k in want] == want
    # the states the second chunk starts from are not zero
    assert rec["layers.1.mixer/kda_states"]["absmax"] > 0
    denominator = rec["layers.1.ffn/renorm_denominator"]
    assert 0 < denominator["absmin"] <= denominator["absmax"] < 2.0
    assert "absmin" not in rec["layers.1.ffn/hidden"]
    # backward: every trainable leaf once, the root's head first, the
    # embedding last, a block's leaves together
    backward = [k for k in keys if rec[k]["pass"] == "backward"]
    names = [n for n, _ in step.model.named_parameters()]
    assert sorted(k for k in backward if "/" not in k) == sorted(names)
    assert backward[0] == "head" and backward[-1] == "embed.weight"
    blocks = [k.split(".")[1] for k in backward if k.startswith("layers.")]
    assert blocks == sorted(blocks, reverse=True)
    # the five cotangents that leave the hand-written backward, after
    # the cotangent that enters it
    taps = [k for k in backward if k.startswith("layers.1.mixer/")]
    assert taps == ["layers.1.mixer/kda_" + n + ".grad"
                    for n in ("o", "beta", "g", "v", "k", "q")]
    assert all(rec[k]["absmax"] > 0 for k in taps)
    # a handed-on, never called layer's leaf goes with the layer round it
    assert backward.index("layers.2.ffn.router.weight") \
        < backward.index("layers.2.post_norm.weight") \
        < backward.index("layers.1.ffn.experts_up")
    assert tuple(routing.shape) == (3, 2)


# -- (b) a planted forward fault --------------------------------------------
def test_a_forward_fault_is_named_with_its_indexed_path():
    def plant(model):
        w = model.layers[1].mixer.o_proj.weight
        w._value = w._value.at[3, 5].set(jnp.inf)

    step, (loss, _) = _run(BUILDERS["kimi"], plant)
    rec = step.numerics()
    assert not np.isfinite(float(loss))
    assert (rec.first_nonfinite, rec.first_pass) == \
        ("layers.1.mixer.o_proj", "forward")
    keys = list(rec)
    before = keys[:keys.index("layers.1.mixer.o_proj")]
    assert "layers.1.mixer/kda_o" in before and "layers.0" in before
    assert all(rec[k]["nonfinite"] == 0 for k in before)
    assert rec["layers.1.mixer"]["nonfinite"] > 0
    assert rec["loss"]["nonfinite"] == 1
    # (i) the debugging mode raises, naming key and pass
    with pytest.raises(FloatingPointError,
                       match=r"'layers\.1\.mixer\.o_proj'.*forward pass"):
        step.numerics(check=True)


def test_a_forward_fault_in_bert_is_named():
    def plant(model):
        w = model.bert.encoder.layers[1].linear1.weight
        w._value = w._value.at[0, 0].set(jnp.nan)

    step, _ = _run(BUILDERS["bert"], plant)
    rec = step.numerics()
    assert (rec.first_nonfinite, rec.first_pass) == \
        ("bert.encoder.layers.1.linear1", "forward")
    assert rec["bert.encoder.layers.0"]["nonfinite"] == 0


# -- (c) a planted backward-only fault --------------------------------------
@jax.custom_vjp
def _poisoned(x):
    return x


_poisoned.defvjp(lambda x: (x, None),
                 lambda _, ct: (jnp.full_like(ct, jnp.nan),))


class _Block(nn.Layer):
    def __init__(self, width, poisoned=False):
        super().__init__()
        self.proj = nn.Linear(width, width)
        self.poisoned = poisoned

    def forward(self, x):
        from paddle_tpu.framework.tensor import Tensor

        y = self.proj(x)
        if self.poisoned:       # finite forward, NaN cotangent
            y = Tensor(_poisoned(y._value))
        return x + paddle.tanh(y)


class _Stack(nn.Layer):
    def __init__(self, poisoned, recompute=False, width=8, depth=4):
        super().__init__()
        self.layers = nn.LayerList(
            [_Block(width, n == poisoned) for n in range(depth)])
        self.recompute = recompute

    def forward(self, x):
        from paddle_tpu.optimizer.meta import recompute

        for block in self.layers:
            x = recompute(block, x) if self.recompute else block(x)
        return x


@pytest.mark.parametrize("through_recompute", [False, True])
def test_a_backward_only_fault_names_the_deepest_layer(through_recompute):
    def build():
        paddle.seed(3)
        model = _Stack(poisoned=2, recompute=through_recompute)
        x = np.random.default_rng(1).normal(size=(4, 8)).astype("float32")
        return model, (lambda m, x: paddle.mean(m(x) ** 2)), (x,)

    step, loss = _run(build)
    rec = step.numerics()
    assert np.isfinite(float(loss))
    forward = [k for k in rec if rec[k]["pass"] == "forward"]
    assert forward == [f"layers.{n}{s}" for n in range(4)
                       for s in (".proj", "")] + ["loss"]
    assert all(rec[k]["nonfinite"] == 0 for k in forward)
    # block 3 is behind the fault and sound; block 2's own leaves are the
    # deepest non-finite ones; blocks 1 and 0 inherit the cotangent
    assert rec.first_nonfinite.startswith("layers.2.proj.")
    assert rec.first_pass == "backward"
    bad = {k for k in rec if rec[k]["nonfinite"]}
    assert bad == {f"layers.{n}.proj.{leaf}" for n in (0, 1, 2)
                   for leaf in ("weight", "bias")}
    assert rec["layers.3.proj.weight"]["absmax"] > 0
    with pytest.raises(FloatingPointError, match="backward pass"):
        step.numerics(check=True)


# -- (d) through checkpoint, recompute and beside a switch -------------------
class _Probed(nn.Layer):
    """A probe inside ``jax.checkpoint``, two inside the branches of a
    ``lax.switch`` and one next to a plain ``lax.switch``."""

    def __init__(self):
        super().__init__()
        self.scale = self.create_parameter(
            [4], default_initializer=nn.initializer.Constant(2.0))

    def forward(self, x):
        from paddle_tpu.framework import nan_inf
        from paddle_tpu.framework.tensor import Tensor

        s, v = self.scale._value, x._value

        @nan_inf.checkpoint
        def inside(v, s):
            return nan_inf.probe("inside", v * s, grad=True) + 1.0

        v = inside(v, s)
        v = nan_inf.switch(
            jnp.int32(1),
            [lambda a: nan_inf.probe("branch", a * 3.0),
             lambda a: nan_inf.probe("branch", a * 5.0)], v)
        v = nan_inf.probe("beside", jax.lax.switch(
            jnp.int32(0), [lambda a: a - 1.0, lambda a: a + 1.0], v))
        return Tensor(v)


class _Holder(nn.Layer):
    def __init__(self, recompute):
        super().__init__()
        self.probed = _Probed()
        self.recompute = recompute

    def forward(self, x):
        from paddle_tpu.optimizer.meta import recompute

        return recompute(self.probed, x) if self.recompute \
            else self.probed(x)


@pytest.mark.parametrize("through_recompute", [False, True])
def test_records_leave_checkpoint_recompute_and_switch(through_recompute):
    x = np.array([[1.0, -2.0, 0.5, 4.0]], "float32")

    def build():
        return (_Holder(through_recompute),
                (lambda m, x: paddle.sum(m(x))), (x,))

    step, loss = _run(build)
    rec = step.numerics()
    # ((x * 2 + 1) * 5) - 1
    assert float(loss) == pytest.approx(float(((x * 2 + 1) * 5 - 1).sum()))
    assert list(rec) == [
        "probed/inside", "probed/branch", "probed/beside", "probed", "loss",
        "probed.scale", "probed/inside.grad"]
    assert rec["probed/inside"]["absmax"] == 8.0
    assert rec["probed/branch"]["absmax"] == 45.0       # the branch that ran
    assert rec["probed/beside"]["absmax"] == 44.0
    assert rec["probed/inside.grad"] == {
        "pass": "backward", "nonfinite": 0, "absmax": 5.0}
    assert rec["probed.scale"]["absmax"] == 20.0        # 5 * |x|
    assert rec.first_nonfinite is None


def test_switch_branches_have_to_record_alike(flag_on):
    from paddle_tpu.framework import nan_inf

    class Uneven(nn.Layer):
        def __init__(self):
            super().__init__()
            self.inner = nn.Linear(4, 4)

        def forward(self, x):
            out = nan_inf.switch(
                jnp.int32(0), [lambda a: nan_inf.probe("one", a),
                               lambda a: nan_inf.probe("other", a)], x._value)
            return self.inner(paddle.to_tensor(out))

    model = Uneven()
    opt = optimizer.SGD(learning_rate=0.1, parameters=model.parameters())
    step = TrainStep(model, lambda m, x: paddle.sum(m(x)), opt)
    with pytest.raises(ValueError, match="different records"):
        step(paddle.to_tensor(np.ones((2, 4), "float32")))


# -- (e) a probe with the flag off ------------------------------------------
def test_probe_off_is_the_identity_on_the_jaxpr():
    from paddle_tpu.framework import nan_inf

    def plain(x):
        return jnp.sum(jnp.tanh(x) * 2.0)

    def probed(x):
        y = nan_inf.probe("t", jnp.tanh(x), grad=True, smallest=True)
        nan_inf.probe_row("r", None)
        return jnp.sum(y * 2.0)

    x = jnp.arange(4.0)
    assert nan_inf.record is None
    assert nan_inf.probe("t", x) is x
    assert str(jax.make_jaxpr(jax.grad(probed))(x)) == \
        str(jax.make_jaxpr(jax.grad(plain))(x))
    # the carriers are jax's own transforms
    wrapped = nan_inf.checkpoint(plain)
    assert str(jax.make_jaxpr(wrapped)(x)) == \
        str(jax.make_jaxpr(jax.checkpoint(plain))(x))
    branches = [lambda a: a + 1.0, lambda a: a * 2.0]
    assert str(jax.make_jaxpr(
        lambda i, a: nan_inf.switch(i, branches, a))(1, x)) == \
        str(jax.make_jaxpr(
            lambda i, a: jax.lax.switch(i, branches, a))(1, x))


# -- (f) what the step returns, and when the flag is read --------------------
@pytest.mark.parametrize("name", ["bert", "mellum"])
def test_the_step_returns_what_it_returned(name):
    off_step, batch = _step(BUILDERS[name])
    off = off_step(*batch)
    on_step, on = _run(BUILDERS[name])
    structure = jax.tree_util.tree_structure
    assert structure(on) == structure(off) and type(on) is type(off)
    for a, b in zip(jax.tree_util.tree_leaves(on),
                    jax.tree_util.tree_leaves(off)):
        assert a.shape == b.shape and a.dtype == b.dtype
    loss_on = on[0] if isinstance(on, tuple) else on
    loss_off = off[0] if isinstance(off, tuple) else off
    assert float(loss_on) == pytest.approx(float(loss_off), rel=1e-3)
    assert on_step.numerics()["loss"]["absmax"] == \
        pytest.approx(float(loss_on), rel=1e-6)


def test_the_flag_is_read_when_the_step_is_built():
    step, _ = _run(BUILDERS["bert"])
    batch = [paddle.to_tensor(a) for a in BUILDERS["bert"]()[2]]
    assert not flags.get_flag("check_nan_inf")
    compiled = step._compiled
    first = step.numerics()["loss"]["absmax"]
    step(*batch)                # the flag is off now: the same program
    assert step._compiled is compiled
    assert step.numerics()["loss"]["absmax"] < first
    off_step, batch = _step(BUILDERS["bert"])
    off_step(*batch)
    flags.set_flags({"check_nan_inf": True})
    try:
        off_step(*batch)        # built with it off: no record, no retrace
    finally:
        flags.set_flags({"check_nan_inf": False})
    assert off_step.numerics() is None


# -- (g) the eager flag ------------------------------------------------------
def test_the_eager_flag_still_raises_naming_the_op(flag_on):
    x = paddle.to_tensor(np.array([1.0, 0.0], "float32"))
    assert np.isfinite(paddle.exp(x).numpy()).all()
    with pytest.raises(FloatingPointError, match="Operator log"):
        paddle.log(x)
    x.stop_gradient = False
    with pytest.raises(FloatingPointError, match="NaN/Inf"):
        paddle.log(x)


# -- (h) a row's arithmetic --------------------------------------------------
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_a_row_counts_and_ignores_the_nonfinite(dtype):
    from paddle_tpu.framework import nan_inf

    x = jnp.asarray([[1.0, -7.5, np.inf], [np.nan, 0.25, -np.inf]], dtype)
    assert np.asarray(nan_inf.row(x, smallest=True)).tolist() == \
        [3.0, 7.5, 0.25]
    assert np.asarray(nan_inf.row(x)).tolist() == [3.0, 7.5, np.inf]
    assert np.asarray(nan_inf.row(jnp.zeros((0, 3), dtype),
                                  smallest=True)).tolist() == \
        [0.0, 0.0, np.inf]
    assert np.asarray(nan_inf.row(jnp.asarray([np.nan], dtype),
                                  smallest=True)).tolist() == \
        [1.0, 0.0, np.inf]
    big = jnp.asarray([3.0e38, -1.0e-30], dtype)
    n, top, low = np.asarray(nan_inf.row(big, smallest=True))
    assert n == 0 and top == float(big[0]) and low == float(-big[1])


def test_report_orders_forward_before_backward():
    from paddle_tpu.framework import nan_inf

    keys = (("a", "forward", False), ("a/den", "forward", True),
            ("loss", "forward", False), ("b.w", "backward", False),
            ("a.w", "backward", False))
    table = np.array([[0, 1, np.inf], [0, 2, .5], [0, 3, np.inf],
                      [0, 4, np.inf], [7, 5, np.inf]], "float32")
    rec = nan_inf.report(keys, table)
    assert list(rec) == ["a", "a/den", "loss", "b.w", "a.w"]
    assert rec["a/den"] == {"pass": "forward", "nonfinite": 0,
                            "absmax": 2.0, "absmin": 0.5}
    assert (rec.first_nonfinite, rec.first_pass) == ("a.w", "backward")
    table[2, 0] = 1
    rec = nan_inf.report(keys, table)
    assert (rec.first_nonfinite, rec.first_pass) == ("loss", "forward")
    with pytest.raises(FloatingPointError, match="'loss'.*forward"):
        nan_inf.report(keys, table, check=True)


# -- (j) the tool ------------------------------------------------------------
def _tool():
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import find_nonfinite
    finally:
        sys.path.pop(0)
    return find_nonfinite


def test_the_tool_renders_a_recorded_history():
    tool = _tool()

    def rec(hidden, grad_bad=0, den=(4.0, 3.5)):
        return {
            "embed": {"pass": "forward", "nonfinite": 0, "absmax": 0.08},
            "layers.1.ffn/renorm_denominator": {
                "pass": "forward", "nonfinite": 0, "absmax": den[0],
                "absmin": den[1]},
            "layers.1.ffn/hidden": {"pass": "forward", "nonfinite": 0,
                                    "absmax": hidden},
            "loss": {"pass": "forward", "nonfinite": 0, "absmax": 9.5},
            "layers.1.ffn.experts_up": {
                "pass": "backward", "nonfinite": grad_bad,
                "absmax": 1e-4 if not grad_bad else 0.0}}

    history = [(51, rec(1.0)), (52, rec(10.0)), (53, rec(250.0, 12))]
    text = tool.render(history, "layers.1.ffn.experts_up", "backward")
    lines = text.splitlines()
    assert lines[0] == ("step 53: first non-finite value at "
                        "'layers.1.ffn.experts_up', backward pass")
    assert lines[1] == ("non-finite in that step: layers.1.ffn.experts_up "
                        "(backward, 12 elements)")
    assert lines[2] == "non-finite in step 52: nothing"
    assert "| key | pass | 51 | 52 | 53 |" in lines
    assert "| layers.1.ffn/hidden | forward | 1 | 10 | 250 |" in lines
    assert ("| layers.1.ffn/renorm_denominator | forward | 4 ~3.5 | 4 ~3.5 "
            "| 4 ~3.5 |") in lines
    assert ("| layers.1.ffn.experts_up | backward | 0.0001 | 0.0001 | 0! |"
            in lines)
    # a key that is no probe and did not move is left out
    assert not [ln for ln in lines if ln.startswith("| embed ")]
    finite = tool.render(history[:2], None, None)
    assert finite.splitlines()[0] == "finite through step 52"
    assert tool.extremes({"embed": 0.08, "layers.1.ffn/hidden": 250.0,
                          "layers.1.ffn/renorm_denominator": 4.0},
                         {"layers.1.ffn/renorm_denominator": 3.5}) == (
        "largest absmax over the run, by probe:\n"
        "| layers.1.ffn/hidden | 250 |\n"
        "| layers.1.ffn/renorm_denominator | 4 | smallest |x| 3.5 |")


def test_the_tool_drives_a_tiny_cell_with_the_flag_on_and_off(
        tmp_path, capsys):
    """The Kimi cell cut to test size, as NEW files in a copy of
    ``benchmarks/``: three steps of its own ``Loop`` each way."""
    import shutil

    from benchmarks import harness

    tool = _tool()
    root = str(tmp_path / "benchmarks")
    shutil.copytree(harness.ROOT, root,
                    ignore=shutil.ignore_patterns("__pycache__"))
    cfg = harness.load_json(os.path.join(
        root, "configs/kimi-linear-48b-a3b.json"))
    cfg.update(name="kimi-tiny", hidden_size=64, intermediate_size=96,
               moe_intermediate_size=32, kv_lora_rank=32,
               qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
               num_attention_heads=4, vocab_size=512, num_experts=4,
               num_experts_per_token=4, num_hidden_layers=2)
    cfg["published"] = dict(cfg["published"], num_experts=16)
    cfg["linear_attn_config"] = dict(
        cfg["linear_attn_config"], num_heads=4, head_dim=16,
        kda_layers=[1], full_attn_layers=[2])
    with open(os.path.join(root, "configs/kimi-tiny.json"), "w") as f:
        json.dump(cfg, f)
    cell = harness.load_json(os.path.join(
        root, "workloads/kimi-linear-48b-a3b.pretrain-seq8k.json"))
    cell.update(name="kimi-tiny.pretrain", config="kimi-tiny")
    cell["traffic"].update(batch=2, seq=64, host_batches=2)
    with open(os.path.join(root, "workloads/kimi-tiny.pretrain.json"),
              "w") as f:
        json.dump(cell, f)
    seed = 2 ** 31 + 37
    on = tool.find("kimi-tiny.pretrain", seed, steps=3, history=2,
                   out=str(tmp_path / "out"), root=root, check_device=False)
    assert not flags.get_flag("check_nan_inf")      # restored
    assert (on["steps"], on["first_nonfinite"]) == (3, None)
    assert [h["step"] for h in on["history"]] == [2, 3]
    assert on["largest_absmax"]["layers.1.ffn/hidden"] > 0
    assert on["smallest_absmin"]["layers.1.ffn/renorm_denominator"] > 0
    off = tool.find("kimi-tiny.pretrain", seed, steps=3, flag=False,
                    root=root, check_device=False)
    assert off["history"] == [] and off["first_nonfinite"] is None
    assert off["losses"] == pytest.approx(on["losses"], rel=1e-3)
    with open(tmp_path / "out" / f"kimi-tiny.pretrain.{seed}.on.json") as f:
        assert json.load(f)["steps"] == 3
    text = capsys.readouterr().out
    assert "finite through step 3" in text
    assert "| layers.0.mixer/kda_g.grad | backward |" in text
    assert "every loss finite through step 3" in text



if __name__ == "__main__":      # the digests of a tree, for PARENT_TEXT
    print(json.dumps({n: lowered_digest(n) for n in sys.argv[1:]
                      or sorted(BUILDERS)}, indent=1))
    sys.exit(0)
