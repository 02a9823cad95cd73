"""The optimizer update has one path (PR 29): the static update ops
against float64 closed forms written here, the fp16-scaler
FoundInfinite skip gating, the ZeRO lamb two-phase trust-ratio chunk
composition, ``Optimizer.step`` equal to ``rule`` leaf by leaf, and a
compiled ``TrainStep`` that holds no optimizer kernel and counts no
``fused_opt`` dispatch with the Pallas gate open — plus the static
expert-parallel MoE leg (``__moe_ep`` stamp, all-to-all counters, cost
accounting, dense parity).
"""
import functools
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
import paddle_tpu.framework.bringup as bringup
from paddle_tpu import nn, optimizer
from paddle_tpu.jit import TrainStep
from paddle_tpu.ops.pallas import counters
from paddle_tpu.static import executor, stepplan
from paddle_tpu.static.kernels import KERNELS

OPS = ("sgd", "momentum", "adam", "lamb")
_OLDS = {"ParamOut": "Param", "VelocityOut": "Velocity",
         "Moment1Out": "Moment1", "Moment2Out": "Moment2",
         "Beta1PowOut": "Beta1Pow", "Beta2PowOut": "Beta2Pow"}


@pytest.fixture(autouse=True)
def _reset():
    counters.reset()
    yield
    counters.reset()
    # the MoE tests below switch to static mode; leave the worker as
    # the next file expects it
    paddle.disable_static()


@pytest.fixture
def open_gate(monkeypatch):
    """Every Pallas family's backend gate open, kernels interpreted: a
    dispatch that could put the update on a kernel would."""
    from jax.experimental import pallas as pl

    monkeypatch.setattr(pl, "pallas_call",
                        functools.partial(pl.pallas_call, interpret=True))
    monkeypatch.setattr(bringup, "pallas_enabled", lambda: True)


def _ins(op, n, seed=0, found=None):
    rng = np.random.RandomState(seed)
    ins = {"Param": [jnp.asarray(rng.randn(n), jnp.float32)],
           "Grad": [jnp.asarray(rng.randn(n), jnp.float32)],
           "LearningRate": [jnp.asarray([0.01], jnp.float32)]}
    if op == "momentum":
        ins["Velocity"] = [jnp.asarray(rng.randn(n), jnp.float32)]
    elif op in ("adam", "lamb"):
        ins["Moment1"] = [jnp.asarray(rng.randn(n) * 0.1, jnp.float32)]
        ins["Moment2"] = [jnp.asarray(rng.rand(n) * 0.1, jnp.float32)]
        ins["Beta1Pow"] = [jnp.asarray([0.9], jnp.float32)]
        ins["Beta2Pow"] = [jnp.asarray([0.999], jnp.float32)]
    if found is not None:
        ins["FoundInfinite"] = [jnp.asarray([found], jnp.float32)]
    return ins


# ---------------------------------------------------------------------------
# the static update ops against float64 closed forms
# ---------------------------------------------------------------------------


def _closed_form(op, ins, attrs):
    """The reference operators' update rules (operators/optimizers/
    {sgd,momentum,adam,lamb}_op.h) in float64 NumPy."""
    x = {k: np.asarray(v[0], np.float64) for k, v in ins.items()}
    p, g, lr = x["Param"], x["Grad"], x["LearningRate"]
    if op == "sgd":
        return {"ParamOut": p - lr * g}
    if op == "momentum":
        mu = attrs.get("mu", 0.9)
        v = mu * x["Velocity"] + g
        step = g + mu * v if attrs.get("use_nesterov", False) else v
        return {"ParamOut": p - lr * step, "VelocityOut": v}
    b1, b2 = attrs.get("beta1", 0.9), attrs.get("beta2", 0.999)
    b1p, b2p = x["Beta1Pow"] * b1, x["Beta2Pow"] * b2
    m = b1 * x["Moment1"] + (1 - b1) * g
    v = b2 * x["Moment2"] + (1 - b2) * g * g
    out = {"Moment1Out": m, "Moment2Out": v, "Beta1PowOut": b1p,
           "Beta2PowOut": b2p}
    if op == "adam":
        eps = attrs.get("epsilon", 1e-8)
        out["ParamOut"] = p - lr * np.sqrt(1 - b2p) / (1 - b1p) * m / (
            np.sqrt(v) + eps)
        return out
    eps, wd = attrs.get("epsilon", 1e-6), attrs.get("weight_decay", 0.01)
    r = (m / (1 - b1p)) / (np.sqrt(v / (1 - b2p)) + eps) + wd * p
    out["ParamOut"] = p - lr * np.linalg.norm(p) / np.linalg.norm(r) * r
    return out


def _assert_matches_closed_form(op, ins, attrs):
    out = KERNELS[op](ins, attrs, None)
    ref = _closed_form(op, ins, attrs)
    assert sorted(out) == sorted(ref)
    for slot, want in ref.items():
        got = out[slot][0]
        assert got.dtype == jnp.float32
        np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5,
                                   atol=1e-6, err_msg=f"{op}:{slot}")


@pytest.mark.parametrize("op", OPS)
@pytest.mark.parametrize("n", [1024, 1337])  # a whole tile and a ragged one
def test_static_update_matches_the_closed_form(open_gate, op, n):
    _assert_matches_closed_form(op, _ins(op, n),
                                {"mu": 0.9, "use_nesterov": False})
    assert not counters.snapshot()


def test_nesterov_momentum_matches_the_closed_form(open_gate):
    _assert_matches_closed_form("momentum", _ins("momentum", 2048),
                                {"mu": 0.85, "use_nesterov": True})


# ---------------------------------------------------------------------------
# FoundInfinite skip gating (GradScaler semantics inside the program)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("op", OPS)
def test_found_infinite_skips_step_bitwise(op):
    ins = _ins(op, 1024, found=1.0)
    out = KERNELS[op](ins, {}, None)
    for slot, src in _OLDS.items():
        if slot in out:
            assert np.array_equal(
                np.asarray(out[slot][0]).reshape(-1),
                np.asarray(ins[src][0]).reshape(-1)), f"{op}:{slot}"


def test_found_infinite_zero_still_steps():
    ins = _ins("adam", 1024, found=0.0)
    out = KERNELS["adam"](ins, {}, None)
    assert not np.array_equal(np.asarray(out["ParamOut"][0]),
                              np.asarray(ins["Param"][0]))


# ---------------------------------------------------------------------------
# ZeRO chunk composition: lamb's two-phase trust plan across shards
# ---------------------------------------------------------------------------


def _ref_lamb_per_param(ins, attrs, param_elems):
    """Per-param lamb reference: the unsharded op applied to each
    param's own segment of the concat buffer (trust ratios are
    per-param, not per-buffer)."""
    outs = {"ParamOut": [], "Moment1Out": [], "Moment2Out": []}
    off = 0
    for e in param_elems:
        seg = {k: [v[0][off:off + e]] for k, v in ins.items()
               if k in ("Param", "Grad", "Moment1", "Moment2")}
        seg.update({k: ins[k] for k in ("Beta1Pow", "Beta2Pow",
                                        "LearningRate")})
        r = KERNELS["lamb"](seg, attrs, None)
        for slot in outs:
            outs[slot].append(np.asarray(r[slot][0]))
        off += e
    return {k: np.concatenate(v) for k, v in outs.items()}


def test_zero_lamb_chunk_matches_per_param_reference():
    from jax.sharding import Mesh, PartitionSpec as P
    try:
        from jax.experimental.shard_map import shard_map
    except ImportError:
        from jax.shard_map import shard_map

    n, g = 2048, 2
    c = n // g
    param_elems = (1536, 512)  # param boundary crosses a chunk edge
    attrs = {"beta1": 0.9, "beta2": 0.999, "epsilon": 1e-6,
             "weight_decay": 0.01}
    ins = _ins("lamb", n, seed=5)
    mesh = Mesh(np.array(jax.devices()[:g]), ("dp",))

    def step(p, gg, m, v):
        pos = jax.lax.axis_index("dp") * c
        chunk = {"Param": [p], "Grad": [gg], "Moment1": [m],
                 "Moment2": [v], "Beta1Pow": ins["Beta1Pow"],
                 "Beta2Pow": ins["Beta2Pow"],
                 "LearningRate": ins["LearningRate"]}
        outs = stepplan.chunk_update("lamb", chunk, attrs, None, axis="dp",
                                     param_elems=param_elems,
                                     position=pos)
        return (outs["ParamOut"][0], outs["Moment1Out"][0],
                outs["Moment2Out"][0])

    f = shard_map(step, mesh=mesh, in_specs=(P("dp"),) * 4,
                  out_specs=(P("dp"),) * 3, check_rep=False)
    p2, m2, v2 = f(ins["Param"][0], ins["Grad"][0], ins["Moment1"][0],
                   ins["Moment2"][0])
    ref = _ref_lamb_per_param(ins, attrs, param_elems)
    # tolerance, not bitwise: the sq-norm sums reassociate across chunks
    np.testing.assert_allclose(np.asarray(p2), ref["ParamOut"],
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(m2), ref["Moment1Out"],
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(v2), ref["Moment2Out"],
                               rtol=1e-5, atol=1e-6)


def test_chunk_update_non_lamb_is_the_plain_op():
    ins = _ins("adam", 1024)
    out = stepplan.chunk_update("adam", ins, {}, None, axis=None,
                                param_elems=(1024,), position=0)
    ref = KERNELS["adam"](ins, {}, None)
    np.testing.assert_allclose(np.asarray(out["ParamOut"][0]),
                               np.asarray(ref["ParamOut"][0]),
                               rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# the dygraph update: ``rule`` on every leaf, whatever the backend's gate
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("make", [
    lambda ps: optimizer.SGD(0.05, parameters=ps),
    lambda ps: optimizer.Momentum(0.05, 0.85, parameters=ps,
                                  use_nesterov=True),
    lambda ps: optimizer.Adam(0.01, parameters=ps),
    lambda ps: optimizer.AdamW(0.01, parameters=ps, weight_decay=0.1),
    lambda ps: optimizer.Lamb(0.01, parameters=ps),
], ids=["SGD", "Momentum", "Adam", "AdamW", "Lamb"])
def test_optimizer_step_is_the_rule_leaf_by_leaf(open_gate, make):
    paddle.seed(3)
    net = nn.Linear(64, 32)           # a 2048-element leaf and a 32-element one
    opt = make(net.parameters())
    x = paddle.to_tensor(np.random.RandomState(3).randn(8, 64).astype(
        "float32"))
    slots = {id(p): opt.init_slot(p.value) for p in net.parameters()}
    for t in (1, 2):
        opt.clear_grad()
        (net(x) ** 2).mean().backward()

        @jax.jit
        def leaf(g, p, s, lr, t):
            p2, s2 = opt.rule(g, p, s, lr, t)
            if opt.DECOUPLED_WD:      # AdamW: decay beside the rule
                p2 = p2 - lr * opt._l2_coeff * p
            return p2, s2

        want = {}
        for p in net.parameters():
            want[id(p)], slots[id(p)] = leaf(
                p.grad.value, p.value, slots[id(p)],
                jnp.asarray(opt.get_lr(), jnp.float32),
                jnp.asarray(t, jnp.int32))
        opt.step()
        for p in net.parameters():
            assert np.array_equal(np.asarray(p.value),
                                  np.asarray(want[id(p)])), (t, p.name)
    assert not [k for k in counters.snapshot() if k.startswith("fused_opt")]


def _bert_step():
    from paddle_tpu.models.bert import BertConfig, BertForPretraining

    model = BertForPretraining(BertConfig(
        vocab_size=512, hidden_size=128, num_hidden_layers=2,
        num_attention_heads=2, intermediate_size=256,
        max_position_embeddings=128, hidden_dropout_prob=0.0,
        attention_probs_dropout_prob=0.0))
    model.eval()
    rng = np.random.default_rng(0)
    ids = rng.integers(0, 512, (2, 128)).astype("int32")
    mlm = np.where(rng.random((2, 128)) < 0.15, ids, -100).astype("int32")
    batch = (ids, np.zeros((2, 128), "int32"), mlm,
             rng.integers(0, 2, (2,)).astype("int32"))
    return model, (lambda m, i, tt, l, nsp: m.loss(i, tt, l, nsp)), batch


def _causal_lm_step():
    from paddle_tpu.models.causal_lm import CausalLM

    model = CausalLM.from_config({
        "hidden_size": 64, "num_attention_heads": 4,
        "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
        "kv_lora_rank": 32, "q_lora_rank": None, "mla_use_nope": True,
        "rms_norm_eps": 1e-5,
        "linear_attn_config": {"kda_layers": [1], "full_attn_layers": [2],
                               "num_heads": 4, "head_dim": 16,
                               "short_conv_kernel_size": 4},
        "first_k_dense_replace": 1, "intermediate_size": 96,
        "hidden_act": "silu", "moe_intermediate_size": 32,
        "num_experts": 4, "num_experts_per_token": 2,
        "num_shared_experts": 1, "moe_renormalize": True,
        "routed_scaling_factor": 2.446,
        "moe_router_activation_func": "sigmoid", "num_hidden_layers": 2,
        "vocab_size": 256, "tie_word_embeddings": False})
    ids = np.random.default_rng(0).integers(0, 256, (2, 64)).astype("int32")
    labels = np.full_like(ids, -100)
    labels[:, :-1] = ids[:, 1:]
    return model, (lambda m, i, l: m.loss(i, l)), (ids, labels)


@pytest.mark.parametrize("build", [_bert_step, _causal_lm_step],
                         ids=["bert", "causal_lm"])
def test_compiled_step_holds_no_optimizer_kernel(open_gate, build):
    """The update of every leaf is the reference rule inside the step's
    one XLA program, behind a barrier on its gradient: no custom call
    under the ``optimizer`` scope, no ``fused_opt`` dispatch counted, no
    optimizer role in the ledger — with every family's gate open."""
    model, loss_fn, batch = build()
    leaves = [p for p in model.parameters() if p.value.size >= 1024]
    assert leaves and all(p.value.dtype == jnp.float32 for p in leaves)
    opt = optimizer.AdamW(learning_rate=1e-4, parameters=model.parameters())
    step = TrainStep(model, loss_fn, opt)
    text = step.lower(*[paddle.to_tensor(a) for a in batch]).as_text(
        debug_info=True)
    prims = set(re.findall(r"jit\(train_step\)/optimizer/(\w+)", text))
    assert {"sqrt", "optimization_barrier"} <= prims
    assert not prims & {"pallas", "pallas_call", "custom_call"}
    for name in ("fused_adam", "fused_sgd", "fused_lamb", "fused_momentum"):
        assert name not in text
    assert not [k for k in counters.snapshot() if k.startswith("fused_opt")]
    assert not [role for role in counters.step_work("train_step")
                if "adam" in role or "sgd" in role]


def test_the_executors_cache_keys_read_no_optimizer_escape(monkeypatch):
    monkeypatch.setenv("PADDLE_FUSED_OPT", "0")
    monkeypatch.setenv("PADDLE_FUSED_OPT_INTERPRET", "1")
    names = [k for k, _ in executor._escape_env_signature()]
    assert names == ["PADDLE_MOE_A2A"]
    monkeypatch.delenv("PADDLE_FUSED_OPT")
    monkeypatch.delenv("PADDLE_FUSED_OPT_INTERPRET")
    assert [k for k, _ in executor._escape_env_signature()] == names


# ---------------------------------------------------------------------------
# static expert-parallel MoE (the tentpole's second leg)
# ---------------------------------------------------------------------------


def _build_moe_program(static, seed=7, codec=None):
    main, startup = static.Program(), static.Program()
    main.random_seed = startup.random_seed = seed
    with static.program_guard(main, startup):
        x = static.data("x", [32, 16])
        label = static.data("label", [32, 1], dtype="int64")
        h = static.nn.fc(x, 16, act="relu")
        m, aux = static.nn.moe(h, num_experts=4, d_hidden=32,
                               capacity_factor=2.0, dispatch_codec=codec)
        logits = static.nn.fc(m, 4)
        loss = static.mean(
            static.softmax_with_cross_entropy(logits, label)) \
            + static.mean(aux) * 0.01
        static.SGD(0.05).minimize(loss)
    return main, startup, loss


def _run_moe(strategy=None, steps=2, codec=None):
    import paddle_tpu as paddle
    from paddle_tpu import static
    from paddle_tpu.utils import unique_name

    paddle.enable_static()
    rng = np.random.RandomState(0)
    feed = {"x": rng.randn(32, 16).astype(np.float32),
            "label": rng.randint(0, 4, (32, 1)).astype(np.int64)}
    with unique_name.guard():
        scope = static.Scope()
        with static.scope_guard(scope):
            main, startup, loss = _build_moe_program(static, codec=codec)
            exe = static.Executor()
            exe.run(startup)
            target = (static.CompiledProgram(main, build_strategy=strategy)
                      if strategy is not None else main)
            out = [exe.run(target, feed=feed, fetch_list=[loss])[0]
                   for _ in range(steps)]
            return np.concatenate([np.ravel(v) for v in out]), exe


def test_static_moe_ep_stamp_parity_and_cost():
    from paddle_tpu import static

    bs = static.BuildStrategy()
    bs.mesh_shape = {"ep": 4, "dp": 2}

    counters.reset()
    dense, _ = _run_moe()
    assert "moe_a2a.a2a" not in counters.snapshot()

    counters.reset()
    ep, exe = _run_moe(bs)
    snap = counters.snapshot()
    assert snap.get("moe_a2a.a2a", 0) >= 1, snap
    # explicit dispatch/combine is numerically the dense oracle:
    # capacity slots are globally unique, the a2a+sum adds exact zeros
    np.testing.assert_allclose(ep, dense, rtol=1e-5, atol=1e-6)
    cs = exe.cost_stats()
    assert cs.get("moe_a2a_bytes", 0) > 0, cs


def test_static_moe_int8_dispatch_tracks_dense_on_fewer_wire_bytes():
    """``dispatch_codec="int8"``: the rows the experts exchange travel
    encoded, the loss stays inside the quant gate of the dense path and
    the cost model charges the all_to_all fewer bytes than at f32."""
    from paddle_tpu import static

    bs = static.BuildStrategy()
    bs.mesh_shape = {"ep": 4, "dp": 2}
    dense, _ = _run_moe()
    _, exe_f32 = _run_moe(bs)
    counters.reset()
    quant, exe_q = _run_moe(bs, codec="int8")
    assert counters.snapshot().get("moe_a2a.a2a", 0) >= 1
    assert np.abs(quant - dense).max() <= 1e-2, (quant, dense)
    wire_q = exe_q.cost_stats()["moe_a2a_bytes"]
    assert 0 < wire_q < exe_f32.cost_stats()["moe_a2a_bytes"]


def test_moe_ep_pass_stamps_exchange_plan():
    import paddle_tpu as paddle
    from paddle_tpu import static

    paddle.enable_static()
    main, _startup, loss = _build_moe_program(static)
    bs = static.BuildStrategy()
    bs.mesh_shape = {"ep": 4, "dp": 2}
    _opt, report = static.apply_passes(main, ["x", "label"],
                                       [loss.name], bs)
    assert report.shard.get("moe_ep_stamped", 0) >= 1, report.shard
    stamped = [op for op in _opt.global_block.ops if op.type == "moe"
               and "__moe_ep" in op.attrs]
    assert stamped, "forward moe op lost its __moe_ep stamp"
    axis, n, shape = stamped[0].attrs["__moe_ep"]
    assert axis == "ep" and int(n) == 4
    assert {str(a): int(s) for a, s in shape} == {"ep": 4, "dp": 2}


def test_moe_a2a_env_escape_stays_dense(monkeypatch):
    from paddle_tpu import static

    dense, _ = _run_moe()
    monkeypatch.setenv("PADDLE_MOE_A2A", "0")
    bs = static.BuildStrategy()
    bs.mesh_shape = {"ep": 4, "dp": 2}
    counters.reset()
    ep, _ = _run_moe(bs)
    snap = counters.snapshot()
    assert "moe_a2a.a2a" not in snap
    assert snap.get("moe_a2a.xla", 0) >= 1, snap
    np.testing.assert_allclose(ep, dense, rtol=1e-5, atol=1e-6)
