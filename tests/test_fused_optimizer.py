"""Fused Pallas optimizer kernels (ISSUE 19): kernel-vs-XLA parity per
rule in interpret mode (CPU-hermetic), fp16-scaler FoundInfinite skip
gating, the ZeRO lamb two-phase trust-ratio chunk composition, the
``PADDLE_FUSED_OPT=0`` bitwise escape, dispatch counters with reasons,
and autotune verdict persistence — plus the static expert-parallel MoE
leg (``__moe_ep`` stamp, all-to-all counters, cost accounting, dense
parity) that rides the same PR.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.ops.pallas import autotune, counters
from paddle_tpu.ops.pallas import fused_optimizer as fo


@pytest.fixture(autouse=True)
def _reset(monkeypatch, tmp_path):
    # hermetic dispatch: no stale escape env, per-test autotune cache
    monkeypatch.delenv("PADDLE_FUSED_OPT", raising=False)
    monkeypatch.delenv("PADDLE_FUSED_OPT_INTERPRET", raising=False)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    autotune.reset()
    counters.reset()
    yield
    autotune.reset()
    counters.reset()


@pytest.fixture
def interpret(monkeypatch):
    # the CI / CPU-probe leg: force the kernel in interpret mode
    monkeypatch.setenv("PADDLE_FUSED_OPT_INTERPRET", "1")
    yield


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
# kernel-vs-XLA parity per rule (interpret mode)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("op", fo.FUSED_OPS)
@pytest.mark.parametrize("n", [1024, 1337])  # exact tile + ragged pad
def test_kernel_matches_xla_reference(interpret, op, n):
    attrs = {"mu": 0.9, "use_nesterov": False}
    ins = _ins(op, n)
    before = counters.snapshot()
    out = fo.fused_op_update(op, ins, attrs)
    assert counters.delta(before).get("fused_opt.pallas") == 1
    ref = fo._XLA[op](ins, attrs)
    for slot in ref:
        np.testing.assert_allclose(
            np.asarray(out[slot][0]), np.asarray(ref[slot][0]),
            rtol=1e-5, atol=1e-6, err_msg=f"{op}:{slot}")


def test_nesterov_momentum_parity(interpret):
    attrs = {"mu": 0.85, "use_nesterov": True}
    ins = _ins("momentum", 2048)
    out = fo.fused_op_update("momentum", ins, attrs)
    ref = fo._XLA["momentum"](ins, attrs)
    for slot in ("ParamOut", "VelocityOut"):
        np.testing.assert_allclose(np.asarray(out[slot][0]),
                                   np.asarray(ref[slot][0]),
                                   rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# FoundInfinite skip gating (GradScaler semantics inside the kernel)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("op", fo.FUSED_OPS)
def test_found_infinite_skips_step_bitwise(interpret, op):
    ins = _ins(op, 1024, found=1.0)
    before = counters.snapshot()
    out = fo.fused_op_update(op, ins, {})
    assert counters.delta(before).get("fused_opt.pallas") == 1
    olds = {"ParamOut": "Param", "VelocityOut": "Velocity",
            "Moment1Out": "Moment1", "Moment2Out": "Moment2",
            "Beta1PowOut": "Beta1Pow", "Beta2PowOut": "Beta2Pow"}
    for slot, src in olds.items():
        if slot in out:
            assert np.array_equal(
                np.asarray(out[slot][0]).reshape(-1),
                np.asarray(ins[src][0]).reshape(-1)), f"{op}:{slot}"


def test_found_infinite_zero_still_steps(interpret):
    ins = _ins("adam", 1024, found=0.0)
    out = fo.fused_op_update("adam", ins, {})
    assert not np.array_equal(np.asarray(out["ParamOut"][0]),
                              np.asarray(ins["Param"][0]))


# ---------------------------------------------------------------------------
# escape hatch: PADDLE_FUSED_OPT=0 is bitwise the pre-fusion math
# ---------------------------------------------------------------------------


def test_escape_env_is_bitwise_xla(monkeypatch):
    monkeypatch.setenv("PADDLE_FUSED_OPT", "0")
    assert fo.fused_opt_escaped()
    for op in fo.FUSED_OPS:
        ins = _ins(op, 1024)
        before = counters.snapshot()
        out = fo.fused_op_update(op, ins, {})
        d = counters.delta(before)
        assert d.get("fused_opt.xla") == 1 and "fused_opt.pallas" not in d
        ref = fo._XLA[op](ins, {})
        for slot in ref:
            assert np.array_equal(np.asarray(out[slot][0]),
                                  np.asarray(ref[slot][0])), f"{op}:{slot}"


# ---------------------------------------------------------------------------
# dispatch gate: reasons surface in the counter path
# ---------------------------------------------------------------------------


def test_dispatch_reasons(interpret, monkeypatch):
    path, reason, _ = fo._dispatch("rmsprop", 4096, jnp.float32)
    assert path == "xla" and "no fused kernel" in reason
    path, reason, _ = fo._dispatch("adam", 100, jnp.float32)
    assert path == "xla" and "below one (8, 128) tile" in reason
    path, reason, _ = fo._dispatch("adam", 4096, jnp.float16)
    assert path == "xla" and "not f32" in reason
    path, _, interp = fo._dispatch("adam", 4096, jnp.float32)
    assert path == "pallas" and interp
    monkeypatch.setenv("PADDLE_FUSED_OPT", "0")
    path, reason, _ = fo._dispatch("adam", 4096, jnp.float32)
    assert path == "xla" and "PADDLE_FUSED_OPT=0" in reason


def test_dispatch_cpu_without_interpret_falls_back():
    # no interpret force, CPU backend: pallas is gated off and the
    # reason names the backend — the dygraph hook then returns None so
    # the reference rule stays bitwise
    path, reason, _ = fo._dispatch("adam", 4096, jnp.float32)
    assert path == "xla" and "backend" in reason

    class SGD:  # matches _DY_RULES by class name
        pass

    p = jnp.ones((64, 64), jnp.float32)
    assert fo.fused_try_rule(SGD(), p * 0.1, p, {}, 0.01, None) is None


def test_counter_reason_recorded_on_fallback():
    before = counters.snapshot()
    fo.fused_op_update("sgd", _ins("sgd", 8), {})
    assert counters.delta(before) == {"fused_opt.xla": 1}


# ---------------------------------------------------------------------------
# dygraph hook: engage-or-None
# ---------------------------------------------------------------------------


def test_dygraph_try_rule_sgd_engages(interpret):
    class SGD:
        pass

    rng = np.random.RandomState(3)
    p = jnp.asarray(rng.randn(32, 64), jnp.float32)
    g = jnp.asarray(rng.randn(32, 64), jnp.float32)
    before = counters.snapshot()
    got = fo.fused_try_rule(SGD(), g, p, {}, 0.05, None)
    assert got is not None
    p2, slots = got
    assert counters.delta(before).get("fused_opt.pallas") == 1
    np.testing.assert_allclose(np.asarray(p2), np.asarray(p - 0.05 * g),
                               rtol=1e-5, atol=1e-6)
    assert slots == {}


def test_dygraph_try_rule_unknown_opt_is_none(interpret):
    class RMSProp:
        pass

    p = jnp.ones((64, 64), jnp.float32)
    assert fo.fused_try_rule(RMSProp(), p, p, {}, 0.01, None) is None


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
        r = fo._xla_lamb(seg, attrs)
        for slot in outs:
            outs[slot].append(np.asarray(r[slot][0]))
        off += e
    return {k: np.concatenate(v) for k, v in outs.items()}


def test_zero_lamb_chunk_matches_per_param_reference(interpret):
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
        outs = fo.fused_chunk_update("lamb", chunk, attrs, axis="dp",
                                     param_elems=param_elems,
                                     position=pos)
        return (outs["ParamOut"][0], outs["Moment1Out"][0],
                outs["Moment2Out"][0])

    before = counters.snapshot()
    f = shard_map(step, mesh=mesh, in_specs=(P("dp"),) * 4,
                  out_specs=(P("dp"),) * 3, check_rep=False)
    p2, m2, v2 = f(ins["Param"][0], ins["Grad"][0], ins["Moment1"][0],
                   ins["Moment2"][0])
    # the kernel engaged once per shard-mapped trace
    assert counters.delta(before).get("fused_opt.pallas", 0) >= 1
    ref = _ref_lamb_per_param(ins, attrs, param_elems)
    # tolerance, not bitwise: the sq-norm sums reassociate across chunks
    np.testing.assert_allclose(np.asarray(p2), ref["ParamOut"],
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(m2), ref["Moment1Out"],
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(v2), ref["Moment2Out"],
                               rtol=1e-5, atol=1e-6)


def test_chunk_update_non_lamb_is_plain_fused_op(interpret):
    ins = _ins("adam", 1024)
    out = fo.fused_chunk_update("adam", ins, {}, axis=None,
                                param_elems=(1024,), position=0)
    ref = fo._XLA["adam"](ins, {})
    np.testing.assert_allclose(np.asarray(out["ParamOut"][0]),
                               np.asarray(ref["ParamOut"][0]),
                               rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# autotune verdict: persistence + dispatch demotion
# ---------------------------------------------------------------------------


def test_autotune_verdict_persists_and_demotes(monkeypatch, interpret):
    import paddle_tpu.framework.bringup as bringup
    import paddle_tpu.utils.timing as timing

    monkeypatch.setattr(bringup, "TPU_PLATFORMS", ("cpu", "tpu"))
    calls = []
    times = iter([5.0, 1.0])  # pallas slower -> verdict "xla"

    def fake_timeit(fn, *a, **k):
        calls.append(fn)
        return next(times)

    monkeypatch.setattr(timing, "timeit", fake_timeit)
    assert autotune.best_fused_opt_impl("adam", 4096, "float32") == "xla"
    assert len(calls) == 2
    # memoized: same key re-serves without timing
    assert autotune.best_fused_opt_impl("adam", 4096, "float32") == "xla"
    assert len(calls) == 2
    # disk round-trip: clear the memo, the verdict relaunches from disk
    autotune.reset()
    monkeypatch.setattr(timing, "timeit",
                        lambda *a, **k: pytest.fail("re-timed a "
                                                    "persisted verdict"))
    assert autotune.best_fused_opt_impl("adam", 4096, "float32") == "xla"
    # and the dispatch gate honors the demotion
    path, reason, _ = fo._dispatch("adam", 4096, jnp.float32)
    assert path == "xla" and "autotune verdict" in reason


# ---------------------------------------------------------------------------
# static expert-parallel MoE (the tentpole's second leg)
# ---------------------------------------------------------------------------


def _build_moe_program(static, seed=7):
    main, startup = static.Program(), static.Program()
    main.random_seed = startup.random_seed = seed
    with static.program_guard(main, startup):
        x = static.data("x", [32, 16])
        label = static.data("label", [32, 1], dtype="int64")
        h = static.nn.fc(x, 16, act="relu")
        m, aux = static.nn.moe(h, num_experts=4, d_hidden=32,
                               capacity_factor=2.0)
        logits = static.nn.fc(m, 4)
        loss = static.mean(
            static.softmax_with_cross_entropy(logits, label)) \
            + static.mean(aux) * 0.01
        static.SGD(0.05).minimize(loss)
    return main, startup, loss


def _run_moe(strategy=None, steps=2):
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
            main, startup, loss = _build_moe_program(static)
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
