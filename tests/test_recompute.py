"""Activation rematerialization + in-step gradient merge (ISSUE 5).

Contract being pinned:
- remat on/off is BITWISE on the loss trajectory — including dropout
  inside a recomputed segment (jax.checkpoint replays the identical
  fold_in draws; fresh Executor per leg because exe._step folds into
  the RNG key — the PR 4 gotcha)
- the remat leg's compiled step holds the recomputation and the plain
  leg's holds none (read from the step's text, so it holds on every
  backend); exe.memory_stats() adds up and equals the xla_* gauges
- gradient_merge_k in {1,2,4} matches the unmerged run within 1e-5
  (avg=True = single-large-batch semantics), one compiled dispatch
  covers k microbatches, fp16 FoundInfinite from ANY microbatch skips
  the merged update
- AMP x remat x merge compose; remat/merge config flips never reuse a
  stale executable; PADDLE_IR_PASSES=0 restores the exact baseline
- dygraph RecomputeOptimizer really rematerializes (one tape node per
  segment, bitwise-equal update incl. dropout), GradientMergeOptimizer
  avg semantics survive multiple merge cycles
- fleet.distributed_optimizer routes recompute/gradient_merge onto the
  static BuildStrategy knobs when minimize() gets a static loss
"""
import os
import re
import subprocess
import sys

import numpy as np
import pytest

import paddle_tpu.static as static
from paddle_tpu.static import passes as passes_mod
from paddle_tpu.utils import unique_name

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

H, FF, B, L = 16, 64, 16, 2


def _program(dropout=True, seed=1234):
    # Hermetic naming: the legs of a comparison build "the same"
    # program, but auto-generated var names come from a process-global
    # counter pool — after an unrelated suite (e.g. test_ir_passes) the
    # names shift and with them the remat env flattening order (sorted
    # by name). A fresh guard pins the names.
    with unique_name.guard():
        return _program_body(dropout, seed)


def _program_body(dropout, seed):
    main, startup = static.Program(), static.Program()
    main.random_seed = startup.random_seed = seed
    with static.program_guard(main, startup):
        x = static.data("x", [-1, H])
        label = static.data("label", [-1, 1], dtype="int64")
        h = x
        for _ in range(L):
            h = static.nn.fc(h, FF, act="relu")
            if dropout:
                h = static.dropout(h, dropout_prob=0.2)
            h = static.nn.fc(h, H)
        logits = static.nn.fc(h, 4)
        loss = static.mean(
            static.softmax_with_cross_entropy(logits, label))
        static.SGD(0.05).minimize(loss)
    return main, startup, loss


def _feed(n=B, seed=0):
    rng = np.random.RandomState(seed)
    return {"x": rng.randn(n, H).astype(np.float32),
            "label": rng.randint(0, 4, (n, 1)).astype(np.int64)}


def _run_leg(strategy, steps=3, dropout=True, feed=None, fetch_extra=()):
    """Fresh Scope + Executor per leg: exe._step folds into the RNG key,
    so legs must start from step 0 to be comparable."""
    scope = static.Scope()
    with static.scope_guard(scope):
        main, startup, loss = _program(dropout=dropout)
        exe = static.Executor()
        exe.run(startup)
        cp = static.CompiledProgram(main, build_strategy=strategy)
        f = feed or _feed()
        losses = []
        for _ in range(steps):
            out = exe.run(cp, feed=f,
                          fetch_list=[loss, *fetch_extra])
            losses.append(np.ravel(out[0]))
        return np.concatenate(losses), exe, dict(exe.counters)


def _bs(**kw):
    bs = static.BuildStrategy()
    for k, v in kw.items():
        setattr(bs, k, v)
    return bs


# ---------------------------------------------------------------------------
# rematerialization
# ---------------------------------------------------------------------------
def _step_scopes(exe):
    """The op names of the last step ``exe`` compiled (the executable
    memory_stats() reads the analysis of). A ``jax.checkpoint`` that was
    differentiated leaves its recomputation under a
    ``checkpoint/rematted_computation`` scope."""
    return "\n".join(re.findall(
        r'op_name="([^"]*)"', exe._last_entry.compiled.as_text()))


def test_remat_bitwise_parity_with_dropout_and_recomputation_in_the_step():
    off, exe_off, _ = _run_leg(_bs())
    on, exe_on, counters = _run_leg(_bs(recompute=True))
    assert off.tobytes() == on.tobytes(), (off, on)
    assert counters["remat_segments"] > 1
    # the program, not a compiler's allocation: the remat leg's step
    # recomputes its stamped segments, the plain leg's holds no
    # checkpoint. Whether that lowers memory on a v5e is ROADMAP S7(xv)'s
    # (memory_analysis() of the step compiled for a described v5e).
    assert "checkpoint/rematted_computation" in _step_scopes(exe_on)
    assert "checkpoint" not in _step_scopes(exe_off)


def test_remat_parity_without_dropout():
    off, _, _ = _run_leg(_bs(), dropout=False)
    on, _, _ = _run_leg(_bs(recompute=True), dropout=False)
    assert off.tobytes() == on.tobytes()


@pytest.mark.parametrize("nseg", [1, 2, 3])
def test_remat_segment_count_matrix(nseg):
    on, _, counters = _run_leg(
        _bs(recompute=True, recompute_segments=nseg))
    off, _, _ = _run_leg(_bs())
    assert off.tobytes() == on.tobytes()
    assert counters["remat_segments"] == nseg


def test_remat_stamps_and_auto_heuristic():
    main, _, loss = _program()
    opt, report = passes_mod.apply_passes(
        main, ["x", "label"], [loss.name], _bs(recompute=True))
    blk = opt.global_block
    bwd = next(i for i, op in enumerate(blk.ops) if op.type == "backward")
    segs = [op.attrs.get("__remat_seg") for op in blk.ops[:bwd]
            if op.type not in ("feed", "fetch")]
    # every forward op stamped, segment ids contiguous non-decreasing
    assert all(s is not None for s in segs)
    assert segs == sorted(segs)
    n = len(segs)
    assert max(segs) + 1 == max(2, int(round(n ** 0.5)))
    # nothing after the backward boundary is stamped
    assert all("__remat_seg" not in op.attrs for op in blk.ops[bwd:])
    # the user program is untouched
    assert all("__remat_seg" not in op.attrs
               for op in main.global_block.ops)
    assert report.remat["remat_segments"] == max(segs) + 1
    assert report.remat_table and \
        sum(r["ops"] for r in report.remat_table) == n


def test_remat_user_checkpoints_set_boundaries():
    main, _, loss = _program(dropout=False)
    blk = main.global_block
    # pick the output of the first fc's relu chain as the checkpoint
    fc_outs = [op.outputs["Out"][0] for op in blk.ops
               if op.type == "relu"]
    cp_name = fc_outs[0]
    opt, report = passes_mod.apply_passes(
        main, ["x", "label"], [loss.name],
        _bs(recompute=True, recompute_checkpoints=(cp_name,)))
    bwd = next(i for i, op in enumerate(opt.global_block.ops)
               if op.type == "backward")
    stamped = [op.attrs.get("__remat_seg")
               for op in opt.global_block.ops[:bwd]
               if "__remat_seg" in op.attrs]
    # exactly one boundary -> two segments, split right after cp_name
    assert max(stamped) == 1
    producer = next(i for i, op in enumerate(opt.global_block.ops)
                    if cp_name in op.output_names())
    assert opt.global_block.ops[producer].attrs["__remat_seg"] == 0
    after = [op for op in opt.global_block.ops[producer + 1:bwd]
             if "__remat_seg" in op.attrs]
    assert after and all(op.attrs["__remat_seg"] == 1 for op in after)
    assert report.remat_table[0]["boundary"] == cp_name
    # parity with the user-chosen boundary
    off, _, _ = _run_leg(_bs(), dropout=False)
    on, _, _ = _run_leg(
        _bs(recompute=True, recompute_checkpoints=(cp_name,)),
        dropout=False)
    assert off.tobytes() == on.tobytes()


def test_memory_stats_surface_and_gauges():
    _, exe, counters = _run_leg(_bs(), steps=1)
    mem = exe.memory_stats()
    for key in ("peak_bytes", "temp_bytes", "argument_bytes",
                "output_bytes"):
        assert key in mem and mem[key] >= 0
    assert mem["peak_bytes"] == (mem["temp_bytes"] + mem["argument_bytes"]
                                 + mem["output_bytes"])
    assert counters["xla_temp_bytes"] == mem["temp_bytes"]
    assert counters["xla_peak_bytes"] == mem["peak_bytes"]


def test_append_backward_checkpoints_still_segment():
    """The pre-existing append_backward(checkpoints=...) spelling rides
    the same segmentation pass via the backward op's attr."""
    scope = static.Scope()
    with static.scope_guard(scope):
        main, startup = static.Program(), static.Program()
        main.random_seed = startup.random_seed = 7
        with static.program_guard(main, startup):
            x = static.data("x", [-1, H])
            label = static.data("label", [-1, 1], dtype="int64")
            h = static.nn.fc(x, FF, act="relu")
            mid = static.nn.fc(h, H)
            logits = static.nn.fc(mid, 4)
            loss = static.mean(
                static.softmax_with_cross_entropy(logits, label))
            opt = static.SGD(0.05)
            from paddle_tpu.static.backward import append_backward
            pgs = append_backward(loss, checkpoints=[mid])
            opt.apply_gradients(pgs)
        opt_prog, report = passes_mod.apply_passes(
            main, ["x", "label"], [loss.name], _bs(recompute=True))
        assert report.remat["remat_segments"] == 2
        exe = static.Executor()
        exe.run(startup)
        out = exe.run(static.CompiledProgram(
            main, build_strategy=_bs(recompute=True)),
            feed=_feed(), fetch_list=[loss])
        assert np.isfinite(np.ravel(out[0])).all()


# ---------------------------------------------------------------------------
# gradient merge
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("k", [1, 2, 4])
def test_gradient_merge_loss_parity(k):
    base, _, _ = _run_leg(_bs(), dropout=False, steps=3)
    merged, _, counters = _run_leg(
        _bs(gradient_merge_k=k), dropout=False, steps=3)
    assert np.abs(base - merged).max() <= 1e-5, (base, merged)
    if k > 1:
        # one compiled dispatch per k microbatches, compiled once
        assert counters["gm_dispatches"] == 3
        assert counters["gm_microbatches"] == 3 * k
        assert counters["compile_cache_misses"] == 1


def test_gradient_merge_sum_vs_avg():
    """avg=False sums the k microbatch grads — equivalent to k x lr on
    identical microbatches — and must NOT equal the avg run."""
    avg, _, _ = _run_leg(_bs(gradient_merge_k=2), dropout=False, steps=2)
    summed, _, _ = _run_leg(
        _bs(gradient_merge_k=2, gradient_merge_avg=False),
        dropout=False, steps=2)
    assert avg[0] == summed[0]            # first loss pre-update agrees
    assert np.abs(avg[1:] - summed[1:]).max() > 0


def test_gradient_merge_batch_not_divisible_raises():
    scope = static.Scope()
    with static.scope_guard(scope):
        main, startup, loss = _program(dropout=False)
        exe = static.Executor()
        exe.run(startup)
        cp = static.CompiledProgram(
            main, build_strategy=_bs(gradient_merge_k=3))
        with pytest.raises(ValueError, match="divisible"):
            exe.run(cp, feed=_feed(n=B), fetch_list=[loss])


def test_amp_remat_merge_compose():
    """bf16 AMP x remat x k=2 merge: tracks the f32 x k=2 run within
    roundoff (same-k legs so dropout masks line up)."""
    f32, _, _ = _run_leg(_bs(gradient_merge_k=2), steps=3)
    mixed, mem, counters = _run_leg(
        _bs(gradient_merge_k=2, recompute=True, amp=True,
            amp_dtype="bfloat16"), steps=3)
    assert np.isfinite(mixed).all()
    denom = max(abs(f32[0]), 1e-6)
    assert abs(mixed[0] - f32[0]) / denom <= 1e-2
    assert counters["remat_segments"] > 1
    assert counters["gm_dispatches"] == 3
    assert counters["amp_ops_lowprec"] > 0


def test_fp16_found_inf_gates_merged_update():
    """A NaN in ONE microbatch must skip the whole merged update (the
    OR-reduced FoundInfinite), leaving every param bitwise unchanged."""
    scope = static.Scope()
    with static.scope_guard(scope):
        main, startup, loss = _program(dropout=False)
        exe = static.Executor()
        exe.run(startup)
        params = {p.name: np.array(scope._peek(p.name))
                  for p in main.all_parameters()}
        feed = _feed()
        feed["x"] = feed["x"].copy()
        feed["x"][: B // 2] = np.nan    # poison microbatch 0 only
        cp = static.CompiledProgram(
            main, build_strategy=_bs(gradient_merge_k=2, amp=True,
                                     amp_dtype="float16"))
        exe.run(cp, feed=feed, fetch_list=[loss])
        for name, before in params.items():
            after = np.array(scope._peek(name))
            assert np.array_equal(before, after), name


# ---------------------------------------------------------------------------
# cache-key separation + escape hatch
# ---------------------------------------------------------------------------
def test_remat_and_merge_flips_never_reuse_executable():
    scope = static.Scope()
    with static.scope_guard(scope):
        # distinct seed -> distinct content key: hermetic naming makes
        # programs identical across tests, and this test counts misses
        # against the process-global executable cache
        main, startup, loss = _program(dropout=False, seed=4321)
        exe = static.Executor()
        exe.run(startup)
        feed = _feed()
        misses = 0
        for bs in (_bs(), _bs(recompute=True),
                   _bs(gradient_merge_k=2),
                   _bs(gradient_merge_k=4),
                   _bs(recompute=True, gradient_merge_k=2)):
            cp = static.CompiledProgram(main, build_strategy=bs)
            exe.run(cp, feed=feed, fetch_list=[loss])
            misses += 1
            assert exe.counters["compile_cache_misses"] == misses, vars(bs)
        # a DIFFERENT segment count restamps the program -> new content
        auto_nseg = passes_mod.apply_passes(
            main, ["x", "label"], [loss.name],
            _bs(recompute=True))[1].remat["remat_segments"]
        cp = static.CompiledProgram(main, build_strategy=_bs(
            recompute=True, recompute_segments=auto_nseg + 1))
        exe.run(cp, feed=feed, fetch_list=[loss])
        assert exe.counters["compile_cache_misses"] == misses + 1
        # while the SAME config (a fresh equal strategy) hits the cache
        cp = static.CompiledProgram(main, build_strategy=_bs(
            recompute=True))
        exe.run(cp, feed=feed, fetch_list=[loss])
        assert exe.counters["compile_cache_misses"] == misses + 1


def test_ir_passes_escape_restores_baseline():
    """PADDLE_IR_PASSES=0 must disable remat AND merge together with
    the rest of the pipeline — the escape leg is the exact baseline."""
    baseline, _, _ = _run_leg(_bs(), dropout=False, steps=2)
    os.environ["PADDLE_IR_PASSES"] = "0"
    try:
        escaped, _, counters = _run_leg(
            _bs(recompute=True, gradient_merge_k=4), dropout=False,
            steps=2)
    finally:
        del os.environ["PADDLE_IR_PASSES"]
    # passes-off vs passes-on baseline is itself bitwise (PR 3 gate),
    # so the escape leg must match the knobless run bitwise
    assert escaped.tobytes() == baseline.tobytes()
    assert "gm_dispatches" not in counters
    assert "remat_segments" not in counters


# ---------------------------------------------------------------------------
# dygraph satellites
# ---------------------------------------------------------------------------
def _dy_model():
    import paddle_tpu as paddle
    from paddle_tpu import nn

    paddle.seed(0)

    class M(nn.Layer):
        def __init__(self):
            super().__init__()
            self.seg1 = nn.Sequential(nn.Linear(8, 32), nn.ReLU(),
                                      nn.Dropout(0.2))
            self.seg2 = nn.Sequential(nn.Linear(32, 4))

        def forward(self, x):
            return self.seg2(self.seg1(x))

    return M()


def test_dygraph_recompute_optimizer_bitwise():
    import paddle_tpu as paddle
    from paddle_tpu import optimizer

    rng = np.random.RandomState(0)
    X = rng.randn(16, 8).astype(np.float32)
    Y = rng.randn(16, 4).astype(np.float32)

    m1 = _dy_model()
    o1 = optimizer.SGD(learning_rate=0.1, parameters=m1.parameters())
    paddle.seed(42)
    loss1 = ((m1(paddle.to_tensor(X)) - paddle.to_tensor(Y)) ** 2).mean()
    loss1.backward()
    o1.step()

    m2 = _dy_model()
    o2 = optimizer.RecomputeOptimizer(
        optimizer.SGD(learning_rate=0.1, parameters=m2.parameters()))
    o2._set_checkpoints([m2.seg1, m2.seg2])
    paddle.seed(42)
    loss2 = ((m2(paddle.to_tensor(X)) - paddle.to_tensor(Y)) ** 2).mean()
    o2.minimize(loss2)

    assert float(loss1) == float(loss2)
    for p1, p2 in zip(m1.parameters(), m2.parameters()):
        assert np.array_equal(np.asarray(p1.numpy()),
                              np.asarray(p2.numpy()))


def test_dygraph_recompute_single_tape_node_per_segment():
    import paddle_tpu as paddle
    from paddle_tpu import optimizer

    m = _dy_model()
    opt = optimizer.RecomputeOptimizer(
        optimizer.SGD(learning_rate=0.1, parameters=m.parameters()))
    opt._set_checkpoints([m.seg1])
    x = paddle.to_tensor(np.ones((4, 8), np.float32))
    h = m.seg1(x)
    # the segment recorded ONE node (whole-segment vjp, recompute at
    # backward), not a per-op chain
    assert h._node is not None and h._node.name == "recompute"
    # unwrapping restores the original per-op recording
    opt._set_checkpoints([])
    h2 = m.seg1(x)
    assert h2._node is None or h2._node.name != "recompute"


def test_gradient_merge_optimizer_multi_cycle_parity():
    """Two merge cycles via the minimize-only protocol must match two
    large-batch steps: the merged grad is divided by k ONCE and cleared
    after the update (a stale merged grad used to double-count into the
    next cycle's first backward)."""
    import paddle_tpu as paddle
    from paddle_tpu import optimizer

    rng = np.random.RandomState(0)
    X = rng.randn(16, 8).astype(np.float32)
    Y = rng.randn(16, 4).astype(np.float32)

    def model():
        paddle.seed(0)
        from paddle_tpu import nn
        return nn.Linear(8, 4)

    m1 = model()
    o1 = optimizer.SGD(learning_rate=0.1, parameters=m1.parameters())
    for _ in range(2):  # two large-batch steps
        loss = ((m1(paddle.to_tensor(X)) - paddle.to_tensor(Y)) ** 2).mean()
        o1.minimize(loss)
        o1.clear_grad()
    w1 = np.asarray(m1.weight.numpy())

    m2 = model()
    o2 = optimizer.GradientMergeOptimizer(
        optimizer.SGD(learning_rate=0.1, parameters=m2.parameters()),
        k_steps=2, avg=True)
    for _cycle in range(2):
        for half in range(2):   # two half-batches per cycle, minimize only
            xs = X[half * 8:(half + 1) * 8]
            ys = Y[half * 8:(half + 1) * 8]
            loss = ((m2(paddle.to_tensor(xs)) -
                     paddle.to_tensor(ys)) ** 2).mean()
            o2.minimize(loss)
    w2 = np.asarray(m2.weight.numpy())
    assert np.abs(w1 - w2).max() <= 1e-6, np.abs(w1 - w2).max()


# ---------------------------------------------------------------------------
# fleet routing + tooling
# ---------------------------------------------------------------------------
def test_fleet_routes_strategies_to_build_knobs():
    from paddle_tpu.distributed import fleet as fleet_mod

    f = fleet_mod.Fleet()
    strategy = fleet_mod.DistributedStrategy()
    strategy.recompute = True
    strategy.gradient_merge = True
    strategy.gradient_merge_configs.k_steps = 2
    f.init(strategy=strategy)

    scope = static.Scope()
    with static.scope_guard(scope):
        main, startup = static.Program(), static.Program()
        main.random_seed = startup.random_seed = 9
        with static.program_guard(main, startup):
            x = static.data("x", [-1, H])
            label = static.data("label", [-1, 1], dtype="int64")
            h = static.nn.fc(x, FF, act="relu")
            logits = static.nn.fc(h, 4)
            loss = static.mean(
                static.softmax_with_cross_entropy(logits, label))
            opt = f.distributed_optimizer(static.SGD(0.05), strategy)
            opt.minimize(loss)
        bs = main._fleet_build_strategy
        assert bs.recompute is True and bs.gradient_merge_k == 2
        exe = static.Executor()
        exe.run(startup)
        out = exe.run(main, feed=_feed(), fetch_list=[loss])
        assert np.isfinite(np.ravel(out[0])).all()
        assert exe.counters["gm_dispatches"] == 1
        assert exe.counters["gm_microbatches"] == 2
        assert exe.counters["remat_segments"] >= 1


def test_gm_counters_not_bumped_without_backward():
    """A gradient_merge_k strategy on a backward-less (inference)
    program falls back to the plain step — its dispatches must not be
    reported as merged."""
    scope = static.Scope()
    with static.scope_guard(scope):
        main, startup = static.Program(), static.Program()
        main.random_seed = startup.random_seed = 3
        with static.program_guard(main, startup):
            x = static.data("x", [-1, H])
            logits = static.nn.fc(x, 4)
        exe = static.Executor()
        exe.run(startup)
        cp = static.CompiledProgram(
            main, build_strategy=_bs(gradient_merge_k=4))
        exe.run(cp, feed={"x": _feed()["x"]}, fetch_list=[logits])
        assert "gm_dispatches" not in exe.counters
        assert "gm_microbatches" not in exe.counters


def test_global_grad_clip_applies_through_meta_minimize():
    """set_gradient_clip's program-level default must reach the static
    minimize bodies in RecomputeOptimizer and fleet (they resolve via
    static.optimizer.resolve_grad_clip, not just the instance attr)."""
    from paddle_tpu.optimizer.meta import RecomputeOptimizer
    from paddle_tpu.static.optimizer import set_gradient_clip

    class _SpyClip:
        def __init__(self):
            self.called = 0

        def __call__(self, params_grads):
            self.called += 1
            return params_grads

    spy = _SpyClip()
    set_gradient_clip(spy)
    try:
        scope = static.Scope()
        with static.scope_guard(scope):
            main, startup = static.Program(), static.Program()
            with static.program_guard(main, startup):
                x = static.data("x", [-1, H])
                label = static.data("label", [-1, 1], dtype="int64")
                logits = static.nn.fc(x, 4)
                loss = static.mean(
                    static.softmax_with_cross_entropy(logits, label))
                RecomputeOptimizer(static.SGD(0.05)).minimize(loss)
        assert spy.called == 1
    finally:
        set_gradient_clip(None)


def test_dump_passes_remat_cli():
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "dump_passes.py"),
         "--demo", "--remat"],
        capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode == 0, out.stderr[-2000:]
    assert "recompute_segmentation" in out.stdout
    assert "stash_vars" in out.stdout and "recomp_vars" in out.stdout
