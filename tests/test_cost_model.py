"""Graph-derived cost model + MFU/roofline plane tests.

Covers the PR-12 acceptance surface:

- per-op rule counts vs closed-form analytics on a bert-shaped probe
  net, across AMP on/off x gradient_merge k in {1,2} x TP-sharded
  (per-shard flops divide, psum comm bytes counted) x remat (recompute
  flops added)
- executor integration: ``exe.cost_stats()``, the live
  step_model_flops/step_hbm_bytes/step_comm_bytes/mfu/arith_intensity
  gauges on ``/metrics``, and the schema-versioned step-trace rows +
  per-executable ``kind="cost"`` record
- tools/perf_report.py golden-output tests on a canned trace (report,
  ``--compare`` regression delta, unknown-schema refusal)
- tools/metrics_watch.py bucket-derived p50/p99 deltas between polls
- observability/device_peaks.py resolution (substring precedence, env
  pins, machine balance)
- the IR walk's train-step FLOPs against the bert and nmt closed forms
  on a transformer-shaped program (within 2%)
"""
import json
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

import paddle_tpu.static as static  # noqa: E402
from paddle_tpu.static.cost_model import program_cost  # noqa: E402
from paddle_tpu.static.passes import (apply_passes,  # noqa: E402
                                      resolve_gradient_merge,
                                      resolve_sharding)
from paddle_tpu.utils import unique_name  # noqa: E402

# probe shapes: bert-shaped mini encoder (attention via real matmuls)
H, FF, S, B, L, V = 32, 64, 8, 4, 2, 32


@pytest.fixture(autouse=True)
def _pin_env(monkeypatch):
    """The escape hatches must not defang the legs under test."""
    for k in ("PADDLE_AMP", "PADDLE_AMP_LEVEL", "PADDLE_IR_PASSES",
              "PADDLE_PEAK_FLOPS", "PADDLE_PEAK_HBM_GBPS"):
        monkeypatch.delenv(k, raising=False)
    yield


def _closed_form_flops():
    """PaLM-style matmul accounting for the probe net: per layer
    qkv+out 8H^2 + scores/values 4SH + ffn 4H*FF per token, head
    2H*V; train step = 3x forward."""
    per_token = L * (8 * H * H + 4 * H * FF + 4 * S * H) + 2 * H * V
    return 3 * per_token * B * S


def _build_probe(dropout=False):
    """Bert-shaped static probe: L encoder layers (q/k/v/out fc,
    scores/values matmuls, relu ffn) + vocab head + SGD minimize."""
    main, startup = static.Program(), static.Program()
    main.random_seed = startup.random_seed = 7
    with static.program_guard(main, startup):
        x = static.data("x", [-1, S, H])
        h = x
        for _ in range(L):
            q = static.nn.fc(h, H, num_flatten_dims=2)
            k = static.nn.fc(h, H, num_flatten_dims=2)
            v = static.nn.fc(h, H, num_flatten_dims=2)
            probs = static.softmax(
                static.matmul(q, k, transpose_y=True))
            h = static.nn.fc(static.matmul(probs, v), H,
                             num_flatten_dims=2)
            f = static.nn.fc(h, FF, num_flatten_dims=2, act="relu")
            if dropout:
                f = static.dropout(f, dropout_prob=0.1)
            h = static.nn.fc(f, H, num_flatten_dims=2)
        logits = static.nn.fc(h, V, num_flatten_dims=2)
        loss = static.mean(logits)
        static.SGD(0.05).minimize(loss)
    params = [p.name for p in main.all_parameters()]
    return main, startup, loss, params


def _cost(strategy=None, gm=None, shard=False, batch=B):
    with unique_name.guard():
        main, _startup, loss, params = _build_probe()
        if shard:
            strategy = static.BuildStrategy()
            strategy.mesh_shape = {"tp": 2}
            # ffn pair: column-parallel up-proj, row-parallel
            # down-proj (the contracted-dim hint that needs a psum)
            strategy.sharding_hints = {
                params[8]: (None, "tp"), params[10]: ("tp", None)}
        opt, _report = apply_passes(main, ["x"], [loss.name], strategy)
        return program_cost(
            opt, feed_shapes={"x": (batch, S, H)},
            gm=gm, shard_cfg=resolve_sharding(strategy))


# ---------------------------------------------------------------------------
# rule counts vs closed form
# ---------------------------------------------------------------------------
def test_matches_closed_form_exactly():
    report = _cost()
    assert report.model_flops == _closed_form_flops()
    assert report.hbm_bytes > 0 and report.comm_bytes == 0
    # MFU numerator counts matmul-class ops only
    assert set(report.by_type("flops")) <= {"mul", "matmul"}
    # bandwidth-class ops still show up in the byte ledger
    assert "softmax" in report.by_type("hbm_bytes")


def test_amp_halves_bytes_not_flops():
    bs = static.BuildStrategy()
    bs.amp = True
    # tiny-batch shapes are master-weight-cast dominated (f32 reads +
    # bf16 writes); at an activation-dominated batch the dtype-aware
    # ledger shows the real AMP traffic drop
    base = _cost(batch=256)
    amp = _cost(strategy=bs, batch=256)
    # MACs are dtype-independent; traffic is dtype-aware (bf16 stamps
    # from the AMP pass halve most operand bytes)
    assert amp.model_flops == base.model_flops
    assert amp.hbm_bytes < 0.75 * base.hbm_bytes
    # and it drops at the tiny probe batch too, just less
    assert _cost(strategy=bs).hbm_bytes < _cost().hbm_bytes


@pytest.mark.parametrize("k", [1, 2])
def test_gradient_merge_invariant_totals(k):
    gm = (k, True) if k > 1 else None
    report = _cost(gm=gm)
    # k microbatches at B/k == one batch at B for batch-linear ops: the
    # per-step totals are structure-invariant, and the structure is
    # recorded
    assert report.model_flops == _closed_form_flops()
    assert report.gm_k == k


def test_tp_sharding_divides_flops_and_counts_comm():
    base = _cost()
    sharded = _cost(shard=True)
    # the two hinted ffn matmuls (12 of 3*L*... flops) halve per chip
    assert sharded.model_flops < base.model_flops
    assert sharded.n_shards == 2
    # the row-parallel (contracted-dim) hint costs a psum: ring
    # all-reduce bytes appear, attributed to a factor-2 sharded op
    assert sharded.comm_bytes > 0
    psum_ops = [o for o in sharded.ops if o.comm_bytes]
    assert psum_ops and all(o.shard_factor == 2 for o in psum_ops)


def test_remat_adds_recompute_flops():
    bs = static.BuildStrategy()
    bs.recompute = True
    base = _cost()
    remat = _cost(strategy=bs)
    # every stamped forward op re-runs once in the backward: 4x forward
    # instead of 3x, exactly
    assert remat.model_flops * 3 == base.model_flops * 4
    assert remat.hbm_bytes > base.hbm_bytes


# ---------------------------------------------------------------------------
# executor integration: cost_stats, gauges, step trace
# ---------------------------------------------------------------------------
def _run_probe_steps(steps=3, strategy=None):
    rng = np.random.RandomState(0)
    feed = {"x": rng.randn(B, S, H).astype(np.float32)}
    with unique_name.guard():
        scope = static.Scope()
        with static.scope_guard(scope):
            main, startup, loss, _ = _build_probe()
            exe = static.Executor()
            exe.run(startup)
            target = static.CompiledProgram(
                main, build_strategy=strategy) if strategy else main
            for _ in range(steps):
                exe.run(target, feed=feed, fetch_list=[loss])
    return exe


def test_executor_cost_stats_and_live_gauges(monkeypatch):
    monkeypatch.setenv("PADDLE_PEAK_FLOPS", "1e12")
    monkeypatch.setenv("PADDLE_PEAK_HBM_GBPS", "100")
    exe = _run_probe_steps()
    cs = exe.cost_stats(top=5)
    assert cs["model_flops"] == _closed_form_flops()
    assert cs["hbm_bytes"] > 0
    assert cs["top_flops"] and cs["top_flops"][0]["type"] in (
        "mul", "matmul")
    assert cs["peak_flops"] == 1e12
    assert cs["machine_balance"] == pytest.approx(10.0)
    # live derived gauges from the measured step
    assert cs["step_model_flops"] == cs["model_flops"]
    assert 0 < cs["mfu"] < 1
    assert cs["arith_intensity"] > 0
    assert exe.counters["step_model_flops"] == cs["model_flops"]
    # acceptance: the gauges ride the /metrics plane
    from paddle_tpu import profiler

    text = profiler.render_prometheus()
    assert "# TYPE mfu gauge" in text
    assert "# TYPE step_model_flops gauge" in text
    assert "# TYPE arith_intensity gauge" in text
    samples = {ln.split()[0]: ln.split()[1]
               for ln in text.splitlines()
               if ln and not ln.startswith("#") and len(ln.split()) == 2}
    assert float(samples["mfu"]) > 0
    assert float(samples["step_model_flops"]) == cs["model_flops"]


def test_executor_gm_step_same_cost():
    plain = _run_probe_steps().cost_stats()
    bs = static.BuildStrategy()
    bs.gradient_merge_k = 2
    merged = _run_probe_steps(strategy=bs).cost_stats()
    assert merged["gm_k"] == 2
    assert merged["model_flops"] == plain["model_flops"]


def test_step_trace_rows_carry_cost_fields(tmp_path, monkeypatch):
    from paddle_tpu.observability.step_trace import (SCHEMA_VERSION,
                                                     disable_step_trace,
                                                     enable_step_trace)

    monkeypatch.setenv("PADDLE_PEAK_FLOPS", "1e12")
    path = str(tmp_path / "trace.jsonl")
    enable_step_trace(path)
    try:
        _run_probe_steps()
    finally:
        disable_step_trace()
    recs = [json.loads(ln) for ln in open(path) if ln.strip()]
    assert recs and all(r.get("schema") == SCHEMA_VERSION
                        for r in recs)
    steps = [r for r in recs if r["kind"] == "executor"
             and r.get("phases", {}).get("dispatch") is not None]
    assert len(steps) == 3
    for r in steps:
        assert r["step_model_flops"] == _closed_form_flops()
        assert r["step_hbm_bytes"] > 0
        assert r["step_comm_bytes"] == 0
        assert 0 < r["mfu"] < 1
        assert r["arith_intensity"] > 0
    # one per-executable cost record, de-duped across the warm steps,
    # carrying the per-op tables perf_report's top-K/roofline read
    costs = [r for r in recs if r["kind"] == "cost"]
    assert len(costs) == 1
    c = costs[0]
    assert c["model_flops"] == _closed_form_flops()
    assert c["top_flops"] and c["top_bytes"]
    assert c["peak_flops"] == 1e12


def test_conv_ops_count_flops():
    """conv2d and the IR's real transpose-conv op type both get MAC
    counts — with the layout-correct element base (output for forward
    conv, input for transpose conv)."""
    with unique_name.guard():
        main, startup = static.Program(), static.Program()
        with static.program_guard(main, startup):
            img = static.data("img", [-1, 3, 8, 8])
            c = static.nn.conv2d(img, num_filters=4, filter_size=3,
                                 padding=1)
            static.conv2d_transpose(c, num_filters=2, filter_size=2,
                                    stride=2)
        report = program_cost(main, feed_shapes={"img": (2, 3, 8, 8)})
    by_type = report.by_type("flops")
    # conv2d: 2 * out(2,4,8,8) * Ci*kh*kw(3*3*3)
    assert by_type["conv2d"] == 2 * (2 * 4 * 8 * 8) * (3 * 3 * 3)
    # transpose: 2 * in(2,4,8,8) * W.shape[1:](2*2*2)
    assert by_type["conv2d_transpose_s"] == \
        2 * (2 * 4 * 8 * 8) * (2 * 2 * 2)


def test_matmul_v2_trans_x_spelling():
    """matmul_v2 (deserialized 2.x programs) spells its transpose attr
    "trans_x"; the contracted dim must come from the right axis."""
    from paddle_tpu.static.ir import Program, VarDesc

    prog = Program()
    blk = prog.global_block
    blk.vars["a"] = VarDesc("a", (8, 4))    # stored (K, M), trans_x
    blk.vars["b"] = VarDesc("b", (8, 5))
    blk.vars["o"] = VarDesc("o", (4, 5))
    blk.append_op("matmul_v2", {"X": ["a"], "Y": ["b"]},
                  {"Out": ["o"]}, {"trans_x": True})
    report = program_cost(prog)
    assert report.model_flops == 2 * 4 * 5 * 8  # K=8, not M=4


def test_none_dim_shapes_are_costable():
    """The Paddle 2.x ``[None, ...]`` dynamic-dim spelling must cost
    like ``-1``, not TypeError into a silently-disabled MFU plane."""
    with unique_name.guard():
        main, startup = static.Program(), static.Program()
        with static.program_guard(main, startup):
            x = static.data("x", [None, 8])
            y = static.nn.fc(x, 4)
        report = program_cost(main, feed_shapes={"x": (6, 8)})
    assert report.batch == 6
    assert report.model_flops == 2 * 6 * 8 * 4  # one 8->4 mul at B=6


def test_cost_record_deduped_across_alternating_programs(tmp_path):
    """A train+eval-style loop alternating two compiled programs must
    emit ONE cost record per executable, not one per step."""
    from paddle_tpu.observability.step_trace import (disable_step_trace,
                                                     enable_step_trace)

    path = str(tmp_path / "alt.jsonl")
    rng = np.random.RandomState(0)

    def build(width):
        main, startup = static.Program(), static.Program()
        with static.program_guard(main, startup):
            x = static.data("x", [-1, 8])
            y = static.nn.fc(x, width)
        return main, startup, y

    enable_step_trace(path)
    try:
        with unique_name.guard():
            scope = static.Scope()
            with static.scope_guard(scope):
                exe = static.Executor()
                progs = []
                for width in (4, 6):
                    main, startup, y = build(width)
                    exe.run(startup)
                    progs.append((main, y))
                feed = {"x": rng.randn(2, 8).astype(np.float32)}
                for _ in range(5):
                    for main, y in progs:
                        exe.run(main, feed=feed, fetch_list=[y])
    finally:
        disable_step_trace()
    recs = [json.loads(ln) for ln in open(path) if ln.strip()]
    costs = [r for r in recs if r["kind"] == "cost"]
    assert len(costs) == 2, [c["model_flops"] for c in costs]
    assert {c["model_flops"] for c in costs} == {
        2 * 2 * 8 * 4, 2 * 2 * 8 * 6}


def test_uncostable_step_zeroes_stale_gauges(monkeypatch):
    """Switching to a program the model can't cost must not leave the
    previous program's flops/mfu on the dashboard."""
    monkeypatch.setenv("PADDLE_PEAK_FLOPS", "1e12")
    exe = _run_probe_steps(steps=1)
    assert exe.counters["step_model_flops"] > 0

    from paddle_tpu.static import cost_model

    def _boom(*a, **k):
        raise RuntimeError("uncostable")

    monkeypatch.setattr(cost_model, "program_cost", _boom)
    with unique_name.guard():
        scope = static.Scope()
        with static.scope_guard(scope):
            main, startup = static.Program(), static.Program()
            with static.program_guard(main, startup):
                x = static.data("x", [-1, 4])
                y = static.nn.fc(x, 2)
            exe.run(startup)
            exe.run(main, feed={"x": np.ones((2, 4), np.float32)},
                    fetch_list=[y])
    from paddle_tpu import profiler

    assert exe.counters["step_model_flops"] == 0
    assert exe.counters["mfu"] == 0
    assert profiler.counters_snapshot()["step_model_flops"] == 0


def test_matmul_free_step_zeroes_mfu(monkeypatch):
    """A costed but matmul-free program (model_flops == 0) must report
    mfu 0, never the previous program's value."""
    monkeypatch.setenv("PADDLE_PEAK_FLOPS", "1e12")
    exe = _run_probe_steps(steps=1)
    assert exe.counters["mfu"] > 0
    with unique_name.guard():
        scope = static.Scope()
        with static.scope_guard(scope):
            main, startup = static.Program(), static.Program()
            with static.program_guard(main, startup):
                x = static.data("x", [-1, 4])
                y = static.scale(x, scale=2.0)
            exe.run(startup)
            exe.run(main, feed={"x": np.ones((2, 4), np.float32)},
                    fetch_list=[y])
    assert exe.counters["step_model_flops"] == 0
    assert exe.counters["mfu"] == 0
    assert exe.counters["step_hbm_bytes"] > 0  # still a real byte cost


# ---------------------------------------------------------------------------
# tools/perf_report.py
# ---------------------------------------------------------------------------
def _canned_step(i, mfu, dur, disp, flops=1000000):
    return {"schema": 2, "step": i, "kind": "executor", "dur_ms": dur,
            "phases": {"feed": 1.0, "dispatch": disp,
                       "fetch": dur - 1.0 - disp},
            "cache_hit": i > 0, "mfu": mfu, "step_model_flops": flops,
            "step_hbm_bytes": 250000, "step_comm_bytes": 0,
            "arith_intensity": 4.0}


def _canned_cost():
    return {
        "schema": 2, "step": 99, "kind": "cost", "model_flops": 1000000,
        "hbm_bytes": 250000, "comm_bytes": 0, "arith_intensity": 4.0,
        "n_ops": 4, "batch": 8, "gm_k": 2, "pp_stages": 1,
        "n_shards": 1, "device_kind": "testchip", "peak_flops": 1e12,
        "peak_hbm_bytes_per_s": 1e11,
        "flops_by_type": {"mul": 1000000},
        "bytes_by_type": {"mul": 150000, "softmax": 100000},
        "top_flops": [
            {"index": 1, "type": "mul", "out": "fc_0.tmp",
             "flops": 800000, "hbm_bytes": 50000, "comm_bytes": 0,
             "mult": 3, "shard_factor": 1, "arith_intensity": 16.0},
            {"index": 3, "type": "mul", "out": "fc_1.tmp",
             "flops": 200000, "hbm_bytes": 100000, "comm_bytes": 0,
             "mult": 3, "shard_factor": 1, "arith_intensity": 2.0}],
        "top_bytes": [
            {"index": 2, "type": "softmax", "out": "sm.tmp", "flops": 0,
             "hbm_bytes": 100000, "comm_bytes": 0, "mult": 3,
             "shard_factor": 1, "arith_intensity": 0.0},
            {"index": 3, "type": "mul", "out": "fc_1.tmp",
             "flops": 200000, "hbm_bytes": 100000, "comm_bytes": 0,
             "mult": 3, "shard_factor": 1, "arith_intensity": 2.0}]}


def _canned_steps():
    return [_canned_step(0, 0.10, 20.0, 10.0),
            _canned_step(1, 0.20, 10.0, 5.0),
            _canned_step(2, 0.30, 8.0, 4.0),
            _canned_step(3, 0.40, 6.0, 3.0)]


GOLDEN_REPORT = """\
== step summary ==
steps 4   total 44.0 ms   mean 11.00 ms/step
  phase feed           1.00 ms    9.1%
  phase dispatch       5.50 ms   50.0%
  phase fetch          4.50 ms   40.9%
  cache hits 3/4

== mfu trend ==
steps           mean_mfu   mean_ms  model_flops
0..0              0.1000     20.00        1.00M
1..1              0.2000     10.00        1.00M
2..2              0.3000      8.00        1.00M
3..3              0.4000      6.00        1.00M

== cost model (per compiled step) ==
model_flops 1.00M   hbm_bytes 250.00K   comm_bytes 0   arith_intensity 4.0
batch 8   gm_k 2   pp_stages 1   n_shards 1   device testchip
machine balance 10.0 flops/byte -> step is bandwidth-bound

-- top ops by model flops --
op                        out                           flops    bytes      AI  bound
mul                       fc_0.tmp                    800.00K   50.00K   16.00  compute
mul                       fc_1.tmp                    200.00K  100.00K    2.00  bandwidth

-- top ops by hbm bytes --
op                        out                           flops    bytes      AI  bound
softmax                   sm.tmp                            0  100.00K    0.00  bandwidth
mul                       fc_1.tmp                    200.00K  100.00K    2.00  bandwidth

-- roofline buckets (costed ops) --
compute-bound      1 ops   80.0% of flops
bandwidth-bound    2 ops   20.0% of flops
"""

GOLDEN_COMPARE = """\
== regression delta (before -> after) ==
metric                      before         after     delta
mean_step_ms                    11            22   +100.0%
mean_dispatch_ms               5.5            11   +100.0%
mean_mfu                      0.25         0.125    -50.0%
model_flops                  1.00M         1.00M     +0.0%
hbm_bytes                  250.00K       250.00K     +0.0%
comm_bytes                       0             0       n/a
"""


def test_perf_report_golden_output():
    from tools.perf_report import render_report

    out = render_report(_canned_steps(), [_canned_cost()], top=2)
    assert out == GOLDEN_REPORT


def test_perf_report_compare_golden_delta(tmp_path, capsys):
    from tools.perf_report import main, render_compare

    steps = _canned_steps()
    after = [_canned_step(i, s["mfu"] * 0.5, s["dur_ms"] * 2,
                          s["phases"]["dispatch"] * 2)
             for i, s in enumerate(steps)]
    out = render_compare((steps, [_canned_cost()]),
                         (after, [_canned_cost()]))
    assert out == GOLDEN_COMPARE
    # CLI round trip: --compare over the files reproduces the delta
    bf, af = tmp_path / "before.jsonl", tmp_path / "after.jsonl"
    bf.write_text("".join(json.dumps(r) + "\n"
                          for r in steps + [_canned_cost()]))
    af.write_text("".join(json.dumps(r) + "\n"
                          for r in after + [_canned_cost()]))
    assert main(["--compare", str(bf), str(af)]) == 0
    assert capsys.readouterr().out == GOLDEN_COMPARE


def test_perf_report_cli_on_trace_file(tmp_path, capsys):
    from tools.perf_report import main

    p = tmp_path / "t.jsonl"
    p.write_text("".join(json.dumps(r) + "\n"
                         for r in _canned_steps() + [_canned_cost()]))
    assert main([str(p), "--top", "2"]) == 0
    assert capsys.readouterr().out == GOLDEN_REPORT


def test_perf_report_refuses_unknown_schema(tmp_path, capsys):
    from tools.perf_report import PerfReportError, load_trace, main

    p = tmp_path / "future.jsonl"
    p.write_text(json.dumps({"schema": 99, "step": 0,
                             "kind": "executor"}) + "\n")
    with pytest.raises(PerfReportError) as ei:
        load_trace(str(p))
    msg = str(ei.value)
    assert "99" in msg and "MIGRATION.md" in msg
    assert main([str(p)]) == 2
    assert "unknown step-trace schema" in capsys.readouterr().err


def test_perf_report_reads_schema1_rows(tmp_path):
    """PR 9 traces (no "schema" field) stay readable as version 1."""
    from tools.perf_report import load_trace

    rec = {"step": 0, "kind": "executor", "dur_ms": 5.0,
           "phases": {"feed": 1.0, "dispatch": 3.0, "fetch": 1.0}}
    p = tmp_path / "v1.jsonl"
    p.write_text(json.dumps(rec) + "\n")
    steps, costs = load_trace(str(p))
    assert len(steps) == 1 and not costs


def test_perf_report_unreachable_endpoint_exits_1(capsys):
    from tools.perf_report import main

    assert main(["--metrics", "127.0.0.1:9"]) == 1
    assert "cannot scrape" in capsys.readouterr().err
    # a typo'd filename with no colon must exit 1 too, not ValueError
    assert main(["--metrics", "no_such_scrape.txt"]) == 1
    assert "cannot scrape" in capsys.readouterr().err


def test_perf_report_all_zero_mfu_prints_guidance():
    """mfu=0 rows (unknown peak / matmul-free) carry no signal: the
    trend section must show guidance, not a flat 0.0000 trend, and
    --compare must not average the zeros."""
    from tools.perf_report import _trace_metrics, render_report

    steps = [dict(_canned_step(i, 0, 10.0, 5.0), mfu=0)
             for i in range(4)]
    out = render_report(steps, [_canned_cost()], top=2)
    assert "no nonzero mfu samples" in out
    assert _trace_metrics(steps, [])["mean_mfu"] == 0


def test_metrics_watch_counter_reset_guard():
    """A scraped-server restart (cumulative counts go backwards) must
    fall back to the fresh cumulative distribution, not interpolate a
    non-monotone series or drop the row."""
    from paddle_tpu.observability.metrics import (MetricsRegistry,
                                                  parse_prometheus_text)
    from tools.metrics_watch import histogram_percentile_deltas

    old = MetricsRegistry()
    h_old = old.histogram("lat_ms")
    for v in (1, 2, 3, 4, 5, 6, 7, 8):
        h_old.observe(v)
    prev = parse_prometheus_text(old.render_prometheus())
    fresh = MetricsRegistry()            # restarted process
    h_new = fresh.histogram("lat_ms")
    for v in (40, 45):
        h_new.observe(v)
    cur = parse_prometheus_text(fresh.render_prometheus())
    d = histogram_percentile_deltas(cur, prev)
    row = d["lat_ms"]
    assert row["count"] == 2             # the fresh cumulative, kept
    assert 25 < row["p50"] <= 50


def test_perf_report_metrics_view(monkeypatch):
    monkeypatch.setenv("PADDLE_PEAK_FLOPS", "1e12")
    _run_probe_steps()
    from paddle_tpu import profiler
    from paddle_tpu.observability.metrics import parse_prometheus_text
    from tools.perf_report import render_metrics

    out = render_metrics(parse_prometheus_text(
        profiler.render_prometheus()))
    assert "mfu" in out and "step_model_flops" in out
    assert "executor_step_phase_ms" in out


# ---------------------------------------------------------------------------
# tools/metrics_watch.py percentile deltas
# ---------------------------------------------------------------------------
def test_metrics_watch_interval_percentiles():
    from paddle_tpu.observability.metrics import (MetricsRegistry,
                                                  parse_prometheus_text)
    from tools.metrics_watch import (format_percentile_table,
                                     histogram_percentile_deltas)

    r = MetricsRegistry()
    h = r.histogram("lat_ms", labels=("phase",))
    for v in (1, 2, 3, 4, 5):
        h.observe(v, phase="dispatch")
    prev = parse_prometheus_text(r.render_prometheus())
    for v in (40, 45, 47, 49, 50):
        h.observe(v, phase="dispatch")
    cur = parse_prometheus_text(r.render_prometheus())
    d = histogram_percentile_deltas(cur, prev)
    row = d['lat_ms{phase="dispatch"}']
    # the INTERVAL distribution is the 40-50ms batch alone: its p50
    # must land in the 25..50 bucket, not near the cumulative ~5ms
    assert row["count"] == 5
    assert 25 < row["p50"] <= 50
    assert row["p99"] <= 50
    cum = histogram_percentile_deltas(cur, None)
    assert cum['lat_ms{phase="dispatch"}']["count"] == 10
    assert cum['lat_ms{phase="dispatch"}']["p50"] <= 10
    table = format_percentile_table(d)
    assert "lat_ms" in table and "p50_ms" in table


def test_percentile_interpolation_is_shared():
    from paddle_tpu.observability.metrics import (MetricsRegistry,
                                                  percentile_from_buckets)

    r = MetricsRegistry()
    h = r.histogram("x_ms")
    for v in (0.3, 2.0, 7.0, 30.0, 400.0):
        h.observe(v)
    snap = h.snapshot()
    for q in (50, 90, 99):
        assert h.percentile(q) == percentile_from_buckets(
            snap["buckets"], q)
    assert percentile_from_buckets([], 50) == 0.0


# ---------------------------------------------------------------------------
# device peaks registry
# ---------------------------------------------------------------------------
def test_device_peaks_resolution():
    from paddle_tpu.observability import device_peaks as dp

    assert dp.peak_flops("TPU v4") == 275e12
    # substring precedence: "v5 lite" wins before the bare "v5" family
    assert dp.peak_flops("TPU v5 lite") == 197e12
    assert dp.peak_flops("TPU v5p") == 459e12
    assert dp.peak_flops("unknown chip") is None
    assert dp.hbm_bandwidth("TPU v4") == 1228e9
    assert dp.machine_balance("TPU v4") == pytest.approx(
        275e12 / 1228e9)
    assert dp.machine_balance("mystery") is None


def test_device_peaks_env_pins(monkeypatch):
    from paddle_tpu.observability import device_peaks as dp

    monkeypatch.setenv("PADDLE_PEAK_FLOPS", "2e12")
    monkeypatch.setenv("PADDLE_PEAK_HBM_GBPS", "50")
    p = dp.peaks_for("cpu")
    assert p is not None
    assert p.flops == 2e12 and p.hbm_bytes_per_s == 50e9
    assert dp.machine_balance("cpu") == pytest.approx(40.0)
    # a pinned flops with a known chip keeps the chip's bandwidth
    monkeypatch.delenv("PADDLE_PEAK_HBM_GBPS")
    p4 = dp.peaks_for("TPU v4")
    assert p4.flops == 2e12 and p4.hbm_bytes_per_s == 1228e9


# ---------------------------------------------------------------------------
# the IR walk against the closed forms of a transformer-shaped program
# ---------------------------------------------------------------------------
def _transformer_ir_flops(layers, batch, seq, hidden, ffn, vocab,
                          dec_layers=0, head_transform=True):
    """IR-derived train-step model FLOPs of a transformer-shaped static
    program: per encoder layer qkv+out projections, scores/values
    matmuls and the ffn pair (+ a cross-attention block per decoder
    layer), plus the vocab head, walked by static/cost_model.py (the
    per-op rules behind the executor's live mfu gauge).

    Graph construction only: no Scope, no execution, no device."""
    H = hidden

    def attention(h, kv):
        # 3 H->H projections + out proj (the closed form's 8H^2/token),
        # scores q@k^T and probs@v (its 4*S*H/token)
        q = static.nn.fc(h, H, num_flatten_dims=2)
        k = static.nn.fc(kv, H, num_flatten_dims=2)
        v = static.nn.fc(kv, H, num_flatten_dims=2)
        probs = static.softmax(static.matmul(q, k, transpose_y=True))
        return static.nn.fc(static.matmul(probs, v), H,
                            num_flatten_dims=2)

    def ffn_block(h):
        h = static.nn.fc(h, ffn, num_flatten_dims=2, act="relu")
        return static.nn.fc(h, H, num_flatten_dims=2)

    with unique_name.guard():
        main, startup = static.Program(), static.Program()
        with static.program_guard(main, startup):
            x = static.data("x", [-1, seq, H])
            h = x
            for _ in range(layers):
                h = ffn_block(attention(h, h))
            if dec_layers:
                y = static.data("y", [-1, seq, H])
                enc = h
                h = y
                for _ in range(dec_layers):
                    h = attention(h, h)          # decoder self-attention
                    h = ffn_block(attention(h, enc))  # cross-attention
            if head_transform:
                h = static.nn.fc(h, H, num_flatten_dims=2)
            logits = static.nn.fc(h, vocab, num_flatten_dims=2)
            loss = static.mean(logits)
            static.SGD(0.01).minimize(loss)
        report = program_cost(
            main, feed_shapes={"x": (batch, seq, H)})
    return int(report.model_flops)


def test_ir_flops_matches_bert_closed_form():
    h, i, v, layers, b, s = 128, 256, 1024, 2, 2, 16
    closed = 3 * (layers * (8 * h * h + 4 * h * i + 4 * s * h)
                  + 2 * h * h + 2 * h * v) * b * s
    ir = _transformer_ir_flops(layers=layers, batch=b, seq=s,
                               hidden=h, ffn=i, vocab=v)
    assert abs(ir - closed) / closed <= 0.02


def test_ir_flops_matches_nmt_closed_form():
    v, h, i, le, b, s = 512, 64, 128, 2, 2, 16
    enc = le * (8 * h * h + 4 * h * i + 4 * s * h)
    dec = le * (16 * h * h + 4 * h * i + 8 * s * h) + 2 * h * v
    closed = 3 * (enc + dec) * b * s
    ir = _transformer_ir_flops(layers=le, batch=b, seq=s,
                               hidden=h, ffn=i, vocab=v,
                               dec_layers=le,
                               head_transform=False)
    assert abs(ir - closed) / closed <= 0.02


# ---------------------------------------------------------------------------
# int8 KV pages in the cost model: the ONE closed form (ps.codec.
# encoded_nbytes) prices the wire codec, the decode cost, and the IR
# rule — they can never drift apart
# ---------------------------------------------------------------------------
def test_paged_decode_cost_int8_charges_encoded_bytes():
    from paddle_tpu.inference.decode import DecodeModelConfig
    from paddle_tpu.ps.codec import encoded_nbytes
    from paddle_tpu.static.cost_model import paged_decode_cost

    cfg = DecodeModelConfig(vocab_size=32, n_layers=2, n_heads=2,
                            head_dim=8, ffn_dim=32, max_context=64)
    E = cfg.hidden
    off = paged_decode_cost(cfg, [9, 17], page_size=8, itemsize=4)
    on = paged_decode_cost(cfg, [9, 17], page_size=8, itemsize=4,
                           kv_codec="int8")
    assert off["kv_codec"] == "off" and on["kv_codec"] == "int8"
    # the closed form, verbatim: one f32 scale per token row
    assert off["kv_row_bytes"] == E * 4
    assert on["kv_row_bytes"] == encoded_nbytes(E, "int8", block=E) \
        == E + 4
    # page traffic shrinks by exactly the row-byte ratio; flops don't
    page_tokens = off["live_page_tokens"]
    saved = 2 * cfg.n_layers * (page_tokens + 2) * (E * 4 - (E + 4))
    assert off["hbm_bytes"] - on["hbm_bytes"] == saved
    assert on["model_flops"] == off["model_flops"]
    assert on["arith_intensity"] > off["arith_intensity"]


def test_program_cost_paged_attention_int8_rule():
    """An int8 KPages operand flips the IR rule to ENCODED page bytes
    (payload + scale rows), closed-form-checked against
    encoded_nbytes."""
    from paddle_tpu.ps.codec import encoded_nbytes
    from paddle_tpu.static.cost_model import program_cost
    from paddle_tpu.static.ir import Program

    def build(kv_dtype):
        prog = Program()
        b = prog.global_block
        b.create_var("q", shape=[4, 8, 64], dtype="float32")
        b.create_var("kp", shape=[1000, 128, 8, 64], dtype=kv_dtype)
        b.create_var("vp", shape=[1000, 128, 8, 64], dtype=kv_dtype)
        b.create_var("pt", shape=[4, 4], dtype="int32")
        b.create_var("lens", shape=[4], dtype="int32")
        b.create_var("out", shape=[4, 8, 64], dtype="float32")
        b.append_op("paged_attention",
                    inputs={"Q": ["q"], "KPages": ["kp"],
                            "VPages": ["vp"], "PageTable": ["pt"],
                            "SeqLens": ["lens"]},
                    outputs={"Out": ["out"]})
        (op,) = program_cost(prog).ops
        return op

    f32 = build("float32")
    i8 = build("int8")
    live_tokens = 4 * 4 * 128
    row = 8 * 64
    delta = 2 * live_tokens * (row * 4 - encoded_nbytes(row, "int8",
                                                       block=row))
    assert f32.hbm_bytes - i8.hbm_bytes == delta
    assert i8.flops == f32.flops


def test_perf_report_metrics_decode_section():
    from tools.perf_report import render_metrics

    out = render_metrics({"decode_tokens": 128.0, "spec_accept_rate":
                          0.42, "kv_prefix_hits": 3.0, "mfu": 0.1})
    assert "decode token economics" in out
    assert "spec_accept_rate" in out and "0.42" in out
    # absent decode samples -> no empty section
    assert "decode token economics" not in render_metrics({"mfu": 0.1})
