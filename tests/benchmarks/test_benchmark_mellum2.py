"""The ``mellum2-12b-a2.5b`` configuration and its cell: the data files
against the catalog and the contract's form, the arithmetic against the
issue's numbers, the reference against the program through ``TrainStep``
(and the fp8 control, which has to fail), the program's work ledger
against ``work_mellum2.py``, and the whole command at tiny size through
the harness — on the CPU, never a measurement."""
import functools
import json
import os
import re

import numpy as np
import pytest

from benchmarks import harness, lm_traffic, work_mellum2
from benchmarks.reference import mellum2 as ref

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CONFIG = "mellum2-12b-a2.5b"
CELL = CONFIG + ".pretrain-seq8k"
TINY = "mellum2-tiny.pretrain"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REHEARSAL_PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes": 1e9,
                   "hbm_bytes_per_s": 1e11}
#: what the contract calls a width: never cut, never in ``reduced``
WIDTH = re.compile(r"(hidden_size|intermediate|latent|state_size|proj|"
                   r"head_dim|_dim$|_rank$|expansion|experts_per)")
NEW_METRICS = ["gqa_attn_device_share_pct.train",
               "gqa_attn_roofline_pct.train"]


def _write(root, rel, obj):
    path = os.path.join(root, rel)
    assert not os.path.exists(path), f"{rel} would edit an existing file"
    with open(path, "w") as f:
        json.dump(obj, f)


@pytest.fixture(scope="module")
def tiny(bench_root):
    """(root, cell, config) of ``mellum2-tiny.pretrain``: the real files
    cut to test size and dropped into the copy of ``benchmarks/`` as NEW
    files. float32 end to end, so that the program is held to the
    reference tightly; the window (24) is shorter than the rows (64)."""
    root, before = bench_root
    cfg = harness.load_json(os.path.join(root, f"configs/{CONFIG}.json"))
    cfg.update(name="mellum2-tiny", hidden_size=64, head_dim=16,
               num_attention_heads=4, num_key_value_heads=2,
               moe_intermediate_size=32, num_experts=4,
               num_experts_per_tok=4, vocab_size=512, sliding_window=24)
    cfg["published"] = dict(cfg["published"], num_experts=16)
    cfg["program"] = dict(cfg["program"], amp_level="O0")
    _write(root, "configs/mellum2-tiny.json", cfg)
    cell = harness.load_json(os.path.join(root, f"workloads/{CELL}.json"))
    cell.update(name=TINY, config="mellum2-tiny")
    cell["traffic"].update(batch=2, seq=64, host_batches=4,
                           loss_fetch_every=2)
    cell["correct"].update(block_rows=32, limits={
        "loss_gap": 1e-5, "grad_norm_gap": 1e-3, "delta_norm_gap": 3e-3})
    _write(root, f"workloads/{TINY}.json", cell)
    return root, cell, cfg, before


def rehearse(root, cell, trace=False, seed=2 ** 31 + 31, seconds=1.0):
    lines = []
    result = harness.run_cell(cell, seed=seed, seconds=seconds, trace=trace,
                              root=root, peaks=REHEARSAL_PEAKS,
                              check_device=False, log=lines.append)
    return json.loads(json.dumps(result)), lines


# ---------------------------------------------------------------------------
# the data files
# ---------------------------------------------------------------------------
def _catalog_row():
    if not os.path.isfile(CATALOG):
        pytest.skip("the model catalog is not on this machine")
    with open(CATALOG) as f:
        rows = [json.loads(line) for line in f]
    return next(r for r in rows if r["name"] == "Mellum2-12B-A2.5B-Instruct")


def test_config_keeps_every_published_key_but_the_listed_cuts():
    row = _catalog_row()
    cfg = harness.load_json(os.path.join(harness.ROOT,
                                         f"configs/{CONFIG}.json"))
    pub = row["config"]
    assert cfg["source"] == row["source_url"]
    changed = sorted(k for k, v in pub.items() if cfg[k] != v)
    assert changed == sorted(cfg["reduced"]) == sorted([
        "num_hidden_layers", "num_experts", "vocab_size", "layer_types",
        "mlp_layer_types"])
    assert not [k for k in cfg["reduced"] if WIDTH.search(k)]
    # every width is the source's, and the nested group is copied whole
    for key in ("hidden_size", "head_dim", "num_attention_heads",
                "num_key_value_heads", "moe_intermediate_size",
                "num_experts_per_tok", "sliding_window",
                "intermediate_size", "rope_parameters", "norm_topk_prob"):
        assert cfg[key] == pub[key], key
    assert cfg["published"] == {
        "num_hidden_layers": 28, "num_experts": 64, "vocab_size": 98304,
        "layer_types": pub["layer_types"],
        "mlp_layer_types": pub["mlp_layer_types"]}
    # the cut: one whole period, a quarter of the experts and of the
    # vocabulary — over the guide's floors (4 layers, 8 experts, 1/8)
    assert cfg["layer_types"] == pub["layer_types"][:4] == \
        ["sliding_attention"] * 3 + ["full_attention"]
    assert cfg["mlp_layer_types"] == ["sparse"] * 4
    assert cfg["num_experts"] * 4 == 64 and cfg["vocab_size"] * 4 == 98304
    assert "4 chips" in cfg["stands_for"]
    assert "Qwen3-MoE" in cfg["assumed"]["qk_norm"] and cfg["qk_norm"] is True
    assert any("multi-token-prediction" in d for d in cfg["departs"])
    assert any("intermediate_size 7168" in d for d in cfg["departs"])


def test_benchmark_json_only_gained_entries():
    bench = harness.load_json(os.path.join(REPO, "BENCHMARK.json"))
    names = [c["name"] for c in bench["configs"]]
    assert names[:3] == ["bert-base", "kimi-linear-48b-a3b", CONFIG]
    cells = [w["name"] for w in bench["workloads"]]
    assert cells[:4] == ["bert-base.pretrain-seq512",
                         "bert-base.pretrain-seq128",
                         "kimi-linear-48b-a3b.pretrain-seq8k", CELL]
    entry = bench["workloads"][3]
    assert entry["chips"] == 1 and len(entry["why"]) <= 200
    assert (entry["config"], entry["traffic"]) == (CONFIG, "pretrain-seq8k")
    cell, cfg = harness.load_cell(CELL)
    assert cell["why"] == entry["why"] and cfg["name"] == CONFIG
    assert bench["configs"][2]["reduced"] == cfg["reduced"]
    assert bench["configs"][2]["file"] == f"benchmarks/configs/{CONFIG}.json"
    new = [m for m in bench["per_layer"] if m.get("workloads") == [CELL]]
    assert [m["name"] for m in new] == NEW_METRICS
    files = {m["name"]: m for m in harness.layer_metrics()}
    for m in new:
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        for key in ("unit", "better", "source", "layer", "moves"):
            assert files[m["name"]][key] == m[key]
    assert [m["name"] for m in bench["per_layer"]][13:15] == NEW_METRICS
    # nothing of the accepted benchmark lists the new cell, and what it
    # had is as it was
    for m in bench["per_layer"][:13]:
        assert CELL not in m.get("workloads", [])
    assert bench["run_seconds"] == 51
    assert [e["name"] for e in bench["end_to_end"]] == [
        "train_tokens_per_s", "setup_s"]


def test_cell_is_the_issues_traffic_and_counts_the_share_its_files_state():
    cell, cfg = harness.load_cell(CELL)
    driver = harness.load_driver(cfg)
    mcfg = driver.model_config(cfg)
    shapes = driver.param_shapes(mcfg)
    total = sum(int(np.prod(s)) for s in shapes.values())
    per_layer = sum(int(np.prod(s)) for k, s in shapes.items()
                    if k.startswith("layers.0."))
    # attention 21.23 M + router 0.147 M + 16 experts 99.09 M (+ the
    # norms): 120.5 M a layer, 595.2 M in all, 9.52 GB at 16 B each
    assert per_layer == 120_476_416
    assert total == 595_154_176
    assert mcfg["num_experts"] == 64 and mcfg["experts_held"] == 16
    feed = cell["traffic"]
    assert (feed["batch"], feed["seq"]) == (2, 8192)
    assert (feed["zipf_exponent"], feed["host_batches"],
            feed["loss_fetch_every"]) == (1.0, 8, 5)
    assert cell["correct"]["steps"] == 3
    assert cell["correct"]["control_precisions"] == ["fp8"]
    kimi, _ = harness.load_cell("kimi-linear-48b-a3b.pretrain-seq8k")
    assert cell["optimizer"] == kimi["optimizer"]
    # the expected finding: one rung, the top one
    from paddle_tpu.nn.moe import _row_ladder

    assert _row_ladder(16384 * 8, 16, 64) == (131072,)


def test_band_pairs_and_flops_are_the_issues_numbers():
    _, cfg = harness.load_cell(CELL)
    mcfg = harness.load_driver(cfg).model_config(cfg)
    assert work_mellum2.band_pairs(8192, 1024) == 7_864_832
    assert 8192 * 8193 // 2 == 33_558_528
    assert work_mellum2.band_pairs(8192, 1024) / 33_558_528 == \
        pytest.approx(0.234, abs=5e-4)
    assert work_mellum2.band_pairs(10, 100) == 55
    assert work_mellum2.attention_matrix_params(mcfg) == 21_233_664
    g = 1e9
    scores_full = 12 * 128 * 32 * 4096
    scores_slide = 12 * 128 * 32 * 7_864_832 / 8192
    assert scores_full / g == pytest.approx(0.201, abs=1e-3)
    assert scores_slide / g == pytest.approx(0.047, abs=1e-3)
    parts = {"scores": scores_full + 3 * scores_slide,
             "projections": 4 * 6 * 21_233_664,
             "experts": 4 * 6 * 2 * 3 * 2304 * 896,
             "router": 4 * 6 * 2304 * 64,
             "head": 6 * 2304 * 24576 * 8191 / 8192}
    want = {"scores": 0.34, "projections": 0.51, "experts": 0.30,
            "router": 0.004, "head": 0.34}
    for k, v in want.items():
        assert parts[k] / g == pytest.approx(v, abs=6e-3), k
    total = work_mellum2.train_flops_per_token(mcfg, 8192, 8191)
    assert total == pytest.approx(sum(parts.values()))
    assert total / g == pytest.approx(1.49, abs=5e-3)
    # the new attention path is 57% of the required work, the experts 20%
    assert (parts["scores"] + parts["projections"]) / total == \
        pytest.approx(0.57, abs=5e-3)
    assert parts["experts"] / total == pytest.approx(0.20, abs=5e-3)


def test_kernel_work_counts_keys_once_a_key_head_and_the_bands_pairs():
    _, cfg = harness.load_cell(CELL)
    mcfg = harness.load_driver(cfg).model_config(cfg)
    work = work_mellum2.gqa_kernel_work(mcfg, 2, 8192)
    # one role a layer kind, its forward and backward together
    assert {k: w["calls"] for k, w in work.items()} == {
        "flash_attention_grouped": 2, "flash_attention_window": 6}
    assert work["flash_attention_grouped"]["flops"] == \
        12.0 * 2 * 32 * (8192 * 8192 / 2) * 128
    assert work["flash_attention_window"]["flops"] == \
        3 * 12.0 * 2 * 32 * 7_864_832 * 128
    q = 2 * 8192 * 32 * 128 * 2
    kv = 2 * 2 * 8192 * 4 * 128 * 2
    lse = 4 * 2 * 8192 * 32
    assert work["flash_attention_grouped"]["bytes"] == \
        (2 * q + kv + lse) + (4 * q + 2 * kv + lse)
    assert work["flash_attention_window"]["bytes"] == \
        3 * work["flash_attention_grouped"]["bytes"]


# ---------------------------------------------------------------------------
# the reference against the program, and the control
# ---------------------------------------------------------------------------
def test_three_adamw_steps_through_trainstep_match_the_reference(tiny):
    root, cell, cfg, _ = tiny
    driver = harness.load_driver(cfg, root)
    mcfg = driver.model_config(cfg)
    batches = lm_traffic.lm_batches(cell["traffic"], mcfg["vocab_size"], 6)
    want = driver._reference(mcfg, cell, batches[:3], 6)
    loop = driver.Loop(cfg, cell, driver.make_params(mcfg, 6), 6)
    assert loop.model.recompute                    # the cell's own setting
    assert [b.mixer.window for b in loop.model.layers] == [24, 24, 24, None]
    got = driver.first_steps(loop, mcfg, batches, 6, 3)
    sound = driver.compare(got, want, cell["correct"]["limits"])
    assert all(c["ok"] for c in sound), sound
    assert set(got["grad_norm"]) == set(want["grad_norm"]) == \
        set(driver.param_shapes(mcfg))
    # the control: fp8 operands in the reference's products
    low = driver._reference(mcfg, cell, batches[:3], 6,
                            matmuls=ref.fp8_matmuls)
    assert not all(c["ok"] for c in driver.compare(
        low, want, cell["correct"]["limits"]))
    # and through the tool's entry, which has to report it as failing
    ctx, drv, _ = harness.context(TINY, 6, 1.0, root, check_device=False,
                                  log=lambda _m: None)
    out = drv.control(ctx)
    assert [c for c in out["checks"] if not c["ok"]]
    assert all(c["name"].startswith("fp8 ") for c in out["checks"])


def test_the_other_reading_of_the_source_is_one_word_in_the_file(tiny):
    """``qk_norm: false`` reaches the program and the reference alike,
    and they still agree."""
    root, cell, cfg, _ = tiny
    cfg = dict(cfg, qk_norm=False)
    driver = harness.load_driver(cfg, root)
    mcfg = driver.model_config(cfg)
    assert "layers.0.mixer.q_norm.weight" not in driver.param_shapes(mcfg)
    batches = lm_traffic.lm_batches(cell["traffic"], mcfg["vocab_size"], 7)
    want = driver._reference(mcfg, cell, batches[:1], 7)
    loop = driver.Loop(cfg, cell, driver.make_params(mcfg, 7), 7)
    got = driver.first_steps(loop, mcfg, batches, 7, 1)
    assert all(c["ok"] for c in driver.compare(
        got, want, cell["correct"]["limits"]))


def test_reference_imports_nothing_of_the_program():
    with open(os.path.join(harness.ROOT, "reference", "mellum2.py")) as f:
        text = f.read()
    code = text.split('"""', 2)[2]
    assert "paddle_tpu" not in code
    assert "jnp.where(ok[None], s, -jnp.inf)" in code   # an explicit mask
    assert "for e in range(" in code       # a dense loop over the experts


# ---------------------------------------------------------------------------
# the whole command at tiny size
# ---------------------------------------------------------------------------
def test_new_cell_rehearses_through_the_harness(tiny, digest):
    root, _, _, before = tiny
    result, lines = rehearse(root, TINY)
    assert result["correct"] is True, lines
    assert set(result["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert result["failed"] == 0 and result["attempted"] >= 2
    for name in ("loss_gap", "grad_norm_gap", "delta_norm_gap",
                 "window_compilations", "window_nonfinite_losses"):
        assert [ln for ln in lines if ln.startswith(f"check {name}: value")]
    counters_line = next(ln for ln in lines if "pallas counters" in ln)
    # on the CPU the grouped, windowed calls go to XLA, counted
    assert "flash_attention.xla" in counters_line
    after = digest(root)
    assert {k: after[k] for k in before} == before   # no existing file


def test_traced_rehearsal_reports_the_cells_layer_metrics(tiny):
    root, _, _, _ = tiny
    result, lines = rehearse(root, TINY, trace=True)
    got = result["metrics"]
    assert {"moe_rows_used_pct.train", "mfu_pct.train",
            "dispatch_ms.train"} <= set(got)
    assert 0 < got["moe_rows_used_pct.train"]["value"] <= 100
    # a CPU has no device plane and launches no kernel: the trace-fed
    # readers find nothing and their metrics are left out, as on a
    # commit whose program has no such kernel
    assert not [m for m in got if m.startswith(("gqa_", "kda_"))]
    assert not [m for m in got if m.endswith(".serve")]


def test_other_cells_do_not_see_the_new_metrics(bench_root):
    root, _ = bench_root
    result = harness.run_cell("bert-tiny.pretrain", seed=3, seconds=1.0,
                              trace=True, root=root, peaks=REHEARSAL_PEAKS,
                              check_device=False, log=lambda _m: None)
    assert result["correct"] is True
    assert not [m for m in result["metrics"] if m.startswith("gqa_")]


def _fake_trace(rows):
    return {"busy_s": 2.0, "window_s": 2.5, "device_ops": rows}


def test_the_new_readers_read_grouped_rows_and_nothing_else(monkeypatch):
    """The readers on a made-up reduction: they need the program's own
    ``flash_attention.grouped`` counter (absent on the parent commit, zero
    in the Kimi cell, whose MLA layer launches the same stream roles) and
    then read the attention rows, one a layer kind."""
    from paddle_tpu.ops.pallas import counters

    metrics = {m["name"]: m["read"] for m in harness.layer_metrics()}
    share, roof = (metrics[n] for n in NEW_METRICS)
    rows = [["fusion", 0.9], ["kernel:flash_attention_grouped", 0.6],
            ["kernel:flash_attention_window", 0.4],
            ["kernel:fused_xent_fwd", 0.05]]
    work = {"flash_attention_grouped": {"calls": 2, "flops": 3e10,
                                        "bytes": 3e6},
            "flash_attention_window": {"calls": 6, "flops": 1e6,
                                       "bytes": 5e9},
            "fused_xent_fwd": {"calls": 1, "flops": 9e12, "bytes": 1.0}}
    run = {"trace": _fake_trace(rows), "peaks": REHEARSAL_PEAKS,
           "cell": {"traffic": {"loss_fetch_every": 5}}}
    counters.reset()
    monkeypatch.setattr(counters, "step_work", lambda step: work)
    try:
        assert share(run) is None and roof(run) is None
        counters.bump("flash_attention", "grouped")
        assert share(run) == pytest.approx(100.0 * 1.0 / 2.0)
        # per role the larger of FLOP / 1e12 and bytes / 1e11, x 5 steps
        least = 5 * (3e10 / 1e12 + 5e9 / 1e11)
        assert roof(run) == pytest.approx(100.0 * least / 1.0)
        # a run with no trace (the CPU, --trace 0) reads nothing
        assert share({"trace": None}) is None
        assert roof(dict(run, trace=None)) is None
    finally:
        counters.reset()


def test_step_work_of_the_attention_roles_is_the_work_files(tiny,
                                                            monkeypatch):
    """The program's ledger for one TrainStep (what
    gqa_attn_roofline_pct reads) equals benchmarks/work_mellum2.py's
    count, at lane-dense heads, kernels in interpret mode; and the
    counters show the grouped, windowed path in every layer."""
    from jax.experimental import pallas as pl

    import paddle_tpu.framework.bringup as bringup
    from paddle_tpu.ops.pallas import counters

    monkeypatch.setattr(pl, "pallas_call",
                        functools.partial(pl.pallas_call, interpret=True))
    monkeypatch.setattr(bringup, "pallas_enabled", lambda: True)
    root, cell, cfg, _ = tiny
    cfg = dict(cfg, head_dim=128, num_attention_heads=2,
               num_key_value_heads=1, sliding_window=128)
    cell = dict(cell, traffic=dict(cell["traffic"], batch=1, seq=256))
    driver = harness.load_driver(cfg, root)
    mcfg = driver.model_config(cfg)
    batches = lm_traffic.lm_batches(cell["traffic"], mcfg["vocab_size"], 8)
    counters.reset()
    try:
        loop = driver.Loop(cfg, cell, driver.make_params(mcfg, 8), 8)
        loss = float(loop.feed_and_step(batches[0]))
        work = counters.step_work("train_step")
        snap = counters.snapshot()
    finally:
        counters.reset()
    assert np.isfinite(loss)
    want = work_mellum2.gqa_kernel_work(mcfg, 1, 256, itemsize=4)
    assert {k: work[k] for k in want} == want
    assert "flash_attention.xla" not in snap
    assert "flash_attention.grouped_replicated_kv" not in snap
    # every trace of a layer's attention is grouped; three in four are
    # windowed
    assert snap["flash_attention.grouped"] == snap["flash_attention.pallas"]
    assert snap["flash_attention.grouped"] % 4 == 0
    assert 4 * snap["flash_attention.windowed"] == \
        3 * snap["flash_attention.grouped"]
