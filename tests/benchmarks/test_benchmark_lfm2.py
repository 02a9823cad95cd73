"""The ``lfm2-24b-a2b`` configuration and its cell: the data files against
the catalog and the contract's form, the arithmetic against the issue's
numbers, the reference against the program through ``TrainStep``
(bfloat16 autocast and the fp8 control have to fail), the eight shares
of an expert layer against the uncut layer, the program's work ledger
against ``work_lfm2.py``, the new readers on a made-up reduction, EVERY
reader on a program that lacks what this PR adds (the fault PR 45 was
refused for), and the whole command at tiny size through the harness, on
the CPU, never a measurement."""
import ast
import functools
import json
import os
import re

import jax
import numpy as np
import pytest

from benchmarks import harness, lm_traffic, work_lfm2
from benchmarks.reference import lfm2 as ref

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CONFIG = "lfm2-24b-a2b"
CELL = CONFIG + ".pretrain-seq8k"
TINY = "lfm2-tiny.pretrain"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REHEARSAL_PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes": 1e9,
                   "hbm_bytes_per_s": 1e11}
#: what the contract calls a width: never cut, never in ``reduced``
WIDTH = re.compile(r"(hidden_size|intermediate|latent|state_size|proj|"
                   r"head_dim|_dim$|_rank$|expansion|experts_per)")
NEW_METRICS = ["gated_conv_device_share_pct.train",
               "gated_conv_roofline_pct.train"]
REDUCED = ["num_hidden_layers", "num_experts", "vocab_size", "layer_types"]
#: the accepted benchmark, first and in order: held by its HEAD
OLD_CONFIGS = ["bert-base", "kimi-linear-48b-a3b", "mellum2-12b-a2.5b",
               "nemotron-3-nano-30b-a3b", "kanana-2-30b-a3b", "ouro-2.6b"]
OLD_CELLS = ["bert-base.pretrain-seq512", "bert-base.pretrain-seq128",
             "kimi-linear-48b-a3b.pretrain-seq8k",
             "mellum2-12b-a2.5b.pretrain-seq8k",
             "nemotron-3-nano-30b-a3b.pretrain-seq8k",
             "kanana-2-30b-a3b.pretrain-seq8k", "ouro-2.6b.pretrain-seq8k"]


def _write(root, rel, obj):
    path = os.path.join(root, rel)
    assert not os.path.exists(path), f"{rel} would edit an existing file"
    with open(path, "w") as f:
        json.dump(obj, f)


@pytest.fixture(scope="module")
def tiny(bench_root):
    """(root, cell, config) of ``lfm2-tiny.pretrain``: the real files cut
    to test size and dropped into the copy of ``benchmarks/`` as NEW
    files. float32 end to end, so that the program is held to the
    reference tightly; four layers hold every kind (conv + dense, conv +
    dense, attention + experts, conv + experts)."""
    root, before = bench_root
    cfg = harness.load_json(os.path.join(root, f"configs/{CONFIG}.json"))
    cfg.update(name="lfm2-tiny", hidden_size=64, intermediate_size=96,
               moe_intermediate_size=32, num_attention_heads=4,
               num_key_value_heads=2, vocab_size=512, num_experts=4,
               num_hidden_layers=4, layer_types=cfg["layer_types"][:4])
    cfg["published"] = dict(cfg["published"], num_experts=16)
    cfg["program"] = dict(cfg["program"], amp_level="O0")
    _write(root, "configs/lfm2-tiny.json", cfg)
    cell = harness.load_json(os.path.join(root, f"workloads/{CELL}.json"))
    cell.update(name=TINY, config="lfm2-tiny")
    cell["traffic"].update(batch=2, seq=64, host_batches=4,
                           loss_fetch_every=2)
    cell["correct"].update(block_rows=32, limits={
        "loss_gap": 1e-5, "grad_norm_gap": 1e-3, "delta_norm_gap": 3e-3})
    _write(root, f"workloads/{TINY}.json", cell)
    return root, cell, cfg, before


def rehearse(root, cell, trace=False, seed=2 ** 31 + 46, seconds=1.0):
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
    return next(r for r in rows if r["name"] == "LFM2-24B-A2B")


def test_config_keeps_every_published_key_but_the_listed_cuts():
    row = _catalog_row()
    cfg = harness.load_json(os.path.join(harness.ROOT,
                                         f"configs/{CONFIG}.json"))
    pub = row["config"]
    assert cfg["source"] == row["source_url"]
    changed = sorted(k for k, v in pub.items() if cfg[k] != v)
    assert changed == sorted(cfg["reduced"]) == sorted(REDUCED)
    assert not [k for k in cfg["reduced"] if WIDTH.search(k)]
    # every width is the source's
    widths = {"hidden_size": 2048, "num_attention_heads": 32,
              "num_key_value_heads": 8, "intermediate_size": 11776,
              "moe_intermediate_size": 1536, "num_experts_per_tok": 4,
              "conv_L_cache": 3, "conv_bias": False, "norm_eps": 1e-5,
              "num_dense_layers": 2, "norm_topk_prob": True,
              "routed_scaling_factor": 1, "use_expert_bias": True,
              "rope_parameters": {"rope_theta": 1000000,
                                  "rope_type": "default"}}
    for key, value in widths.items():
        assert cfg[key] == pub[key] == value, key
    assert cfg["hidden_size"] // cfg["num_attention_heads"] == 64
    assert cfg["published"] == {
        "num_hidden_layers": 40, "num_experts": 64, "vocab_size": 65536,
        "layer_types": pub["layer_types"]}
    assert pub["layer_types"] == ["conv", "conv", "full_attention",
                                  "conv"] * 10
    # the cut: both dense layers and one whole period of the layers that
    # follow, 8 of 64 experts, an eighth of the vocabulary
    assert cfg["num_hidden_layers"] == 6
    assert cfg["layer_types"] == pub["layer_types"][:6]
    assert ref.layer_kinds(cfg) == [
        ("conv", "dense"), ("conv", "dense"), ("full_attention", "moe"),
        ("conv", "moe"), ("conv", "moe"), ("conv", "moe")]
    assert cfg["num_experts"] * 8 == 64 and cfg["vocab_size"] * 8 == 65536
    assert cfg["vocab_size"] % 128 == 0
    assert "one of 8 chips share each layer" in cfg["stands_for"]
    assert "first 6 of 40 layers" in cfg["stands_for"]
    assert "68.6%" in cfg["reduced_notes"]["num_hidden_layers"]
    assert set(cfg["reduced_notes"]) == set(REDUCED)
    # what no key of the source states
    assert (cfg["scoring_func"], cfg["tie_word_embeddings"]) == (
        "sigmoid", True)
    assert "1e-6" in cfg["assumed"]["renormalisation"]
    assert ref.RENORM_EPSILON == 1e-6
    assert any("1e-6" in d and "not in the program" in d
               for d in cfg["departs"])
    for key in ("scoring_func", "use_expert_bias", "renormalisation",
                "tie_word_embeddings", "intermediate_size", "weights",
                "optimizer", "precision"):
        assert cfg["assumed"][key], key
        assert key not in pub or key in ("use_expert_bias",
                                         "intermediate_size")
    assert any("56 absent experts" in d for d in cfg["departs"])
    assert any("fixed at zero" in d for d in cfg["departs"])
    assert any("recomputed" in d for d in cfg["departs"])
    assert any("dropout" in d for d in cfg["departs"])
    kanana = harness.load_json(os.path.join(
        harness.ROOT, "configs/kanana-2-30b-a3b.json"))
    assert cfg["program"] == kanana["program"]


def test_benchmark_json_only_gained_entries():
    """The lists are held by their HEAD: the six configurations and seven
    cells of the accepted benchmark first and in order, then this PR's;
    whatever a later PR appends behind them moves nothing here."""
    bench = harness.load_json(os.path.join(REPO, "BENCHMARK.json"))
    names = [c["name"] for c in bench["configs"]]
    assert names[:7] == OLD_CONFIGS + [CONFIG]
    cells = [w["name"] for w in bench["workloads"]]
    assert cells[:8] == OLD_CELLS + [CELL]
    entry = bench["workloads"][7]
    assert entry["chips"] == 1 and len(entry["why"]) <= 200
    assert (entry["config"], entry["traffic"]) == (CONFIG, "pretrain-seq8k")
    assert all(len(w["why"]) <= 200 and "\n" not in w["why"]
               for w in bench["workloads"] + bench["configs"])
    assert all(w["chips"] == 1 for w in bench["workloads"][:8])
    cell, cfg = harness.load_cell(CELL)
    assert cell["why"] == entry["why"] and cfg["name"] == CONFIG
    assert bench["configs"][6]["reduced"] == cfg["reduced"] == REDUCED
    assert bench["configs"][6]["file"] == f"benchmarks/configs/{CONFIG}.json"
    assert bench["configs"][6]["source"] == cfg["source"]
    new = [m for m in bench["per_layer"] if m.get("workloads") == [CELL]]
    assert [m["name"] for m in new] == NEW_METRICS
    files = {m["name"]: m for m in harness.layer_metrics()}
    for m in new:
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        for key in ("unit", "better", "source", "layer", "moves"):
            assert files[m["name"]][key] == m[key]
        assert m["layer"] == "kernel dispatch + Pallas kernels"
        assert (m["moves"], m["source"], m["unit"]) == (
            "train_tokens_per_s", "device_trace", "%")
    assert [m["name"] for m in bench["per_layer"]][23:25] == NEW_METRICS
    # nothing of the accepted benchmark lists the new cell, and what it
    # had is as it was
    for m in bench["per_layer"][:23]:
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

    def layer(n):
        return sum(int(np.prod(s)) for k, s in shapes.items()
                   if k.startswith(f"layers.{n}."))

    assert "head" not in shapes                 # the head is the embedding
    conv = 2048 * 6144 + 2048 * 3 + 2048 * 2048
    attention = 2 * 2048 * 2048 + 2 * 2048 * 512 + 2 * 64
    dense = 3 * 2048 * 11776
    experts = 8 * 3 * 2048 * 1536 + 2048 * 64
    norms = 2 * 2048
    assert (conv, attention, dense) == (16_783_360, 10_485_888, 72_351_744)
    assert experts == 75_497_472 + 131_072
    assert [layer(n) for n in range(6)] == [
        conv + dense + norms] * 2 + [attention + experts + norms] \
        + [conv + experts + norms] * 3
    assert (layer(0), layer(2), layer(3)) == (89_139_200, 86_118_528,
                                              92_416_000)
    assert total == 558_424_192
    assert round(total * 16 / 1e9, 2) == 8.93
    assert round(100 * total * 16 / 2 ** 34, 1) == 52.0
    assert mcfg["num_experts"] == 64 and mcfg["experts_held"] == 8
    feed = cell["traffic"]
    assert (feed["batch"], feed["seq"]) == (2, 8192)
    assert (feed["zipf_exponent"], feed["host_batches"],
            feed["loss_fetch_every"]) == (1.0, 8, 5)
    assert cell["correct"]["steps"] == 3
    assert cell["correct"]["block_rows"] == 512
    assert cell["correct"]["control_precisions"] == ["fp8"]
    assert set(cell["correct"]["limits"]) == {
        "loss_gap", "grad_norm_gap", "delta_norm_gap"}
    assert "PR 46" in cell["correct"]["limits_from"]
    kimi, _ = harness.load_cell("kimi-linear-48b-a3b.pretrain-seq8k")
    assert cell["optimizer"] == kimi["optimizer"]
    # 8 held of top 4 in 64: the ladder has the dense rung alone, so the
    # step's time does not follow the routing
    from paddle_tpu.nn.moe import _row_ladder

    assert _row_ladder(65536, 8, 64, 131072) == (65536,)
    assert 16384 * 4 // 64 == 1024 and 8 * 1024 == 8192


def test_the_program_counts_the_issues_parameters():
    """558,424,192 parameters as the PROGRAM counts them: the model built
    from the cell's own file on shape structs alone."""
    from paddle_tpu.models.causal_lm import CausalLM
    from paddle_tpu.ops.pallas import counters

    _, cfg = harness.load_cell(CELL)
    driver = harness.load_driver(cfg)
    mcfg = driver.model_config(cfg)
    before = counters.snapshot().get("causal_lm.tied_head", 0)
    shapes = {}

    def build():
        model = CausalLM.from_config(mcfg, recompute=True)
        shapes.update({k: tuple(p.shape)
                       for k, p in model.named_parameters()})
        return [p._value for p in model.parameters()]

    leaves = jax.eval_shape(build)
    assert sum(int(np.prod(x.shape)) for x in leaves) == 558_424_192
    assert shapes == {k: tuple(s) for k, s in
                      driver.param_shapes(mcfg).items()}
    assert counters.snapshot()["causal_lm.tied_head"] == before + 1


def test_flops_and_bytes_are_the_issues_numbers():
    _, cfg = harness.load_cell(CELL)
    mcfg = harness.load_driver(cfg).model_config(cfg)
    mflop = 1e-6 / 3.0      # forward MFLOP a token of a 3 x forward count
    h = 2048
    assert work_lfm2.mixer_matrix_params(mcfg, "conv") == 4 * h * h
    assert round(6.0 * 4 * h * h * mflop, 1) == 33.6
    assert round(6.0 * work_lfm2.ffn_matrix_params(mcfg, 0) * mflop, 1) \
        == 144.7
    # an expert layer needs 0.5 expert a token and the router
    assert round(6.0 * work_lfm2.ffn_matrix_params(mcfg, 2) * mflop, 1) == 9.7
    assert round(6.0 * work_lfm2.mixer_matrix_params(
        mcfg, "full_attention") * mflop + 2 * 2 * 64 * 32 * 4096 * 1e-6,
        1) == 54.5
    required = work_lfm2.train_flops_per_token(mcfg, 8192, 8191)
    assert round(required * mflop) == 584
    # the gated convolution: 11 arrays of 2 x 8192 x 2048 bfloat16 a
    # layer and step, 0.74 GB; 3.7 GB over the five layers
    work = work_lfm2.gated_conv_work(mcfg, 2, 8192)
    assert work == {"gated_conv": {
        "calls": 10, "flops": 0.0, "bytes": 5 * 11.0 * 2 * 8192 * 2048 * 2}}
    assert round(work["gated_conv"]["bytes"] / 5e9, 2) == 0.74
    assert work_lfm2.gated_conv_work(
        dict(mcfg, layer_types=["full_attention"]), 2, 8192) == {}
    gqa = work_lfm2.gqa_kernel_work(mcfg, 2, 8192)
    assert gqa["flash_attention_grouped"]["calls"] == 2
    assert gqa["flash_attention_grouped"]["flops"] \
        == 12.0 * 2 * 32 * 8192 * 4096 * 64


# ---------------------------------------------------------------------------
# the reference against the program, and the controls
# ---------------------------------------------------------------------------
def test_three_adamw_steps_through_trainstep_match_the_reference(tiny):
    root, cell, cfg, _ = tiny
    driver = harness.load_driver(cfg, root)
    mcfg = driver.model_config(cfg)
    batches = lm_traffic.lm_batches(cell["traffic"], mcfg["vocab_size"], 6)
    want = driver._reference(mcfg, cell, batches[:3], 6)
    loop = driver.Loop(cfg, cell, driver.make_params(mcfg, 6), 6)
    assert loop.model.recompute                    # the cell's own setting
    assert [(b.mixer_kind, b.ffn_kind) for b in loop.model.layers] == [
        ("conv", "dense"), ("conv", "dense"), ("gqa", "moe"),
        ("conv", "moe")]
    assert loop.model.tied and "head" not in dict(
        loop.model.named_parameters())
    moe = loop.model.layers[2].ffn
    assert (moe.score_func, moe.renormalize, moe.top_k) == (
        "sigmoid", True, 4)
    got = driver.first_steps(loop, mcfg, batches, 6, 3)
    sound = driver.compare(got, want, cell["correct"]["limits"])
    assert all(c["ok"] for c in sound), sound
    assert set(got["grad_norm"]) == set(want["grad_norm"]) == \
        set(driver.param_shapes(mcfg))
    # bfloat16 autocast does not pass the float32 limits
    low_cfg = dict(cfg, program=dict(cfg["program"], amp_level="O1"))
    loop = driver.Loop(low_cfg, cell, driver.make_params(mcfg, 6), 6)
    bf16 = driver.first_steps(loop, mcfg, batches, 6, 3)
    assert not all(c["ok"] for c in driver.compare(
        bf16, want, cell["correct"]["limits"]))
    # the control through the tool's entry: fp8 operands in the
    # reference's products, which has to come out as failing
    ctx, drv, _ = harness.context(TINY, 6, 1.0, root, check_device=False,
                                  log=lambda _m: None)
    out = drv.control(ctx)
    assert [c for c in out["checks"] if not c["ok"]]
    assert all(c["name"].startswith("fp8 ") for c in out["checks"])


def test_eight_shares_add_up_to_the_uncut_layer():
    """The share test: at a small size, the parts that the eight chips of
    the deployment compute (there is no shared expert to count once) are
    the uncut 64-expert reference's layer; and the program's layer on a
    share is the reference's share."""
    import paddle_tpu as paddle
    from paddle_tpu import nn

    cfg = {"num_experts": 64, "num_experts_per_tok": 4,
           "norm_topk_prob": True, "routed_scaling_factor": 1,
           "moe_intermediate_size": 16}
    key = jax.random.key(46)
    names = {"router.weight": (32, 64), "experts_gate": (64, 32, 16),
             "experts_up": (64, 32, 16), "experts_down": (64, 16, 32)}
    whole = {"f." + n: 0.3 * jax.random.normal(jax.random.fold_in(key, i), s)
             for i, (n, s) in enumerate(names.items())}
    x = jax.random.normal(jax.random.fold_in(key, 99), (48, 32))
    want = ref.moe(whole, "f.", x, cfg, ref._dense)
    total = 0.0
    for chip in range(8):
        share = {k: v[8 * chip:8 * chip + 8] if "experts_" in k else v
                 for k, v in whole.items()}
        part = ref.moe(share, "f.", x, dict(cfg, expert_offset=8 * chip),
                       ref._dense)
        total = total + part
        if chip in (0, 3, 7):
            # without the reference's 1e-6 (the configuration's
            # ``departs``): 5e-7 of a weight, far inside the tolerance
            layer = nn.SparseMoELayer(32, 16, 64, 4, experts_held=8,
                                      expert_offset=8 * chip)
            for name, q in layer.named_parameters():
                q._value = share["f." + name]
            got = layer(paddle.to_tensor(x)).value
            np.testing.assert_allclose(np.asarray(got), np.asarray(part),
                                       atol=2e-5)
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               atol=2e-5)
    # every token's four picks landed somewhere, once; the weights sum to
    # one less the epsilon's share
    picked, weight = ref.router_weights(x, whole["f.router.weight"], cfg)
    assert picked.shape == (48, 4)
    assert np.all(np.asarray(weight.sum(1)) < 1.0)
    np.testing.assert_allclose(np.asarray(weight.sum(1)), 1.0, rtol=1e-5)


def test_the_references_epsilon_moves_a_weight_by_under_a_millionth(
        monkeypatch):
    """The configuration's ``departs``: the program divides the picked
    scores by their sum alone. At the cell's initialisation (normal(0,
    0.02) router, unit-scale rows: four sigmoids near a half) the
    reference's 1e-6 under that sum moves a weight by 5e-7 of itself."""
    cfg = {"num_experts_per_tok": 4, "norm_topk_prob": True,
           "routed_scaling_factor": 1}
    key = jax.random.key(7)
    x = jax.random.normal(key, (256, 64))
    w = 0.02 * jax.random.normal(jax.random.fold_in(key, 1), (64, 64))
    picked, with_eps = ref.router_weights(x, w, cfg)
    monkeypatch.setattr(ref, "RENORM_EPSILON", 0.0)
    same, without = ref.router_weights(x, w, cfg)
    np.testing.assert_array_equal(np.asarray(picked), np.asarray(same))
    np.testing.assert_allclose(np.asarray(without.sum(1)), 1.0, rtol=3e-7)
    gap = np.abs(np.asarray(with_eps) / np.asarray(without) - 1.0)
    assert 0.0 < gap.max() < 1e-6


def test_reference_imports_nothing_of_the_program():
    with open(os.path.join(harness.ROOT, "reference", "lfm2.py")) as f:
        text = f.read()
    code = text.split('"""', 2)[2]
    assert "paddle_tpu" not in code
    assert "for j in range(taps)" in code   # a sum over shifted copies
    assert "for e in range(" in code        # a dense loop over the experts
    assert "+ RENORM_EPSILON)" in code
    assert 'p["embed.weight"].T' in code    # the tied head
    assert "pallas" not in code and "bfloat16" not in code


# ---------------------------------------------------------------------------
# the whole command at tiny size
# ---------------------------------------------------------------------------
def test_new_cell_rehearses_through_the_harness(tiny, digest):
    from paddle_tpu.ops.pallas import counters

    root, _, _, before = tiny
    counters.reset()        # the table is the process's: this run's alone
    result, lines = rehearse(root, TINY)
    assert result["correct"] is True, lines
    assert set(result["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert result["failed"] == 0 and result["attempted"] >= 2
    for name in ("loss_gap", "grad_norm_gap", "delta_norm_gap",
                 "window_compilations", "window_nonfinite_losses"):
        assert [ln for ln in lines if ln.startswith(f"check {name}: value")]
    counters_line = next(ln for ln in lines if "pallas counters" in ln)
    assert "'causal_lm.tied_head': 1" in counters_line
    assert "'gated_conv.xla': 3" in counters_line     # a CPU: the formula
    assert "'sparse_moe.gated'" in counters_line
    after = digest(root)
    assert {k: after[k] for k in before} == before   # no existing file


def test_traced_rehearsal_reports_the_cells_layer_metrics(tiny):
    root, _, _, _ = tiny
    result, _ = rehearse(root, TINY, trace=True)
    got = result["metrics"]
    assert {"mfu_pct.train", "dispatch_ms.train"} <= set(got)
    # a CPU has no device plane and launches no kernel: the trace-fed
    # readers find nothing and their metrics are left out
    assert not [m for m in got if m.startswith(
        ("gated_conv_", "mla_", "ssd_", "gqa_", "kda_"))]
    assert not [m for m in got if m.endswith(".serve")]


def test_the_parent_refuses_the_driver_cleanly(tiny, monkeypatch):
    """On a program without ``nn.GatedShortConv`` (the parent commit with
    this PR's benchmark files laid over it) the driver refuses BEFORE the
    reference's minutes: ``run.py`` then prints REFUSED and exits 2."""
    from paddle_tpu import nn

    root, _, cfg, _ = tiny
    driver = harness.load_driver(cfg, root)
    monkeypatch.delattr(nn, "GatedShortConv")
    monkeypatch.setattr(driver.reference, "train", lambda *a, **k: 1 / 0)
    with pytest.raises(harness.Refused, match="GatedShortConv"):
        harness.run_cell(TINY, seed=1, seconds=1.0, trace=False, root=root,
                         peaks=REHEARSAL_PEAKS, check_device=False,
                         log=lambda _m: None)


# ---------------------------------------------------------------------------
# the readers
# ---------------------------------------------------------------------------
def _fake_trace(rows):
    return {"busy_s": 2.0, "window_s": 2.5, "device_ops": rows}


def test_the_new_readers_read_the_gated_conv_row_behind_its_counter(
        monkeypatch):
    from paddle_tpu.ops.pallas import counters

    metrics = {m["name"]: m["read"] for m in harness.layer_metrics()}
    share, roof = (metrics[n] for n in NEW_METRICS)
    rows = [["fusion", 0.9], ["kernel:gated_conv", 0.1],
            ["kernel:mamba2_conv", 0.3], ["kernel:fused_xent_fwd", 0.05]]
    work = {"gated_conv": {"calls": 10, "flops": 0.0, "bytes": 1e9},
            "mamba2_conv": {"calls": 2, "flops": 0.0, "bytes": 1e12}}
    run = {"trace": _fake_trace(rows), "peaks": REHEARSAL_PEAKS,
           "cell": {"traffic": {"loss_fetch_every": 5}}}
    monkeypatch.setattr(counters, "step_work", lambda step: work)
    # the counter gates both: absent (the parent), on the formula alone
    monkeypatch.setattr(counters, "snapshot", lambda: {})
    assert share(run) is None and roof(run) is None
    monkeypatch.setattr(counters, "snapshot",
                        lambda: {"gated_conv.xla": 5,
                                 "mamba2_stage.fused": 8})
    assert share(run) is None and roof(run) is None
    monkeypatch.setattr(counters, "snapshot", lambda: {"gated_conv.fused": 5})
    assert share(run) == pytest.approx(100.0 * 0.1 / 2.0)
    # bytes / 1e11 x 5 steps over the row's seconds
    assert roof(run) == pytest.approx(100.0 * 5 * 1e9 / 1e11 / 0.1)
    # a reduction without the row, a run with no trace, no ledger
    other = dict(run, trace=_fake_trace(rows[:1] + rows[2:]))
    assert share(other) is None and roof(other) is None
    # the row under the reduction's ten, as the cell's driver hands it on
    under = dict(other, observations={
        "kernel_rows_under_top": [["kernel:gated_conv", 0.1]]})
    assert share(under) == share(run) and roof(under) == roof(run)
    assert other["trace"]["device_ops"] == rows[:1] + rows[2:]
    assert share({"trace": None}) is None
    assert roof(dict(run, trace=None)) is None
    monkeypatch.setattr(counters, "step_work", lambda step: {})
    assert roof(run) is None


#: what 312beb6 (this PR's parent) returns in the Kanana cell's traced
#: rehearsal: no ``gated_conv.*``, no ``causal_lm.tied_head``, no
#: ``gated_conv`` role
PARENT_SNAPSHOT = {
    "flash_attention.pallas": 7, "flash_attention.latent": 7,
    "flash_attention.kept_across_recompute": 7, "mla.rotary": 7,
    "sparse_moe.every_pair": 6, "sparse_moe.gated": 6,
    "fused_xent.pallas": 1, "fused_xent.ladder": 1}
PARENT_WORK = {
    "flash_attention_stream_fwd": {"calls": 7, "flops": 2e13, "bytes": 3e9},
    "flash_attention_stream_bwd": {"calls": 7, "flops": 4e13, "bytes": 6e9},
    "fused_xent_fwd": {"calls": 1, "flops": 1e12, "bytes": 1e8},
    "fused_xent_bwd": {"calls": 1, "flops": 2e12, "bytes": 2e8}}
PARENT_ROWS = [
    ["fusion", 1.374], ["kernel:flash_attention_stream_bwd", 1.015],
    ["kernel:flash_attention_stream_fwd", 0.471],
    ["multiply_reduce_fusion", 0.305], ["copy", 0.246],
    ["bitcast_convert_fusion", 0.174], ["cond", 0.148],
    ["kernel:fused_xent_bwd", 0.116], ["pad_maximum_fusion", 0.115],
    ["multiply_subtract_fusion", 0.099]]


def test_every_reader_returns_a_number_or_none_on_the_parents_program(
        monkeypatch):
    """The rule PR 45 broke (``benchmark_breaks_parent``): the driver
    makes traced runs of the PARENT's program with this PR's benchmark
    files, ``layer_metrics()`` imports every reader there and ``run_cell``
    calls each one whose ``moves`` the cell lists. With the program's
    counters and ledger as the parent has them in the Kanana cell, every
    reader gives a number or None and none raises."""
    from paddle_tpu.ops.pallas import counters

    monkeypatch.setattr(counters, "snapshot", lambda: dict(PARENT_SNAPSHOT))
    monkeypatch.setattr(counters, "step_work",
                        lambda step: {k: dict(v)
                                      for k, v in PARENT_WORK.items()})
    cell, cfg = harness.load_cell("kanana-2-30b-a3b.pretrain-seq8k")
    run = {"trace": dict(_fake_trace(PARENT_ROWS), idle_pct=0.2,
                         idle_gaps=[["bench.step", 0.004]], n_devices=1),
           "peaks": harness.load_json(os.path.join(
               harness.ROOT, "peaks.json"))["TPU v5 lite"],
           "cell": cell, "config": cfg, "chips": 1, "seconds": 51.0,
           "attempted": 60, "failed": 0, "setup_s": 25.0,
           "device": {"memory_peak_bytes": 9_500_000_000},
           "metrics": {"train_tokens_per_s": 18548.0},
           "observations": {"dispatch_ms": [4.7, 4.8, 4.9],
                            "train_tokens_per_s": 18548.0,
                            "flops_per_token": 3.7e9,
                            "moe_rows_used_pct": 37.5,
                            "hbm_peak_pct": 55.5}}
    seen = {}
    for meta in harness.layer_metrics():
        if meta["moves"] not in cell["end_to_end"]:
            continue
        value = meta["read"](run)
        assert value is None or np.isfinite(float(value)), meta["name"]
        seen[meta["name"]] = value
    assert seen["gated_conv_device_share_pct.train"] is None
    assert seen["gated_conv_roofline_pct.train"] is None
    assert seen["mla_attn_device_share_pct.train"] == pytest.approx(
        100.0 * (1.015 + 0.471) / 2.0)
    assert seen["mfu_pct.train"] > 0


def test_the_new_benchmark_code_imports_the_program_inside_read_alone():
    """An ``ast`` walk: no file-level import from ``paddle_tpu`` in the
    two new readers or in ``work_lfm2.py`` (a module imported where the
    parent's program runs must name nothing the parent lacks), and inside
    ``read`` only ``ops.pallas.counters``."""
    files = [os.path.join(harness.ROOT, "layer_metrics", n + ".py")
             for n in NEW_METRICS] + [os.path.join(harness.ROOT,
                                                   "work_lfm2.py")]
    for path in files:
        with open(path) as f:
            tree = ast.parse(f.read())
        for node in tree.body:
            if isinstance(node, (ast.Import, ast.ImportFrom)):
                names = [a.name for a in node.names] + [
                    getattr(node, "module", "") or ""]
                assert not [n for n in names if "paddle_tpu" in n], path
        inner = [n for n in ast.walk(tree)
                 if isinstance(n, ast.ImportFrom)
                 and "paddle_tpu" in (n.module or "")]
        for node in inner:
            assert node.module == "paddle_tpu.ops.pallas", path
            assert [a.name for a in node.names] == ["counters"], path
    # the driver may import the program only inside its functions too
    with open(os.path.join(harness.ROOT, "drivers",
                           "conv_hybrid_lm_step.py")) as f:
        tree = ast.parse(f.read())
    for node in tree.body:
        if isinstance(node, (ast.Import, ast.ImportFrom)):
            assert "paddle_tpu" not in (getattr(node, "module", "") or "")
            assert not [a for a in node.names if "paddle_tpu" in a.name]


def test_step_work_of_the_gated_conv_role_is_the_work_files(tiny,
                                                            monkeypatch):
    """The program's ledger for one TrainStep (what
    gated_conv_roofline_pct reads) equals benchmarks/work_lfm2.py's count,
    at lane-dense widths (hidden 128, heads of 64), kernels in interpret
    mode; and the counters the acceptance names are set: ``gated_conv.
    fused`` once a conv layer, ``flash_attention.grouped`` and ``.kept_
    across_recompute`` once, ``causal_lm.tied_head`` once; the lowered
    step carries the ``gated_conv`` scope."""
    from jax.experimental import pallas as pl

    import paddle_tpu.framework.bringup as bringup
    from paddle_tpu.ops.pallas import counters

    monkeypatch.setattr(pl, "pallas_call",
                        functools.partial(pl.pallas_call, interpret=True))
    monkeypatch.setattr(bringup, "pallas_enabled", lambda: True)
    root, cell, cfg, _ = tiny
    cfg = dict(cfg, hidden_size=128, num_attention_heads=2,
               num_key_value_heads=1)
    cell = dict(cell, traffic=dict(cell["traffic"], batch=1, seq=256))
    driver = harness.load_driver(cfg, root)
    mcfg = driver.model_config(cfg)
    batches = lm_traffic.lm_batches(cell["traffic"], mcfg["vocab_size"], 8)
    counters.reset()
    jax.clear_caches()
    try:
        loop = driver.Loop(cfg, cell, driver.make_params(mcfg, 8), 8)
        loss = float(loop.feed_and_step(batches[0]))
        work = counters.step_work("train_step")
        snap = counters.snapshot()
        text = loop.step.lower(*[loop._to_tensor(a) for a in batches[0]]
                               ).as_text(debug_info=True)
    finally:
        counters.reset()
        jax.clear_caches()
    assert np.isfinite(loss)
    want = work_lfm2.gated_conv_work(mcfg, 1, 256, itemsize=4)
    assert want["gated_conv"]["calls"] == 6
    assert {k: work[k] for k in want} == want
    gqa = work_lfm2.gqa_kernel_work(mcfg, 1, 256, itemsize=4)
    assert {k: work[k] for k in gqa} == gqa
    assert "gated_conv.xla" not in snap and "flash_attention.xla" not in snap
    assert snap["gated_conv.fused"] == 3
    assert snap["flash_attention.grouped"] == 1
    assert snap["flash_attention.kept_across_recompute"] == 1
    assert snap["causal_lm.tied_head"] == 1
    assert snap["sparse_moe.gated"] == snap["sparse_moe.every_pair"]
    assert "gated_conv" in text


def test_the_cells_tracer_hands_on_a_kernel_row_under_the_tenth(tiny):
    """``trace_reduce.reduce`` keeps the ten largest rows; this cell's
    mixer kernel is a small row by design. The driver's tracer leaves the
    reduction (the result line's ``breakdown``) as the harness makes it
    and keeps the ``kernel:`` rows under the tenth (and nothing else)
    beside it, where the two new readers find them."""
    from benchmarks import trace_reduce

    root, _, cfg, _ = tiny
    driver = harness.load_driver(cfg, root)
    names = ["fusion", "copy", "cond", "reshape", "transpose", "pad",
             "slice", "gather", "scatter", "reduce", "select", "iota"]
    events = [(f"{n}.{k}", 1000.0 * k, 100.0 - k)
              for k, n in enumerate(names)]
    events += [("kernel:gated_conv", 20000.0, 5.0), ("tiny.1", 21000.0, 1.0)]
    trace = {"devices": {"/device:TPU:0": events}, "host_spans": []}
    tracer = driver.KernelRowTracer("x")
    tracer.take(trace)
    assert tracer.result == trace_reduce.reduce(trace)
    assert [n for n, _ in tracer.result["device_ops"]] == names[:10]
    assert tracer.kernels == ["kernel:gated_conv"]
    assert tracer.under_top == [["kernel:gated_conv", 5e-9]]
    # a row among the ten is not handed on twice
    big = dict(trace, devices={"/device:TPU:0": events[:9] + events[-2:]})
    tracer.take(big)
    assert tracer.under_top == []
    # a trace with no device operation stays None, and forgets the rows
    tracer.take(trace)
    tracer.take({"devices": {}, "host_spans": []})
    assert tracer.result is None and tracer.under_top == []
