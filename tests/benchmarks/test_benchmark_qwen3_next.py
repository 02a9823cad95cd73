"""The ``qwen3-next-80b-a3b`` configuration and its cell: the data files
against the catalog and the contract's form, the arithmetic against the
issue's numbers, the reference against the program through ``TrainStep``
(bfloat16 autocast and the fp8 control have to fail), the sixteen shares
of an expert layer with the gated shared expert counted once against the
uncut layer, the program's work ledger against ``work_qwen3_next.py``, the
new readers on a made-up reduction, EVERY reader on a program that lacks
what this PR adds (the fault PR 45 was refused for), and the whole command
at tiny size through the harness, on the CPU, never a measurement."""
import ast
import functools
import json
import os
import re

import jax
import numpy as np
import pytest

from benchmarks import harness, lm_traffic, work_qwen3_next
from benchmarks.reference import qwen3_next as ref

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CONFIG = "qwen3-next-80b-a3b"
CELL = CONFIG + ".pretrain-seq8k"
TINY = "qwen3-next-tiny.pretrain"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REHEARSAL_PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes": 1e9,
                   "hbm_bytes_per_s": 1e11}
#: what the contract calls a width: never cut, never in ``reduced``
WIDTH = re.compile(r"(hidden_size|intermediate|latent|state_size|proj|"
                   r"head_dim|_dim$|_rank$|expansion|experts_per)")
NEW_METRICS = ["gdn_device_share_pct.train", "gdn_roofline_pct.train",
               "gated_attn_device_share_pct.train",
               "gated_attn_roofline_pct.train"]
REDUCED = ["num_hidden_layers", "num_experts", "vocab_size"]
#: the accepted benchmark, first and in order: held by its HEAD
OLD_CONFIGS = ["bert-base", "kimi-linear-48b-a3b", "mellum2-12b-a2.5b",
               "nemotron-3-nano-30b-a3b", "kanana-2-30b-a3b", "ouro-2.6b",
               "lfm2-24b-a2b"]
OLD_CELLS = ["bert-base.pretrain-seq512", "bert-base.pretrain-seq128",
             "kimi-linear-48b-a3b.pretrain-seq8k",
             "mellum2-12b-a2.5b.pretrain-seq8k",
             "nemotron-3-nano-30b-a3b.pretrain-seq8k",
             "kanana-2-30b-a3b.pretrain-seq8k", "ouro-2.6b.pretrain-seq8k",
             "lfm2-24b-a2b.pretrain-seq8k"]


def _write(root, rel, obj):
    path = os.path.join(root, rel)
    assert not os.path.exists(path), f"{rel} would edit an existing file"
    with open(path, "w") as f:
        json.dump(obj, f)


@pytest.fixture(scope="module")
def tiny(bench_root):
    """(root, cell, config) of ``qwen3-next-tiny.pretrain``: the real files
    cut to test size and dropped into the copy of ``benchmarks/`` as NEW
    files. float32 end to end, so that the program is held to the
    reference tightly; four layers are one whole period (three Gated
    DeltaNet layers of 2 key heads under 4 value heads, one gated
    attention layer of 4 query heads on 2 key heads), 8 of 32 experts
    held of top 4 (more than twice the picks, so sorted rungs alone)."""
    root, before = bench_root
    cfg = harness.load_json(os.path.join(root, f"configs/{CONFIG}.json"))
    cfg.update(name="qwen3-next-tiny", hidden_size=64, intermediate_size=96,
               moe_intermediate_size=32, shared_expert_intermediate_size=32,
               num_attention_heads=4, num_key_value_heads=2, head_dim=32,
               linear_num_key_heads=2, linear_num_value_heads=4,
               linear_key_head_dim=16, linear_value_head_dim=16,
               vocab_size=512, num_experts=8, num_experts_per_tok=3,
               num_hidden_layers=4)
    cfg["published"] = dict(cfg["published"], num_experts=32)
    cfg["program"] = dict(cfg["program"], amp_level="O0")
    _write(root, "configs/qwen3-next-tiny.json", cfg)
    cell = harness.load_json(os.path.join(root, f"workloads/{CELL}.json"))
    cell.update(name=TINY, config="qwen3-next-tiny")
    cell["traffic"].update(batch=2, seq=64, host_batches=4,
                           loss_fetch_every=2)
    # the change's limit: a head's decay gradient can be all but zero
    # (A = 16 under softplus(a + 1) forgets at e^-21 a token) and Adam
    # divides it by its own size, so one of A_log's four elements moves at
    # full rate by the sign of rounding noise: 0.008 on one such leaf here
    cell["correct"].update(block_rows=32, limits={
        "loss_gap": 1e-5, "grad_norm_gap": 1e-3, "delta_norm_gap": 3e-2})
    _write(root, f"workloads/{TINY}.json", cell)
    return root, cell, cfg, before


def rehearse(root, cell, trace=False, seed=2 ** 31 + 49, seconds=1.0):
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
    return next(r for r in rows if r["name"] == "Qwen3-Next-80B-A3B-Instruct")


def test_config_keeps_every_published_key_but_the_listed_cuts():
    row = _catalog_row()
    cfg = harness.load_json(os.path.join(harness.ROOT,
                                         f"configs/{CONFIG}.json"))
    pub = row["config"]
    assert cfg["source"] == row["source_url"]
    changed = sorted(k for k, v in pub.items() if cfg[k] != v)
    assert changed == sorted(cfg["reduced"]) == sorted(REDUCED)
    assert not [k for k in cfg["reduced"] if WIDTH.search(k)]
    widths = {"hidden_size": 2048, "head_dim": 256,
              "num_attention_heads": 16, "num_key_value_heads": 2,
              "intermediate_size": 5120, "moe_intermediate_size": 512,
              "shared_expert_intermediate_size": 512,
              "num_experts_per_tok": 10, "linear_num_key_heads": 16,
              "linear_num_value_heads": 32, "linear_key_head_dim": 128,
              "linear_value_head_dim": 128, "linear_conv_kernel_dim": 4,
              "full_attention_interval": 4, "partial_rotary_factor": 0.25,
              "rope_theta": 10000000, "rope_scaling": None,
              "rms_norm_eps": 1e-6, "norm_topk_prob": True,
              "decoder_sparse_step": 1, "mlp_only_layers": [],
              "tie_word_embeddings": False}
    for key, value in widths.items():
        assert cfg[key] == pub[key] == value, key
    assert cfg["published"] == {"num_hidden_layers": 48, "num_experts": 512,
                                "vocab_size": 151936}
    # the cut: one whole period, 32 of 512 experts, an eighth of the
    # vocabulary rounded UP to whole 128-column blocks
    assert cfg["num_hidden_layers"] == 4
    assert ref.layer_kinds(cfg) == [("gdn", "moe")] * 3 + [("gqa", "moe")]
    assert cfg["num_experts"] * 16 == 512
    assert cfg["vocab_size"] == 19072 == 149 * 128
    assert 151936 / 8 <= cfg["vocab_size"] < 151936 / 8 + 128
    assert "one of 16 chips that share each layer" in cfg["stands_for"]
    assert "first of twelve pipeline stages" in cfg["stands_for"]
    assert set(cfg["reduced_notes"]) == set(REDUCED)
    assert "19,456" in cfg["reduced_notes"]["vocab_size"]
    # what no key of the source states, each with its other reading
    assert (cfg["attn_output_gate"], cfg["zero_centered_norm"],
            cfg["qk_norm"]) == (True, True, True)
    for key in ("attn_output_gate", "zero_centered_norm", "qk_norm",
                "gated_delta_net", "rope", "shared_expert", "router"):
        assert "other reading" in cfg["assumed"][key], key
        assert key not in pub
    for key in ("precision", "weights", "data", "optimizer"):
        assert cfg["assumed"][key], key
    assert "A uniform in (0, 16]" in cfg["assumed"]["weights"]
    assert any("multi-token-prediction" in d and "MTP 1" in d
               for d in cfg["departs"])
    assert any("480 absent experts" in d for d in cfg["departs"])
    assert any("AdamW" in d for d in cfg["departs"])
    assert any("permutation" in d for d in cfg["departs"])
    assert any("recomputed" in d for d in cfg["departs"])
    assert any("max_position_embeddings" in d and "use_sliding_window" in d
               and "intermediate_size" in d for d in cfg["departs"])
    kimi = harness.load_json(os.path.join(
        harness.ROOT, "configs/kimi-linear-48b-a3b.json"))
    assert cfg["program"] == kimi["program"]


def test_benchmark_json_only_gained_entries():
    """The lists are held by their HEAD: the seven configurations and
    eight cells of the accepted benchmark first and in order, then this
    PR's; whatever a later PR appends behind them moves nothing here."""
    bench = harness.load_json(os.path.join(REPO, "BENCHMARK.json"))
    names = [c["name"] for c in bench["configs"]]
    assert names[:8] == OLD_CONFIGS + [CONFIG]
    cells = [w["name"] for w in bench["workloads"]]
    assert cells[:9] == OLD_CELLS + [CELL]
    entry = bench["workloads"][8]
    assert entry["chips"] == 1 and len(entry["why"]) <= 200
    assert (entry["config"], entry["traffic"]) == (CONFIG, "pretrain-seq8k")
    assert all(len(w["why"]) <= 200 and "\n" not in w["why"]
               for w in bench["workloads"] + bench["configs"])
    assert all(w["chips"] == 1 for w in bench["workloads"][:9])
    cell, cfg = harness.load_cell(CELL)
    assert cell["why"] == entry["why"] and cfg["name"] == CONFIG
    assert "16x" in cell["why"] and "160 rows" in cell["why"]
    assert bench["configs"][7]["reduced"] == cfg["reduced"] == REDUCED
    assert bench["configs"][7]["file"] == f"benchmarks/configs/{CONFIG}.json"
    assert bench["configs"][7]["source"] == cfg["source"]
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
    assert [m["name"] for m in bench["per_layer"]][25:29] == NEW_METRICS
    # nothing of the accepted benchmark lists the new cell, and what it
    # had is as it was
    for m in bench["per_layer"][:25]:
        assert CELL not in m.get("workloads", [])
    assert bench["run_seconds"] == 51
    assert [e["name"] for e in bench["end_to_end"]] == [
        "train_tokens_per_s", "setup_s"]
    assert len(json.dumps(bench, indent=2)) < 64 * 1024


def test_cell_is_the_issues_traffic_and_counts_the_share_its_files_state():
    cell, cfg = harness.load_cell(CELL)
    driver = harness.load_driver(cfg)
    mcfg = driver.model_config(cfg)
    shapes = driver.param_shapes(mcfg)
    total = sum(int(np.prod(s)) for s in shapes.values())

    def layer(n):
        return sum(int(np.prod(s)) for k, s in shapes.items()
                   if k.startswith(f"layers.{n}."))

    gdn = 2048 * 12288 + 2048 * 64 + 4 * 8192 + 32 + 32 + 128 + 4096 * 2048
    attention = 2048 * 8192 + 2 * 2048 * 512 + 4096 * 2048 + 2 * 256
    experts = 2048 * 512 + 32 * 3 * 2048 * 512 + 3 * 2048 * 512 + 2048
    assert (gdn, attention, experts) == (33_718_464, 27_263_488,
                                         104_859_648)
    assert [layer(n) for n in range(4)] == [gdn + experts + 4096] * 3 \
        + [attention + experts + 4096]
    assert (layer(0), layer(3)) == (138_582_208, 132_127_232)
    assert total == 625_994_816
    assert round(total * 16 / 1e9, 2) == 10.02
    assert round(100 * total * 16 / 2 ** 34, 1) == 58.3
    # the same shapes at 48 layers, 512 experts and the whole vocabulary:
    # the published model's size
    whole = 36 * (gdn + 4096) + 12 * (attention + 4096) \
        + 48 * (experts + 480 * 3 * 2048 * 512) + 2 * 151936 * 2048 + 2048
    assert round(whole / 1e9, 1) == 79.7
    assert mcfg["num_experts"] == 512 and mcfg["experts_held"] == 32
    feed = cell["traffic"]
    assert (feed["kind"], feed["batch"], feed["seq"]) == ("lm_feed", 1, 8192)
    assert (feed["zipf_exponent"], feed["host_batches"],
            feed["loss_fetch_every"]) == (1.0, 8, 5)
    assert cell["correct"]["steps"] == 3
    assert cell["correct"]["block_rows"] == 512
    assert cell["correct"]["control_precisions"] == ["fp8"]
    assert set(cell["correct"]["limits"]) == {
        "loss_gap", "grad_norm_gap", "delta_norm_gap"}
    assert "PR 49" in cell["correct"]["limits_from"]
    kimi, _ = harness.load_cell("kimi-linear-48b-a3b.pretrain-seq8k")
    assert cell["optimizer"] == kimi["optimizer"]
    # 32 held of top 10 in 512: sorted rungs alone; a held expert sees
    # 160 rows a step where 16 data-parallel ranks bring 2,560
    from paddle_tpu.nn.moe import _row_ladder

    assert _row_ladder(81920, 32, 512) == (40960, 81920)
    assert 8192 * 10 // 512 == 160 and 16 * 160 == 2560


def test_the_seeded_start_is_the_familys_where_it_is_not_the_initialisers():
    _, cfg = harness.load_cell(CELL)
    driver = harness.load_driver(cfg)
    mcfg = dict(driver.model_config(cfg), hidden_size=64, vocab_size=256,
                moe_intermediate_size=32, experts_held=2,
                shared_expert_intermediate_size=32, head_dim=32)
    params = driver.make_params(mcfg, 2 ** 31 + 5)
    assert set(params) == set(driver.param_shapes(mcfg))
    for name, value in params.items():
        value = np.asarray(value)
        if name.endswith("norm.weight"):
            assert np.all(value == 0.0), name       # 1 + w starts at one
        elif name.endswith("o_norm") or name.endswith("dt_bias"):
            assert np.all(value == 1.0), name
        elif name.endswith("A_log"):
            assert np.all(np.isfinite(value)) and value.max() <= np.log(16)
            assert value.min() < np.log(8.0)
        elif name.endswith("qkv_conv"):
            assert np.abs(value).max() <= 0.5
        else:
            assert 0.015 < value.std() < 0.025, name
    again = driver.make_params(mcfg, 2 ** 31 + 5)
    other = driver.make_params(mcfg, 2 ** 31 + 6)
    key = "layers.0.mixer.A_log"
    assert np.all(np.asarray(again[key]) == np.asarray(params[key]))
    assert np.any(np.asarray(other[key]) != np.asarray(params[key]))


def test_flops_and_bytes_are_the_issues_numbers():
    _, cfg = harness.load_cell(CELL)
    mcfg = harness.load_driver(cfg).model_config(cfg)
    mflop = 1e-6 / 3.0      # forward MFLOP a token of a 3 x forward count
    assert round(6.0 * work_qwen3_next.mixer_matrix_params(mcfg, "gdn")
                 * mflop) == 67
    assert round(work_qwen3_next.recurrence_flops_per_token(mcfg) * 1e-6,
                 1) == 3.1
    attention = 6.0 * work_qwen3_next.mixer_matrix_params(mcfg, "gqa") \
        * mflop
    causal = 2 * 2 * 256 * 16 * 4096 * 1e-6
    assert (round(attention + causal), round(causal)) == (122, 67)
    assert round(6.0 * work_qwen3_next.ffn_matrix_params(mcfg) * mflop,
                 1) == 12.3
    assert round(6.0 * 2048 * 19072 * mflop) == 78
    required = work_qwen3_next.train_flops_per_token(mcfg, 8192, 8191)
    assert round(required * mflop) == 461
    # the recurrence: q and k at 16 heads, g and beta one float a head
    work = work_qwen3_next.gdn_kernel_work(mcfg, 1, 8192)
    moved = 4.0 * 8192 * (2 * 16 * 128 + 32 + 32 * 257)
    flops = 6.0 * 8192 * 32 * 128 * 128
    assert work == {
        "kda_chunk_fwd": {"calls": 3, "flops": 3 * flops,
                          "bytes": 3 * moved},
        "kda_chunk_bwd": {"calls": 3, "flops": 6 * flops,
                          "bytes": 6 * moved}}
    # under the Kimi call's bytes at the same launch shape
    assert moved < 4.0 * 8192 * 32 * (5 * 128 + 1)
    gqa = work_qwen3_next.gated_attn_kernel_work(mcfg, 1, 8192)
    assert gqa["flash_attention_grouped"]["calls"] == 2
    assert gqa["flash_attention_grouped"]["flops"] \
        == 12.0 * 16 * 8192 * 4096 * 256
    assert work_qwen3_next.gdn_kernel_work(
        dict(mcfg, full_attention_interval=1), 1, 8192) == {}


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
        ("gdn", "moe")] * 3 + [("gqa", "moe")]
    assert not loop.model.tied
    attention = loop.model.layers[3].mixer
    assert attention.output_gate and attention.rotary_dim == 8
    moe = loop.model.layers[0].ffn
    assert (moe.score_func, moe.renormalize, moe.top_k) == (
        "softmax", True, 3)
    assert moe.shared_gate is not None
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


def test_sixteen_shares_and_one_gated_shared_expert_add_up_to_the_layer():
    """The share test: at a small size, the routed parts that the sixteen
    chips of the deployment compute, with the gated shared expert (which
    every chip computes alike) counted ONCE, are the uncut 64-expert
    reference's layer; and the program's layer on a share is the
    reference's share."""
    import paddle_tpu as paddle
    from paddle_tpu import nn

    cfg = {"num_experts": 64, "num_experts_per_tok": 5,
           "norm_topk_prob": True, "moe_intermediate_size": 16}
    key = jax.random.key(49)
    names = {"router.weight": (32, 64), "experts_gate": (64, 32, 16),
             "experts_up": (64, 32, 16), "experts_down": (64, 16, 32),
             "shared.gate_proj.weight": (32, 24),
             "shared.up_proj.weight": (32, 24),
             "shared.down_proj.weight": (24, 32),
             "shared_gate.weight": (32, 1)}
    whole = {"f." + n: 0.3 * jax.random.normal(jax.random.fold_in(key, i), s)
             for i, (n, s) in enumerate(names.items())}
    x = jax.random.normal(jax.random.fold_in(key, 99), (48, 32))
    want = ref.moe(whole, "f.", x, cfg, ref._dense)
    shared = ref.shared_expert(whole, "f.", x, ref._dense)
    total = shared
    for chip in range(16):
        share = {k: v[4 * chip:4 * chip + 4] if "experts_" in k else v
                 for k, v in whole.items()}
        scfg = dict(cfg, expert_offset=4 * chip)
        part = ref.routed(share, "f.", x, scfg, ref._dense)
        total = total + part
        if chip in (0, 5, 15):
            layer = nn.SparseMoELayer(
                32, 16, 64, 5, experts_held=4, expert_offset=4 * chip,
                score_func="softmax", shared_width=24, shared_gate=True)
            for name, q in layer.named_parameters():
                q._value = share["f." + name]
            got = layer(paddle.to_tensor(x)).value
            np.testing.assert_allclose(np.asarray(got),
                                       np.asarray(part + shared), atol=2e-5)
            np.testing.assert_allclose(
                np.asarray(ref.moe(share, "f.", x, scfg, ref._dense)),
                np.asarray(part + shared), atol=1e-6)
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               atol=2e-5)
    # every token's five picks landed somewhere, once, and sum to one
    picked, weight = ref.router_weights(x, whole["f.router.weight"], cfg)
    assert picked.shape == (48, 5)
    np.testing.assert_allclose(np.asarray(weight.sum(1)), 1.0, rtol=1e-5)


def test_reference_imports_nothing_of_the_program():
    with open(os.path.join(harness.ROOT, "reference", "qwen3_next.py")) as f:
        text = f.read()
    doc, code = text.split('"""', 2)[1:]
    assert "paddle_tpu" not in code
    assert "delta_rule_recurrence(" in code    # token by token
    assert "for e in range(" in code        # a dense loop over the experts
    assert "jax.lax.top_k(scores" in code   # a plain top k over all
    assert "1.0 + w" in code                # the zero-centred norm
    assert 'p["head"].T' in code            # the untied head
    assert "pallas" not in code and "bfloat16" not in code
    # the equations stand in the docstring
    for line in ("S' = exp(g_t,j) S_{t-1}", "(1 + w)",
                 "value head j reads query/key head j // r",
                 "sigmoid(x w_s) * Shared(x)", "o * sigmoid(gate)"):
        assert line in doc, line


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
    for counted in ("'gdn.scalar_decay': 3", "'gqa.output_gate': 1",
                    "'gqa.partial_rotary': 1", "'moe.shared_gate': 4",
                    "'sparse_moe.sorted': 4", "'kda_chunk.xla': 3"):
        assert counted in counters_line, counted   # a CPU: the XLA forms
    assert "'sparse_moe.every_pair'" not in counters_line
    window = next(ln for ln in lines if ln.startswith("window: "))
    assert "largest rung" in window
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
        ("gdn_", "gated_attn_", "gated_conv_", "mla_", "ssd_", "gqa_",
         "kda_"))]
    assert not [m for m in got if m.endswith(".serve")]


def test_the_parent_refuses_the_driver_cleanly(tiny, monkeypatch):
    """On a program without ``nn.GatedDeltaNet`` (the parent commit with
    this PR's benchmark files laid over it) the driver refuses BEFORE the
    reference's minutes: ``run.py`` then prints REFUSED and exits 2."""
    from paddle_tpu import nn

    root, _, cfg, _ = tiny
    driver = harness.load_driver(cfg, root)
    monkeypatch.delattr(nn, "GatedDeltaNet")
    monkeypatch.setattr(driver.reference, "train", lambda *a, **k: 1 / 0)
    with pytest.raises(harness.Refused, match="GatedDeltaNet"):
        harness.run_cell(TINY, seed=1, seconds=1.0, trace=False, root=root,
                         peaks=REHEARSAL_PEAKS, check_device=False,
                         log=lambda _m: None)


# ---------------------------------------------------------------------------
# the readers
# ---------------------------------------------------------------------------
def _fake_trace(rows):
    return {"busy_s": 2.0, "window_s": 2.5, "device_ops": rows}


def test_the_new_readers_read_their_rows_behind_their_counters(monkeypatch):
    from paddle_tpu.ops.pallas import counters

    metrics = {m["name"]: m["read"] for m in harness.layer_metrics()}
    gdn_share, gdn_roof, attn_share, attn_roof = (
        metrics[n] for n in NEW_METRICS)
    rows = [["fusion", 0.9], ["kernel:kda_chunk_bwd", 0.2],
            ["kernel:kda_chunk_fwd", 0.1], ["kernel:kda_conv", 0.3],
            ["kernel:flash_attention_grouped", 0.05]]
    work = {"kda_chunk_fwd": {"calls": 3, "flops": 1e9, "bytes": 1e9},
            "kda_chunk_bwd": {"calls": 3, "flops": 2e9, "bytes": 2e9},
            "kda_conv": {"calls": 9, "flops": 0.0, "bytes": 1e12},
            "flash_attention_grouped": {"calls": 2, "flops": 4e9,
                                        "bytes": 1e8}}
    run = {"trace": _fake_trace(rows), "peaks": REHEARSAL_PEAKS,
           "cell": {"traffic": {"loss_fetch_every": 5}}}
    monkeypatch.setattr(counters, "step_work", lambda step: work)
    # each counter gates its pair: absent (the parent, or the Kimi cell,
    # whose process launches the same kernels)
    monkeypatch.setattr(counters, "snapshot",
                        lambda: {"kda_chunk.pallas": 4,
                                 "flash_attention.grouped": 1})
    assert all(metrics[n](run) is None for n in NEW_METRICS)
    monkeypatch.setattr(counters, "snapshot",
                        lambda: {"gdn.scalar_decay": 3})
    assert gdn_share(run) == pytest.approx(100.0 * 0.3 / 2.0)
    # bytes / 1e11 x 5 steps over the two rows' seconds (the bytes' bound
    # is the larger for both roles)
    assert gdn_roof(run) == pytest.approx(100.0 * 5 * 3e9 / 1e11 / 0.3)
    assert attn_share(run) is None and attn_roof(run) is None
    monkeypatch.setattr(counters, "snapshot", lambda: {"gqa.output_gate": 1})
    assert gdn_share(run) is None and gdn_roof(run) is None
    assert attn_share(run) == pytest.approx(100.0 * 0.05 / 2.0)
    assert attn_roof(run) == pytest.approx(100.0 * 5 * 4e9 / 1e12 / 0.05)
    # a row under the reduction's ten, as the cell's driver hands it on
    other = dict(run, trace=_fake_trace(rows[:4]))
    assert attn_share(other) is None and attn_roof(other) is None
    under = dict(other, observations={"kernel_rows_under_top": [rows[4]]})
    assert attn_share(under) == attn_share(run)
    assert attn_roof(under) == attn_roof(run)
    assert other["trace"]["device_ops"] == rows[:4]
    assert attn_share({"trace": None}) is None
    assert attn_roof(dict(run, trace=None)) is None
    monkeypatch.setattr(counters, "step_work", lambda step: {})
    assert attn_roof(run) is None


#: what 2c186de (this PR's parent) returns in the Kimi cell's traced
#: rehearsal: the same kernels and roles this PR's new cell launches, no
#: ``gdn.*``, no ``gqa.*``, no ``moe.shared_gate``
PARENT_SNAPSHOT = {
    "kda_stage.fused": 8, "kda_chunk.pallas": 4, "kda_chunk.heads4": 4,
    "kda_chunk.kept_across_recompute": 4, "flash_attention.pallas": 1,
    "flash_attention.latent": 1, "flash_attention.kept_across_recompute": 1,
    "sparse_moe.every_pair": 4, "sparse_moe.gated": 4,
    "fused_xent.pallas": 1, "fused_xent.ladder": 1}
PARENT_WORK = {
    "kda_chunk_fwd": {"calls": 4, "flops": 1e11, "bytes": 2.7e9},
    "kda_chunk_bwd": {"calls": 4, "flops": 2e11, "bytes": 5.4e9},
    "kda_conv": {"calls": 12, "flops": 0.0, "bytes": 3e9},
    "kda_gate_norm": {"calls": 4, "flops": 0.0, "bytes": 2e9},
    "flash_attention_stream_fwd": {"calls": 1, "flops": 2e12, "bytes": 3e8},
    "flash_attention_stream_bwd": {"calls": 1, "flops": 4e12, "bytes": 6e8},
    "fused_xent_fwd": {"calls": 1, "flops": 1e12, "bytes": 1e8},
    "fused_xent_bwd": {"calls": 1, "flops": 2e12, "bytes": 2e8}}
PARENT_ROWS = [
    ["fusion", 0.581], ["cond", 0.280], ["multiply_reduce_fusion", 0.189],
    ["kernel:kda_chunk_bwd", 0.184], ["copy", 0.154],
    ["multiply_bitcast_fusion", 0.151], ["multiply_subtract_fusion", 0.105],
    ["kernel:kda_chunk_fwd", 0.102], ["reshape", 0.099],
    ["kernel:flash_attention_stream_bwd", 0.099]]


def test_every_reader_returns_a_number_or_none_on_the_parents_program(
        monkeypatch):
    """The rule PR 45 broke (``benchmark_breaks_parent``): the driver
    makes traced runs of the PARENT's program with this PR's benchmark
    files, ``layer_metrics()`` imports every reader there and ``run_cell``
    calls each one whose ``moves`` the cell lists. With the program's
    counters and ledger as the parent has them in the Kimi cell (the cell
    whose kernels the new readers' rows name), every reader gives a number
    or None and none raises; the new four give None, so the Kimi cell does
    not grow a second reading of its own kernel."""
    from paddle_tpu.ops.pallas import counters

    monkeypatch.setattr(counters, "snapshot", lambda: dict(PARENT_SNAPSHOT))
    monkeypatch.setattr(counters, "step_work",
                        lambda step: {k: dict(v)
                                      for k, v in PARENT_WORK.items()})
    cell, cfg = harness.load_cell("kimi-linear-48b-a3b.pretrain-seq8k")
    run = {"trace": dict(_fake_trace(PARENT_ROWS), idle_pct=0.2,
                         idle_gaps=[["bench.step", 0.004]], n_devices=1),
           "peaks": harness.load_json(os.path.join(
               harness.ROOT, "peaks.json"))["TPU v5 lite"],
           "cell": cell, "config": cfg, "chips": 1, "seconds": 51.0,
           "attempted": 140, "failed": 0, "setup_s": 29.0,
           "device": {"memory_peak_bytes": 10_100_000_000},
           "metrics": {"train_tokens_per_s": 22750.0},
           "observations": {"dispatch_ms": [4.7, 4.8, 4.9],
                            "train_tokens_per_s": 22750.0,
                            "flops_per_token": 2.3e9,
                            "moe_rows_used_pct": 27.6,
                            "hbm_peak_pct": 59.1}}
    seen = {}
    for meta in harness.layer_metrics():
        if meta["moves"] not in cell["end_to_end"]:
            continue
        value = meta["read"](run)
        assert value is None or np.isfinite(float(value)), meta["name"]
        seen[meta["name"]] = value
    assert all(seen[n] is None for n in NEW_METRICS)
    assert seen["kda_device_share_pct.train"] == pytest.approx(
        100.0 * (0.184 + 0.102) / 2.0)
    assert seen["kda_roofline_pct.train"] > 0
    assert seen["mfu_pct.train"] > 0


def test_the_new_benchmark_code_imports_the_program_inside_functions_alone():
    """An ``ast`` walk: no file-level import from ``paddle_tpu`` in the
    four new readers, ``rows_under_top.py``, ``work_qwen3_next.py`` or the
    reference (a module imported where the parent's program runs must name
    nothing the parent lacks), inside ``read`` only
    ``ops.pallas.counters``; and the new driver's file-level imports name
    no ``paddle_tpu`` module either (it asks for ``nn.GatedDeltaNet``
    inside ``run``, under a ``try``)."""
    files = [os.path.join(harness.ROOT, "layer_metrics", n + ".py")
             for n in NEW_METRICS] + [
        os.path.join(harness.ROOT, n) for n in (
            "rows_under_top.py", "work_qwen3_next.py",
            "reference/qwen3_next.py")]
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
    with open(os.path.join(harness.ROOT, "drivers",
                           "gated_delta_lm_step.py")) as f:
        tree = ast.parse(f.read())
    for node in tree.body:
        if isinstance(node, (ast.Import, ast.ImportFrom)):
            assert "paddle_tpu" not in (getattr(node, "module", "") or "")
            assert not [a for a in node.names if "paddle_tpu" in a.name]
    # the tracer is IMPORTED, not copied
    from benchmarks.drivers import conv_hybrid_lm_step

    driver = harness.load_driver({"driver": "gated_delta_lm_step"})
    assert driver.KernelRowTracer.__module__ \
        == conv_hybrid_lm_step.KernelRowTracer.__module__
    assert driver.KernelRowTracer.take.__code__ \
        is conv_hybrid_lm_step.KernelRowTracer.take.__code__


def test_step_work_of_the_new_cells_kernels_is_the_work_files(tiny,
                                                              monkeypatch):
    """The program's ledger for one TrainStep (what gdn_roofline_pct and
    gated_attn_roofline_pct read) equals benchmarks/work_qwen3_next.py's
    count, at lane-dense widths (heads of 128 x 128 under the delta rule,
    heads of 128 in the attention), kernels in interpret mode; and the
    counters the acceptance names are set; the lowered step carries the
    three scopes."""
    from jax.experimental import pallas as pl

    import paddle_tpu.framework.bringup as bringup
    from paddle_tpu.ops.pallas import counters

    monkeypatch.setattr(pl, "pallas_call",
                        functools.partial(pl.pallas_call, interpret=True))
    monkeypatch.setattr(bringup, "pallas_enabled", lambda: True)
    root, cell, cfg, _ = tiny
    cfg = dict(cfg, hidden_size=128, linear_num_key_heads=1,
               linear_num_value_heads=2, linear_key_head_dim=128,
               linear_value_head_dim=128, num_attention_heads=2,
               num_key_value_heads=1, head_dim=128)
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
    want = work_qwen3_next.gdn_kernel_work(mcfg, 1, 256)
    assert want["kda_chunk_fwd"]["calls"] == 3
    assert {k: work[k] for k in want} == want
    gqa = work_qwen3_next.gated_attn_kernel_work(mcfg, 1, 256, itemsize=4)
    assert {k: work[k] for k in gqa} == gqa
    assert not [k for k in snap if k.endswith(".xla")
                and k.split(".")[0] in ("kda_chunk", "kda_stage",
                                        "flash_attention")], snap
    assert snap["gdn.scalar_decay"] == snap["kda_chunk.pallas"] \
        == snap["kda_chunk.kept_across_recompute"] == 3
    assert snap["flash_attention.grouped"] == 1
    assert snap["flash_attention.kept_across_recompute"] == 1
    assert (snap["gqa.output_gate"], snap["gqa.partial_rotary"],
            snap["moe.shared_gate"]) == (1, 1, 4)
    assert snap["sparse_moe.sorted"] == 4
    for scope in ("gdn_before", "gdn_after", "gated_attn"):
        assert scope in text, scope
