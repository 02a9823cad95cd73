"""The ``kanana-2-30b-a3b`` configuration and its cell: the data files
against the catalog and the contract's form, the arithmetic against the
issue's numbers, the reference against the program through ``TrainStep``
(bfloat16 autocast and the fp8 control have to fail), the sixteen shares
of an expert layer against the uncut layer, planted faults of the
rotation through the whole command, the program's work ledger against
``work_deepseek_v3.py``, the new readers on a made-up reduction, and the
whole command at tiny size through the harness, on the CPU, never a
measurement."""
import functools
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import harness, lm_traffic, work_deepseek_v3
from benchmarks.reference import deepseek_v3 as ref

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CONFIG = "kanana-2-30b-a3b"
CELL = CONFIG + ".pretrain-seq8k"
TINY = "kanana-tiny.pretrain"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REHEARSAL_PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes": 1e9,
                   "hbm_bytes_per_s": 1e11}
#: what the contract calls a width: never cut, never in ``reduced``
WIDTH = re.compile(r"(hidden_size|intermediate|latent|state_size|proj|"
                   r"head_dim|_dim$|_rank$|expansion|experts_per)")
NEW_METRICS = ["mla_attn_device_share_pct.train",
               "mla_attn_roofline_pct.train"]
REDUCED = ["num_hidden_layers", "n_routed_experts", "vocab_size"]


def _write(root, rel, obj):
    path = os.path.join(root, rel)
    assert not os.path.exists(path), f"{rel} would edit an existing file"
    with open(path, "w") as f:
        json.dump(obj, f)


@pytest.fixture(scope="module")
def tiny(bench_root):
    """(root, cell, config) of ``kanana-tiny.pretrain``: the real files
    cut to test size and dropped into the copy of ``benchmarks/`` as NEW
    files. float32 end to end, so that the program is held to the
    reference tightly; three layers hold both kinds (dense, moe, moe)."""
    root, before = bench_root
    cfg = harness.load_json(os.path.join(root, f"configs/{CONFIG}.json"))
    cfg.update(name="kanana-tiny", hidden_size=64, intermediate_size=96,
               moe_intermediate_size=32, kv_lora_rank=32,
               qk_nope_head_dim=16, qk_rope_head_dim=8, qk_head_dim=24,
               head_dim=8, v_head_dim=16, num_attention_heads=4,
               num_key_value_heads=4, vocab_size=512, n_routed_experts=4,
               num_experts_per_tok=4, num_hidden_layers=3)
    cfg["published"] = dict(cfg["published"], n_routed_experts=16)
    cfg["program"] = dict(cfg["program"], amp_level="O0")
    _write(root, "configs/kanana-tiny.json", cfg)
    cell = harness.load_json(os.path.join(root, f"workloads/{CELL}.json"))
    cell.update(name=TINY, config="kanana-tiny")
    cell["traffic"].update(batch=2, seq=64, host_batches=4,
                           loss_fetch_every=2)
    cell["correct"].update(block_rows=32, limits={
        "loss_gap": 1e-5, "grad_norm_gap": 1e-3, "delta_norm_gap": 3e-3})
    _write(root, f"workloads/{TINY}.json", cell)
    return root, cell, cfg, before


def rehearse(root, cell, trace=False, seed=2 ** 31 + 39, seconds=1.0):
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
    return next(r for r in rows
                if r["name"] == "kanana-2-30b-a3b-instruct-2601")


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
              "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
              "v_head_dim": 128, "kv_lora_rank": 512,
              "intermediate_size": 6144, "moe_intermediate_size": 768,
              "num_experts_per_tok": 6, "n_shared_experts": 2,
              "rope_theta": 1000000, "rope_interleave": True,
              "routed_scaling_factor": 2.448, "rms_norm_eps": 1e-6,
              "first_k_dense_replace": 1, "scoring_func": "sigmoid",
              "norm_topk_prob": True, "q_lora_rank": None,
              "rope_scaling": None}
    for key, value in widths.items():
        assert cfg[key] == pub[key] == value, key
    assert cfg["published"] == {"num_hidden_layers": 48,
                                "n_routed_experts": 128,
                                "vocab_size": 128256}
    # the cut: the dense layer and six that follow, 8 of 128 experts, an
    # eighth of the vocabulary rounded UP to whole 128-column blocks
    assert cfg["num_hidden_layers"] == 7
    assert ref.layer_kinds(cfg) == ["dense"] + ["moe"] * 6
    assert cfg["n_routed_experts"] * 16 == 128
    assert cfg["vocab_size"] == 16384 >= 128256 / 8 > 16384 - 3 * 128
    assert cfg["vocab_size"] % 128 == 0
    assert "16 chips" in cfg["stands_for"]
    assert set(cfg["reduced_notes"]) == set(REDUCED)
    for key in ("weights", "router_bias", "balance_loss", "optimizer",
                "precision"):
        assert cfg["assumed"][key]
    assert any("120 absent experts" in d for d in cfg["departs"])
    assert any("recomputed" in d for d in cfg["departs"])
    assert any("head_dim 64" in d and "num_key_value_heads" in d
               for d in cfg["departs"])
    assert any("num_nextn_predict_layers" in d for d in cfg["departs"])
    assert "num_nextn_predict_layers" not in pub
    nemotron = harness.load_json(os.path.join(
        harness.ROOT, "configs/nemotron-3-nano-30b-a3b.json"))
    assert cfg["program"] == nemotron["program"]


def test_benchmark_json_only_gained_entries():
    bench = harness.load_json(os.path.join(REPO, "BENCHMARK.json"))
    names = [c["name"] for c in bench["configs"]]
    assert names[:5] == ["bert-base", "kimi-linear-48b-a3b",
                         "mellum2-12b-a2.5b", "nemotron-3-nano-30b-a3b",
                         CONFIG]
    cells = [w["name"] for w in bench["workloads"]]
    assert cells[:6] == ["bert-base.pretrain-seq512",
                         "bert-base.pretrain-seq128",
                         "kimi-linear-48b-a3b.pretrain-seq8k",
                         "mellum2-12b-a2.5b.pretrain-seq8k",
                         "nemotron-3-nano-30b-a3b.pretrain-seq8k", CELL]
    entry = bench["workloads"][5]
    assert entry["chips"] == 1 and len(entry["why"]) <= 200
    assert (entry["config"], entry["traffic"]) == (CONFIG, "pretrain-seq8k")
    assert all(len(w["why"]) <= 200 and "\n" not in w["why"]
               for w in bench["workloads"] + bench["configs"])
    assert all(w["chips"] == 1 for w in bench["workloads"][:6])
    cell, cfg = harness.load_cell(CELL)
    assert cell["why"] == entry["why"] and cfg["name"] == CONFIG
    assert bench["configs"][4]["reduced"] == cfg["reduced"] == REDUCED
    assert bench["configs"][4]["file"] == f"benchmarks/configs/{CONFIG}.json"
    assert bench["configs"][4]["source"] == cfg["source"]
    new = [m for m in bench["per_layer"] if m.get("workloads") == [CELL]]
    assert [m["name"] for m in new] == NEW_METRICS
    files = {m["name"]: m for m in harness.layer_metrics()}
    for m in new:
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        for key in ("unit", "better", "source", "layer", "moves"):
            assert files[m["name"]][key] == m[key]
        assert m["layer"] == "kernel dispatch + Pallas kernels"
        assert (m["moves"], m["source"]) == ("train_tokens_per_s",
                                             "device_trace")
    assert [m["name"] for m in bench["per_layer"]][17:19] == NEW_METRICS
    # nothing of the accepted benchmark lists the new cell, and what it
    # had is as it was
    for m in bench["per_layer"][:17]:
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

    # latent attention 26.35 M, the dense FFN 37.75 M; an expert layer
    # 26.35 + router 0.26 + shared 9.44 + 8 x 4.72 M = 73.79 M;
    # embedding + head 67.11 M: the issue's 573.97 M of matrices (573.96
    # to the digit) and 34,304 norm scales, 9.18 GB at 16 B each
    attention = 2048 * 32 * 192 + 2048 * 576 + 512 * 32 * 256 + 4096 * 2048
    assert attention == 26_345_472
    norms = 2 * 2048 + 512
    assert layer(0) == attention + 3 * 2048 * 6144 + norms == 64_098_816
    expert_layer = attention + 2048 * 128 + 3 * 2048 * 1536 \
        + 8 * 3 * 2048 * 768 + norms
    assert [layer(n) for n in range(1, 7)] == [expert_layer] * 6
    assert expert_layer == 73_798_144
    assert total == 573_998_592
    assert total - 7 * norms - 2048 == 573_964_288
    assert round(total * 16 / 1e9, 2) == 9.18
    assert round(100 * total * 16 / 2 ** 34, 1) == 53.5
    counted = work_deepseek_v3.param_count(mcfg)
    assert counted["total"] == total
    assert (counted["dense"], counted["moe"],
            counted["embedding_and_head"]) == (
        64_098_816, 73_798_144, 67_108_864)
    # whole, one expert layer is 10.24 GB: a chip cannot hold two; with 16
    # held the same seven layers are 800 M parameters
    whole = work_deepseek_v3.param_count(dict(mcfg, experts_held=128))
    assert round(whole["moe"] * 16 / 1e9, 2) == 10.24
    wide = work_deepseek_v3.param_count(dict(mcfg, experts_held=16))
    assert round(wide["total"] / 1e6) == 800
    assert mcfg["n_routed_experts"] == 128 and mcfg["experts_held"] == 8
    feed = cell["traffic"]
    assert (feed["batch"], feed["seq"]) == (2, 8192)
    assert (feed["zipf_exponent"], feed["host_batches"],
            feed["loss_fetch_every"]) == (1.0, 8, 5)
    assert cell["correct"]["steps"] == 3
    assert cell["correct"]["control_precisions"] == ["fp8"]
    assert set(cell["correct"]["limits"]) == {
        "loss_gap", "grad_norm_gap", "delta_norm_gap"}
    assert "PR 39" in cell["correct"]["limits_from"]
    kimi, _ = harness.load_cell("kimi-linear-48b-a3b.pretrain-seq8k")
    assert cell["optimizer"] == kimi["optimizer"]
    # 8 held of top 6 in 128: the ladder has the dense rung alone, so the
    # step's time does not follow the routing; 16 held would bring the
    # sorted rung
    from paddle_tpu.nn.moe import _row_ladder

    assert _row_ladder(16384 * 6, 8, 128, 16384 * 8) == (98304,)
    assert len(_row_ladder(16384 * 6, 16, 128, 16384 * 16)) > 0
    assert 16384 * 6 // 128 == 768 and 16 * 768 == 12288


def test_flops_are_the_issues_numbers():
    _, cfg = harness.load_cell(CELL)
    mcfg = harness.load_driver(cfg).model_config(cfg)
    m = 1e6
    scores = work_deepseek_v3.attention_flops_per_token(mcfg, 8192)
    assert scores == 2 * 320 * 4096 * 32
    assert scores / m == pytest.approx(83.9, abs=0.05)
    proj = 2 * work_deepseek_v3.mla_matrix_params(mcfg)
    assert proj / m == pytest.approx(52.7, abs=0.05)
    dense = work_deepseek_v3.ffn_flops_per_token(mcfg, "dense")
    assert dense / m == pytest.approx(75.5, abs=0.05)
    moe = work_deepseek_v3.ffn_flops_per_token(mcfg, "moe")
    # shared 18.9, router 0.5, held routed 3.5 at 0.375 picks a token
    assert 6 * 8 / 128 == 0.375
    assert moe / m == pytest.approx(18.87 + 0.524 + 3.54, abs=0.01)
    head = 2 * 2048 * 16384 * 8191 / 8192
    assert head / m == pytest.approx(67.1, abs=0.05)
    forward = 7 * (scores + proj) + dense + 6 * moe + head
    assert forward / m == pytest.approx(1236, abs=1)
    total = work_deepseek_v3.train_flops_per_token(mcfg, 8192, 8191)
    assert total == pytest.approx(3 * forward)
    # latent attention is 77% of the required work, its kernels 47%
    assert 100 * 7 * (scores + proj) / forward == pytest.approx(77.3,
                                                                abs=0.1)
    assert 100 * 7 * scores / forward == pytest.approx(47.5, abs=0.1)
    # what the dense rung spends where 3.5 MFLOP a token are required
    assert 2 * 8 * 3 * 2048 * 768 / m == pytest.approx(75.5, abs=0.05)


def test_kernel_work_is_the_stream_roles_at_192_and_128():
    _, cfg = harness.load_cell(CELL)
    mcfg = harness.load_driver(cfg).model_config(cfg)
    work = work_deepseek_v3.mla_kernel_work(mcfg, 2, 8192)
    assert list(work) == ["flash_attention_stream_fwd",
                          "flash_attention_stream_bwd"]
    fwd, bwd = work.values()
    rows = 2 * 8192 * 32
    assert fwd["calls"] == bwd["calls"] == 7
    assert fwd["flops"] == 7 * rows * 4096 * 2 * 320
    assert bwd["flops"] == 2 * fwd["flops"]
    assert fwd["bytes"] == 7 * rows * (2 * (192 + 192 + 128) + 2 * 128 + 4)
    assert bwd["bytes"] == 7 * rows * (4 * (192 + 192 + 128) + 4 * 128 + 4)
    # compute-bound by far: 2.5 GFLOP a byte-second at the v5e's peaks
    assert fwd["flops"] / fwd["bytes"] > 1000


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
        ("mla", "dense"), ("mla", "moe"), ("mla", "moe")]
    assert all(b.mixer.interleave and b.mixer.inv_freq is not None
               for b in loop.model.layers)
    assert loop.model.layers[1].ffn.score_func == "sigmoid"
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


def test_sixteen_shares_and_the_shared_ffn_once_add_up_to_the_layer():
    """The share test: at a small size, the routed parts that the sixteen
    chips of the deployment compute, with what every chip computes alike
    (the shared experts) counted once, are the uncut reference's layer;
    and the program's layer on a share is the reference's share."""
    import paddle_tpu as paddle
    from paddle_tpu import nn

    cfg = {"n_routed_experts": 32, "num_experts_per_tok": 6,
           "norm_topk_prob": True, "routed_scaling_factor": 2.448,
           "n_shared_experts": 2, "moe_intermediate_size": 16}
    key = jax.random.key(39)
    names = {"router.weight": (32, 32), "experts_gate": (32, 32, 16),
             "experts_up": (32, 32, 16), "experts_down": (32, 16, 32),
             "shared.gate_proj.weight": (32, 32),
             "shared.up_proj.weight": (32, 32),
             "shared.down_proj.weight": (32, 32)}
    whole = {"f." + n: 0.3 * jax.random.normal(jax.random.fold_in(key, i), s)
             for i, (n, s) in enumerate(names.items())}
    x = jax.random.normal(jax.random.fold_in(key, 99), (48, 32))
    want = ref.moe(whole, "f.", x, cfg, ref._dense)
    total = ref.shared(whole, "f.", x, ref._dense)
    for chip in range(16):
        share = {k: v[2 * chip:2 * chip + 2] if "experts_" in k else v
                 for k, v in whole.items()}
        part = ref.routed(share, "f.", x, dict(cfg, expert_offset=2 * chip),
                          ref._dense)
        total = total + part
        if chip in (0, 7, 15):
            layer = nn.SparseMoELayer(32, 16, 32, 6, experts_held=2,
                                      expert_offset=2 * chip, scaling=2.448,
                                      shared_width=32)
            for name, q in layer.named_parameters():
                q._value = share["f." + name]
            got = layer(paddle.to_tensor(x)).value
            np.testing.assert_allclose(
                np.asarray(got),
                np.asarray(part + ref.shared(whole, "f.", x, ref._dense)),
                atol=2e-5)
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               atol=2e-5)
    # every token's six picks landed somewhere, once
    picked, weight = ref.router_weights(x, whole["f.router.weight"], cfg)
    assert picked.shape == (48, 6)
    np.testing.assert_allclose(np.asarray(weight.sum(1)), 2.448, rtol=1e-5)


def test_reference_imports_nothing_of_the_program():
    with open(os.path.join(harness.ROOT, "reference", "deepseek_v3.py")) as f:
        text = f.read()
    code = text.split('"""', 2)[2]
    assert "paddle_tpu" not in code
    assert "jnp.where(at >= jnp.arange(t)[None, :], s, -jnp.inf)" in code
    assert "for e in range(" in code       # a dense loop over the experts
    assert "d // 2, 2), -1, -2)" in code   # the source's de-interleave
    assert "+ 1e-20" in code
    assert "pallas" not in code and "bfloat16" not in code


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
    assert "'mla.rotary'" in counters_line
    assert "'mla.nope'" not in counters_line
    assert "'sparse_moe.gated'" in counters_line
    after = digest(root)
    assert {k: after[k] for k in before} == before   # no existing file


def _k_pe_left_unrotated(monkeypatch):
    """The rotation left off the shared key row: q's part alone turns."""
    from paddle_tpu.nn import latent_attention as la

    original = la.mla_rope

    def broken(q, k_pe, inv_freq, nope, interleave=False):
        return original(q, k_pe, inv_freq, nope, interleave)[0], k_pe

    monkeypatch.setattr(la, "mla_rope", broken)


def _q_pe_in_the_wrong_layout(monkeypatch):
    """q's positional part rotated as if its pairs were stored in halves
    (no de-interleave) while k_pe is rotated as the file says."""
    from paddle_tpu.nn import latent_attention as la

    original = la.mla_rope

    def broken(q, k_pe, inv_freq, nope, interleave=False):
        wrong, _ = original(q, k_pe, inv_freq, nope, False)
        _, k_rot = original(q, k_pe, inv_freq, nope, interleave)
        return wrong, k_rot

    monkeypatch.setattr(la, "mla_rope", broken)


def _state_left_unchanged(monkeypatch):
    """A step that runs and hands its parameters back as they were."""
    from benchmarks.drivers.causal_lm_step import Loop

    original = Loop.feed_and_step

    def broken(self, batch):
        saved = {k: jnp.copy(p.value)
                 for k, p in self.model.named_parameters()}
        loss = original(self, batch)
        for k, p in self.model.named_parameters():
            p._value = saved[k]
        return loss

    monkeypatch.setattr(Loop, "feed_and_step", broken)


@pytest.mark.parametrize("fault,fails", [
    (_k_pe_left_unrotated, ["grad_norm_gap"]),
    (_q_pe_in_the_wrong_layout, ["grad_norm_gap"]),
    (_state_left_unchanged, ["delta_norm_gap"]),
], ids=lambda v: getattr(v, "__name__", None))
def test_a_planted_fault_comes_out_not_correct(tiny, monkeypatch, fault,
                                               fails):
    """The timed path broken underneath, through the whole command: the
    comparison behind ``correct`` has to say so, by the limits named."""
    root, _, _, _ = tiny
    fault(monkeypatch)
    result, lines = rehearse(root, TINY)
    assert result["correct"] is False
    for name in fails:
        assert [ln for ln in lines
                if ln.startswith(f"check {name}") and "NOT OK" in ln], \
                [ln for ln in lines if ln.startswith("check ")]


def test_traced_rehearsal_reports_the_cells_layer_metrics(tiny):
    root, _, _, _ = tiny
    result, lines = rehearse(root, TINY, trace=True)
    got = result["metrics"]
    assert {"mfu_pct.train", "dispatch_ms.train"} <= set(got)
    assert 0 < got["moe_rows_used_pct.train"]["value"] <= 100
    # a CPU has no device plane and launches no kernel: the trace-fed
    # readers find nothing and their metrics are left out, as on a
    # commit whose program does not count the dispatch
    assert not [m for m in got
                if m.startswith(("mla_", "ssd_", "gqa_", "kda_"))]
    assert not [m for m in got if m.endswith(".serve")]


def test_the_parent_refuses_the_driver_cleanly(tiny, monkeypatch):
    """On a program whose ``MLAttention`` has no rotary variant (the
    parent commit with this PR's benchmark files laid over it) the driver
    refuses before the reference's minutes."""
    from paddle_tpu.nn import latent_attention

    root, _, _, _ = tiny
    monkeypatch.delattr(latent_attention, "mla_rope")
    with pytest.raises(harness.Refused, match="rotary"):
        harness.run_cell(TINY, seed=1, seconds=1.0, trace=False, root=root,
                         peaks=REHEARSAL_PEAKS, check_device=False,
                         log=lambda _m: None)


def _fake_trace(rows):
    return {"busy_s": 2.0, "window_s": 2.5, "device_ops": rows}


def test_the_new_readers_read_the_stream_rows_behind_the_latent_counter(
        monkeypatch):
    from paddle_tpu.ops.pallas import counters

    metrics = {m["name"]: m["read"] for m in harness.layer_metrics()}
    share, roof = (metrics[n] for n in NEW_METRICS)
    rows = [["fusion", 0.9], ["kernel:flash_attention_stream_bwd", 0.5],
            ["kernel:flash_attention_stream_fwd", 0.3],
            ["kernel:flash_attention_grouped", 0.4],
            ["kernel:fused_xent_fwd", 0.05]]
    work = {"flash_attention_stream_fwd":
            {"calls": 7, "flops": 2e10, "bytes": 1e9},
            "flash_attention_stream_bwd":
            {"calls": 7, "flops": 4e10, "bytes": 1.0},
            "flash_attention_grouped":
            {"calls": 2, "flops": 1e12, "bytes": 1.0}}
    run = {"trace": _fake_trace(rows), "peaks": REHEARSAL_PEAKS,
           "cell": {"traffic": {"loss_fetch_every": 5}}}
    monkeypatch.setattr(counters, "step_work", lambda step: work)
    # the counter gates both: absent (the parent), zero (a cell whose
    # attention has one width)
    monkeypatch.setattr(counters, "snapshot", lambda: {})
    assert share(run) is None and roof(run) is None
    monkeypatch.setattr(counters, "snapshot",
                        lambda: {"flash_attention.grouped": 4,
                                 "flash_attention.pallas": 4})
    assert share(run) is None and roof(run) is None
    monkeypatch.setattr(counters, "snapshot",
                        lambda: {"flash_attention.latent": 7})
    assert share(run) == pytest.approx(100.0 * 0.8 / 2.0)
    # the larger of FLOP / 1e12 and bytes / 1e11, x 5 steps, both roles
    assert roof(run) == pytest.approx(100.0 * 5 * (2e10 + 4e10) / 1e12
                                      / 0.8)
    # a reduction without the rows, a run with no trace, no ledger
    other = dict(run, trace=_fake_trace(rows[:1] + rows[3:]))
    assert share(other) is None and roof(other) is None
    assert share({"trace": None}) is None
    assert roof(dict(run, trace=None)) is None
    monkeypatch.setattr(counters, "step_work", lambda step: {})
    assert roof(run) is None


def test_step_work_of_the_stream_roles_is_the_work_files(tiny, monkeypatch):
    """The program's ledger for one TrainStep (what mla_attn_roofline_pct
    reads) equals benchmarks/work_deepseek_v3.py's count, at lane-dense
    widths (keys 128, values 64), kernels in interpret mode; and the
    counters the acceptance names are set: ``mla.rotary`` and
    ``flash_attention.latent`` in every layer, no ``flash_attention.xla``;
    the lowered step carries the ``mla_rope`` scope."""
    from jax.experimental import pallas as pl

    import paddle_tpu.framework.bringup as bringup
    from paddle_tpu.ops.pallas import counters

    monkeypatch.setattr(pl, "pallas_call",
                        functools.partial(pl.pallas_call, interpret=True))
    monkeypatch.setattr(bringup, "pallas_enabled", lambda: True)
    root, cell, cfg, _ = tiny
    cfg = dict(cfg, num_attention_heads=2, num_key_value_heads=2,
               qk_nope_head_dim=64, qk_rope_head_dim=64, qk_head_dim=128,
               head_dim=64, v_head_dim=64)
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
        text = loop.step.lower(*[loop._to_tensor(a) for a in batches[0]]
                               ).as_text(debug_info=True)
    finally:
        counters.reset()
    assert np.isfinite(loss)
    want = work_deepseek_v3.mla_kernel_work(mcfg, 1, 256, itemsize=4)
    assert {k: work[k] for k in want} == want
    assert "flash_attention.xla" not in snap and "mla.nope" not in snap
    assert snap["flash_attention.latent"] == snap["flash_attention.pallas"] \
        == snap["mla.rotary"]
    assert snap["mla.rotary"] % 3 == 0
    # 4 held of top 4: the dense top rung (the cell's too, 8 held)
    assert snap["sparse_moe.gated"] == snap["sparse_moe.every_pair"]
    assert "mla_rope" in text and "rotary_embedding" in text
