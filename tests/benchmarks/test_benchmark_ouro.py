"""The ``ouro-2.6b`` configuration and its cell: the data files against the
catalog and the contract's form, the arithmetic against the issue's
numbers, the reference against the program through ``TrainStep``
(bfloat16 autocast and the fp8 control have to fail), planted faults of
the loop through the whole command, the program's work ledger against
``work_ouro.py``, the new readers on a made-up reduction, and the whole
command at tiny size through the harness, on the CPU, never a
measurement."""
import functools
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import harness, lm_traffic, work_ouro
from benchmarks.reference import ouro as ref

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CONFIG = "ouro-2.6b"
CELL = CONFIG + ".pretrain-seq8k"
TINY = "ouro-tiny.pretrain"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REHEARSAL_PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes": 1e9,
                   "hbm_bytes_per_s": 1e11}
#: what the contract calls a width: never cut, never in ``reduced``
WIDTH = re.compile(r"(hidden_size|intermediate|latent|state_size|proj|"
                   r"head_dim|_dim$|_rank$|expansion|experts_per)")
NEW_METRICS = ["ut_xent_device_share_pct.train", "ut_xent_roofline_pct.train",
               "ut_attn_device_share_pct.train", "ut_attn_roofline_pct.train"]
REDUCED = ["num_hidden_layers", "layer_types"]


def _write(root, rel, obj):
    path = os.path.join(root, rel)
    assert not os.path.exists(path), f"{rel} would edit an existing file"
    with open(path, "w") as f:
        json.dump(obj, f)


@pytest.fixture(scope="module")
def tiny(bench_root):
    """(root, cell, config) of ``ouro-tiny.pretrain``: the real files cut
    to test size and dropped into the copy of ``benchmarks/`` as NEW
    files. float32 end to end, so that the program is held to the
    reference tightly; two layers, four passes."""
    root, before = bench_root
    cfg = harness.load_json(os.path.join(root, f"configs/{CONFIG}.json"))
    cfg.update(name="ouro-tiny", hidden_size=64, intermediate_size=96,
               head_dim=16, num_attention_heads=4, num_key_value_heads=4,
               vocab_size=512, num_hidden_layers=2,
               layer_types=["full_attention"] * 2)
    cfg["program"] = dict(cfg["program"], amp_level="O0")
    _write(root, "configs/ouro-tiny.json", cfg)
    cell = harness.load_json(os.path.join(root, f"workloads/{CELL}.json"))
    cell.update(name=TINY, config="ouro-tiny")
    cell["traffic"].update(batch=2, seq=64, host_batches=4,
                           loss_fetch_every=2)
    cell["correct"].update(block_rows=32, limits={
        "loss_gap": 1e-5, "grad_norm_gap": 1e-3, "delta_norm_gap": 3e-3})
    _write(root, f"workloads/{TINY}.json", cell)
    return root, cell, cfg, before


def rehearse(root, cell, trace=False, seed=2 ** 31 + 41, seconds=1.0):
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
    return next(r for r in rows if r["name"] == "Ouro-2.6B")


def test_config_keeps_every_published_key_but_the_listed_cuts():
    row = _catalog_row()
    cfg = harness.load_json(os.path.join(harness.ROOT,
                                         f"configs/{CONFIG}.json"))
    pub = row["config"]
    assert cfg["source"] == row["source_url"]
    changed = sorted(k for k, v in pub.items() if cfg[k] != v)
    assert changed == sorted(cfg["reduced"]) == sorted(REDUCED)
    assert not [k for k in cfg["reduced"] if WIDTH.search(k)]
    # every width is the source's, and the loop's depth too
    widths = {"hidden_size": 2048, "num_attention_heads": 16,
              "num_key_value_heads": 16, "head_dim": 128,
              "intermediate_size": 5632, "rope_theta": 1000000,
              "rope_scaling": None, "sliding_window": None,
              "rms_norm_eps": 1e-6, "vocab_size": 49152,
              "total_ut_steps": 4, "early_exit_threshold": 1,
              "max_position_embeddings": 65536,
              "tie_word_embeddings": False, "hidden_act": "silu"}
    for key, value in widths.items():
        assert cfg[key] == pub[key] == value, key
    assert cfg["published"] == {"num_hidden_layers": 48,
                                "layer_types": ["full_attention"] * 48}
    # the cut: the first six layers of a period of one, the guide's floor
    # four; nothing shares a layer, the vocabulary is whole
    assert cfg["num_hidden_layers"] == 6 >= 4
    assert cfg["layer_types"] == pub["layer_types"][:6]
    assert "first 6 of 48 layers" in cfg["stands_for"]
    assert "eight pipeline stages" in cfg["stands_for"]
    assert "nothing shares a layer" in cfg["stands_for"]
    assert set(cfg["reduced_notes"]) == set(REDUCED)
    # what the source's file does not say: each with its other reading,
    # and that it was written from memory
    assert (cfg["sandwich_norm"], cfg["qk_norm"], cfg["attention_bias"],
            cfg["exit_entropy_beta"]) == (True, False, False, 0.1)
    for key in ("sandwich_norm", "qk_norm", "attention_bias",
                "final_norm_in_loop", "exit_gate", "exit_entropy_beta"):
        assert "other reading" in cfg["assumed"][key], key
    for key in ("weights", "optimizer", "precision", "data"):
        assert cfg["assumed"][key]
    assert "memory" in cfg["assumed"]["written_from_memory"]
    assert "FROM MEMORY" in cfg["source_notes"]
    assert any("recomputed" in d for d in cfg["departs"])
    assert any("early_exit_threshold" in d and "max_window_layers" in d
               and "use_sliding_window" in d for d in cfg["departs"])
    assert any("close the loop on themselves" in d for d in cfg["departs"])
    kanana = harness.load_json(os.path.join(
        harness.ROOT, "configs/kanana-2-30b-a3b.json"))
    program = dict(kanana["program"])
    del program["expert_offset"]
    assert cfg["program"] == program and cfg["driver"] == "looped_lm_step"


def test_benchmark_json_only_gained_entries():
    bench = harness.load_json(os.path.join(REPO, "BENCHMARK.json"))
    names = [c["name"] for c in bench["configs"]]
    assert names[:6] == ["bert-base", "kimi-linear-48b-a3b",
                         "mellum2-12b-a2.5b", "nemotron-3-nano-30b-a3b",
                         "kanana-2-30b-a3b", CONFIG]
    cells = [w["name"] for w in bench["workloads"]]
    assert cells[:7] == ["bert-base.pretrain-seq512",
                         "bert-base.pretrain-seq128",
                         "kimi-linear-48b-a3b.pretrain-seq8k",
                         "mellum2-12b-a2.5b.pretrain-seq8k",
                         "nemotron-3-nano-30b-a3b.pretrain-seq8k",
                         "kanana-2-30b-a3b.pretrain-seq8k", CELL]
    entry = bench["workloads"][6]
    assert entry["chips"] == 1 and len(entry["why"]) <= 200
    assert (entry["config"], entry["traffic"]) == (CONFIG, "pretrain-seq8k")
    assert all(len(w["why"]) <= 200 and "\n" not in w["why"]
               for w in bench["workloads"] + bench["configs"])
    assert all(w["chips"] == 1 for w in bench["workloads"][:7])
    cell, cfg = harness.load_cell(CELL)
    assert cell["why"] == entry["why"] and cfg["name"] == CONFIG
    assert bench["configs"][5]["reduced"] == cfg["reduced"] == REDUCED
    assert bench["configs"][5]["file"] == f"benchmarks/configs/{CONFIG}.json"
    assert bench["configs"][5]["source"] == cfg["source"]
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
    assert [m["name"] for m in bench["per_layer"]][19:23] == NEW_METRICS
    # nothing of the accepted benchmark lists the new cell, and what it
    # had is as it was
    for m in bench["per_layer"][:19]:
        assert CELL not in m.get("workloads", [])
    assert bench["run_seconds"] == 51
    assert [e["name"] for e in bench["end_to_end"]] == [
        "train_tokens_per_s", "setup_s"]
    # the attention pair says whose rows it shares
    for name in NEW_METRICS[2:]:
        assert "mla_attn_" in files[name]["what"]
        assert "Kanana" in files[name]["what"]


def test_cell_is_the_issues_traffic_and_counts_the_share_its_files_state():
    cell, cfg = harness.load_cell(CELL)
    driver = harness.load_driver(cfg)
    mcfg = driver.model_config(cfg)
    shapes = driver.param_shapes(mcfg)
    total = sum(int(np.prod(s)) for s in shapes.values())

    def layer(n):
        return sum(int(np.prod(s)) for k, s in shapes.items()
                   if k.startswith(f"layers.{n}."))

    # attention 16.78 M, the FFN 34.60 M, four norms 8 K: 51.39 M a layer;
    # embedding + head 201.33 M, whole; 509.7 M = 8.15 GB at 16 B each,
    # 47.5% of the chip
    attention = 4 * 2048 * 2048
    ffn = 3 * 2048 * 5632
    assert (attention, ffn) == (16_777_216, 34_603_008)
    assert [layer(n) for n in range(6)] == [attention + ffn + 4 * 2048] * 6
    assert round(layer(0) / 1e6, 2) == 51.39
    assert 2 * 49152 * 2048 == 201_326_592
    assert total == 6 * layer(0) + 201_326_592 + 2048 + 2048 + 1
    assert round(total / 1e6, 1) == 509.7
    assert round(total * 16 / 1e9, 2) == 8.15      # the issue rounds up: 8.16
    assert round(100 * total * 16 / 2 ** 34, 1) == 47.5
    counted = work_ouro.param_count(mcfg)
    assert counted["total"] == total and counted["layer"] == layer(0)
    # eight layers would be 9.80 GB before any activation
    eight = work_ouro.param_count(dict(mcfg, num_hidden_layers=8))
    assert round(eight["total"] * 16 / 1e9, 2) == 9.80
    assert mcfg["total_ut_steps"] == 4 and mcfg["num_hidden_layers"] == 6
    assert (mcfg["sandwich_norm"], mcfg["qk_norm"],
            mcfg["exit_entropy_beta"]) == (True, False, 0.1)
    feed = cell["traffic"]
    assert (feed["kind"], feed["batch"], feed["seq"]) == ("lm_feed", 1, 8192)
    assert (feed["zipf_exponent"], feed["host_batches"],
            feed["loss_fetch_every"]) == (1.0, 8, 5)
    assert cell["correct"]["steps"] == 3
    assert cell["correct"]["block_rows"] == 512
    assert cell["correct"]["control_precisions"] == ["fp8"]
    assert set(cell["correct"]["limits"]) == {
        "loss_gap", "grad_norm_gap", "delta_norm_gap"}
    assert "PR 41" in cell["correct"]["limits_from"]
    kimi, _ = harness.load_cell("kimi-linear-48b-a3b.pretrain-seq8k")
    assert cell["optimizer"] == kimi["optimizer"]
    assert cell["traffic"] == kimi["traffic"]         # the Kimi cell's shape
    # what 24 block applications keep under recomputation: a float32 input
    # and the flash output with its logsumexp, 2.43 GB a row of 8,192
    kept = 24 * (8192 * 2048 * 4 + 8192 * 16 * (2 * 128 + 4))
    assert round(kept / 1e9, 2) == 2.43


def test_flops_are_the_issues_numbers():
    _, cfg = harness.load_cell(CELL)
    mcfg = harness.load_driver(cfg).model_config(cfg)
    m = 1e6
    block = work_ouro.block_flops_per_token(mcfg, 8192)
    assert block["projections"] / m == pytest.approx(33.6, abs=0.05)
    assert block["attention"] == 2 * 2 * 128 * 4096 * 16
    assert block["attention"] / m == pytest.approx(33.6, abs=0.05)
    assert block["ffn"] / m == pytest.approx(69.2, abs=0.05)
    assert sum(block.values()) / m == pytest.approx(136.3, abs=0.1)
    head = work_ouro.head_flops_per_token(mcfg, 8192, 8191)
    assert head / m == pytest.approx(201.3, abs=0.05)
    forward = 24 * sum(block.values()) + 4 * head
    assert forward / m == pytest.approx(4077, abs=1)
    total = work_ouro.train_flops_per_token(mcfg, 8192, 8191)
    assert total == pytest.approx(3 * forward)
    assert total / 1e9 == pytest.approx(12.2, abs=0.05)
    # what exists only because of the loop (passes 2-4): three quarters;
    # the four heads 19.7% here, 3% of the 48-layer model's forward
    assert 4 * head / forward == pytest.approx(0.197, abs=0.001)
    whole = 4 * (48 * sum(block.values()) + head)
    assert 4 * head / whole == pytest.approx(0.03, abs=0.001)
    assert (4 * head / forward) / (4 * head / whole) == pytest.approx(
        6.6, abs=0.1)           # the issue: "about 6.5 times"


def test_kernel_work_is_the_stream_roles_24_times_and_one_stacked_head():
    _, cfg = harness.load_cell(CELL)
    mcfg = harness.load_driver(cfg).model_config(cfg)
    work = work_ouro.attn_kernel_work(mcfg, 1, 8192)
    assert list(work) == ["flash_attention_stream_fwd",
                          "flash_attention_stream_bwd"]
    fwd, bwd = work.values()
    rows = 8192 * 16
    assert fwd["calls"] == bwd["calls"] == 24
    assert fwd["flops"] == 24 * rows * 4096 * 4 * 128
    assert bwd["flops"] == 2 * fwd["flops"]
    assert fwd["bytes"] == 24 * rows * (2 * 3 * 128 + 2 * 128 + 4)
    assert bwd["bytes"] == 24 * rows * (4 * 3 * 128 + 4 * 128 + 4)
    assert fwd["flops"] / fwd["bytes"] > 1000       # compute-bound by far
    xent = work_ouro.xent_rows_work(mcfg, 1, 8192)
    assert list(xent) == ["fused_xent_fwd", "fused_xent_bwd"]
    k = 4 * 8192
    assert xent["fused_xent_fwd"]["flops"] == 2.0 * k * 2048 * 49152
    assert xent["fused_xent_bwd"]["flops"] == 4.0 * k * 2048 * 49152
    table = 49152 * 2048 * 4 + 4 * 49152
    rows_h = k * 2048 * 4
    assert xent["fused_xent_fwd"]["bytes"] == rows_h + 4 * k + table + 12 * k
    assert xent["fused_xent_bwd"]["bytes"] == 2 * (rows_h + table) + 16 * k
    # one call where four would each write a float32 dW of 403 MB
    assert round(49152 * 2048 * 4 / 1e6) == 403


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
    assert loop.model.ut_steps == 4
    assert [(b.mixer_kind, b.ffn_kind, b.sandwich)
            for b in loop.model.layers] == [("gqa", "dense", True)] * 2
    assert all(b.mixer.inv_freq is not None and b.mixer.q_norm is None
               for b in loop.model.layers)
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


def test_reference_imports_nothing_of_the_program():
    with open(os.path.join(harness.ROOT, "reference", "ouro.py")) as f:
        text = f.read()
    head, code = text.split('"""', 2)[1:]
    assert "paddle_tpu" not in code
    assert "pallas" not in code and "bfloat16" not in code
    # the loop written literally, the four norms spelled out, the literal
    # products, and that it was written from memory
    assert 'for _t in range(cfg["total_ut_steps"]):' in code
    assert 'for n in range(cfg["num_hidden_layers"]):' in code
    for norm in ("input_norm", "mixer_out_norm", "post_norm", "ffn_out_norm"):
        assert f'p[pre + "{norm}.weight"]' in code
    assert "stay = stay * (1.0 - lam_t)" in code
    assert "log_sigmoid" not in code
    assert "jnp.where(at >= jnp.arange(s)[None, :], scores, -jnp.inf)" in code
    assert "Written from memory" in head and "Departures" in head


# ---------------------------------------------------------------------------
# the whole command at tiny size
# ---------------------------------------------------------------------------
def test_new_cell_rehearses_through_the_harness(tiny, digest):
    from paddle_tpu.ops.pallas import counters

    root, _, _, before = tiny
    counters.reset()            # a process's counts, as a run starts with
    try:
        result, lines = rehearse(root, TINY)
    finally:
        counters.reset()
    assert result["correct"] is True, lines
    assert set(result["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert result["failed"] == 0 and result["attempted"] >= 2
    for name in ("loss_gap", "grad_norm_gap", "delta_norm_gap",
                 "window_compilations", "window_nonfinite_losses"):
        assert [ln for ln in lines if ln.startswith(f"check {name}: value")]
    counters_line = next(ln for ln in lines if "pallas counters" in ln)
    assert "'causal_lm.ut_steps': 4" in counters_line
    assert "'causal_lm.block_applications': 8" in counters_line
    assert "'fused_xent.per_row': 1" in counters_line
    after = digest(root)
    assert {k: after[k] for k in before} == before   # no existing file


def _three_passes(monkeypatch):
    """The stack walked three times, not four."""
    from paddle_tpu.models.causal_lm import CausalLM

    original = CausalLM.__init__

    def broken(self, cfg, recompute=False):
        original(self, cfg, recompute=recompute)
        self.ut_steps = 3

    monkeypatch.setattr(CausalLM, "__init__", broken)


def _no_norm_between_passes(monkeypatch):
    """The head and the gate read the normed states; the next pass starts
    from the stream as the last block left it."""
    from paddle_tpu import ops
    from paddle_tpu.models.causal_lm import CausalLM

    def broken(self, input_ids):
        x = self.embed(input_ids)
        passes = []
        for _ in range(self.ut_steps):
            for block in self.layers:
                x, _ = block(x)
            passes.append(self.final_norm(x))
        return passes, ops.zeros([len(self.layers), 2], "float32")

    monkeypatch.setattr(CausalLM, "hidden_passes", broken)


def _second_sandwich_norm_left_out(monkeypatch):
    """The FFN's output added as it is."""
    from paddle_tpu import ops
    from paddle_tpu.models.causal_lm import DecoderBlock

    def broken(self, x):
        x = x + self.mixer_out_norm(self.mixer(self.input_norm(x)))
        x = x + self.ffn(self.post_norm(x))
        return x, ops.zeros([2], "float32")

    monkeypatch.setattr(DecoderBlock, "forward", broken)


def _expected_loss_with(expected):
    """``F.expected_exit_loss`` with its per-position value swapped."""
    from paddle_tpu.framework.op import primitive

    @primitive("expected_exit_loss", nondiff=("label",))
    def broken(pass_loss, gate_logit, label, beta=0.0, ignore_index=-100):
        stay = jnp.cumsum(jax.nn.log_sigmoid(-gate_logit), axis=0)
        zero = jnp.zeros_like(stay[:1])
        log_p = jnp.concatenate([zero, stay], 0) + jnp.concatenate(
            [jax.nn.log_sigmoid(gate_logit), zero], 0)
        valid = label != ignore_index
        each = jnp.sum(expected(jnp.exp(log_p), log_p, pass_loss, beta), 0)
        return jnp.sum(jnp.where(valid, each, 0.0)) / jnp.sum(valid)

    return broken


def _stop_gradient_on_p(monkeypatch):
    """The exit probabilities weigh the passes' losses as constants: the
    gate learns from the entropy term alone."""
    from paddle_tpu.nn import functional

    monkeypatch.setattr(functional, "expected_exit_loss", _expected_loss_with(
        lambda p, log_p, loss, beta:
        jax.lax.stop_gradient(p) * loss + beta * p * log_p))


def _entropy_term_dropped(monkeypatch):
    from paddle_tpu.nn import functional

    monkeypatch.setattr(functional, "expected_exit_loss", _expected_loss_with(
        lambda p, log_p, loss, beta: p * loss))


@pytest.mark.parametrize("fault,fails", [
    (_three_passes, ["loss_gap", "grad_norm_gap"]),
    (_no_norm_between_passes, ["loss_gap", "grad_norm_gap"]),
    (_second_sandwich_norm_left_out, ["loss_gap", "grad_norm_gap"]),
    (_stop_gradient_on_p, ["grad_norm_gap"]),
    (_entropy_term_dropped, ["loss_gap", "grad_norm_gap"]),
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
    # a CPU has no device plane and launches no kernel: the trace-fed
    # readers find nothing and their metrics are left out, as on a
    # commit whose program does not count the dispatch
    assert not [m for m in got
                if m.startswith(("ut_", "mla_", "ssd_", "gqa_", "kda_"))]
    assert "moe_rows_used_pct.train" not in got      # no experts
    assert not [m for m in got if m.endswith(".serve")]


def test_the_parent_refuses_the_driver_cleanly(tiny, monkeypatch):
    """On a program that has no expected exit loss (the parent commit
    with this PR's benchmark files laid over it) the driver refuses
    before the reference's minutes."""
    from paddle_tpu.nn import functional

    root, _, _, _ = tiny
    monkeypatch.delattr(functional, "expected_exit_loss")
    with pytest.raises(harness.Refused, match="total_ut_steps"):
        harness.run_cell(TINY, seed=1, seconds=1.0, trace=False, root=root,
                         peaks=REHEARSAL_PEAKS, check_device=False,
                         log=lambda _m: None)


def _fake_trace(rows):
    return {"busy_s": 2.0, "window_s": 2.5, "device_ops": rows}


def test_the_new_readers_read_their_rows_behind_the_programs_counters(
        monkeypatch):
    from paddle_tpu.ops.pallas import counters

    metrics = {m["name"]: m["read"] for m in harness.layer_metrics()}
    xent_share, xent_roof, attn_share, attn_roof = (
        metrics[n] for n in NEW_METRICS)
    rows = [["fusion", 0.9], ["kernel:flash_attention_stream_bwd", 0.5],
            ["kernel:flash_attention_stream_fwd", 0.3],
            ["kernel:flash_attention_grouped", 0.4],
            ["kernel:fused_xent_bwd", 0.15], ["kernel:fused_xent_fwd", 0.05]]
    work = {"flash_attention_stream_fwd":
            {"calls": 24, "flops": 2e10, "bytes": 1e9},
            "flash_attention_stream_bwd":
            {"calls": 24, "flops": 4e10, "bytes": 1.0},
            "flash_attention_grouped":
            {"calls": 2, "flops": 1e12, "bytes": 1.0},
            "fused_xent_fwd": {"calls": 1, "flops": 1e10, "bytes": 1.0},
            "fused_xent_rows4096_fwd":
            {"calls": 1, "flops": 1e12, "bytes": 1.0},
            "fused_xent_bwd": {"calls": 1, "flops": 1.0, "bytes": 3e9}}
    run = {"trace": _fake_trace(rows), "peaks": REHEARSAL_PEAKS,
           "cell": {"traffic": {"loss_fetch_every": 5}}}
    every = (xent_share, xent_roof, attn_share, attn_roof)
    monkeypatch.setattr(counters, "step_work", lambda step: work)
    # the counters gate them: absent (the parent), those of a one-pass
    # cell (a mean head; a model that walks its layers once)
    monkeypatch.setattr(counters, "snapshot", lambda: {})
    assert [read(run) for read in every] == [None] * 4
    monkeypatch.setattr(counters, "snapshot",
                        lambda: {"fused_xent.pallas": 1,
                                 "fused_xent.ladder": 1,
                                 "flash_attention.pallas": 7,
                                 "flash_attention.latent": 7})
    assert [read(run) for read in every] == [None] * 4
    monkeypatch.setattr(counters, "snapshot",
                        lambda: {"causal_lm.ut_steps": 4})
    assert xent_share(run) is None and xent_roof(run) is None
    assert attn_share(run) == pytest.approx(100.0 * 0.8 / 2.0)
    # the larger of FLOP / 1e12 and bytes / 1e11, x 5 steps, both roles
    assert attn_roof(run) == pytest.approx(100.0 * 5 * (2e10 + 4e10) / 1e12
                                           / 0.8)
    monkeypatch.setattr(counters, "snapshot",
                        lambda: {"fused_xent.per_row": 1})
    assert attn_share(run) is None and attn_roof(run) is None
    assert xent_share(run) == pytest.approx(100.0 * 0.2 / 2.0)
    # forward compute-bound, backward by its bytes; the rung that did not
    # run has no row and counts on neither side
    assert xent_roof(run) == pytest.approx(
        100.0 * 5 * (1e10 / 1e12 + 3e9 / 1e11) / 0.2)
    # a reduction without the rows, a run with no trace, no ledger
    monkeypatch.setattr(counters, "snapshot",
                        lambda: {"fused_xent.per_row": 1,
                                 "causal_lm.ut_steps": 4})
    other = dict(run, trace=_fake_trace(rows[:1] + rows[3:4]))
    assert [read(other) for read in every] == [None] * 4
    assert xent_share({"trace": None}) is None
    assert attn_roof(dict(run, trace=None)) is None
    monkeypatch.setattr(counters, "step_work", lambda step: {})
    assert xent_roof(run) is None and attn_roof(run) is None


def test_step_work_of_both_kernels_is_the_work_files(tiny, monkeypatch):
    """The program's ledger for one TrainStep (what the two ``ut_*``
    roofline metrics read) equals benchmarks/work_ouro.py's count, at
    lane-dense widths (heads of 128, hidden 256), kernels in interpret
    mode: 4 x 2 stream launches, ONE stacked head call on its top rung;
    and the counters the acceptance names are set."""
    from jax.experimental import pallas as pl

    import paddle_tpu.framework.bringup as bringup
    from paddle_tpu.ops.pallas import counters

    monkeypatch.setattr(pl, "pallas_call",
                        functools.partial(pl.pallas_call, interpret=True))
    monkeypatch.setattr(bringup, "pallas_enabled", lambda: True)
    root, cell, cfg, _ = tiny
    cfg = dict(cfg, hidden_size=256, num_attention_heads=2,
               num_key_value_heads=2, head_dim=128, intermediate_size=128)
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
    want = work_ouro.attn_kernel_work(mcfg, 1, 256, itemsize=4)
    assert {k: work[k] for k in want} == want
    want = work_ouro.xent_rows_work(mcfg, 1, 256)
    assert {k: work[k] for k in want} == want
    assert "flash_attention.xla" not in snap and "fused_xent.xla" not in snap
    assert "flash_attention.latent" not in snap      # one width: 128 / 128
    assert snap["causal_lm.ut_steps"] == 4
    assert snap["flash_attention.kept_across_recompute"] \
        == snap["flash_attention.pallas"] \
        == snap["causal_lm.block_applications"]
    assert snap["causal_lm.block_applications"] % 8 == 0
    assert snap["fused_xent.per_row"] == snap["fused_xent.pallas"]
    assert "ut_step4" in text and "ut_exit_loss" in text
    assert "rotary_embedding" in text
