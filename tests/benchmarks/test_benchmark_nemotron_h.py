"""The ``nemotron-3-nano-30b-a3b`` configuration and its cell: the data
files against the catalog and the contract's form, the arithmetic against
the issue's numbers, the reference against the program through
``TrainStep`` (and the fp8 control, which has to fail), the program's
work ledger against ``work_nemotron_h.py``, the new readers on a made-up
reduction, and the whole command at tiny size through the harness — on
the CPU, never a measurement."""
import functools
import json
import os
import re

import numpy as np
import pytest

from benchmarks import harness, lm_traffic, work_nemotron_h
from benchmarks.reference import nemotron_h as ref

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CONFIG = "nemotron-3-nano-30b-a3b"
CELL = CONFIG + ".pretrain-seq8k"
TINY = "nemotron-tiny.pretrain"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REHEARSAL_PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes": 1e9,
                   "hbm_bytes_per_s": 1e11}
#: what the contract calls a width: never cut, never in ``reduced``
WIDTH = re.compile(r"(hidden_size|intermediate|latent|state_size|proj|"
                   r"head_dim|_dim$|_rank$|expansion|experts_per)")
NEW_METRICS = ["ssd_device_share_pct.train", "ssd_roofline_pct.train"]
REDUCED = ["num_hidden_layers", "n_routed_experts", "vocab_size",
           "hybrid_override_pattern"]
PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"


def _write(root, rel, obj):
    path = os.path.join(root, rel)
    assert not os.path.exists(path), f"{rel} would edit an existing file"
    with open(path, "w") as f:
        json.dump(obj, f)


@pytest.fixture(scope="module")
def tiny(bench_root):
    """(root, cell, config) of ``nemotron-tiny.pretrain``: the real files
    cut to test size and dropped into the copy of ``benchmarks/`` as NEW
    files. float32 end to end, so that the program is held to the
    reference tightly; the nine layers and their pattern are the cell's."""
    root, before = bench_root
    cfg = harness.load_json(os.path.join(root, f"configs/{CONFIG}.json"))
    cfg.update(name="nemotron-tiny", hidden_size=64, head_dim=16,
               num_attention_heads=4, num_key_value_heads=2,
               mamba_num_heads=4, mamba_head_dim=16, ssm_state_size=16,
               n_groups=2, moe_intermediate_size=32,
               moe_shared_expert_intermediate_size=48, n_routed_experts=4,
               vocab_size=512)
    cfg["published"] = dict(cfg["published"], n_routed_experts=16)
    cfg["program"] = dict(cfg["program"], amp_level="O0")
    _write(root, "configs/nemotron-tiny.json", cfg)
    cell = harness.load_json(os.path.join(root, f"workloads/{CELL}.json"))
    cell.update(name=TINY, config="nemotron-tiny")
    cell["traffic"].update(batch=2, seq=64, host_batches=4,
                           loss_fetch_every=2)
    cell["correct"].update(block_rows=32, limits={
        "loss_gap": 1e-5, "grad_norm_gap": 1e-3, "delta_norm_gap": 3e-3})
    _write(root, f"workloads/{TINY}.json", cell)
    return root, cell, cfg, before


def rehearse(root, cell, trace=False, seed=2 ** 31 + 33, seconds=1.0):
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
                if r["name"] == "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16")


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
    widths = {"hidden_size": 2688, "mamba_num_heads": 64,
              "mamba_head_dim": 64, "ssm_state_size": 128, "n_groups": 8,
              "conv_kernel": 4, "num_attention_heads": 32,
              "num_key_value_heads": 2, "head_dim": 128,
              "moe_intermediate_size": 1856,
              "moe_shared_expert_intermediate_size": 3712,
              "num_experts_per_tok": 6, "routed_scaling_factor": 2.5,
              "n_shared_experts": 1, "intermediate_size": 1856}
    for key, value in widths.items():
        assert cfg[key] == pub[key] == value, key
    assert pub["hybrid_override_pattern"] == PATTERN
    assert cfg["published"] == {
        "num_hidden_layers": 52, "n_routed_experts": 128,
        "vocab_size": 131072, "hybrid_override_pattern": PATTERN}
    # the cut: the first nine layers, an eighth of the vocabulary, 8 of
    # 128 experts: the guide's floors, the last two exactly
    assert cfg["hybrid_override_pattern"] == PATTERN[:9] == "MEMEM*EME"
    assert cfg["num_hidden_layers"] == 9
    assert cfg["n_routed_experts"] * 16 == 128
    assert cfg["vocab_size"] * 8 == 131072
    assert "16 chips" in cfg["stands_for"]
    assert (PATTERN.count("M"), PATTERN.count("E"),
            PATTERN.count("*")) == (23, 23, 6)
    assert [n + 1 for n, c in enumerate(PATTERN) if c == "*"] == [
        6, 13, 20, 27, 34, 43]
    # what the keys leave open, each with its reason
    assert cfg["rope"] is None and "no" in cfg["assumed"]["rope"].lower()
    assert cfg["moe_router_activation_func"] == "sigmoid"
    assert "5376" in cfg["assumed"]["mamba_inner_width"]
    for key in ("weights", "router_bias", "optimizer", "precision"):
        assert cfg["assumed"][key]
    assert any("rescale_prenorm_residual" in d for d in cfg["departs"])
    assert any("chunk_size" in d for d in cfg["departs"])
    assert any("120 absent experts" in d for d in cfg["departs"])


def test_benchmark_json_only_gained_entries():
    bench = harness.load_json(os.path.join(REPO, "BENCHMARK.json"))
    names = [c["name"] for c in bench["configs"]]
    assert names == ["bert-base", "kimi-linear-48b-a3b",
                     "mellum2-12b-a2.5b", CONFIG]
    cells = [w["name"] for w in bench["workloads"]]
    assert cells == ["bert-base.pretrain-seq512",
                     "bert-base.pretrain-seq128",
                     "kimi-linear-48b-a3b.pretrain-seq8k",
                     "mellum2-12b-a2.5b.pretrain-seq8k", CELL]
    entry = bench["workloads"][4]
    assert entry["chips"] == 1 and len(entry["why"]) <= 200
    assert (entry["config"], entry["traffic"]) == (CONFIG, "pretrain-seq8k")
    assert all(len(w["why"]) <= 200 and "\n" not in w["why"]
               for w in bench["workloads"] + bench["configs"])
    cell, cfg = harness.load_cell(CELL)
    assert cell["why"] == entry["why"] and cfg["name"] == CONFIG
    assert bench["configs"][3]["reduced"] == cfg["reduced"] == REDUCED
    assert bench["configs"][3]["file"] == f"benchmarks/configs/{CONFIG}.json"
    assert bench["configs"][3]["source"] == cfg["source"]
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
    assert [m["name"] for m in bench["per_layer"]][15:] == NEW_METRICS
    # nothing of the accepted benchmark lists the new cell, and what it
    # had is as it was
    for m in bench["per_layer"][:15]:
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

    # Mamba-2 38.74 M, attention 23.40 M, experts 100.13 M (8 held),
    # embedding + head 88.08 M: 667.0 M in all, 10.67 GB at 16 B each
    assert [layer(n) for n in range(9)] == [
        38_744_896, 100_125_312, 38_744_896, 100_125_312, 38_744_896,
        23_399_040, 100_125_312, 38_744_896, 100_125_312]
    assert total == 666_962_944
    assert round(total * 16 / 1e9, 2) == 10.67
    assert round(100 * total * 16 / 2 ** 34, 1) == 62.1
    counted = work_nemotron_h.param_count(mcfg)
    assert counted["total"] == total
    assert (counted["mamba2"], counted["attention"], counted["moe"],
            counted["embedding_and_head"]) == (
        38_744_896, 23_399_040, 100_125_312, 88_080_384)
    # with 16 experts held the same nine layers leave no room
    wide = work_nemotron_h.param_count(dict(mcfg, experts_held=16))
    assert round(wide["total"] / 1e6) == 986
    assert mcfg["n_routed_experts"] == 128 and mcfg["experts_held"] == 8
    feed = cell["traffic"]
    assert (feed["batch"], feed["seq"]) == (2, 8192)
    assert (feed["zipf_exponent"], feed["host_batches"],
            feed["loss_fetch_every"]) == (1.0, 8, 5)
    assert cell["correct"]["steps"] == 3
    assert cell["correct"]["control_precisions"] == ["fp8"]
    assert set(cell["correct"]["limits"]) == {
        "loss_gap", "grad_norm_gap", "delta_norm_gap"}
    assert "PR 33" in cell["correct"]["limits_from"]
    kimi, _ = harness.load_cell("kimi-linear-48b-a3b.pretrain-seq8k")
    assert cell["optimizer"] == kimi["optimizer"]
    # the ladder's sorted rung for this share is 0.375 of the dense top
    # rung's rows, over the third it may be: every step runs the dense
    # rung, and the seed's routing does not move the step's time
    from paddle_tpu.nn.moe import _row_ladder

    assert _row_ladder(16384 * 6, 8, 128) == (49152, 98304)
    assert _row_ladder(16384 * 6, 8, 128, 16384 * 8) == (98304,)
    assert 16384 * 6 * 8 // 128 == 6144


def test_flops_are_the_issues_numbers():
    _, cfg = harness.load_cell(CELL)
    mcfg = harness.load_driver(cfg).model_config(cfg)
    m = 1e6
    assert work_nemotron_h.mamba_matrix_params(mcfg) == \
        2688 * 10304 + 4096 * 2688
    assert 2 * work_nemotron_h.mamba_matrix_params(mcfg) / m == \
        pytest.approx(77.4, abs=0.05)
    scan = work_nemotron_h.scan_flops_per_token(mcfg)
    assert scan == 8 * 2 * 128 * 128 + 64 * (2 * 128 * 64 + 4 * 128 * 64)
    assert scan / m == pytest.approx(3.41, abs=5e-3)
    layers = {k: work_nemotron_h.layer_flops_per_token(mcfg, k, 8192)
              for k in ("mamba2", "attention", "moe")}
    # an expert layer: shared 39.9, router 0.7, held routed 7.5 MFLOP at
    # 0.375 picks a token here
    assert 6 * 8 / 128 == 0.375
    assert layers["moe"] / m == pytest.approx(39.9 + 0.7 + 7.5, abs=0.05)
    head = 2 * 2688 * 16384 * 8191 / 8192
    forward = 4 * layers["mamba2"] + layers["attention"] \
        + 4 * layers["moe"] + head
    assert forward / 1e9 == pytest.approx(0.718, abs=5e-4)
    total = work_nemotron_h.train_flops_per_token(mcfg, 8192, 8191)
    assert total == pytest.approx(3 * forward)
    assert total / 1e9 == pytest.approx(2.15, abs=5e-3)
    shares = {"mamba2": 4 * layers["mamba2"], "attention":
              layers["attention"], "moe": 4 * layers["moe"], "head": head}
    want = {"mamba2": 45.1, "attention": 15.9, "moe": 26.8, "head": 12.3}
    for k, v in want.items():
        assert 100 * shares[k] / forward == pytest.approx(v, abs=0.06), k
    # the dense top rung would cost 200 MFLOP a token and layer forward
    # + backward against 48 required forward: it must stay the rare rung
    assert 3 * 2 * 8 * 2 * 2688 * 1856 / m == pytest.approx(479, abs=1)
    assert 2 * 8 * 2 * 2688 * 1856 / m == pytest.approx(160, abs=1)


def test_kernel_work_is_one_role_for_forward_and_backward():
    _, cfg = harness.load_cell(CELL)
    mcfg = harness.load_driver(cfg).model_config(cfg)
    work = work_nemotron_h.ssd_kernel_work(mcfg, 2, 8192)
    assert list(work) == ["ssd_chunk"]
    row = work["ssd_chunk"]
    assert row["calls"] == 8
    assert row["flops"] == 4 * 3 * 16384 * 3_407_872
    assert row["bytes"] == 4 * 3 * 16384 * (2 * (2 * 4096 + 2048) + 4 * 64)


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
    assert [(b.mixer_kind, b.ffn_kind) for b in loop.model.layers] == [
        ("mamba2", None), (None, "moe"), ("mamba2", None), (None, "moe"),
        ("mamba2", None), ("gqa", None), (None, "moe"), ("mamba2", None),
        (None, "moe")]
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


def test_the_other_reading_of_the_source_is_one_entry_in_the_file(tiny):
    """``rope: {...}`` reaches the program and the reference alike, and
    they still agree; it is not the reading the file takes."""
    root, cell, cfg, _ = tiny
    rotated = dict(cfg, rope={"rope_type": "default", "rope_theta": 10000})
    driver = harness.load_driver(cfg, root)
    batches = lm_traffic.lm_batches(cell["traffic"], cfg["vocab_size"], 7)
    losses = []
    for c in (rotated, cfg):
        mcfg = driver.model_config(c)
        want = driver._reference(mcfg, cell, batches[:1], 7)
        loop = driver.Loop(c, cell, driver.make_params(mcfg, 7), 7)
        got = driver.first_steps(loop, mcfg, batches, 7, 1)
        assert all(x["ok"] for x in driver.compare(
            got, want, cell["correct"]["limits"]))
        losses.append(want["loss"][0])
    assert loop.model.layers[5].mixer.inv_freq is None
    assert abs(losses[0] - losses[1]) > 1e-6


def test_reference_imports_nothing_of_the_program():
    with open(os.path.join(harness.ROOT, "reference", "nemotron_h.py")) as f:
        text = f.read()
    code = text.split('"""', 2)[2]
    assert "paddle_tpu" not in code
    assert "jax.lax.scan(token, state, xs)" in code  # the literal recurrence
    assert "jnp.where(ok[None], s, -jnp.inf)" in code   # an explicit mask
    assert "for e in range(" in code       # a dense loop over the experts
    assert "chunk" not in code.lower().replace("segment", "")


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
    # on the CPU the scan takes its XLA formulation, counted, and the
    # plain experts say so
    assert "'ssd.xla'" in counters_line
    assert "'sparse_moe.plain'" in counters_line
    assert "'sparse_moe.gated'" not in counters_line
    after = digest(root)
    assert {k: after[k] for k in before} == before   # no existing file


def _state_left_unchanged(monkeypatch):
    """A step that runs and hands its parameters back as they were."""
    from benchmarks.drivers.causal_lm_step import Loop

    original = Loop.feed_and_step

    def broken(self, batch):
        import jax.numpy as jnp

        saved = {k: jnp.copy(p.value)
                 for k, p in self.model.named_parameters()}
        loss = original(self, batch)
        for k, p in self.model.named_parameters():
            p._value = saved[k]
        return loss

    monkeypatch.setattr(Loop, "feed_and_step", broken)


def _half_of_the_batch_left_out(monkeypatch):
    """A step that trains on the batch's first row alone."""
    from benchmarks.drivers.causal_lm_step import Loop

    original = Loop.feed_and_step

    def broken(self, batch):
        ids, labels = batch
        labels = np.array(labels)
        labels[len(labels) // 2:] = -100
        return original(self, (ids, labels))

    monkeypatch.setattr(Loop, "feed_and_step", broken)


def _scan_without_its_state(monkeypatch):
    """A state-space layer whose state forgets everything at once (the
    decay rate A taken for -1e4 where it is -exp(A_log)): each token
    sees its own write alone."""
    from paddle_tpu.ops.pallas import ssd

    original = ssd.ssd_chunk_scan

    def broken(u, delta, a, bm, cm, groups, chunk=ssd.CHUNK):
        return original(u, delta, a * 0.0 - 1e4, bm, cm, groups, chunk)

    monkeypatch.setattr(ssd, "ssd_chunk_scan", broken)


@pytest.mark.parametrize("fault,fails", [
    (_state_left_unchanged, ["delta_norm_gap"]),
    (_half_of_the_batch_left_out, ["loss_gap", "grad_norm_gap"]),
    # at the seeded start the loss is ln(vocabulary) whatever a mixer
    # does, and the steps are small, so the state's part of a layer's
    # output is small beside the skip's: the leaf NORMS of the first
    # gradient hardly move (4.8e-4 here), and it is Adam's step, which
    # follows each gradient's direction, that says it (A_log's, 0.08)
    (_scan_without_its_state, ["delta_norm_gap"]),
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
    # the driver observes the routing; the accepted metric lists its own
    # cells in BENCHMARK.json, the harness reports what a reader finds
    assert 0 < got["moe_rows_used_pct.train"]["value"] <= 100
    # a CPU has no device plane and launches no kernel: the trace-fed
    # readers find nothing and their metrics are left out, as on a
    # commit whose program has no such kernel
    assert not [m for m in got if m.startswith(("ssd_", "gqa_", "kda_"))]
    assert not [m for m in got if m.endswith(".serve")]


def test_the_parent_refuses_the_driver_cleanly(tiny, monkeypatch):
    """On a program without ``nn.Mamba2Mixer`` (the parent commit with
    this PR's benchmark files laid over it) the driver refuses before the
    reference's minutes."""
    import paddle_tpu.nn as nn_mod

    root, _, _, _ = tiny
    monkeypatch.delattr(nn_mod, "Mamba2Mixer")
    with pytest.raises(harness.Refused, match="Mamba2Mixer"):
        harness.run_cell(TINY, seed=1, seconds=1.0, trace=False, root=root,
                         peaks=REHEARSAL_PEAKS, check_device=False,
                         log=lambda _m: None)


def _fake_trace(rows):
    return {"busy_s": 2.0, "window_s": 2.5, "device_ops": rows}


def test_the_new_readers_read_the_scans_row_and_nothing_else(monkeypatch):
    from paddle_tpu.ops.pallas import counters

    metrics = {m["name"]: m["read"] for m in harness.layer_metrics()}
    share, roof = (metrics[n] for n in NEW_METRICS)
    rows = [["fusion", 0.9], ["kernel:ssd_chunk", 0.5],
            ["kernel:flash_attention_grouped", 0.4],
            ["kernel:fused_xent_fwd", 0.05]]
    work = {"ssd_chunk": {"calls": 8, "flops": 3e10, "bytes": 2e9},
            "flash_attention_grouped": {"calls": 2, "flops": 1e12,
                                        "bytes": 1.0}}
    run = {"trace": _fake_trace(rows), "peaks": REHEARSAL_PEAKS,
           "cell": {"traffic": {"loss_fetch_every": 5}}}
    monkeypatch.setattr(counters, "step_work", lambda step: work)
    assert share(run) == pytest.approx(100.0 * 0.5 / 2.0)
    # the larger of FLOP / 1e12 and bytes / 1e11, x 5 steps
    assert roof(run) == pytest.approx(100.0 * 5 * 3e10 / 1e12 / 0.5)
    # a reduction without the row (the parent, another cell), a run with
    # no trace (the CPU, --trace 0), a program without a ledger
    other = dict(run, trace=_fake_trace(rows[:1] + rows[2:]))
    assert share(other) is None and roof(other) is None
    assert share({"trace": None}) is None
    assert roof(dict(run, trace=None)) is None
    monkeypatch.setattr(counters, "step_work", lambda step: {})
    assert roof(run) is None


def test_step_work_of_the_scans_role_is_the_work_files(tiny, monkeypatch):
    """The program's ledger for one TrainStep (what ssd_roofline_pct
    reads) equals benchmarks/work_nemotron_h.py's count, at lane-dense
    groups, kernels in interpret mode; and the counters show the scan's
    kernel in all four Mamba-2 layers and the grouped flash kernels in
    the attention layer."""
    from jax.experimental import pallas as pl

    import paddle_tpu.framework.bringup as bringup
    from paddle_tpu.ops.pallas import counters

    monkeypatch.setattr(pl, "pallas_call",
                        functools.partial(pl.pallas_call, interpret=True))
    monkeypatch.setattr(bringup, "pallas_enabled", lambda: True)
    root, cell, cfg, _ = tiny
    cfg = dict(cfg, mamba_num_heads=4, mamba_head_dim=32, n_groups=1,
               ssm_state_size=128, head_dim=128, num_attention_heads=2,
               num_key_value_heads=1)
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
    want = work_nemotron_h.ssd_kernel_work(mcfg, 1, 256, itemsize=4)
    assert {k: work[k] for k in want} == want
    assert "ssd.xla" not in snap and snap["ssd.pallas"] % 4 == 0
    assert "flash_attention.xla" not in snap
    assert snap["flash_attention.grouped"] == snap["flash_attention.pallas"]
    # 4 held of top 6: the dense top rung (the cell's too, 8 held)
    assert snap["sparse_moe.plain"] == snap["sparse_moe.every_pair"]
