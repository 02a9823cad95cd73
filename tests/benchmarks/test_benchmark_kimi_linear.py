"""The ``kimi-linear-48b-a3b`` configuration and its cell: the data files
against the catalog and the contract's form, the generator, the
arithmetic, the reference against the program through ``TrainStep`` (and
the fp8 control, which has to fail), and the whole command at tiny size
through the harness — on the CPU, never a measurement."""
import functools
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import harness, lm_traffic, work_kimi_linear
from benchmarks.reference import kimi_linear as ref

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CONFIG = "kimi-linear-48b-a3b"
CELL = CONFIG + ".pretrain-seq8k"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REHEARSAL_PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes": 1e9}
#: what the contract calls a width: never cut, never in ``reduced``
WIDTH = re.compile(r"(hidden_size|intermediate|latent|state_size|proj|"
                   r"head_dim|_dim$|_rank$|expansion|experts_per)")


def _write(root, rel, obj):
    path = os.path.join(root, rel)
    assert not os.path.exists(path), f"{rel} would edit an existing file"
    with open(path, "w") as f:
        json.dump(obj, f)


@pytest.fixture(scope="module")
def tiny(bench_root):
    """(root, cell, config) of ``kimi-tiny.pretrain``: the real files cut
    to test size and dropped into the copy of ``benchmarks/`` as NEW
    files. float32 end to end, so that the program is held to the
    reference tightly."""
    root, before = bench_root
    cfg = harness.load_json(os.path.join(root, f"configs/{CONFIG}.json"))
    cfg.update(name="kimi-tiny", hidden_size=64, intermediate_size=96,
               moe_intermediate_size=32, kv_lora_rank=32,
               qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
               num_attention_heads=4, vocab_size=512, num_experts=4,
               num_experts_per_token=4)
    cfg["published"] = dict(cfg["published"], num_experts=16)
    # three layers hold every kind: (kda, dense), (mla, moe), (kda, moe)
    cfg["num_hidden_layers"] = 3
    cfg["linear_attn_config"] = dict(
        cfg["linear_attn_config"], num_heads=4, head_dim=16,
        kda_layers=[1, 3], full_attn_layers=[2])
    cfg["program"] = dict(cfg["program"], amp_level="O0")
    _write(root, "configs/kimi-tiny.json", cfg)
    cell = harness.load_json(os.path.join(root, f"workloads/{CELL}.json"))
    cell.update(name="kimi-tiny.pretrain", config="kimi-tiny")
    cell["traffic"].update(batch=2, seq=64, host_batches=4,
                           loss_fetch_every=2)
    cell["correct"].update(block_rows=32, limits={
        "loss_gap": 1e-5, "grad_norm_gap": 1e-3, "delta_norm_gap": 3e-3})
    _write(root, "workloads/kimi-tiny.pretrain.json", cell)
    return root, cell, cfg, before


def rehearse(root, cell, trace=False, seed=2 ** 31 + 26, seconds=1.0):
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
    return next(r for r in rows if r["name"] == "Kimi-Linear-48B-A3B-Instruct")


def test_config_keeps_every_published_key_but_the_listed_cuts():
    row = _catalog_row()
    cfg = harness.load_json(os.path.join(harness.ROOT,
                                         f"configs/{CONFIG}.json"))
    assert cfg["source"] == row["source_url"]
    changed = sorted(k for k, v in row["config"].items() if cfg[k] != v)
    assert changed == sorted(cfg["reduced"])
    assert not [k for k in cfg["reduced"] if WIDTH.search(k)]
    lin, pub = cfg["linear_attn_config"], row["config"]["linear_attn_config"]
    assert {k: lin[k] for k in ("num_heads", "head_dim",
                                "short_conv_kernel_size")} == \
        {k: pub[k] for k in ("num_heads", "head_dim",
                             "short_conv_kernel_size")}
    assert cfg["published"] == {
        "num_hidden_layers": 27, "num_experts": 256, "vocab_size": 163840,
        "kda_layers": pub["kda_layers"],
        "full_attn_layers": pub["full_attn_layers"]}
    # the cut is at the guide's floors: a whole period after the dense
    # layer, 8 experts, an eighth of the vocabulary
    assert ref.layer_kinds(dict(cfg, first_k_dense_replace=1)) == [
        ("kda", "dense"), ("kda", "moe"), ("kda", "moe"), ("mla", "moe"),
        ("kda", "moe")]
    assert cfg["num_experts"] == 8 and cfg["vocab_size"] * 8 == 163840


def test_benchmark_json_only_gained_entries():
    bench = harness.load_json(os.path.join(REPO, "BENCHMARK.json"))
    assert [c["name"] for c in bench["configs"]] == ["bert-base", CONFIG]
    assert [w["name"] for w in bench["workloads"]][-1] == CELL
    entry = bench["workloads"][-1]
    assert entry["chips"] == 1 and len(entry["why"]) <= 200
    cell, cfg = harness.load_cell(CELL)
    assert cell["why"] == entry["why"] and cfg["name"] == CONFIG
    new = [m for m in bench["per_layer"] if m.get("workloads") == [CELL]]
    assert [m["name"] for m in new] == [
        "kda_device_share_pct.train", "kda_roofline_pct.train",
        "moe_rows_used_pct.train"]
    files = {m["name"]: m for m in harness.layer_metrics()}
    for m in new:
        for key in ("unit", "better", "source", "layer", "moves"):
            assert files[m["name"]][key] == m[key]
    # nothing of the accepted benchmark lists the new cell
    for m in bench["per_layer"]:
        if m not in new:
            assert CELL not in m.get("workloads", [])


def test_cell_counts_the_share_its_files_state():
    cell, cfg = harness.load_cell(CELL)
    driver = harness.load_driver(cfg)
    mcfg = driver.model_config(cfg)
    shapes = driver.param_shapes(mcfg)
    total = sum(int(np.prod(s)) for s in shapes.values())
    assert total == 602_449_792                    # 9.64 GB at 16 B each
    assert mcfg["num_experts"] == 256 and mcfg["experts_held"] == 8
    feed = cell["traffic"]
    assert feed["batch"] * feed["seq"] == 8192
    flops = work_kimi_linear.train_flops_per_token(mcfg, 8192, 8191)
    assert 2.2e9 < flops < 2.5e9
    work = work_kimi_linear.kda_kernel_work(mcfg, 1, 8192)
    assert work["kda_chunk_fwd"]["calls"] == 4
    assert work["kda_chunk_fwd"]["flops"] == 4 * 6.0 * 8192 * 32 * 128 * 128


def test_flops_per_token_by_hand():
    mcfg = {"hidden_size": 10, "vocab_size": 100, "num_hidden_layers": 2,
            "first_k_dense_replace": 1, "intermediate_size": 20,
            "moe_intermediate_size": 5, "num_experts": 8, "experts_held": 2,
            "num_experts_per_token": 4, "num_shared_experts": 1,
            "num_attention_heads": 2, "qk_nope_head_dim": 4,
            "qk_rope_head_dim": 2, "v_head_dim": 4, "kv_lora_rank": 3,
            "linear_attn_config": {"kda_layers": [1], "num_heads": 2,
                                   "head_dim": 4,
                                   "short_conv_kernel_size": 4}}
    kda = 4 * 10 * 8 + 2 * (10 * 4 + 4 * 8) + 10 * 2
    mla = 10 * 2 * 6 + 10 * 5 + 3 * 2 * 8 + 2 * 4 * 10
    want = 6 * kda + 18 * 16 * 2 + 6 * 3 * 10 * 20 \
        + 6 * mla + 6 * (6 + 4) * (16 / 2) * 2 \
        + 6 * (10 * 8 + (1 + 4 * 2 / 8) * 3 * 10 * 5) \
        + 6 * 10 * 100 * 15 / 16
    assert work_kimi_linear.train_flops_per_token(mcfg, 16, 15) == \
        pytest.approx(want)


def test_lm_feed_is_seeded_shifted_and_zipfian():
    feed = {"kind": "lm_feed", "batch": 2, "seq": 4096,
            "zipf_exponent": 1.0, "host_batches": 2}
    a = lm_traffic.lm_batches(feed, 20480, 2 ** 31 + 5)
    b = lm_traffic.lm_batches(feed, 20480, 2 ** 31 + 5)
    c = lm_traffic.lm_batches(feed, 20480, 2 ** 31 + 6)
    assert all(np.array_equal(x, y) for x, y in zip(a[0], b[0]))
    assert not np.array_equal(a[0][0], c[0][0])
    ids, labels = a[0]
    assert ids.dtype == labels.dtype == np.int32 and ids.shape == (2, 4096)
    assert np.array_equal(labels[:, :-1], ids[:, 1:])
    assert (labels[:, -1] == -100).all() and (labels != -100).sum() == 8190
    assert 0 <= ids.min() and ids.max() < 20480
    counts = np.sort(np.bincount(np.concatenate(
        [x[0].ravel() for x in a]), minlength=20480))[::-1]
    share = counts[0] / counts.sum()               # 1 / H(20480) = 9.5%
    assert 0.07 < share < 0.12 and counts[1] < counts[0]
    # the frequent ids are not the low ids: ranks are permuted by seed
    assert np.argmax(np.bincount(a[0][0].ravel())) != \
        np.argmax(np.bincount(c[0][0].ravel()))
    with pytest.raises(ValueError):
        lm_traffic.lm_batches(dict(feed, kind="train_feed"), 10, 1)


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
    ctx, drv, _ = harness.context("kimi-tiny.pretrain", 6, 1.0, root,
                                  check_device=False, log=lambda _m: None)
    out = drv.control(ctx)
    assert [c for c in out["checks"] if not c["ok"]]
    assert all(c["name"].startswith("fp8 ") for c in out["checks"])


def test_reference_imports_nothing_of_the_program():
    with open(os.path.join(harness.ROOT, "reference", "kimi_linear.py")) as f:
        text = f.read()
    assert "paddle_tpu" not in text.split('"""', 2)[2]
    assert "lax.scan(token" in text                # KDA by its recurrence


# ---------------------------------------------------------------------------
# the whole command at tiny size
# ---------------------------------------------------------------------------
def test_new_cell_rehearses_through_the_harness(tiny, digest):
    root, _, _, before = tiny
    result, lines = rehearse(root, "kimi-tiny.pretrain")
    assert result["correct"] is True, lines
    assert set(result["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert result["failed"] == 0 and result["attempted"] >= 2
    for name in ("loss_gap", "grad_norm_gap", "delta_norm_gap",
                 "window_compilations", "window_nonfinite_losses"):
        assert [ln for ln in lines if ln.startswith(f"check {name}: value")]
    after = digest(root)
    assert {k: after[k] for k in before} == before   # no existing file


def test_traced_rehearsal_reports_the_cells_layer_metrics(tiny):
    root, _, _, _ = tiny
    result, lines = rehearse(root, "kimi-tiny.pretrain", trace=True)
    got = result["metrics"]
    assert {"moe_rows_used_pct.train", "mfu_pct.train",
            "dispatch_ms.train"} <= set(got)
    assert 0 < got["moe_rows_used_pct.train"]["value"] <= 100
    # a CPU has no device plane and launches no kernel: the trace-fed
    # readers find nothing and their metrics are left out, as on a
    # commit whose program has no such kernel
    assert not [m for m in got if m.startswith("kda_")]
    assert not [m for m in got if m.endswith(".serve")]


def test_bert_cells_do_not_see_the_new_metrics(bench_root):
    root, _ = bench_root
    lines = []
    result = harness.run_cell("bert-tiny.pretrain", seed=3, seconds=1.0,
                              trace=True, root=root, peaks=REHEARSAL_PEAKS,
                              check_device=False, log=lines.append)
    assert result["correct"] is True
    assert not [m for m in result["metrics"]
                if m.startswith(("kda_", "moe_"))]


def test_step_work_of_the_kda_roles_is_the_work_files(tiny, monkeypatch):
    """The program's ledger for one TrainStep (what kda_roofline_pct
    reads) equals benchmarks/work_kimi_linear.py's count, at lane-dense
    heads, kernels in interpret mode."""
    from jax.experimental import pallas as pl

    import paddle_tpu.framework.bringup as bringup
    from paddle_tpu.ops.pallas import counters

    monkeypatch.setattr(pl, "pallas_call",
                        functools.partial(pl.pallas_call, interpret=True))
    monkeypatch.setattr(bringup, "pallas_enabled", lambda: True)
    monkeypatch.setenv("PADDLE_FUSED_OPT", "0")
    root, cell, cfg, _ = tiny
    cfg = dict(cfg, linear_attn_config=dict(
        cfg["linear_attn_config"], num_heads=1, head_dim=128))
    cell = dict(cell, traffic=dict(cell["traffic"], batch=1, seq=128))
    driver = harness.load_driver(cfg, root)
    mcfg = driver.model_config(cfg)
    batches = lm_traffic.lm_batches(cell["traffic"], mcfg["vocab_size"], 8)
    counters.reset()
    try:
        loop = driver.Loop(cfg, cell, driver.make_params(mcfg, 8), 8)
        loss = float(loop.feed_and_step(batches[0]))
        work = counters.step_work("train_step")
    finally:
        counters.reset()
    assert np.isfinite(loss)
    want = work_kimi_linear.kda_kernel_work(mcfg, 1, 128)
    assert {k: work[k] for k in want} == want
