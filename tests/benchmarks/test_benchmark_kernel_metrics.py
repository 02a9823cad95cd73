"""The per-layer metrics that read the program's kernel names, work
ledger and compile seconds (PR 24), each on a hand-made ``run``: rows
present, rows absent (as on a CPU, or on a commit whose kernels have no
role names), and a kernel whose instances carry numeric suffixes."""
import pytest

from benchmarks import harness, trace_reduce
from paddle_tpu.ops.pallas import autotune, counters
from paddle_tpu.static import compile_cache

PEAKS = {"bf16_flops_per_s": 100e12, "hbm_bytes_per_s": 1e12}
CELL = {"traffic": {"loss_fetch_every": 10},
        "end_to_end": {"train_tokens_per_s": "tokens/s", "setup_s": "s"}}
NEW = ("xent_device_share_pct.train", "attn_device_share_pct.train",
       "xent_roofline_pct.train", "attn_roofline_pct.train",
       "step_compile_s.train", "autotune_s.train")


@pytest.fixture(scope="module")
def readers():
    return {m["name"]: m["read"] for m in harness.layer_metrics()}


def _run(rows, busy_s=4.0):
    trace = None if rows is None else {
        "busy_s": busy_s, "window_s": busy_s, "idle_pct": 0.0,
        "device_ops": [list(r) for r in rows], "idle_gaps": []}
    return {"trace": trace, "peaks": PEAKS, "cell": CELL, "chips": 1,
            "observations": {}}


@pytest.fixture
def ledger():
    """One step's work: xent 1e12 + 2e12 FLOP, flash bound by bytes."""
    with counters.capture("train_step"):
        counters.bump("fused_xent", "pallas",
                      work={"fused_xent_fwd": (1e12, 1e6)})
        with counters.differentiated():
            counters.bump("fused_xent", "pallas",
                          grad_work={"fused_xent_bwd": (2e12, 1e6)})
            counters.bump("flash_attention", "pallas", work={
                "flash_attention_short_fwd": (1e9, 5e9)})
    yield
    counters.reset()


ROWS = [["fusion", 1.6], ["kernel:fused_xent_bwd", 0.8],
        ["kernel:fused_xent_fwd", 0.4],
        ["kernel:flash_attention_short_fwd", 0.2], ["copy", 0.1]]
#: name -> value on ROWS with the ledger above, ten traced steps
EXPECTED = {
    "xent_device_share_pct.train": 100 * 1.2 / 4.0,
    "attn_device_share_pct.train": 100 * 0.2 / 4.0,
    # 10 x (1e12 + 2e12) / 100e12 = 0.3 s over 1.2 s
    "xent_roofline_pct.train": 100 * 0.3 / 1.2,
    # bytes bind: 10 x 5e9 / 1e12 = 0.05 s over 0.2 s
    "attn_roofline_pct.train": 100 * 0.05 / 0.2,
}


def test_the_new_metrics_have_their_files_and_their_entries(readers):
    bench = harness.load_json(harness.REPO + "/BENCHMARK.json")
    listed = {m["name"]: m for m in bench["per_layer"]}
    cells = [w["name"] for w in bench["workloads"]]
    for name in NEW:
        assert name in readers and name in listed
        assert set(listed[name]["workloads"]) <= set(cells)
    # a flash kernel runs in the seq-512 cell alone
    assert listed["attn_roofline_pct.train"]["workloads"] == \
        ["bert-base.pretrain-seq512"]


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_rows_present_give_the_value(readers, ledger, name):
    assert readers[name](_run(ROWS)) == pytest.approx(EXPECTED[name])


@pytest.mark.parametrize("name", sorted(EXPECTED))
@pytest.mark.parametrize("rows", [
    None,                                            # a CPU: no trace
    [["fusion", 3.0], ["kernel:jvp__", 0.5],         # the parent's names
     ["kernel:pure_step", 0.1]]], ids=["no_trace", "no_role_names"])
def test_rows_absent_give_none(readers, ledger, name, rows):
    assert readers[name](_run(rows)) is None


@pytest.mark.parametrize("name", ["xent_roofline_pct.train",
                                  "attn_roofline_pct.train"])
def test_no_ledger_gives_no_roofline(readers, name):
    counters.reset()
    assert readers[name](_run(ROWS)) is None


def test_a_suffixed_kernel_counts_in_its_family(readers, ledger):
    # two backward kernels under one role, as the device names them
    events = [["kernel:fused_xent_bwd.3", 0, 5e8],
              ["kernel:fused_xent_bwd.4", 5e8, 3e8],
              ["kernel:fused_xent_fwd.1", 8e8, 4e8], ["fusion.7", 12e8, 28e8]]
    red = trace_reduce.reduce({"devices": {"/device:TPU:0": events},
                               "host_spans": []})
    names = [n for n, _ in red["device_ops"]]
    assert "kernel:fused_xent_bwd" in names
    run = dict(_run(ROWS), trace=red)
    assert readers["xent_device_share_pct.train"](run) == \
        pytest.approx(100 * 1.2 / 4.0)
    assert readers["xent_roofline_pct.train"](run) == \
        pytest.approx(100 * 0.3 / 1.2)
    assert readers["attn_device_share_pct.train"](run) is None


def test_a_role_without_its_row_is_left_out_of_both_sides(readers, ledger):
    rows = [["kernel:fused_xent_fwd", 0.4], ["fusion", 3.6]]
    # forward alone: 10 x 1e12 / 100e12 = 0.1 s over 0.4 s
    assert readers["xent_roofline_pct.train"](_run(rows)) == \
        pytest.approx(100 * 0.1 / 0.4)


def test_setup_counters_are_read_from_the_program(readers, monkeypatch):
    monkeypatch.setattr(compile_cache, "_seconds", {"train_step": 3.25})
    assert readers["step_compile_s.train"](_run(None)) == 3.25
    monkeypatch.setattr(compile_cache, "_seconds", {"other": 1.0})
    assert readers["step_compile_s.train"](_run(None)) is None
    monkeypatch.setitem(autotune._stats, "timed_s", 12.5)
    assert readers["autotune_s.train"](_run(None)) == 12.5


def test_a_program_without_the_counters_reads_as_nothing(readers,
                                                         monkeypatch):
    # the parent commit: no ledger, no seconds, no timed_s
    monkeypatch.delattr(counters, "step_work")
    monkeypatch.delattr(compile_cache, "seconds_by_function")
    monkeypatch.setattr(autotune, "stats", lambda: {"timed": 0})
    run = _run(ROWS)
    for name in ("xent_roofline_pct.train", "attn_roofline_pct.train",
                 "step_compile_s.train", "autotune_s.train"):
        assert readers[name](run) is None
