"""The harness is driven by data: new cells, configurations and layer
metrics are new files; the result line holds the contract's keys; the
device gate refuses a CPU; a broken timed path comes out not correct.
All in-process, on the CPU, at tiny sizes — never a measurement."""
import json
import os
import re
import time

import jax
import numpy as np
import pytest

from benchmarks import harness

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
#: stands in for a row of peaks.json in CPU rehearsals (never a measurement)
REHEARSAL_PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes": 1e9}


def rehearse(root, cell, trace=False, seed=2 ** 31 + 77, seconds=1.0):
    lines = []
    result = harness.run_cell(cell, seed=seed, seconds=seconds, trace=trace,
                              root=root, peaks=REHEARSAL_PEAKS,
                              check_device=False, log=lines.append)
    # what main() prints last must survive a round trip
    return json.loads(json.dumps(result)), lines


def test_new_files_are_found_and_no_existing_file_is_edited(bench_root,
                                                            digest):
    root, before = bench_root
    after = digest(root)
    assert {k: after[k] for k in before} == before
    assert len(after) > len(before)
    cell, config = harness.load_cell("bert-tiny.pretrain", root)
    assert config["name"] == "bert-tiny" and cell["config"] == "bert-tiny"
    names = [m["name"] for m in harness.layer_metrics(root)]
    assert "steps_counted.train" in names and "mfu_pct.train" in names


def test_train_rehearsal_prints_exactly_the_contracts_keys(bench_root):
    root, _ = bench_root
    result, lines = rehearse(root, "bert-tiny.pretrain")
    assert set(result) == RESULT_KEYS
    assert result["correct"] is True, lines
    assert result["failed"] == 0 and result["attempted"] > 0
    assert set(result["metrics"]) == {"train_tokens_per_s", "setup_s"}
    for m in result["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= \
        set(result["device"])
    assert any(ln.startswith("check grad_norm_gap") for ln in lines)


def test_traced_rehearsal_reports_layer_metrics_of_the_cell(bench_root):
    root, _ = bench_root
    result, _ = rehearse(root, "bert-tiny.pretrain", trace=True)
    assert set(result) - {"breakdown"} == RESULT_KEYS
    got = set(result["metrics"])
    # the drop-in metric and the span- and clock-fed ones are read; a CPU
    # has no device plane, so the trace-fed reader finds nothing and its
    # metric is left out; no serving metric leaks into a training cell
    assert {"steps_counted.train", "dispatch_ms.train",
            "mfu_pct.train"} <= got
    assert "device_idle_pct.train" not in got
    assert not [m for m in got if m.endswith(".serve")]


def test_serving_rehearsal_is_correct_and_counts_requests(bench_root):
    root, _ = bench_root
    result, lines = rehearse(root, "opt-tiny.chat", seconds=2.0)
    assert set(result) == RESULT_KEYS
    assert result["correct"] is True, lines
    assert result["attempted"] == 16 and result["failed"] == 0
    assert set(result["metrics"]) == {
        "serve_tokens_per_s", "ttft_p95_ms", "token_gap_p95_ms", "setup_s"}


def _step_that_keeps_its_state(driver):
    """The timed path, broken underneath: a step that runs and returns
    its parameters unchanged."""
    original = driver.Loop.feed_and_step

    def broken(self, batch):
        import jax.numpy as jnp

        saved = {k: jnp.copy(p.value)
                 for k, p in self.model.named_parameters()}
        loss = original(self, batch)
        for k, p in self.model.named_parameters():
            p._value = saved[k]
        return loss

    driver.Loop.feed_and_step = broken
    return driver


def _token_altered_where_it_is_produced(monkeypatch):
    from paddle_tpu.inference.decode import DecodeEngine

    original = DecodeEngine._emit

    def emit(self, req, token):
        if len(req.generated) == 1:
            token = (int(token) + 1) % self.config.vocab_size
        return original(self, req, token)

    monkeypatch.setattr(DecodeEngine, "_emit", emit)


def test_broken_train_step_comes_out_not_correct(bench_root, monkeypatch):
    root, _ = bench_root
    load = harness.load_driver
    monkeypatch.setattr(harness, "load_driver", lambda config, root=None:
                        _step_that_keeps_its_state(load(config, root)))
    result, lines = rehearse(root, "bert-tiny.pretrain")
    assert result["correct"] is False
    assert [ln for ln in lines
            if ln.startswith("check delta_norm_gap") and "NOT OK" in ln]


def test_altered_served_token_comes_out_not_correct(bench_root,
                                                    monkeypatch):
    root, _ = bench_root
    _token_altered_where_it_is_produced(monkeypatch)
    result, lines = rehearse(root, "opt-tiny.chat", seconds=2.0)
    assert result["correct"] is False
    assert result["failed"] >= 1
    assert [ln for ln in lines
            if ln.startswith("check served_token_gap") and "NOT OK" in ln]


def test_device_gate_refuses_a_cpu(capsys):
    assert jax.devices()[0].platform == "cpu"
    rc = harness.main(["--workload", "bert-base.pretrain-seq512",
                       "--seed", "1", "--seconds", "1", "--trace", "0"],
                      time.monotonic())
    out = capsys.readouterr()
    assert rc != 0
    assert out.out.strip() == ""          # no result line, no CPU row
    assert "REFUSED" in out.err
    with pytest.raises(harness.Refused):
        harness.peaks_for("cpu")          # an unknown kind is an error


# ---------------------------------------------------------------------------
# BENCHMARK.json against the contract's rules of form
# ---------------------------------------------------------------------------
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
WIDTH = re.compile(r"(hidden|intermediate|latent|state|proj|head_dim|"
                   r"_dim$|_rank$|expansion|experts_per)")


def _line(text):
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_benchmark_json_keeps_the_contracts_form():
    path = os.path.join(REPO, "BENCHMARK.json")
    assert os.path.getsize(path) <= 64 * 1024
    bench = harness.load_json(path)
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= bench["run_seconds"] <= 51
    assert all(_line(w) for w in bench["command"])
    for p in bench["paths"]:
        assert os.path.isdir(os.path.join(REPO, p))
    configs = {c["name"]: c for c in bench["configs"]}
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and _line(c["source"])
        assert _line(c["why"])
        assert any(c["file"].startswith(p + "/") for p in bench["paths"])
        data = harness.load_json(os.path.join(REPO, c["file"]))
        assert sorted(data["reduced"]) == sorted(c["reduced"])
        for key in c["reduced"]:
            assert NAME.match(key) and not WIDTH.search(key)
    cells = {}
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert _line(w["why"])
        cell, _ = harness.load_cell(w["name"])
        assert cell["config"] == w["config"] and cell["chips"] == w["chips"]
        cells[w["name"]] = cell
    assert {w["config"] for w in bench["workloads"]} == set(configs)
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "bound", "source"}
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    files = {m["name"]: m for m in harness.layer_metrics()}
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "source", "layer", "moves"}
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["source"] in SOURCES and m["moves"] in e2e
        assert _line(m["layer"])
        meta = files[m["name"]]           # its data file agrees with it
        for key in ("unit", "better", "source", "layer", "moves"):
            assert meta[key] == m[key], (m["name"], key)
    names = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(names) == len(set(names))
    for name, cell in cells.items():
        # every cell reports setup_s, another end-to-end metric, and a
        # layer metric; its units are the benchmark's
        assert "setup_s" in cell["end_to_end"] and len(cell["end_to_end"]) > 1
        for metric, unit in cell["end_to_end"].items():
            assert e2e[metric]["unit"] == unit
        assert [m for m in bench["per_layer"]
                if m["moves"] in cell["end_to_end"]]


def test_seed_may_exceed_32_signed_bits():
    from benchmarks import traffic

    a = traffic.train_batches({"batch": 2, "seq": 8, "labelled": 2,
                               "host_batches": 1}, 100, 2 ** 31 + 5)
    b = traffic.train_batches({"batch": 2, "seq": 8, "labelled": 2,
                               "host_batches": 1}, 100, 2 ** 31 + 5)
    assert all(np.array_equal(x, y) for x, y in zip(a[0], b[0]))
