"""Shared fixtures of the benchmark's tests: a temporary copy of
``benchmarks/`` into which tiny configurations, cells and a layer metric
are dropped as NEW files — the way a later PR adds them."""
import hashlib
import json
import os
import shutil

import pytest

from benchmarks import harness


def _digest(root):
    out = {}
    for folder, _dirs, files in os.walk(root):
        for name in files:
            path = os.path.join(folder, name)
            with open(path, "rb") as f:
                out[os.path.relpath(path, root)] = hashlib.sha256(
                    f.read()).hexdigest()
    return out


def _drop(root, rel, obj):
    path = os.path.join(root, rel)
    assert not os.path.exists(path), f"{rel} would edit an existing file"
    with open(path, "w") as f:
        if isinstance(obj, str):
            f.write(obj)
        else:
            json.dump(obj, f)


@pytest.fixture(scope="session")
def bench_root(tmp_path_factory):
    """(root, digest before the drop-ins): a copy of ``benchmarks/`` with
    ``bert-tiny`` / ``opt-tiny`` configurations, one cell each and the
    layer metric ``steps_counted.train`` added as files of their own."""
    root = str(tmp_path_factory.mktemp("bench") / "benchmarks")
    shutil.copytree(harness.ROOT, root,
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = _digest(root)

    bert = harness.load_json(os.path.join(root, "configs/bert-base.json"))
    bert.update(name="bert-tiny", vocab_size=1000, hidden_size=64,
                num_hidden_layers=2, num_attention_heads=2,
                intermediate_size=128, max_position_embeddings=64)
    # float32 end to end: the CPU test holds the program to the reference
    # tightly, and shows that bfloat16 autocast would not pass
    bert["program"].update(padded_vocab_size=1024, amp_level="O0")
    _drop(root, "configs/bert-tiny.json", bert)
    cell = harness.load_json(os.path.join(
        root, "workloads/bert-base.pretrain-seq512.json"))
    cell.update(name="bert-tiny.pretrain", config="bert-tiny")
    cell["traffic"].update(batch=8, seq=32, labelled=5, host_batches=4,
                           loss_fetch_every=4)
    cell["correct"].update(block_rows=4, limits={
        "loss_gap": 1e-4, "grad_norm_gap": 3e-3, "delta_norm_gap": 3e-3})
    _drop(root, "workloads/bert-tiny.pretrain.json", cell)

    opt = harness.load_json(os.path.join(root,
                                         "configs/opt-1.3b-shape.json"))
    opt.update(name="opt-tiny", vocab_size=512, hidden_size=64,
               num_hidden_layers=2, num_attention_heads=4, ffn_dim=128,
               max_position_embeddings=256)
    _drop(root, "configs/opt-tiny.json", opt)
    _drop(root, "workloads/opt-tiny.chat.json", {
        "name": "opt-tiny.chat", "config": "opt-tiny", "chips": 1,
        "why": "tier-1 rehearsal of the serving driver",
        "end_to_end": {"serve_tokens_per_s": "tokens/s",
                       "ttft_p95_ms": "ms", "token_gap_p95_ms": "ms",
                       "setup_s": "s"},
        "engine": {"max_batch": 4, "n_pages": 72, "page_size": 16,
                   "max_pages_per_seq": 16, "max_queue": 512,
                   "temperature": 0.0},
        "traffic": {
            "kind": "open_loop", "rate_rps": 8.0,
            "arrivals": {"process": "poisson"},
            "prompt_tokens": {"dist": "lognormal", "median": 40,
                              "sigma": 0.8, "min": 4, "max": 150},
            "output_tokens": {"dist": "lognormal", "median": 12,
                              "sigma": 0.6, "min": 2, "max": 40},
            "max_total_tokens": 200},
        "drain_s": 60, "trace_seconds": 1,
        "correct": {"sample": 6, "control_precisions": [],
                    "limits": {"served_token_gap": 1e-4}}})

    _drop(root, "layer_metrics/steps_counted.train.json", {
        "layer": "dygraph train step", "unit": "steps", "better": "higher",
        "source": "program_counter", "moves": "train_tokens_per_s",
        "what": "steps in the window (a drop-in metric of the tests)"})
    _drop(root, "layer_metrics/steps_counted.train.py",
          "def read(run):\n    return run[\"attempted\"]\n")
    return root, before


@pytest.fixture(scope="session")
def digest():
    return _digest
