"""The yardstick's arithmetic: traffic from the seed, latency from the
due time, percentiles, operations from shapes, the trace reduction."""
import os
import threading
import time

import numpy as np
import pytest

from benchmarks import flops, harness, trace_reduce, traffic

MIX = {
    "kind": "open_loop", "rate_rps": 4.0,
    "arrivals": {"process": "poisson"},
    "prompt_tokens": {"dist": "lognormal", "median": 384, "sigma": 0.8,
                      "min": 32, "max": 1536},
    "output_tokens": {"dist": "lognormal", "median": 128, "sigma": 0.6,
                      "min": 16, "max": 384},
    "max_total_tokens": 1920,
}


def _sizes(reqs):
    return (sorted(len(r.prompt) for r in reqs),
            sorted(r.max_new_tokens for r in reqs))


def test_generator_is_a_pure_function_of_the_seed():
    a = traffic.open_loop_requests(MIX, 50272, 50.0, 2 ** 31 + 9)
    b = traffic.open_loop_requests(MIX, 50272, 50.0, 2 ** 31 + 9)
    assert [(r.due_s, r.prompt, r.max_new_tokens) for r in a] == \
        [(r.due_s, r.prompt, r.max_new_tokens) for r in b]
    assert len(a) == 200
    due = [r.due_s for r in a]
    assert due == sorted(due) and 0 < due[0] and due[-1] < 50.0


def test_every_seed_offers_the_same_work_in_another_order():
    a = traffic.open_loop_requests(MIX, 50272, 50.0, 1)
    b = traffic.open_loop_requests(MIX, 50272, 50.0, 2)
    assert _sizes(a) == _sizes(b)
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in b]
    gaps = [np.diff([0.0] + [r.due_s for r in x]) for x in (a, b)]
    assert np.allclose(sorted(gaps[0]), sorted(gaps[1]))
    assert a[0].prompt != b[0].prompt
    prompts = np.asarray([len(r.prompt) for r in a])
    # the issue's shape: about a third in the 4-page bucket, a tenth in
    # the 16-page one; prompt + output inside the 2048-token table
    assert 0.25 < np.mean((prompts > 256) & (prompts <= 512)) < 0.40
    assert 0.05 < np.mean(prompts > 1024) < 0.16
    assert max(len(r.prompt) + r.max_new_tokens for r in a) <= 1920


def test_gamma_arrivals_and_mixtures_are_data_not_code():
    bursty = dict(MIX, arrivals={"process": "gamma", "cv": 3.0})
    gaps = np.diff([r.due_s for r in
                    traffic.open_loop_requests(bursty, 100, 50.0, 3)])
    steady = np.diff([r.due_s for r in
                      traffic.open_loop_requests(MIX, 100, 50.0, 3)])
    assert np.std(gaps) / np.mean(gaps) > 2 * np.std(steady) / \
        np.mean(steady)
    mixed = dict(MIX, prompt_tokens={"dist": "mixture", "parts": [
        {"share": 0.8, "dist": "uniform", "min": 32, "max": 256},
        {"share": 0.2, "dist": "uniform", "min": 1024, "max": 1536}]})
    lens = np.asarray([len(r.prompt) for r in
                       traffic.open_loop_requests(mixed, 100, 50.0, 3)])
    assert 0.15 < np.mean(lens >= 1024) < 0.25
    assert not np.any((lens > 256) & (lens < 1024))
    shared = dict(MIX, shared_prefix={"tokens": 64, "groups": 2,
                                      "share": 1.0})
    reqs = traffic.open_loop_requests(shared, 100, 10.0, 3)
    assert len({tuple(r.prompt[:64]) for r in reqs}) == 2


class _Handle:
    def __init__(self, times, n):
        self.meta = {"token_times": times}
        self._tokens = list(range(n))

    def done(self):
        return True

    def result(self, timeout=None):
        return self._tokens


class _StallingEngine:
    """Serves at once, but its first ``submit`` blocks the caller."""

    def __init__(self, stall_s):
        self.stall_s, self.calls = stall_s, 0

    def submit(self, prompt, max_new_tokens):
        self.calls += 1
        if self.calls == 1:
            time.sleep(self.stall_s)
        now = time.monotonic()
        return _Handle([now + 0.001 * (i + 1)
                        for i in range(max_new_tokens)], max_new_tokens)


def test_latency_is_timed_from_the_due_time_not_the_submit():
    driver = harness.load_driver({"driver": "decode_engine"})
    reqs = [traffic.Request(i, 0.05 * (i + 1), [1, 2, 3], 4)
            for i in range(4)]
    win = driver.serve_window(_StallingEngine(0.4), reqs, 0.6, drain_s=5)
    red = driver.reduce_window(win, 0.6)
    assert red["attempted"] == 4 and red["failed"] == 0
    # request 1 was due at 0.10 s and could only be sent at ~0.45 s: the
    # stall shows in its first-token time, not in the lateness alone
    assert red["late_ms"][1] > 250
    assert red["ttft_ms"][1] > red["late_ms"][1] > 250
    assert red["ttft_ms"][0] > 350
    assert len(red["gap_ms"]) == 4 * 3
    assert threading.active_count() < 20


def test_a_refused_request_counts_the_window_as_its_first_token_time():
    class Refusing:
        def submit(self, prompt, max_new_tokens):
            raise RuntimeError("overloaded")

    driver = harness.load_driver({"driver": "decode_engine"})
    win = driver.serve_window(
        Refusing(), [traffic.Request(0, 0.01, [1], 2)], 0.2, drain_s=1)
    red = driver.reduce_window(win, 0.2)
    assert red["failed"] == 1 and red["ttft_ms"] == [200.0]


def test_percentiles_say_how_many_samples_stand_behind_them():
    assert harness.percentile(list(range(1, 101)), 95) == 95
    assert harness.percentile([5.0], 95) == 5.0
    assert harness.supported_percentile(200) == 95.0
    assert harness.supported_percentile(199) == 90.0
    assert harness.supported_percentile(1000) == 99.0
    assert harness.supported_percentile(15) is None
    t = harness.tail([float(i) for i in range(150)])
    assert t["n"] == 150 and t["highest_supported"] == 90.0
    assert t["at_highest"] == 134.0 and t["p50"] == 74.0
    with pytest.raises(ValueError):
        harness.percentile([], 50)


def test_flops_and_parameter_counts_from_shapes():
    bert = harness.load_json(os.path.join(harness.ROOT, "configs",
                                          "bert-base.json"))
    per_token = flops.bert_train_flops_per_token(bert, 512, 80)
    assert per_token == pytest.approx(590e6, rel=0.02)
    assert flops.bert_encoder_params(bert) == 12 * (4 * 768 ** 2
                                                    + 2 * 768 * 3072)
    # credit is for the labelled positions and the published vocabulary
    assert flops.bert_train_flops_per_token(bert, 512, 512) > \
        per_token + 100e6
    opt = harness.load_json(os.path.join(harness.ROOT, "configs",
                                         "opt-1.3b-shape.json"))
    assert flops.decoder_param_count(opt) == pytest.approx(1.42e9, rel=0.01)
    assert flops.decoder_kv_page_bytes(opt, 128, 4) == 24 * 2 * 128 \
        * 2048 * 4


def test_trace_reduction_on_a_recorded_example():
    trace = harness.load_json(os.path.join(harness.ROOT, "tests_data",
                                           "trace_small.json"))
    red = trace_reduce.reduce(trace)
    want = trace["expect"]
    assert red["window_s"] == pytest.approx(want["window_s"])
    assert red["busy_s"] == pytest.approx(want["busy_s"])
    assert red["idle_pct"] == pytest.approx(want["idle_pct"])
    for got, exp in ((red["device_ops"], want["device_ops"]),
                     (red["idle_gaps"], want["idle_gaps"])):
        assert [n for n, _ in got] == [n for n, _ in exp]
        assert [s for _, s in got] == pytest.approx([s for _, s in exp])
    assert trace_reduce.reduce({"devices": {}, "host_spans": []}) is None
    assert trace_reduce.short_name(
        '%jvp__.1 = (f32[1,8]) custom-call(f32[8,8] %x), '
        'custom_call_target="tpu_custom_call"') == "kernel:jvp__.1"
    assert trace_reduce.short_name(
        "%fusion.12 = f32[8] fusion(f32[8] %p)") == "fusion.12"
    assert trace_reduce.op_family("fusion.12") == "fusion"
