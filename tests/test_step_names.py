"""What the compiled train step says about itself (PR 24): the module
and its scopes carry the program's own names, every Pallas kernel runs
under its role name, the work ledger of one step equals the closed
forms, and jax's compile events are summed by function. CPU, tiny
sizes, Pallas in interpret mode: names and counts, never a time."""
import functools
import re

import jax
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.framework.bringup as bringup
from paddle_tpu import nn, optimizer
from paddle_tpu.framework.flags import get_flag, set_flags
from paddle_tpu.jit import TrainStep
from paddle_tpu.models.bert import BertConfig, BertForPretraining
from paddle_tpu.nn.layer import _scope_name
from paddle_tpu.ops.pallas import autotune, counters
from paddle_tpu.static import compile_cache

V, H, L, A, I = 512, 128, 2, 2, 256
B, S = 2, 128
N, D = B * S, H // A


def _batch(seed=0, b=B, s=S):
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, V, (b, s)).astype("int32")
    mlm = np.where(rng.random((b, s)) < 0.15, ids, -100).astype("int32")
    return [paddle.to_tensor(a) for a in (
        ids, np.zeros((b, s), "int32"), mlm,
        rng.integers(0, 2, (b,)).astype("int32"))]


def _train_step(positions=S):
    model = BertForPretraining(BertConfig(
        vocab_size=V, hidden_size=H, num_hidden_layers=L,
        num_attention_heads=A, intermediate_size=I,
        max_position_embeddings=positions, hidden_dropout_prob=0.0,
        attention_probs_dropout_prob=0.0))
    model.eval()
    opt = optimizer.AdamW(learning_rate=1e-4,
                          parameters=model.parameters())
    return TrainStep(
        model, lambda m, ids, tt, mlm, nsp: m.loss(ids, tt, mlm, nsp), opt)


@pytest.fixture(scope="module")
def forced():
    """One tiny BERT step with every kernel of the main path forced on
    (interpret mode), run once: (step, batch, seconds before, after)."""
    from jax.experimental import pallas as pl

    short = get_flag("flash_short_seq")
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(pl, "pallas_call",
                   functools.partial(pl.pallas_call, interpret=True))
        mp.setattr(bringup, "pallas_enabled", lambda: True)
        set_flags({"flash_short_seq": True})
        counters.reset()
        try:
            step, batch = _train_step(), _batch()
            before = compile_cache.seconds_by_function().get(
                "train_step", 0.0)
            step(*batch)
            after = compile_cache.seconds_by_function()["train_step"]
            yield step, batch, before, after
        finally:
            set_flags({"flash_short_seq": short})
            counters.reset()


@pytest.fixture(scope="module")
def lowered(forced):
    step, batch, _, _ = forced
    return step.lower(*batch).as_text(debug_info=True)


@pytest.mark.parametrize("scope", [
    "jvp(loss)/bert/encoder/layer/self_attn",
    "transpose(jvp(loss))/bert/encoder/layer/self_attn",
    "bert/embeddings/word_embeddings", "optimizer/"])
def test_lowered_step_holds_the_scopes(lowered, scope):
    assert f"jit(train_step)/{scope}" in lowered or \
        f"/{scope}" in lowered


def test_no_scope_holds_a_container_index(lowered):
    names = set(re.findall(r'loc\("(jit\(train_step\)[^"]*)"', lowered))
    assert names
    for name in names:
        assert not re.search(r"(^|/|\(|\.)\d+(/|\)|$)", name), name
        assert "layers" not in name.split("/")


def test_module_is_named_train_step(lowered):
    assert "\nmodule @jit_train_step " in lowered


@pytest.mark.parametrize("role", [
    "fused_xent_fwd", "fused_xent_bwd", "flash_attention_short_fwd",
    "flash_attention_short_bwd"])
def test_every_kernel_runs_under_its_role(lowered, role):
    # the role is the innermost scope (what XLA names the custom call
    # after), under the plain ``pallas`` scope that keeps jvp()/
    # transpose() from wrapping it
    assert f"pallas/{role}/pallas_call" in lowered


def test_the_update_is_xla_ops_under_the_optimizer_scope(lowered):
    # the reference rule, inline: a trace charges its seconds to the
    # ``optimizer`` scope (or to the gradient fusion XLA merges it into)
    # and shows no ``kernel:`` row for it
    prims = set(re.findall(r"jit\(train_step\)/optimizer/(\w+)", lowered))
    assert {"sqrt", "square", "div", "sub"} <= prims        # AdamW is there
    # each gradient is whole before its update reads it (PERF.md §6, PR 29:
    # merged into the gradient's fusion the update cost BERT 3% of a step)
    assert "optimization_barrier" in prims
    assert not prims & {"pallas", "pallas_call", "custom_call"}


def test_step_work_equals_the_closed_forms(forced):
    work = counters.step_work("train_step")
    xent = work["fused_xent_fwd"]["flops"] + work["fused_xent_bwd"]["flops"]
    # logits forward 2 N H V; dh and dW backward 4 N H V
    assert xent == 6 * N * H * V
    assert work["fused_xent_fwd"]["calls"] == 1
    attn = (work["flash_attention_short_fwd"]["flops"]
            + work["flash_attention_short_bwd"]["flops"])
    # per layer: QK^T and PV forward 4 B A S^2 D; dV, dP, dQ, dK 8 B A S^2 D
    assert attn == 12 * B * A * S * S * D * L
    assert work["flash_attention_short_fwd"]["calls"] == L
    # q, k, v read and the output written in float32, plus the logsumexp
    assert work["flash_attention_short_fwd"]["bytes"] == \
        L * (4 * B * S * A * D * 4 + 4 * B * A * S)
    # the update launches no kernel: the ledger holds the roles above only
    assert sorted(work) == [
        "flash_attention_short_bwd", "flash_attention_short_fwd",
        "fused_xent_bwd", "fused_xent_fwd"]


@pytest.fixture(scope="module")
def laddered():
    """(fused-xent rows of the ledger, lowered text) of a tiny BERT step
    whose 1024 MLM rows, in blocks of 256, may run at 256, 512 or 1024
    rows: traced once, not run, the module's own ledger left as it was."""
    from jax.experimental import pallas as pl
    from paddle_tpu.ops.pallas import fused_xent as fx

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(pl, "pallas_call",
                   functools.partial(pl.pallas_call, interpret=True))
        mp.setattr(bringup, "pallas_enabled", lambda: True)
        mp.setattr(fx, "_BN_CANDIDATES", (256,))
        mp.setattr(counters, "_STEP_WORK", {})
        text = _train_step(positions=256).lower(
            *_batch(b=4, s=256)).as_text(debug_info=True)
        work = counters.step_work("train_step")
    return {role: w for role, w in work.items()
            if "fused_xent" in role}, text


def test_every_rung_has_roles_of_its_own(laddered):
    work, text = laddered
    assert sorted(work) == sorted(
        ["fused_xent_fwd", "fused_xent_bwd"] + [
            f"fused_xent_rows{k}_{d}" for k in (256, 512)
            for d in ("fwd", "bwd")])
    for role in work:
        assert f"pallas/{role}/pallas_call" in text
        # a trace reader matches rows by "role in row name": no rung's
        # seconds may be charged to another rung's work
        assert not [other for other in work
                    if other != role and role in other], role


@pytest.mark.parametrize("k,tag", [(256, "rows256_"), (512, "rows512_"),
                                   (1024, "")])
def test_a_rung_declares_the_work_of_its_rows(laddered, k, tag):
    work, _ = laddered
    fwd, bwd = work[f"fused_xent_{tag}fwd"], work[f"fused_xent_{tag}bwd"]
    assert fwd["flops"] == 2 * k * H * V and bwd["flops"] == 4 * k * H * V
    assert fwd["calls"] == bwd["calls"] == 1
    # float32 here: K rows of h, the table and the bias, K int32 labels,
    # then lse and the label logit out
    read = 4 * (k * H + V * H + V + k)
    assert fwd["bytes"] == read + 8 * k
    assert bwd["bytes"] == read + 8 * k + 4 * (k * H + V * H + V)


def test_a_trace_of_one_rung_is_read_against_that_rung_alone(laddered,
                                                             monkeypatch):
    """The reading that decides ``impossible_reading``: the rung that ran
    is the only one with ``kernel:`` rows, and only its work counts."""
    from benchmarks import kernel_rows

    monkeypatch.setattr(counters, "_STEP_WORK", {"train_step": laddered[0]})
    peaks = {"bf16_flops_per_s": 1e9, "hbm_bytes_per_s": 1e12}
    run = {"peaks": peaks, "cell": {"traffic": {"loss_fetch_every": 10}},
           "trace": {"busy_s": 9.0, "device_ops": [
               ["fusion", 5.0], ["conditional", 3.0],
               ["kernel:fused_xent_rows512_bwd", 2.0],
               ["kernel:fused_xent_rows512_fwd", 1.0]]}}
    least = 10 * 6 * 512 * H * V / peaks["bf16_flops_per_s"]
    assert kernel_rows.roofline_pct(run, "fused_xent") == \
        pytest.approx(100 * least / 3.0)
    assert kernel_rows.device_share_pct(run, "fused_xent") == \
        pytest.approx(100 * 3.0 / 9.0)


def test_step_work_is_of_one_execution(forced):
    step, batch, _, _ = forced
    work = counters.step_work("train_step")
    step(*batch)
    step(*batch)
    assert counters.step_work("train_step") == work


def test_an_autotune_timing_leaves_the_ledger_alone(forced, monkeypatch,
                                                   tmp_path):
    import paddle_tpu.utils.timing as timing

    work = counters.step_work("train_step")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    monkeypatch.setattr(timing, "timeit", lambda fn, *a, iters=0: 1.0)
    autotune.reset()

    def build():
        # a candidate that dispatches like the program does
        counters.bump("flash_attention", "pallas",
                      work={"flash_attention_short_fwd": (1e9, 1e6)})
        return {"only": lambda x: x}, 0

    try:
        with counters.capture("train_step_2"):
            counters.bump("fused_embedding", "pallas",
                          work={"fused_embedding": (0.0, 28.0)})
            assert autotune._verdict(("t", 1), "test", ("only",),
                                     build) == "only"
        stats = autotune.stats()
        assert stats["timed"] == 1 and stats["timed_s"] > 0
        assert counters.step_work("train_step_2") == {
            "fused_embedding": {"calls": 1, "flops": 0.0, "bytes": 28.0}}
        assert counters.step_work("train_step") == work
    finally:
        autotune.reset()


def test_grad_work_counts_only_where_the_trace_differentiates():
    declared = dict(work={"k_fwd": (2.0, 3.0)},
                    grad_work={"k_bwd": (4.0, 5.0)})
    counters.bump("k", "pallas", **declared)       # no capture: dropped
    with counters.capture("s"):
        counters.bump("k", "pallas", **declared)
        with counters.differentiated():
            counters.bump("k", "pallas", **declared)
    assert counters.step_work("s") == {
        "k_fwd": {"calls": 2, "flops": 4.0, "bytes": 6.0},
        "k_bwd": {"calls": 1, "flops": 4.0, "bytes": 5.0}}
    assert counters.step_work("never traced") == {}


def test_compile_seconds_are_summed_by_function(forced):
    step, batch, before, after = forced
    assert after > before                  # the first call compiled
    now = compile_cache.seconds_by_function()["train_step"]
    step(*batch)
    assert compile_cache.seconds_by_function()["train_step"] == now


def test_a_sublayer_scope_is_its_registered_name_without_an_index():
    assert _scope_name("self_attn") == "self_attn"
    assert _scope_name("3") == _scope_name(11) == "layer"
    net = nn.Sequential(nn.Linear(4, 4), nn.ReLU())
    tail = nn.LayerList([nn.Linear(4, 4)])
    tail.insert(0, nn.Linear(4, 4))
    tail[1] = nn.Linear(4, 2)
    for layer in list(net) + list(tail):
        assert layer._scope == "layer"

    class Net(nn.Layer):
        def __init__(self):
            super().__init__()
            self.head = nn.Linear(4, 2)

        def forward(self, x):
            return self.head(x)

    model = Net()
    assert model.head._scope == "head" and "_scope" not in model.__dict__
    text = jax.jit(lambda x: model(paddle.to_tensor(x)).value).lower(
        np.zeros((2, 4), "float32")).as_text(debug_info=True)
    assert "/head/" in text
