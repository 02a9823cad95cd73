"""What ``models/causal_lm.py`` reads of an ``lfm2_moe`` style config, by
KEY (PR 46): ``layer_types`` entries ``conv`` build ``nn.GatedShortConv``;
``num_dense_layers`` makes the first layers dense; the router scores by
the file's ``scoring_func``; the norms' epsilon comes from ``norm_eps``;
a dense FFN without ``hidden_act`` is SiLU; ``tie_word_embeddings`` makes
the head the embedding. Each reading fails on the PR's parent, which
refused the file (``conv``, the tied head) or misread it silently
(experts in the dense layers, softmax scores, an epsilon of None)."""
import hashlib

import jax
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import amp, nn, optimizer
from paddle_tpu.jit import TrainStep
from paddle_tpu.models import causal_lm
from paddle_tpu.models.causal_lm import CausalLM
from paddle_tpu.ops.pallas import counters


def _lfm2(**changes):
    """The published file's keys at test size: four layers hold every
    kind."""
    cfg = {"conv_L_cache": 3, "conv_bias": False, "hidden_size": 32,
           "intermediate_size": 48,
           "layer_types": ["conv", "conv", "full_attention", "conv"],
           "max_position_embeddings": 128000, "model_type": "lfm2_moe",
           "moe_intermediate_size": 16, "norm_eps": 1e-5,
           "norm_topk_prob": True, "num_attention_heads": 4,
           "num_dense_layers": 2, "num_experts": 8,
           "num_experts_per_tok": 2, "num_hidden_layers": 4,
           "num_key_value_heads": 2,
           "rope_parameters": {"rope_theta": 1000000,
                               "rope_type": "default"},
           "routed_scaling_factor": 1, "use_expert_bias": True,
           "vocab_size": 64, "scoring_func": "sigmoid",
           "tie_word_embeddings": True}
    cfg.update(changes)
    return cfg


def _batch(seed=0, b=2, s=16, vocab=64):
    ids = np.random.default_rng(seed).integers(0, vocab, (b, s)).astype(
        "int32")
    labels = np.full((b, s), -100, "int32")
    labels[:, :-1] = ids[:, 1:]
    return ids, labels


def test_conv_entries_build_the_gated_short_convolution():
    model = CausalLM.from_config(_lfm2())
    kinds = [(b.mixer_kind, type(b.mixer).__name__) for b in model.layers]
    assert kinds == [("conv", "GatedShortConv"), ("conv", "GatedShortConv"),
                     ("gqa", "GroupedQueryAttention"),
                     ("conv", "GatedShortConv")]
    conv = model.layers[0].mixer
    assert tuple(conv.conv_weight.shape) == (32, 3)      # conv_L_cache taps
    assert tuple(conv.in_proj.weight.shape) == (32, 96)
    # heads of hidden / heads, normed, rotated by the file's one entry
    attn = model.layers[2].mixer
    assert tuple(attn.q_norm.weight.shape) == (8,)
    assert "conv" in causal_lm.MIXERS
    with pytest.raises(NotImplementedError, match="conv_bias"):
        CausalLM.from_config(_lfm2(conv_bias=True))
    with pytest.raises(NotImplementedError, match="'linear'.*'conv'"):
        CausalLM.from_config(_lfm2(layer_types=["conv", "conv", "linear",
                                                "conv"]))


def test_num_dense_layers_makes_the_first_layers_dense():
    model = CausalLM.from_config(_lfm2())
    assert [b.ffn_kind for b in model.layers] == ["dense", "dense", "moe",
                                                  "moe"]
    assert isinstance(model.layers[0].ffn, nn.GatedFFN)
    assert [causal_lm.ffn_kind(_lfm2(num_dense_layers=3), n)
            for n in (1, 2, 3, 4)] == ["dense"] * 3 + ["moe"]
    # the older key still reads
    cfg = _lfm2(first_k_dense_replace=1)
    del cfg["num_dense_layers"]
    assert [causal_lm.ffn_kind(cfg, n) for n in (1, 2)] == ["dense", "moe"]


def test_the_router_scores_by_the_files_key_and_keeps_a_zero_bias_buffer():
    moe = CausalLM.from_config(_lfm2()).layers[2].ffn
    assert (moe.score_func, moe.top_k, moe.renormalize, moe.scaling) == (
        "sigmoid", 2, True, 1.0)
    # use_expert_bias: the layer's buffer, zero, no parameter
    np.testing.assert_array_equal(np.asarray(moe.router_bias), 0.0)
    assert "router_bias" not in dict(moe.named_parameters())
    # the fallback for a file without the key is not touched
    cfg = _lfm2()
    del cfg["scoring_func"]
    assert CausalLM.from_config(cfg).layers[2].ffn.score_func == "softmax"


def test_the_epsilon_is_norm_eps_and_a_file_without_one_is_refused():
    model = CausalLM.from_config(_lfm2(norm_eps=3e-4))
    assert model.final_norm._epsilon == 3e-4
    assert model.layers[0].input_norm._epsilon == 3e-4
    assert model.layers[2].mixer.q_norm._epsilon == 3e-4
    cfg = _lfm2()
    del cfg["norm_eps"]
    with pytest.raises(ValueError, match="norm_eps"):
        CausalLM.from_config(cfg)
    assert causal_lm._eps({"rms_norm_eps": 1e-6, "norm_eps": 1e-5}) == 1e-6


def test_a_dense_ffn_without_hidden_act_is_silu():
    x = np.random.default_rng(0).standard_normal((3, 32)).astype("float32")
    ffn = CausalLM.from_config(_lfm2()).layers[0].ffn
    w1, w3, w2 = (np.asarray(p.numpy()) for p in (
        ffn.gate_proj.weight, ffn.up_proj.weight, ffn.down_proj.weight))
    want = (jax.nn.silu(x @ w1) * (x @ w3)) @ w2
    np.testing.assert_allclose(np.asarray(ffn(paddle.to_tensor(x)).numpy()),
                               np.asarray(want), rtol=2e-5, atol=2e-6)
    # the file's own key still decides where there is one
    paddle.seed(1)
    a = CausalLM.from_config(_lfm2()).layers[0].ffn(paddle.to_tensor(x))
    paddle.seed(1)
    b = CausalLM.from_config(_lfm2(hidden_act="gelu")).layers[0].ffn(
        paddle.to_tensor(x))
    assert np.abs(np.asarray(a.numpy()) - np.asarray(b.numpy())).max() > 1e-4


def test_a_tied_head_is_one_leaf_whose_gradient_sums_both_uses():
    counters.reset()
    paddle.seed(7)
    model = CausalLM.from_config(_lfm2())
    assert counters.snapshot() == {"causal_lm.tied_head": 1}
    counters.reset()
    names = [n for n, _ in model.named_parameters()]
    assert "head" not in names and names.count("embed.weight") == 1
    assert model.head_weight is model.embed.weight
    ids, labels = _batch()
    # the logits are h E^T
    h, _ = model.hidden(paddle.to_tensor(ids))
    np.testing.assert_allclose(
        np.asarray(model(paddle.to_tensor(ids)).numpy()),
        np.asarray(h.numpy()) @ np.asarray(model.embed.weight.numpy()).T,
        rtol=2e-5, atol=2e-6)
    # the same weights untied: the tied leaf's gradient is the embedding's
    # plus the head's
    paddle.seed(7)
    untied = CausalLM.from_config(_lfm2(tie_word_embeddings=False))
    mine = dict(model.named_parameters())
    for name, p in untied.named_parameters():
        p._value = mine["embed.weight" if name == "head" else name]._value
    for m in (model, untied):
        m.loss(paddle.to_tensor(ids), paddle.to_tensor(labels)).backward()
    theirs = dict(untied.named_parameters())
    np.testing.assert_allclose(
        np.asarray(mine["embed.weight"].grad.numpy()),
        np.asarray(theirs["embed.weight"].grad.numpy())
        + np.asarray(theirs["head"].grad.numpy()), rtol=2e-5, atol=1e-7)
    assert float(np.abs(theirs["head"].grad.numpy()).max()) > 0.0


def test_a_tied_models_train_step_moves_the_one_leaf():
    paddle.seed(9)
    model = CausalLM.from_config(_lfm2(), recompute=True)
    opt = optimizer.AdamW(learning_rate=1e-2, parameters=model.parameters())

    def loss_fn(m, i, l):
        with amp.auto_cast(level="O1", dtype="bfloat16"):
            return m.loss(i, l, return_routing=True)

    step = TrainStep(model, loss_fn, opt)
    before = np.asarray(model.embed.weight.numpy()).copy()
    batch = [paddle.to_tensor(a) for a in _batch()]
    losses = [float(step(*batch)[0]) for _ in range(4)]
    assert np.all(np.isfinite(losses)) and losses[-1] < losses[0]
    assert np.abs(np.asarray(model.embed.weight.numpy()) - before).max() > 0


#: sha256 of the lowered text of one jitted step of this file's model with
#: ``tie_word_embeddings: false`` and a ``head`` matrix of its own (below),
#: taken with the head's code as the PARENT has it: ``self.head`` read
#: directly, no ``tied`` branch (``PYTHONPATH=. python
#: tests/test_causal_lm_lfm2.py`` prints it). ``tests/test_step_numerics.py``
#: and ``tests/test_looped_lm.py`` pin the accepted cells' steps the same
#: way, all untied.
UNTIED_STEP_TEXT = \
    "bae77ee42563f802ee56dbac7c244f81a893ff98bf3515e9b41e81504452f77a"


def untied_digest(model=None):
    paddle.seed(5)
    model = model or CausalLM.from_config(_lfm2(tie_word_embeddings=False),
                                          recompute=True)
    opt = optimizer.AdamW(learning_rate=1e-3, parameters=model.parameters())

    def loss_fn(m, i, l):
        with amp.auto_cast(level="O1", dtype="bfloat16"):
            return m.loss(i, l, return_routing=True)

    ids, labels = _batch()
    step = TrainStep(model, loss_fn, opt)
    text = step.lower(paddle.to_tensor(ids), paddle.to_tensor(labels))
    return hashlib.sha256(text.as_text().encode()).hexdigest()


class _ParentsHead(CausalLM):
    """The head as the parent reads it: its own matrix, no branch."""
    head_weight = property(lambda self: self.head)


def test_an_untied_files_step_lowers_to_the_text_of_the_parents_head():
    counters.reset()
    paddle.seed(5)
    want = untied_digest(_ParentsHead(_lfm2(tie_word_embeddings=False),
                                      recompute=True))
    assert untied_digest() == want == UNTIED_STEP_TEXT
    assert "causal_lm.tied_head" not in counters.snapshot()
    counters.reset()


if __name__ == "__main__":
    print(untied_digest())
