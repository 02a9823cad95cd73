"""The softmax-routed dropless expert layer and the decoder that
``CausalLM.from_config`` assembles from a Mellum-2 style config: router
arithmetic by hand, the layer against the benchmark's plain reference
(``benchmarks/reference/mellum2.py``), the SHARE test that ties a chip's
cut to the model, and the two config tables — the new keys build the new
kinds, the Kimi file builds what it built before."""
import hashlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from benchmarks.reference import mellum2 as ref
from paddle_tpu import nn
from paddle_tpu.models import causal_lm
from paddle_tpu.models.causal_lm import CausalLM
from paddle_tpu.nn.moe import SCORE_FUNCS, sparse_moe

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _config(name):
    with open(os.path.join(REPO, "benchmarks", "configs", name)) as f:
        return json.load(f)


# ---------------------------------------------------------------------------
# the router
# ---------------------------------------------------------------------------
def test_softmax_then_top8_renormalised_is_softmax_over_the_picked_logits():
    """p_e / sum_{e' in S} p_e' = exp(l_e) / sum_{e' in S} exp(l_e'): the
    other 56 logits cancel."""
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(50, 24), jnp.float32)
    router = jnp.asarray(rng.randn(24, 64), jnp.float32)
    picked, weight = ref.router_weights(x, router, 8)
    logits = np.asarray(x, np.float64) @ np.asarray(router, np.float64)
    order = np.argsort(-logits, axis=1)[:, :8]
    assert np.array_equal(np.sort(np.asarray(picked), 1), np.sort(order, 1))
    mine = np.take_along_axis(logits, np.asarray(picked), axis=1)
    want = np.exp(mine - mine.max(1, keepdims=True))
    want /= want.sum(1, keepdims=True)
    np.testing.assert_allclose(np.asarray(weight), want, rtol=2e-5)
    np.testing.assert_allclose(np.asarray(weight).sum(1), 1.0, rtol=1e-6)
    # without the renormalisation the weights are the softmax's own
    _, raw = ref.router_weights(x, router, 8, renormalize=False)
    assert float(jnp.max(jnp.sum(raw, axis=1))) < 1.0


def _experts(rng, experts, d, f):
    return (jnp.asarray(0.2 * rng.randn(experts, d, f), jnp.float32),
            jnp.asarray(0.2 * rng.randn(experts, d, f), jnp.float32),
            jnp.asarray(0.2 * rng.randn(experts, f, d), jnp.float32))


def _ref_layer(x, router, gate, up, down, top_k, offset, norm=True):
    p = {"f.router.weight": router, "f.experts_gate": gate,
         "f.experts_up": up, "f.experts_down": down}
    cfg = {"num_experts_per_tok": top_k, "norm_topk_prob": norm,
           "expert_offset": offset}
    return ref.moe(p, "f.", x, cfg, ref._dense)


@pytest.mark.parametrize("norm", [True, False], ids=["renorm", "raw"])
def test_sparse_moe_with_softmax_scores_matches_the_reference(norm):
    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.randn(96, 16), jnp.float32)
    router = jnp.asarray(rng.randn(16, 8), jnp.float32)
    gate, up, down = _experts(rng, 8, 16, 12)
    bias = jnp.zeros((8,), jnp.float32)
    w = jnp.asarray(rng.randn(96, 16), jnp.float32)

    def prog(x, router, gate, up, down):
        out, _ = sparse_moe.raw_fn(x, router, bias, gate, up, down, top_k=3,
                                   renormalize=norm, score_func="softmax")
        return jnp.sum(out * w)

    def plain(x, router, gate, up, down):
        return jnp.sum(_ref_layer(x, router, gate, up, down, 3, 0, norm) * w)

    args = (x, router, gate, up, down)
    with jax.default_matmul_precision("highest"):
        got, g_got = jax.value_and_grad(prog, argnums=range(5))(*args)
    want, g_want = jax.value_and_grad(plain, argnums=range(5))(*args)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    for a, b in zip(g_got, g_want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4,
                                   atol=1e-5)


def test_sigmoid_scores_are_what_they_were():
    """``score_func`` defaults to the sigmoid the Kimi cell runs."""
    rng = np.random.RandomState(2)
    x = jnp.asarray(rng.randn(32, 16), jnp.float32)
    router = jnp.asarray(rng.randn(16, 8), jnp.float32)
    gate, up, down = _experts(rng, 8, 16, 12)
    bias = jnp.zeros((8,), jnp.float32)
    a, _ = sparse_moe.raw_fn(x, router, bias, gate, up, down, top_k=2)
    b, _ = sparse_moe.raw_fn(x, router, bias, gate, up, down, top_k=2,
                             score_func="sigmoid")
    assert np.array_equal(np.asarray(a), np.asarray(b))
    soft, _ = sparse_moe.raw_fn(x, router, bias, gate, up, down, top_k=2,
                                score_func="softmax")
    assert not np.allclose(np.asarray(a), np.asarray(soft))
    assert sorted(SCORE_FUNCS) == ["sigmoid", "softmax"]
    with pytest.raises(ValueError, match="softmax"):
        nn.SparseMoELayer(16, 12, 8, 2, score_func="tanh")


def test_the_four_shares_add_up_to_the_uncut_layer():
    """THE SHARE TEST. The cell holds experts 0-15 of 64, one of four
    chips; the other three hold 16-31, 32-47, 48-63. Each share's layer
    routes over all 64 and computes its own experts' part; the four
    parts (there is no shared expert to count once) add up to what the
    uncut reference gives for the whole layer."""
    rng = np.random.RandomState(3)
    tokens, d, f, experts, top_k, held = 64, 32, 24, 64, 8, 16
    x = jnp.asarray(rng.randn(tokens, d), jnp.float32)
    router = jnp.asarray(rng.randn(d, experts), jnp.float32)
    gate, up, down = _experts(rng, experts, d, f)
    whole = _ref_layer(x, router, gate, up, down, top_k, 0)
    paddle.seed(0)
    total, pairs = 0.0, 0.0
    for offset in range(0, experts, held):
        layer = nn.SparseMoELayer(d, f, experts, top_k, experts_held=held,
                                  expert_offset=offset, score_func="softmax")
        layer.router.weight._value = router
        cut = slice(offset, offset + held)
        layer.experts_gate._value = gate[cut]
        layer.experts_up._value = up[cut]
        layer.experts_down._value = down[cut]
        with jax.default_matmul_precision("highest"):
            part = layer(paddle.to_tensor(np.asarray(x))).numpy()
        # each share alone is the reference's share
        np.testing.assert_allclose(
            part, np.asarray(_ref_layer(x, router, gate[cut], up[cut],
                                        down[cut], top_k, offset)),
            rtol=1e-4, atol=1e-5)
        total = total + part
        pairs += float(layer.last_routing.numpy()[0])
    np.testing.assert_allclose(total, np.asarray(whole), rtol=1e-4,
                               atol=1e-5)
    assert pairs == tokens * top_k        # every pick is held by one share


# ---------------------------------------------------------------------------
# the config tables
# ---------------------------------------------------------------------------
def _tiny_mellum():
    cfg = _config("mellum2-12b-a2.5b.json")
    cfg.update(hidden_size=64, head_dim=16, num_attention_heads=4,
               num_key_value_heads=2, moe_intermediate_size=32,
               num_experts=16, experts_held=4, num_experts_per_tok=4,
               vocab_size=256, sliding_window=8)
    return cfg


def test_the_cut_file_builds_three_windowed_layers_to_one_full():
    cfg = _config("mellum2-12b-a2.5b.json")
    assert [causal_lm.mixer_kind(cfg, n) for n in (1, 2, 3, 4)] == \
        ["gqa"] * 4
    assert [causal_lm.ffn_kind(cfg, n) for n in (1, 2, 3, 4)] == ["moe"] * 4
    model = CausalLM.from_config(_tiny_mellum())
    built = [(b.mixer_kind, b.mixer.window, b.mixer.rope_scale, b.ffn_kind,
              b.ffn.score_func, b.ffn.top_k, b.ffn.renormalize)
             for b in model.layers]
    yarn = 1.2772588722239782
    assert built == [("gqa", 8, 1.0, "moe", "softmax", 4, True)] * 3 \
        + [("gqa", None, yarn, "moe", "softmax", 4, True)]
    full, slide = model.layers[3].mixer, model.layers[0].mixer
    assert (full.num_heads, full.num_kv_heads, full.head_dim) == (4, 2, 16)
    # the sliding layers rotate by the plain frequencies, the full one by
    # YaRN's
    assert not np.allclose(full.inv_freq, slide.inv_freq)
    assert slide.q_norm is not None
    bare = CausalLM.from_config(dict(_tiny_mellum(), qk_norm=False))
    assert bare.layers[0].mixer.q_norm is None
    assert "layers.0.mixer.q_norm.weight" not in dict(
        bare.named_parameters())


def test_the_tiny_mellum_model_trains_a_step_through_its_loss():
    paddle.seed(7)
    model = CausalLM.from_config(_tiny_mellum(), recompute=True)
    rng = np.random.RandomState(8)
    ids = paddle.to_tensor(rng.randint(0, 256, (2, 32)).astype("int32"))
    labels = paddle.to_tensor(rng.randint(0, 256, (2, 32)).astype("int32"))
    loss, routing = model.loss(ids, labels, return_routing=True)
    loss.backward()
    assert np.isfinite(float(loss)) and tuple(routing.shape) == (4, 2)
    grads = {k: p.grad for k, p in model.named_parameters()}
    assert all(g is not None and np.isfinite(g.numpy()).all()
               for g in grads.values())
    assert float(np.abs(grads["layers.3.mixer.k_norm.weight"].numpy()).sum())


def test_unknown_layer_types_and_scores_are_refused_by_name():
    cfg = _tiny_mellum()
    with pytest.raises(NotImplementedError, match="sliding_attention"):
        CausalLM.from_config(dict(cfg, layer_types=["chunked_attention"] * 4))
    with pytest.raises(NotImplementedError, match="softmax"):
        CausalLM.from_config(dict(cfg, moe_router_activation_func="tanh"))


def test_the_kimi_file_builds_what_it_built_before():
    """Names, kinds and seeded values of the Kimi configuration's model,
    pinned from the commit before this one read any new key."""
    cfg = _config("kimi-linear-48b-a3b.json")
    cfg.update(hidden_size=64, intermediate_size=96, moe_intermediate_size=32,
               kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
               v_head_dim=16, num_attention_heads=4, vocab_size=512,
               num_experts=16, experts_held=4, num_experts_per_token=4,
               num_hidden_layers=3)
    cfg["linear_attn_config"] = dict(
        cfg["linear_attn_config"], num_heads=4, head_dim=16,
        kda_layers=[1, 3], full_attn_layers=[2])
    paddle.seed(31)
    model = CausalLM.from_config(cfg)
    assert [(b.mixer_kind, b.ffn_kind) for b in model.layers] == [
        ("kda", "dense"), ("mla", "moe"), ("kda", "moe")]
    assert model.layers[1].ffn.score_func == "sigmoid"
    names, values = [], hashlib.sha256()
    for k, p in model.named_parameters():
        names.append(k)
        values.update(k.encode())
        values.update(np.asarray(p.numpy(), np.float32).tobytes())
    assert len(names) == 63
    assert hashlib.sha256("\n".join(names).encode()).hexdigest()[:16] == \
        "2f8f419f6e8567e3"
    assert values.hexdigest()[:16] == "61dea6e4b2927007"


def test_the_top_rung_is_dense_up_to_twice_as_many_experts_as_picks():
    """Held <= 2 x top_k: every token through every held expert (a step's
    time then does not follow its routing); more: ragged_dot on the
    sorted pairs. Both are the reference's layer; the counters say which
    rows ran."""
    rng = np.random.RandomState(4)
    tokens, d, f, experts, top_k = 64, 16, 8, 32, 4
    x = jnp.asarray(rng.randn(tokens, d), jnp.float32)
    router = jnp.asarray(rng.randn(d, experts), jnp.float32)
    gate, up, down = _experts(rng, experts, d, f)
    # every token picks the first four experts, all of them held
    bias = jnp.zeros((experts,), jnp.float32).at[:top_k].set(50.0)
    for held, rows in ((8, 512), (12, 256)):
        with jax.default_matmul_precision("highest"):
            out, (pairs, ran) = sparse_moe.raw_fn(
                x, router, bias, gate[:held], up[:held], down[:held],
                top_k=top_k, score_func="softmax")
        assert (int(pairs), int(ran)) == (tokens * top_k, rows), held
        p = jax.nn.softmax(ref._dense(x, router), axis=-1)[:, :top_k]
        want = sum((p[:, e] / p.sum(1))[:, None] * ref._gated(
            x, gate[e], up[e], down[e], ref._dense) for e in range(top_k))
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=1e-4, atol=1e-5)
