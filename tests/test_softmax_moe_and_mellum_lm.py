"""The softmax-routed dropless expert layer and the decoder that
``CausalLM.from_config`` assembles from a Mellum-2 style config: router
arithmetic by hand, the layer against the benchmark's plain reference
(``benchmarks/reference/mellum2.py``), the SHARE test that ties a chip's
cut to the model, and the two config tables — the new keys build the new
kinds, the Kimi file builds what it built before."""
import hashlib
import json
import os
import re

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


# ---------------------------------------------------------------------------
# the dense top rung: one gated FFN of width held x F
# ---------------------------------------------------------------------------
#: held experts [offset, offset + held) of ``experts``, ``tokens`` rows;
#: ``bias`` is the router's correction on one expert (steers the picks)
WIDE_RUNG = {
    "sigmoid, 8 held of top 8": dict(
        score_func="sigmoid", experts=32, held=8, offset=8, tokens=256),
    "softmax, 16 held of top 8": dict(
        score_func="softmax", experts=64, held=16, offset=0, tokens=256),
    "a token none of whose picks is held": dict(
        score_func="softmax", experts=64, held=8, offset=24, tokens=256),
    "an expert no token picked": dict(
        score_func="softmax", experts=64, held=16, offset=16, tokens=256,
        bias=(19, -50.0)),
    "T not a multiple of 256": dict(
        score_func="sigmoid", experts=32, held=8, offset=0, tokens=200),
}
WIDE_TOP_K, WIDE_D, WIDE_F = 8, 32, 16


def _wide_case(case):
    c = WIDE_RUNG[case]
    rng = np.random.RandomState(11)
    x = jnp.asarray(rng.randn(c["tokens"], WIDE_D), jnp.float32)
    router = jnp.asarray(rng.randn(WIDE_D, c["experts"]), jnp.float32)
    stacks = _experts(rng, c["held"], WIDE_D, WIDE_F)
    cot = jnp.asarray(rng.randn(c["tokens"], WIDE_D), jnp.float32)
    bias = jnp.zeros((c["experts"],), jnp.float32)
    if "bias" in c:
        bias = bias.at[c["bias"][0]].set(c["bias"][1])
    return c, (x, router) + stacks, bias, cot


def _expert_loop(x, router, gate, up, down, bias, c, dtype):
    """The layer as a Python loop over its held experts, one plain gated
    FFN each in ``dtype``, weighted after its down product."""
    scores = SCORE_FUNCS[c["score_func"]](jnp.matmul(
        x, router, precision=jax.lax.Precision.HIGHEST))
    _, picked = jax.lax.top_k(scores + bias, WIDE_TOP_K)
    weight = jnp.take_along_axis(scores, picked, axis=1)
    weight = weight / jnp.sum(weight, axis=1, keepdims=True)
    rows, out = x.astype(dtype), jnp.zeros(x.shape, jnp.float32)
    for e in range(c["held"]):
        y = jnp.matmul(jax.nn.silu(jnp.matmul(rows, gate[e].astype(dtype)))
                       * jnp.matmul(rows, up[e].astype(dtype)),
                       down[e].astype(dtype))
        mine = jnp.sum(jnp.where(picked == e + c["offset"], weight, 0.0), 1)
        out = out + y.astype(jnp.float32) * mine[:, None]
    return out, picked


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", list(WIDE_RUNG))
def test_the_wide_top_rung_matches_a_loop_over_its_experts(case, dtype):
    """Values and gradients (tokens, router, the three expert stacks) of
    the top rung against a per-expert loop: float32 to 1e-5, under
    bfloat16 autocast to ``chip_smoke.py``'s 3e-2 of the largest value
    (the weight meets the hidden activation, not the expert's output:
    another place for the one rounding)."""
    from paddle_tpu import amp

    c, args, bias, cot = _wide_case(case)
    low = dtype == "bfloat16"

    def wide(*args):
        with amp.auto_cast(enable=low, level="O1", dtype="bfloat16"):
            out, routing = sparse_moe.raw_fn(
                args[0], args[1], bias, *args[2:], top_k=WIDE_TOP_K,
                expert_offset=c["offset"], score_func=c["score_func"])
        return jnp.sum(out * cot), (out, routing)

    def loop(*args):
        out, picked = _expert_loop(*args, bias, c, jnp.dtype(dtype))
        return jnp.sum(out * cot), (out, picked)

    with jax.default_matmul_precision("highest"):
        (_, (got, routing)), dgot = jax.value_and_grad(
            wide, argnums=(0, 1, 2, 3, 4), has_aux=True)(*args)
        (_, (want, picked)), dwant = jax.value_and_grad(
            loop, argnums=(0, 1, 2, 3, 4), has_aux=True)(*args)
    # the dense rung ran: every token through every held expert
    local = np.asarray(picked) - c["offset"]
    held_picks = ((local >= 0) & (local < c["held"]))
    rows = -(-c["tokens"] * c["held"] // 256) * 256
    assert [int(v) for v in routing] == [held_picks.sum(), rows]
    if case == "a token none of whose picks is held":
        none = ~held_picks.any(axis=1)
        assert 0 < none.sum() < c["tokens"]
        assert not np.asarray(got)[none].any()
    if case == "an expert no token picked":
        idle = c["bias"][0] - c["offset"]
        assert not (local == idle).any()
        for g in dgot[2:]:
            assert not np.asarray(g[idle]).any()
    tol = 3e-2 if low else 1e-5
    for name, a, b in zip(("out", "dx", "drouter", "dgate", "dup", "ddown"),
                          (got,) + dgot, (want,) + dwant):
        err = float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))
        assert err < tol, (name, err)


def test_the_differentiated_top_rung_stacks_nothing():
    """Under the block's recomputation the rung's backward needs the gate
    and up products again and nothing carried from a loop: its HLO holds
    no ``while`` and no ``dynamic-update-slice`` (a scan over the experts
    has both: it stacks each iteration's residuals)."""
    c, args, bias, cot = _wide_case("softmax, 16 held of top 8")

    def layer(*args):
        return sparse_moe.raw_fn(
            args[0], args[1], bias, *args[2:], top_k=WIDE_TOP_K,
            score_func=c["score_func"])[0]

    lowered = jax.jit(jax.grad(
        lambda *a: jnp.sum(jax.checkpoint(layer)(*a) * cot),
        argnums=(0, 1, 2, 3, 4))).lower(*args)
    unoptimized, compiled = (lowered.as_text(dialect="hlo"),
                             lowered.compile().as_text())
    assert re.search(r"\bdot\(", unoptimized)       # instructions read so
    for text in (unoptimized, compiled):
        assert not re.findall(r"\b(while|dynamic-update-slice)\(", text)
