"""nn.MLAttention's rotary variant and the ``deepseek_v3`` key family in
models.causal_lm: the layer against the literal formula of
benchmarks/reference/deepseek_v3.py on seeded weights (``interleave`` on
and off), the NoPE path's lowered text as the parent left it, the three
readings of the config keys that were wrong or failing before PR 39,
each by a case that fails there, and the counters the benchmark's new
readers gate on."""
import functools
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.framework.bringup as bringup
from benchmarks.reference import deepseek_v3 as ref
from paddle_tpu import amp, nn
from paddle_tpu.models.causal_lm import CausalLM, ffn_kind, mixer_kind
from paddle_tpu.nn import latent_attention
from paddle_tpu.ops.pallas import counters

#: the source's key set at test widths (``model_type: deepseek_v3``)
CFG = {
    "model_type": "deepseek_v3", "attention_bias": False,
    "first_k_dense_replace": 1, "hidden_act": "silu", "hidden_size": 64,
    "intermediate_size": 96, "kv_lora_rank": 32, "moe_intermediate_size": 32,
    "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 16,
    "n_shared_experts": 2, "norm_topk_prob": True, "num_attention_heads": 4,
    "num_experts_per_tok": 3, "num_hidden_layers": 3,
    "num_key_value_heads": 4, "head_dim": 8, "q_lora_rank": None,
    "qk_head_dim": 24, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "rms_norm_eps": 1e-6, "rope_interleave": True, "rope_scaling": None,
    "rope_theta": 1000000, "routed_scaling_factor": 2.448,
    "scoring_func": "sigmoid", "tie_word_embeddings": False,
    "topk_group": 1, "topk_method": "noaux_tc", "v_head_dim": 16,
    "vocab_size": 256,
}


def _seeded(layer, seed, scale=0.2):
    out = {}
    for i, (name, p) in enumerate(sorted(layer.named_parameters())):
        out[name] = scale * jax.random.normal(
            jax.random.fold_in(jax.random.key(seed), i), tuple(p.shape))
        p._value = out[name]
    return out


def _rel(a, b):
    return float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-30))


def _layer(interleave=True, theta=1e6):
    return nn.MLAttention(64, 4, 16, 8, 16, 32, epsilon=1e-6,
                          rope={"rope_theta": theta,
                                "interleave": interleave})


# ---------------------------------------------------------------------------
# the layer
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("interleave", [True, False])
def test_rotary_mla_matches_the_references_literal_formula(interleave):
    layer = _layer(interleave)
    p = {"m." + k: v for k, v in _seeded(layer, 3).items()}
    x = jax.random.normal(jax.random.key(4), (2, 64, 64))
    got = layer(paddle.to_tensor(x)).value
    cfg = dict(CFG, rope_interleave=interleave)
    want = jnp.stack([ref.mla(p, "m.", row, cfg, ref.F32_MATMULS, 32)
                      for row in x])
    assert _rel(got, want) < 1e-5
    # the layout is part of the result: the other one is another layer
    other = jnp.stack([ref.mla(p, "m.", row,
                               dict(cfg, rope_interleave=not interleave),
                               ref.F32_MATMULS, 32) for row in x])
    assert _rel(got, other) > 1e-3
    # and so are the positions: NoPE on the same weights is far off
    nope = nn.MLAttention(64, 4, 16, 8, 16, 32, epsilon=1e-6)
    for name, q in nope.named_parameters():
        q._value = p["m." + name]
    assert _rel(nope(paddle.to_tensor(x)).value, want) > 1e-3


def test_the_references_deinterleave_is_the_sources_view_transpose():
    x = jnp.arange(2 * 3 * 8, dtype=jnp.float32).reshape(2, 3, 8)
    got = ref._deinterleave(x)
    np.testing.assert_array_equal(
        np.asarray(got[0, 0]), [0, 2, 4, 6, 1, 3, 5, 7])
    # rotation by position 0 is the identity, by any position a rotation
    # of each (even, odd) pair: norms of the pairs are kept
    r = ref.rotate(x, 1e6, True)
    np.testing.assert_allclose(np.asarray(r[0]), np.asarray(got[0]))
    pairs = np.asarray(x).reshape(2, 3, 4, 2)
    rot = np.asarray(r)
    np.testing.assert_allclose(
        rot[..., :4] ** 2 + rot[..., 4:] ** 2, (pairs ** 2).sum(-1),
        rtol=1e-5)


def test_k_pe_is_rotated_once_a_token_before_the_heads_share_it(
        monkeypatch):
    seen = []
    rotary = latent_attention.F.rotary_embedding.raw_fn

    def spy(x, inv_freq, *a, **kw):
        seen.append(tuple(x.shape))
        return rotary(x, inv_freq, *a, **kw)

    monkeypatch.setattr(latent_attention.F.rotary_embedding, "raw_fn", spy)
    layer = _layer()
    layer(paddle.to_tensor(jnp.ones((2, 16, 64))))
    assert sorted(seen) == [(2, 16, 1, 8), (2, 16, 4, 8)]
    np.testing.assert_allclose(
        layer.inv_freq, 1e6 ** (-np.arange(4) * 2 / 8.0))


def test_rotary_tables_are_float32_and_cast_to_the_operands_type():
    layer = _layer()
    _seeded(layer, 5)
    x = paddle.to_tensor(jax.random.normal(jax.random.key(6), (1, 32, 64)))
    full = layer(x).value
    with amp.auto_cast(level="O1", dtype="bfloat16"):
        low = layer(x).value
    assert low.dtype == jnp.bfloat16
    assert 1e-4 < _rel(low.astype(jnp.float32), full) < 3e-2


def test_lowered_text_carries_the_rotation_scope_and_nope_does_not():
    def text(layer):
        return jax.jit(lambda a: layer(paddle.to_tensor(a)).value).lower(
            jnp.zeros((1, 16, 64), jnp.float32)).as_text(debug_info=True)

    rotary = text(_layer())
    for scope in ("mla_rope", "rotary_embedding", "q_proj", "kv_norm"):
        assert f'"{scope}' in rotary or f"/{scope}" in rotary, scope
    plain = text(nn.MLAttention(64, 4, 16, 8, 16, 32))
    assert "mla_rope" not in plain and "rotary_embedding" not in plain


#: sha256 of the NoPE layer's lowered text (below) at commit 99f6e6a, the
#: parent of PR 39: the rotary variant was added beside it
NOPE_TEXT = {
    "bfloat16":
        "7ef3bd0afd84c5b5d52dd87b36a52116bae8a774601bde37ad95c61ba4c15ae1",
    "float32":
        "8ec18e4539ef3e66a64c008864270209faadf4635a76a3a2e90e0cddbaca413e"}


@pytest.mark.parametrize("dtype", sorted(NOPE_TEXT))
def test_nope_mla_lowers_to_the_parents_text(dtype):
    """The NoPE layer (the Kimi cell's), differentiated under
    ``jax.checkpoint`` as a block of that cell is, in float32 and under
    the cell's bfloat16 autocast: a PR that means to leave it alone keeps
    these digests."""
    paddle.seed(0)
    layer = nn.MLAttention(64, 4, 16, 8, 16, 32, epsilon=1e-5)
    params = dict(layer.named_parameters())
    names = sorted(params)
    saved = [params[n]._value for n in names]

    @jax.checkpoint
    def f(x, *vals):
        for n, v in zip(names, vals):
            params[n]._value = v
        with amp.auto_cast(enable=dtype != "float32", level="O1",
                           dtype="bfloat16"):
            return jnp.sum(layer(paddle.to_tensor(x)).value.astype(
                jnp.float32))

    shapes = [jax.ShapeDtypeStruct((2, 64, 64), jnp.float32)] + [
        jax.ShapeDtypeStruct(params[n].shape, jnp.float32) for n in names]
    try:
        text = jax.jit(jax.grad(f, argnums=tuple(range(len(shapes))))).lower(
            *shapes).as_text()
    finally:
        for n, v in zip(names, saved):
            params[n]._value = v
    assert hashlib.sha256(text.encode()).hexdigest() == NOPE_TEXT[dtype]


def test_a_scaled_rope_is_refused_by_name():
    with pytest.raises(NotImplementedError, match="yarn"):
        nn.MLAttention(64, 4, 16, 8, 16, 32,
                       rope={"rope_theta": 1e4, "rope_type": "yarn"})


# ---------------------------------------------------------------------------
# counters
# ---------------------------------------------------------------------------
def test_mla_counts_its_variant_once_a_call():
    counters.reset()
    x = paddle.to_tensor(jnp.ones((1, 16, 64)))
    _layer()(x)
    nn.MLAttention(64, 4, 16, 8, 16, 32)(x)
    nn.MLAttention(64, 4, 16, 8, 16, 32)(x)
    snap = counters.snapshot()
    counters.reset()
    assert (snap["mla.rotary"], snap["mla.nope"]) == (1, 2)
    # on the CPU the attention falls back, counted with the value width
    assert snap["flash_attention.xla"] == 3
    assert "flash_attention.latent" not in snap


def test_stream_kernels_count_a_value_width_of_their_own(monkeypatch):
    from jax.experimental import pallas as pl

    monkeypatch.setattr(pl, "pallas_call",
                        functools.partial(pl.pallas_call, interpret=True))
    monkeypatch.setattr(bringup, "pallas_enabled", lambda: True)
    counters.reset()
    try:
        layer = nn.MLAttention(128, 2, 64, 64, 64, 32,
                               rope={"rope_theta": 1e6, "interleave": True})
        p = {"m." + k: v for k, v in _seeded(layer, 7, 0.1).items()}
        x = jax.random.normal(jax.random.key(8), (1, 256, 128))
        got = layer(paddle.to_tensor(x)).value
        snap = counters.snapshot()
        # one width: the same kernels, not counted as latent
        gqa = nn.GroupedQueryAttention(128, 2, 2, 128, rope=None,
                                       qk_norm=False)
        gqa(paddle.to_tensor(x))
        after = counters.snapshot()
    finally:
        counters.reset()
    cfg = dict(CFG, hidden_size=128, num_attention_heads=2,
               qk_nope_head_dim=64, qk_rope_head_dim=64, v_head_dim=64)
    want = ref.mla(p, "m.", x[0], cfg, ref.F32_MATMULS, 128)
    assert _rel(got[0], want) < 1e-5
    assert snap["flash_attention.latent"] == snap["flash_attention.pallas"] \
        == snap["mla.rotary"] == 1
    assert "flash_attention.xla" not in snap
    assert after["flash_attention.pallas"] == 2
    assert after["flash_attention.latent"] == 1


# ---------------------------------------------------------------------------
# the config family
# ---------------------------------------------------------------------------
def test_the_sources_key_set_builds_dense_then_expert_layers_of_rotary_mla():
    kinds = [(mixer_kind(CFG, n), ffn_kind(CFG, n)) for n in (1, 2, 3)]
    assert kinds == [("mla", "dense"), ("mla", "moe"), ("mla", "moe")]
    assert ref.layer_kinds(CFG) == ["dense", "moe", "moe"]
    model = CausalLM.from_config(CFG)
    assert [(b.mixer_kind, b.ffn_kind) for b in model.layers] == kinds
    for block in model.layers:
        assert isinstance(block.mixer, nn.MLAttention)
        assert block.mixer.interleave and len(block.mixer.inv_freq) == 4
        assert block.mixer.inv_freq[1] == pytest.approx(1e6 ** -0.25)
    assert isinstance(model.layers[0].ffn, nn.GatedFFN)
    moe = model.layers[1].ffn
    assert isinstance(moe, nn.SparseMoELayer)
    assert (moe.score_func, moe.renormalize, moe.top_k, moe.scaling) == (
        "sigmoid", True, 3, 2.448)
    assert tuple(moe.router.weight.shape) == (64, 16)
    assert tuple(moe.experts_gate.shape) == (16, 64, 32)
    assert tuple(moe.shared.up_proj.weight.shape) == (64, 2 * 32)


def test_experts_counted_under_n_routed_experts_are_no_dense_ffn():
    """Before PR 39 ``ffn_kind`` asked for ``num_experts`` and gave this
    family a dense FFN of ``intermediate_size`` in every layer,
    silently."""
    assert ffn_kind(CFG, 2) == "moe"
    assert ffn_kind(dict(CFG, moe_layer_freq=2), 2) == "dense"
    assert ffn_kind(dict(CFG, moe_layer_freq=2), 3) == "moe"
    no_experts = {k: v for k, v in CFG.items() if k != "n_routed_experts"}
    assert ffn_kind(no_experts, 2) == "dense"
    renamed = dict(no_experts, num_experts=16)
    assert ffn_kind(renamed, 2) == "moe"


def test_scoring_func_is_read_beside_moe_router_activation_func():
    """Before PR 39 a file with ``norm_topk_prob`` and no
    ``moe_router_activation_func`` scored by softmax, whatever its
    ``scoring_func`` said."""
    assert CausalLM.from_config(CFG).layers[1].ffn.score_func == "sigmoid"
    soft = dict(CFG, scoring_func="softmax")
    assert CausalLM.from_config(soft).layers[1].ffn.score_func == "softmax"
    silent = {k: v for k, v in CFG.items() if k != "scoring_func"}
    assert CausalLM.from_config(silent).layers[1].ffn.score_func == "softmax"
    both = dict(CFG, moe_router_activation_func="softmax")
    assert CausalLM.from_config(both).layers[1].ffn.score_func == "softmax"
    with pytest.raises(NotImplementedError, match="router scores"):
        CausalLM.from_config(dict(CFG, scoring_func="tanh"))


def test_a_file_without_mla_use_nope_rotates():
    """Before PR 39 ``_mixer_mla`` read ``cfg["mla_use_nope"]`` and a
    file without the key failed with a KeyError."""
    assert CausalLM.from_config(CFG).layers[0].mixer.inv_freq is not None
    nope = CausalLM.from_config(dict(CFG, mla_use_nope=True))
    assert nope.layers[0].mixer.inv_freq is None
    plain = CausalLM.from_config(dict(CFG, rope_interleave=False))
    assert not plain.layers[0].mixer.interleave


@pytest.mark.parametrize("change,message", [
    ({"q_lora_rank": 24}, "MLA with a low-rank query projection"),
    ({"rope_scaling": {"type": "yarn", "factor": 4}}, "rope_scaling"),
    ({"n_group": 4, "topk_group": 2}, "group-limited routing"),
])
def test_what_the_family_has_and_the_program_lacks_still_raises(change,
                                                                message):
    with pytest.raises(NotImplementedError, match=message):
        CausalLM.from_config(dict(CFG, **change))


def test_a_tied_head_of_this_family_is_the_embedding():
    """Until PR 46 ``tie_word_embeddings`` raised here (the fourth case
    above); since then the head IS ``embed.weight`` and the model has no
    ``head`` leaf (``tests/test_causal_lm_lfm2.py`` holds the gradient)."""
    model = CausalLM.from_config(dict(CFG, tie_word_embeddings=True))
    names = [n for n, _ in model.named_parameters()]
    assert "head" not in names and "embed.weight" in names
    assert model.head_weight is model.embed.weight


def test_loss_and_first_gradients_match_the_reference():
    paddle.seed(11)
    cfg = dict(CFG, experts_held=4, expert_offset=4)
    model = CausalLM.from_config(cfg)
    p = _seeded(model, 12, 0.05)
    ids = jax.random.randint(jax.random.key(13), (2, 32), 0, 256)
    labels = jnp.concatenate(
        [ids[:, 1:], jnp.full((2, 1), -100, ids.dtype)], axis=1)
    want, want_g = jax.value_and_grad(ref.loss)(p, cfg, ids, labels,
                                                ref.F32_MATMULS, 16)

    def loss(values):
        for name, q in model.named_parameters():
            q._value = values[name]
        return model.loss(paddle.to_tensor(ids),
                          paddle.to_tensor(labels)).value

    got, got_g = jax.value_and_grad(loss)(p)
    for name, q in model.named_parameters():
        q._value = p[name]
    assert abs(float(got) - float(want)) < 1e-5
    assert set(got_g) == set(want_g)
    for name in want_g:
        assert _rel(got_g[name], want_g[name]) < 2e-4, name
