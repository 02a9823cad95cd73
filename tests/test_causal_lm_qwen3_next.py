"""What ``models.causal_lm`` reads of a ``qwen3_next`` style file, by KEY
and never by ``model_type`` (PR 49; each case fails on the parent commit,
which read past the key or raised ``KeyError: 'linear_attn_config'``):
``full_attention_interval`` beside the five ``linear_*`` keys,
``partial_rotary_factor`` beside a top-level ``rope_theta``,
``shared_expert_intermediate_size``, ``attn_output_gate``,
``zero_centered_norm``, ``mlp_only_layers`` / ``decoder_sparse_step``; the
expert layer's shared gate and its ladder of sorted rungs alone; and the
pinned parameter count of the benchmark's file on the program's own model.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import nn
from paddle_tpu.models.causal_lm import (CausalLM, DecoderBlock, ffn_kind,
                                         mixer_kind)
from paddle_tpu.ops.pallas import counters

TINY = {
    "model_type": "qwen3_next", "hidden_size": 64, "num_hidden_layers": 4,
    "vocab_size": 256, "rms_norm_eps": 1e-6, "hidden_act": "silu",
    "full_attention_interval": 4, "linear_num_key_heads": 2,
    "linear_num_value_heads": 4, "linear_key_head_dim": 16,
    "linear_value_head_dim": 8, "linear_conv_kernel_dim": 4,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 32,
    "partial_rotary_factor": 0.25, "rope_theta": 10000000,
    "rope_scaling": None, "num_experts": 16, "num_experts_per_tok": 4,
    "experts_held": 8, "moe_intermediate_size": 32,
    "shared_expert_intermediate_size": 48, "norm_topk_prob": True,
    "decoder_sparse_step": 1, "mlp_only_layers": [],
    "intermediate_size": 96, "tie_word_embeddings": False,
    "attn_output_gate": True, "zero_centered_norm": True, "qk_norm": True}


def test_full_attention_interval_lays_out_the_mixers():
    assert [mixer_kind(TINY, n) for n in range(1, 9)] == [
        "gdn", "gdn", "gdn", "gqa"] * 2
    assert [mixer_kind(dict(TINY, full_attention_interval=2), n)
            for n in (1, 2, 3)] == ["gdn", "gqa", "gdn"]
    bare = {k: v for k, v in TINY.items() if k != "linear_num_value_heads"}
    with pytest.raises(NotImplementedError, match="linear_num_value_heads"):
        mixer_kind(bare, 1)
    # the key decides, not the model_type
    assert mixer_kind(dict(TINY, model_type="anything"), 4) == "gqa"


def test_the_linear_keys_size_the_gated_delta_net():
    block = DecoderBlock(dict(TINY, linear_conv_kernel_dim=3), 1)
    mixer = block.mixer
    assert isinstance(mixer, nn.GatedDeltaNet)
    assert (mixer.num_key_heads, mixer.num_value_heads, mixer.key_head_dim,
            mixer.value_head_dim) == (2, 4, 16, 8)
    assert tuple(mixer.qkv_conv.shape) == (3, 2 * 32 + 32)
    assert tuple(mixer.in_proj_qkvz.weight.shape) == (64, 2 * 32 + 2 * 32)
    assert mixer._epsilon == 1e-6
    # its output norm keeps the ordinary scale under zero-centred norms
    assert np.all(mixer.o_norm.numpy() == 1.0)


def test_partial_rotary_factor_reaches_the_attention_of_such_a_file_alone():
    counters.reset()
    mixer = DecoderBlock(TINY, 4).mixer
    assert isinstance(mixer, nn.GroupedQueryAttention)
    assert (mixer.rotary_dim, mixer.head_dim) == (8, 32)
    assert mixer.window is None
    np.testing.assert_allclose(
        mixer.inv_freq, 1.0 / 1e7 ** (np.arange(0, 8, 2) / 8.0))
    assert counters.snapshot()["gqa.partial_rotary"] == 1
    whole = DecoderBlock({k: v for k, v in TINY.items()
                          if k != "partial_rotary_factor"}, 4).mixer
    assert whole.rotary_dim == 32 and len(whole.inv_freq) == 16
    with pytest.raises(NotImplementedError, match="rope_scaling"):
        DecoderBlock(dict(TINY, rope_scaling={"factor": 2.0}), 4)
    # beside rope_parameters the key is refused, never read past
    layered = {k: v for k, v in TINY.items()
               if k not in ("full_attention_interval", "rope_theta")}
    layered.update(layer_types=["full_attention"] * 4,
                   rope_parameters={"rope_type": "default",
                                    "rope_theta": 1e6})
    with pytest.raises(NotImplementedError, match="partial_rotary_factor"):
        DecoderBlock(layered, 1)
    # a pattern's file keeps the key read by nothing: its attention takes
    # ``rope`` alone (the Nemotron file says partial_rotary_factor 1)
    pattern = {"hybrid_override_pattern": "*", "hidden_size": 64,
               "num_attention_heads": 4, "num_key_value_heads": 2,
               "head_dim": 32, "layer_norm_epsilon": 1e-5,
               "partial_rotary_factor": 0.5, "rope_theta": 10000}
    attention = DecoderBlock(pattern, 1).mixer
    assert attention.inv_freq is None and attention.rotary_dim == 32
    assert not attention.output_gate and attention.q_norm is None


def test_the_three_keys_the_source_has_no_name_for():
    counters.reset()
    block = DecoderBlock(TINY, 4)
    assert block.mixer.output_gate
    assert tuple(block.mixer.q_proj.weight.shape) == (64, 2 * 4 * 32)
    assert counters.snapshot()["gqa.output_gate"] == 1
    for norm in (block.input_norm, block.post_norm, block.mixer.q_norm,
                 block.mixer.k_norm):
        assert norm._zero_centered and np.all(norm.weight.numpy() == 0.0)
    plain = DecoderBlock({k: v for k, v in TINY.items() if k not in (
        "attn_output_gate", "zero_centered_norm")}, 4)
    assert not plain.mixer.output_gate
    assert tuple(plain.mixer.q_proj.weight.shape) == (64, 4 * 32)
    assert np.all(plain.input_norm.weight.numpy() == 1.0)
    assert DecoderBlock(dict(TINY, qk_norm=False), 4).mixer.q_norm is None
    model = CausalLM.from_config(TINY)
    assert model.final_norm._zero_centered
    # the zero-centred norm is x / rms(x) * (1 + w)
    norm = nn.RMSNorm(8, epsilon=1e-6, zero_centered=True)
    norm.weight._value = jnp.asarray(np.linspace(-0.5, 0.5, 8), jnp.float32)
    x = np.random.RandomState(0).randn(3, 8).astype(np.float32)
    want = x / np.sqrt((x * x).mean(-1, keepdims=True) + 1e-6) \
        * (1.0 + np.linspace(-0.5, 0.5, 8))
    np.testing.assert_allclose(norm(paddle.to_tensor(x)).numpy(), want,
                               rtol=1e-5, atol=1e-6)


def test_the_shared_expert_key_brings_one_gated_shared_expert():
    counters.reset()
    ffn = DecoderBlock(TINY, 1).ffn
    assert isinstance(ffn, nn.SparseMoELayer)
    assert tuple(ffn.shared.gate_proj.weight.shape) == (64, 48)
    assert tuple(ffn.shared_gate.weight.shape) == (64, 1)
    assert ffn.shared_gate.bias is None
    assert (ffn.score_func, ffn.top_k, ffn.renormalize) == (
        "softmax", 4, True)
    assert tuple(ffn.router.weight.shape) == (64, 16)
    assert tuple(ffn.experts_up.shape) == (8, 64, 32)
    assert counters.snapshot()["moe.shared_gate"] == 1
    with pytest.raises(NotImplementedError, match="shared_expert_inter"):
        DecoderBlock(dict(TINY, num_shared_experts=1), 1)
    with pytest.raises(ValueError, match="no shared expert"):
        nn.SparseMoELayer(64, 32, 16, 4, shared_gate=True)
    # out = routed + sigmoid(x w_s) * shared(x)
    paddle.seed(2)
    gated = nn.SparseMoELayer(64, 32, 16, 4, score_func="softmax",
                              shared_width=48, shared_gate=True)
    x = paddle.to_tensor(
        np.random.RandomState(1).randn(2, 6, 64).astype(np.float32))
    got = gated(x).numpy()
    logit = x.numpy() @ gated.shared_gate.weight.numpy()
    shared = gated.shared(x).numpy()
    gate, gated.shared_gate = gated.shared_gate, None
    base = gated(x).numpy()         # routed + shared, no gate
    gated.shared_gate = gate
    np.testing.assert_allclose(
        got, base - shared + shared / (1.0 + np.exp(-logit)),
        rtol=1e-4, atol=1e-5)


def test_mlp_only_layers_and_decoder_sparse_step_are_read():
    assert [ffn_kind(TINY, n) for n in (1, 2, 3, 4)] == ["moe"] * 4
    # 0-based, as the family's modelling code reads the list
    assert [ffn_kind(dict(TINY, mlp_only_layers=[0, 2]), n)
            for n in (1, 2, 3, 4)] == ["dense", "moe", "dense", "moe"]
    assert [ffn_kind(dict(TINY, decoder_sparse_step=2), n)
            for n in (1, 2, 3, 4)] == ["dense", "moe", "dense", "moe"]
    dense = DecoderBlock(dict(TINY, mlp_only_layers=[0]), 1).ffn
    assert isinstance(dense, nn.GatedFFN)
    assert tuple(dense.gate_proj.weight.shape) == (64, 96)


def test_a_share_with_no_dense_top_has_sorted_rungs_alone():
    """32 held of top 10 in 512 at 8,192 tokens: more than twice the
    picks are held, ``dense_rows`` is 0 and the ladder is the two sorted
    rungs; a tiny layer of that ratio counts ``sorted`` and never
    ``every_pair``, and matches a dense loop over its experts."""
    from paddle_tpu.nn.moe import _row_ladder

    assert _row_ladder(8192 * 10, 32, 512) == (40960, 81920)
    assert _row_ladder(8192 * 8, 16, 64, 8192 * 16) == (8192 * 16 // 2,)
    paddle.seed(3)
    counters.reset()
    layer = nn.SparseMoELayer(32, 16, 32, 3, experts_held=8,
                              score_func="softmax")
    x = np.random.RandomState(4).randn(40, 32).astype(np.float32)
    got = layer(paddle.to_tensor(x)).numpy()
    snap = counters.snapshot()
    assert snap["sparse_moe.sorted"] == 1
    assert "sparse_moe.every_pair" not in snap
    scores = jax.nn.softmax(x @ layer.router.weight.numpy(), axis=-1)
    weight, picked = jax.lax.top_k(scores, 3)
    weight = weight / weight.sum(-1, keepdims=True)
    want = np.zeros_like(x)
    for e in range(8):
        w_e = np.where(np.asarray(picked) == e, np.asarray(weight),
                       0.0).sum(-1)
        hidden = jax.nn.silu(x @ layer.experts_gate.numpy()[e]) \
            * (x @ layer.experts_up.numpy()[e])
        want += w_e[:, None] * np.asarray(
            hidden @ layer.experts_down.numpy()[e])
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
    pairs, rows = np.asarray(layer.last_routing.numpy())
    assert 0 < pairs <= rows


def test_the_tiny_model_trains_a_step_and_counts_what_it_built():
    counters.reset()
    paddle.seed(0)
    model = CausalLM.from_config(TINY)
    assert [(b.mixer_kind, b.ffn_kind) for b in model.layers] == [
        ("gdn", "moe")] * 3 + [("gqa", "moe")]
    assert "head" in dict(model.named_parameters())
    snap = counters.snapshot()
    assert (snap["gqa.output_gate"], snap["gqa.partial_rotary"],
            snap["moe.shared_gate"]) == (1, 1, 4)
    ids = paddle.to_tensor(np.random.RandomState(0).randint(
        0, 256, (2, 64)).astype("int32"))
    loss = model.loss(ids, ids)
    loss.backward()
    assert np.isfinite(float(loss))
    assert counters.snapshot()["gdn.scalar_decay"] == 3
    for name, p in model.named_parameters():
        assert p.grad is not None and np.isfinite(p.grad.numpy()).all(), name


def test_the_program_counts_the_issues_parameters_at_the_files_sizes():
    """625,994,816 parameters as the PROGRAM counts them: the model built
    from the benchmark's own file on shape structs alone; the GDN mixer
    33,718,464, the attention mixer 27,263,488, an expert layer with 32
    held 104,859,648."""
    from benchmarks import harness

    _, cfg = harness.load_cell("qwen3-next-80b-a3b.pretrain-seq8k")
    mcfg = harness.load_driver(cfg).model_config(cfg)
    shapes = {}

    def build():
        model = CausalLM.from_config(mcfg, recompute=True)
        shapes.update({k: tuple(p.shape)
                       for k, p in model.named_parameters()})
        return [p._value for p in model.parameters()]

    leaves = jax.eval_shape(build)
    assert sum(int(np.prod(x.shape)) for x in leaves) == 625_994_816

    def count(prefix):
        return sum(int(np.prod(s)) for k, s in shapes.items()
                   if k.startswith(prefix))

    assert count("layers.0.mixer.") == 33_718_464
    assert count("layers.3.mixer.") == 27_263_488
    assert count("layers.0.ffn.") == count("layers.3.ffn.") == 104_859_648
    assert count("layers.0.") == 138_582_208
    assert count("layers.3.") == 132_127_232
    assert count("embed.") + count("head") == 2 * 19_072 * 2_048
    assert round(625_994_816 * 16 / 1e9, 2) == 10.02
    assert round(100 * 625_994_816 * 16 / 2 ** 34, 1) == 58.3
