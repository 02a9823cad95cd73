"""The layers Nemotron-3-Nano forced, on seeded weights at a small size,
against the benchmark's plain reference (``benchmarks/reference/
nemotron_h.py``, which imports nothing of the program): ``nn.Mamba2Mixer``
(with its gate-then-norm order and its norm groups), plain relu^2 experts
in both rungs of ``sparse_moe`` (and gated ones as before), the sigmoid
router with its bias, renormalisation and scale against picks computed by
hand, the sixteen shares of one expert layer, attention at group 16 with
and without positions, and the blocks ``CausalLM.from_config`` builds
from the cut file — and still builds from the Kimi and Mellum files."""
import hashlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from benchmarks.reference import nemotron_h as ref
from paddle_tpu import nn
from paddle_tpu.models import causal_lm
from paddle_tpu.models.causal_lm import CausalLM
from paddle_tpu.nn.moe import _row_ladder, sparse_moe
from paddle_tpu.ops.pallas import counters

CONFIGS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks", "configs")
#: (parameters, digest of their names and shapes) on the parent commit
PINNED = {"kimi": (63, "56a5ea830b1dfb8f"),
          "mellum": (51, "b770154bc960acf6")}
MAMBA = {"mamba_num_heads": 4, "mamba_head_dim": 8, "n_groups": 2,
         "ssm_state_size": 16, "layer_norm_epsilon": 1e-5}


def _config(name):
    with open(os.path.join(CONFIGS, name)) as f:
        return json.load(f)


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


# ---------------------------------------------------------------------------
# the Mamba-2 mixer
# ---------------------------------------------------------------------------
def _mixer(seed=0, hidden=24):
    paddle.seed(seed)
    layer = nn.Mamba2Mixer(hidden, 4, 8, 16, groups=2, conv_size=4)
    rng = np.random.RandomState(seed)
    # a bias and a skip that are not their starting constants
    layer.conv_bias._value = jnp.asarray(0.3 * rng.randn(96), jnp.float32)
    layer.D._value = jnp.asarray(1.0 + 0.5 * rng.randn(4), jnp.float32)
    layer.norm_weight._value = jnp.asarray(1.0 + 0.2 * rng.randn(32),
                                           jnp.float32)
    p = {"m." + k: v.value for k, v in layer.named_parameters()}
    return layer, p


def test_mamba2_mixer_matches_the_reference_layer_and_its_gradients():
    layer, p = _mixer()
    assert sorted(p) == sorted("m." + k for k in (
        "in_proj.weight", "xbc_conv", "conv_bias", "A_log", "dt_bias", "D",
        "norm_weight", "out_proj.weight"))
    assert tuple(p["m.in_proj.weight"].shape) == (24, 32 + 96 + 4)
    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.randn(2, 160, 24), jnp.float32)  # 1.25 chunks
    w = jnp.asarray(rng.randn(2, 160, 24), jnp.float32)
    with jax.default_matmul_precision("highest"):
        xt = paddle.to_tensor(np.asarray(x), stop_gradient=False)
        out = layer(xt)
        (out * paddle.to_tensor(np.asarray(w))).sum().backward()

        def loss(p, x):
            return sum(jnp.sum(ref.mamba2(p, "m.", x[b], MAMBA, ref._dense)
                               * w[b]) for b in range(2))

        want = jnp.stack([ref.mamba2(p, "m.", x[b], MAMBA, ref._dense)
                          for b in range(2)])
        gp, gx = jax.grad(loss, argnums=(0, 1))(p, x)
    assert _rel(out.numpy(), want) < 1e-5
    assert _rel(xt.grad.numpy(), gx) < 1e-4
    for name, param in layer.named_parameters():
        assert _rel(param.grad.numpy(), gp["m." + name]) < 2e-4, name


@pytest.mark.parametrize("wrong", ["norm_over_all_4096", "norm_before_gate"])
def test_mamba2_mixer_gates_first_and_norms_each_group(wrong):
    """The reference's order and groups are the program's; the two
    misreadings of the source are not."""
    layer, p = _mixer(seed=3)
    x = jnp.asarray(np.random.RandomState(4).randn(1, 32, 24), jnp.float32)
    with jax.default_matmul_precision("highest"):
        got = layer(paddle.to_tensor(np.asarray(x))).numpy()[0]
        right = ref.mamba2(p, "m.", x[0], MAMBA, ref._dense)

        # the same layer with the norm misplaced
        proj = x[0] @ p["m.in_proj.weight"]
        z, xbc, dt = proj[:, :32], proj[:, 32:128], proj[:, 128:]
        xbc = jax.nn.silu(ref._short_conv(xbc, p["m.xbc_conv"])
                          + p["m.conv_bias"])
        u = xbc[:, :32].reshape(32, 4, 8)
        y = ref.ssm_recurrence(
            u, jax.nn.softplus(dt + p["m.dt_bias"]), -jnp.exp(p["m.A_log"]),
            xbc[:, 32:64].reshape(32, 2, 16), xbc[:, 64:].reshape(32, 2, 16))
        y = (y + p["m.D"][:, None] * u).reshape(32, 32)

        def rms(v, groups):
            v = v.reshape(32, groups, -1)
            return (v * jax.lax.rsqrt(jnp.mean(v * v, -1, keepdims=True)
                                      + 1e-5)).reshape(32, 32)

        if wrong == "norm_over_all_4096":
            y = rms(y * jax.nn.silu(z), 1)
        else:
            y = rms(y, 2) * jax.nn.silu(z)
        other = (y * p["m.norm_weight"]) @ p["m.out_proj.weight"]
    assert _rel(got, right) < 1e-5
    assert _rel(got, other) > 0.05


# ---------------------------------------------------------------------------
# plain and gated experts, both rungs
# ---------------------------------------------------------------------------
def _experts(rng, experts, d, f, gated):
    mats = [jnp.asarray(0.3 * rng.randn(experts, d, f), jnp.float32)
            for _ in range(2 if gated else 1)]
    return mats + [jnp.asarray(0.3 * rng.randn(experts, f, d), jnp.float32)]


@pytest.mark.parametrize("held,rung", [(8, "every_pair"), (16, "sorted")])
def test_plain_relu2_experts_match_the_dense_loop_in_both_rungs(held, rung):
    rng = np.random.RandomState(5)
    tokens, d, f, experts, top_k = 64, 16, 12, 32, 4
    x = jnp.asarray(rng.randn(tokens, d), jnp.float32)
    router = jnp.asarray(rng.randn(d, experts), jnp.float32)
    up, down = _experts(rng, held, d, f, gated=False)
    bias = jnp.zeros((experts,), jnp.float32)
    w = jnp.asarray(rng.randn(tokens, d), jnp.float32)
    cfg = {"num_experts_per_tok": top_k, "routed_scaling_factor": 2.5}

    def prog(x, router, up, down):
        out, _ = sparse_moe.raw_fn(x, router, bias, None, up, down,
                                   top_k=top_k, scaling=2.5)
        return jnp.sum(out * w)

    def want(x, router, up, down):
        p = {"f.router.weight": router, "f.experts_up": up,
             "f.experts_down": down}
        return jnp.sum(ref.routed(p, "f.", x, cfg, ref._dense) * w)

    counters.reset()
    with jax.default_matmul_precision("highest"):
        got = jax.value_and_grad(prog, argnums=(0, 1, 2, 3))(
            x, router, up, down)
        exp = jax.value_and_grad(want, argnums=(0, 1, 2, 3))(
            x, router, up, down)
    snap = counters.snapshot()
    assert snap == {f"sparse_moe.{rung}": 1, "sparse_moe.plain": 1}
    np.testing.assert_allclose(float(got[0]), float(exp[0]), rtol=1e-5)
    for name, a, b in zip(("x", "router", "up", "down"), got[1], exp[1]):
        assert _rel(a, b) < 1e-4, name


def test_every_rung_of_the_ladder_runs_plain_experts():
    """The cell's shape of ladder (two sorted rungs under... here a
    sorted top): picks forced onto the held experts fill a higher rung,
    and the result stays the dense loop's."""
    rng = np.random.RandomState(6)
    tokens, d, f, experts, top_k, held = 256, 16, 8, 64, 2, 8
    assert _row_ladder(tokens * top_k, held, experts) == (512,)
    tokens = 1024
    assert _row_ladder(tokens * top_k, held, experts) == (2048,)
    experts = 256
    rungs = _row_ladder(tokens * top_k, held, experts)
    assert rungs == (512, 2048)
    # with the constants as arguments: half the skew is a rung lower, a
    # dense top keeps the rungs that cost less than its rows, and a
    # costlier row is a shorter ladder
    assert _row_ladder(tokens * top_k, held, experts, row_cost=2.0,
                       skew=4) == (256, 1024, 2048)
    assert _row_ladder(tokens * top_k, held, experts, tokens * held,
                       row_cost=2.0, skew=4) == (256, 1024, 2048)
    assert _row_ladder(tokens * top_k, held, experts, tokens * held,
                       row_cost=16, skew=4) == (256, 2048)
    x = jnp.asarray(rng.randn(tokens, d), jnp.float32)
    router = jnp.asarray(rng.randn(d, experts), jnp.float32)
    up, down = _experts(rng, held, d, f, gated=False)
    cfg = {"num_experts_per_tok": top_k, "routed_scaling_factor": 1.0}
    p = {"f.router.weight": router, "f.experts_up": up,
         "f.experts_down": down}
    for push, rows in ((0.0, 512), (50.0, 2048)):
        bias = jnp.zeros((experts,), jnp.float32).at[:held].set(push)
        with jax.default_matmul_precision("highest"):
            out, (pairs, ran) = sparse_moe.raw_fn(
                x, router, bias, None, up, down, top_k=top_k)
            want = ref.routed(p, "f.", x, cfg, ref._dense, router_bias=bias)
        assert int(ran) == rows and int(pairs) <= rows
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("held", [8, 16])
def test_gated_experts_are_what_they_were(held):
    """Today's call (six positional operands: a gate matrix makes the
    experts gated, nothing else says so) matches the gated dense loop in
    both rungs, and a layer without gates holds plain experts."""
    rng = np.random.RandomState(7)
    tokens, d, f, experts, top_k = 64, 16, 12, 32, 4
    x = jnp.asarray(rng.randn(tokens, d), jnp.float32)
    router = jnp.asarray(rng.randn(d, experts), jnp.float32)
    gate, up, down = _experts(rng, held, d, f, gated=True)
    bias = jnp.zeros((experts,), jnp.float32)
    counters.reset()
    a, _ = sparse_moe.raw_fn(x, router, bias, gate, up, down, top_k=top_k)
    assert counters.snapshot() == {
        "sparse_moe.gated": 1,
        "sparse_moe." + ("every_pair" if held == 8 else "sorted"): 1}
    scores = jax.nn.sigmoid(x @ router)
    weight, picked = jax.lax.top_k(scores, top_k)
    weight = weight / weight.sum(1, keepdims=True)
    want = jnp.zeros_like(x)
    for e in range(held):
        w_e = jnp.sum(jnp.where(picked == e, weight, 0.0), axis=1)
        want = want + w_e[:, None] * (
            (jax.nn.silu(x @ gate[e]) * (x @ up[e])) @ down[e])
    np.testing.assert_allclose(np.asarray(a), np.asarray(want), rtol=2e-4,
                               atol=2e-5)
    # a plain layer holds no gate matrix, and its shared expert is plain
    layer = nn.SparseMoELayer(16, 12, 32, 4, experts_held=held,
                              shared_width=20, gated=False)
    assert sorted(k for k, _ in layer.named_parameters()) == [
        "experts_down", "experts_up", "router.weight",
        "shared.down_proj.weight", "shared.up_proj.weight"]
    assert tuple(layer.shared.up_proj.weight.shape) == (16, 20)
    assert layer.experts_gate is None


def test_sigmoid_bias_top6_renormalise_and_scale_against_hand_picks():
    """One token, eight experts, identity experts: the layer's output is
    the sum of its weights on the held picks. Scores by sigmoid; the pick
    by score + bias; the weights from the scores WITHOUT the bias, over
    all six picks, times 2.5."""
    d, experts, top_k = 8, 8, 6
    logits = np.array([2.0, -1.0, 0.5, 0.0, -3.0, 1.0, -0.5, 3.0])
    bias = np.array([0.0, 0.0, 0.0, 0.0, 5.0, 0.0, 0.0, -5.0])
    # x = e_0 and the router's first row the logits
    x = jnp.zeros((1, d), jnp.float32).at[0, 0].set(1.0)
    router = jnp.zeros((d, experts), jnp.float32).at[0].set(
        jnp.asarray(logits, jnp.float32))
    s = 1.0 / (1.0 + np.exp(-logits))
    picks = sorted(np.argsort(-(s + bias))[:top_k].tolist())
    # expert 4 is in by its bias, expert 7 (the best score) out by its
    assert picks == [0, 2, 3, 4, 5, 6]
    weights = {e: 2.5 * s[e] / sum(s[i] for i in picks) for e in picks}
    assert abs(sum(weights.values()) - 2.5) < 1e-12
    # relu2 experts that map x to x: up = down = I on the first F rows
    held = 4                                     # experts 2..5 live here
    eye = jnp.tile(jnp.eye(d, dtype=jnp.float32)[None], (held, 1, 1))
    out, (pairs, _) = sparse_moe.raw_fn(
        x, router, jnp.asarray(bias, jnp.float32), None, eye, eye,
        top_k=top_k, expert_offset=2, scaling=2.5)
    here = [e for e in picks if 2 <= e < 2 + held]
    assert here == [2, 3, 4, 5] and int(pairs) == 4
    np.testing.assert_allclose(float(out[0, 0]),
                               sum(weights[e] for e in here), rtol=1e-6)
    # the reference's router says the same
    got_p, got_w = ref.router_weights(x, router, top_k, 2.5,
                                      jnp.asarray(bias, jnp.float32))
    assert sorted(np.asarray(got_p)[0].tolist()) == picks
    for e, w in zip(np.asarray(got_p)[0], np.asarray(got_w)[0]):
        assert w == pytest.approx(weights[int(e)], rel=1e-6)


def test_sixteen_shares_and_one_shared_expert_add_up_to_the_uncut_layer():
    """Experts 0-7, ..., 120-127 through the program's layer, each share's
    routed part, plus the shared expert counted once, against the
    reference's layer holding all 128."""
    rng = np.random.RandomState(8)
    tokens, d, f, shared, experts, top_k = 48, 16, 8, 12, 128, 6
    x = jnp.asarray(rng.randn(tokens, d), jnp.float32)
    p = {"f.router.weight": jnp.asarray(rng.randn(d, experts), jnp.float32),
         "f.experts_up": jnp.asarray(0.3 * rng.randn(experts, d, f),
                                     jnp.float32),
         "f.experts_down": jnp.asarray(0.3 * rng.randn(experts, f, d),
                                       jnp.float32),
         "f.shared.up_proj.weight": jnp.asarray(rng.randn(d, shared),
                                                jnp.float32),
         "f.shared.down_proj.weight": jnp.asarray(rng.randn(shared, d),
                                                  jnp.float32)}
    cfg = {"num_experts_per_tok": top_k, "routed_scaling_factor": 2.5}
    bias = jnp.zeros((experts,), jnp.float32)
    with jax.default_matmul_precision("highest"):
        whole = ref.moe(p, "f.", x, cfg, ref._dense)
        total, pairs = jnp.zeros_like(x), 0
        for share in range(16):
            lo = 8 * share
            out, (n, _) = sparse_moe.raw_fn(
                x, p["f.router.weight"], bias, None,
                p["f.experts_up"][lo:lo + 8],
                p["f.experts_down"][lo:lo + 8], top_k=top_k,
                expert_offset=lo, scaling=2.5)
            total, pairs = total + out, pairs + int(n)
        layer = nn.PlainFFN(d, shared, activation="relu2")
        layer.up_proj.weight._value = p["f.shared.up_proj.weight"]
        layer.down_proj.weight._value = p["f.shared.down_proj.weight"]
        total = total + layer(paddle.to_tensor(np.asarray(x))).value
    assert pairs == tokens * top_k            # every pick lives somewhere
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               rtol=2e-4, atol=2e-5)


# ---------------------------------------------------------------------------
# attention at group 16
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("rope", [None, {"rope_type": "default",
                                         "rope_theta": 10000}],
                         ids=["no_positions", "the_other_reading"])
def test_attention_at_group_16_matches_the_reference(rope):
    paddle.seed(9)
    hidden, heads, kv_heads, d, t = 48, 32, 2, 8, 24
    layer = nn.GroupedQueryAttention(hidden, heads, kv_heads, d, rope=rope,
                                     qk_norm=False, epsilon=1e-5)
    assert sorted(k for k, _ in layer.named_parameters()) == [
        f"{n}_proj.weight" for n in "koqv"]
    p = {"a." + k: v.value for k, v in layer.named_parameters()}
    cfg = {"num_attention_heads": heads, "num_key_value_heads": kv_heads,
           "head_dim": d, "rope": rope}
    x = jnp.asarray(np.random.RandomState(10).randn(2, t, hidden),
                    jnp.float32)
    with jax.default_matmul_precision("highest"):
        got = layer(paddle.to_tensor(np.asarray(x))).numpy()
        want = jnp.stack([ref.attention(p, "a.", x[b], cfg, ref.F32_MATMULS,
                                        8) for b in range(2)])
    assert _rel(got, want) < 1e-5
    plain = jnp.stack([ref.attention(p, "a.", x[b], dict(cfg, rope=None),
                                     ref.F32_MATMULS, 8) for b in range(2)])
    assert (_rel(got, plain) < 1e-5) == (rope is None)


# ---------------------------------------------------------------------------
# the model the files build
# ---------------------------------------------------------------------------
def _tiny_nemotron(**over):
    cfg = _config("nemotron-3-nano-30b-a3b.json")
    cfg.update(hidden_size=32, head_dim=8, num_attention_heads=4,
               num_key_value_heads=2, mamba_num_heads=4, mamba_head_dim=8,
               ssm_state_size=16, n_groups=2, moe_intermediate_size=16,
               moe_shared_expert_intermediate_size=24, n_routed_experts=32,
               experts_held=8, vocab_size=256)
    cfg.update(over)
    return cfg


def test_the_cut_file_builds_nine_blocks_of_one_sublayer_each():
    cfg = _config("nemotron-3-nano-30b-a3b.json")
    kinds = [(causal_lm.mixer_kind(cfg, n), causal_lm.ffn_kind(cfg, n))
             for n in range(1, 10)]
    assert [m or f for m, f in kinds] == [
        "mamba2", "moe", "mamba2", "moe", "mamba2", "gqa", "moe", "mamba2",
        "moe"]
    assert all((m is None) != (f is None) for m, f in kinds)
    model = CausalLM.from_config(_tiny_nemotron())
    names = [k for k, _ in model.named_parameters()]
    for n, block in enumerate(model.layers):
        own = {k.split(".")[2] for k in names
               if k.startswith(f"layers.{n}.")}
        # one norm and one sublayer
        assert own == {"norm", "mixer" if block.mixer_kind else "ffn"}
    moe, attn, mamba = (model.layers[1].ffn, model.layers[5].mixer,
                        model.layers[0].mixer)
    assert (moe.score_func, moe.top_k, moe.renormalize, moe.scaling,
            moe.experts_gate) == ("sigmoid", 6, True, 2.5, None)
    assert tuple(moe.router.weight.shape) == (32, 32)
    assert tuple(moe.experts_up.shape) == (8, 32, 16)
    assert tuple(moe.shared.up_proj.weight.shape) == (32, 24)
    assert isinstance(moe.shared, nn.PlainFFN)
    assert (attn.num_heads, attn.num_kv_heads, attn.inv_freq, attn.q_norm,
            attn.window) == (4, 2, None, None, None)
    assert (mamba.num_heads, mamba.head_dim, mamba.groups,
            mamba.state_size) == (4, 8, 2, 16)
    # the inner width is heads x head width, not expand x hidden
    assert tuple(mamba.out_proj.weight.shape) == (32, 32)
    rotated = CausalLM.from_config(_tiny_nemotron(
        rope={"rope_type": "default", "rope_theta": 10000}))
    assert rotated.layers[5].mixer.inv_freq is not None
    dense = CausalLM.from_config(_tiny_nemotron(
        hybrid_override_pattern="M-*E", num_hidden_layers=4))
    assert isinstance(dense.layers[1].ffn, nn.PlainFFN)
    assert tuple(dense.layers[1].ffn.up_proj.weight.shape) == (32, 1856)


def test_the_tiny_nemotron_model_trains_a_step_through_its_loss():
    paddle.seed(11)
    model = CausalLM.from_config(_tiny_nemotron(), recompute=True)
    rng = np.random.RandomState(12)
    ids = paddle.to_tensor(rng.randint(0, 256, (2, 32)).astype("int32"))
    labels = paddle.to_tensor(rng.randint(0, 256, (2, 32)).astype("int32"))
    loss, routing = model.loss(ids, labels, return_routing=True)
    loss.backward()
    assert np.isfinite(float(loss)) and tuple(routing.shape) == (9, 2)
    # one row a layer, zeros where a layer has no experts
    rows = np.asarray(routing.numpy())
    assert [bool(r.any()) for r in rows] == [
        False, True, False, True, False, False, True, False, True]
    grads = {k: p.grad for k, p in model.named_parameters()}
    assert all(g is not None and np.isfinite(g.numpy()).all()
               for g in grads.values())
    for name in ("layers.0.mixer.A_log", "layers.0.mixer.dt_bias",
                 "layers.0.mixer.D", "layers.0.mixer.conv_bias"):
        assert float(np.abs(grads[name].numpy()).sum()), name


def test_router_scores_come_from_the_file_and_no_model_name_decides():
    """nemotron_h says ``norm_topk_prob`` and scores by sigmoid: its file
    says so (``moe_router_activation_func``, as the cut file does), and a
    family's name changes nothing."""
    cfg = _tiny_nemotron()
    assert "norm_topk_prob" in cfg
    assert cfg["moe_router_activation_func"] == "sigmoid"
    assert _config("nemotron-3-nano-30b-a3b.json")[
        "moe_router_activation_func"] == "sigmoid"
    for family in ("nemotron_h", "some_new_family"):
        assert CausalLM.from_config(dict(cfg, model_type=family)
                                    ).layers[1].ffn.score_func == "sigmoid"
    assert CausalLM.from_config(dict(
        cfg, moe_router_activation_func="softmax")
    ).layers[1].ffn.score_func == "softmax"
    with pytest.raises(NotImplementedError, match="SCORE_FUNCS"):
        CausalLM.from_config(dict(cfg, moe_router_activation_func="tanh"))
    with pytest.raises(NotImplementedError, match="relu2"):
        CausalLM.from_config(dict(cfg, mlp_hidden_act="gelu"))
    with pytest.raises(NotImplementedError, match="character"):
        CausalLM.from_config(_tiny_nemotron(hybrid_override_pattern="MEMXM"))


def _names_and_shapes(model):
    lines = [f"{k} {tuple(p.shape)}" for k, p in model.named_parameters()]
    return len(lines), hashlib.sha256("\n".join(lines).encode()
                                      ).hexdigest()[:16]


def test_the_kimi_and_mellum_files_build_what_they_built_before():
    """Parameter names and shapes of both accepted configurations' models
    at a small size, pinned from the commit before this one."""
    kimi = _config("kimi-linear-48b-a3b.json")
    kimi.update(hidden_size=64, intermediate_size=96,
                moe_intermediate_size=32, kv_lora_rank=32,
                qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
                num_attention_heads=4, vocab_size=512, num_experts=16,
                experts_held=4, num_experts_per_token=4,
                num_hidden_layers=3)
    kimi["linear_attn_config"] = dict(
        kimi["linear_attn_config"], num_heads=4, head_dim=16,
        kda_layers=[1, 3], full_attn_layers=[2])
    mellum = _config("mellum2-12b-a2.5b.json")
    mellum.update(hidden_size=64, head_dim=16, num_attention_heads=4,
                  num_key_value_heads=2, moe_intermediate_size=32,
                  num_experts=16, experts_held=4, num_experts_per_tok=4,
                  vocab_size=256, sliding_window=8)
    assert _names_and_shapes(CausalLM.from_config(kimi)) == PINNED["kimi"]
    assert _names_and_shapes(CausalLM.from_config(mellum)) == \
        PINNED["mellum"]
    model = CausalLM.from_config(mellum)
    assert [(b.mixer_kind, b.ffn_kind, b.ffn.score_func,
             b.ffn.experts_gate is not None) for b in model.layers] == [
        ("gqa", "moe", "softmax", True)] * 4

