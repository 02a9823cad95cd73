"""The decoder's layer kinds (nn.KimiDeltaAttention, nn.MLAttention,
nn.SparseMoELayer) and models.causal_lm.CausalLM, each against the plain
reference of benchmarks/reference/kimi_linear.py on seeded weights, and
the widened gates they need (fused xent at hidden 2304, flash attention
with a value width of its own)."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.framework.bringup as bringup
from benchmarks.reference import kimi_linear as ref
from paddle_tpu import nn
from paddle_tpu.models.causal_lm import CausalLM, ffn_kind, mixer_kind
from paddle_tpu.nn.moe import _grouped_cost, _row_ladder, sparse_moe
from paddle_tpu.ops.pallas import counters
from paddle_tpu.ops.pallas import flash_attention as fa
from paddle_tpu.ops.pallas import fused_xent as fx

CFG = {
    "hidden_size": 64, "num_attention_heads": 4, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "kv_lora_rank": 32,
    "q_lora_rank": None, "mla_use_nope": True, "rms_norm_eps": 1e-5,
    "linear_attn_config": {"kda_layers": [1, 2, 3, 5],
                           "full_attn_layers": [4], "num_heads": 4,
                           "head_dim": 16, "short_conv_kernel_size": 4},
    "first_k_dense_replace": 1, "intermediate_size": 96,
    "hidden_act": "silu", "moe_intermediate_size": 32, "num_experts": 16,
    "num_experts_per_token": 4, "num_shared_experts": 1,
    "moe_renormalize": True, "routed_scaling_factor": 2.446,
    "moe_router_activation_func": "sigmoid", "num_hidden_layers": 5,
    "vocab_size": 256, "tie_word_embeddings": False,
}


@pytest.fixture
def interp(monkeypatch):
    from jax.experimental import pallas as pl

    monkeypatch.setattr(pl, "pallas_call",
                        functools.partial(pl.pallas_call, interpret=True))
    monkeypatch.setattr(bringup, "pallas_enabled", lambda: True)
    counters.reset()
    yield
    counters.reset()


def _values(layer, seed, scale=0.2):
    """Seeded values under the layer's own parameter names (its default
    initialisers leave norms at one and biases at zero)."""
    out = {}
    for i, (name, p) in enumerate(sorted(layer.named_parameters())):
        out[name] = scale * jax.random.normal(
            jax.random.fold_in(jax.random.key(seed), i), tuple(p.shape))
        p._value = out[name]
    return out


def _rel(a, b):
    return float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-30))


def test_layer_kinds_follow_the_configs_keys():
    kinds = [(mixer_kind(CFG, n), ffn_kind(CFG, n)) for n in range(1, 6)]
    assert kinds == ref.layer_kinds(CFG) == [
        ("kda", "dense"), ("kda", "moe"), ("kda", "moe"), ("mla", "moe"),
        ("kda", "moe")]


def test_kda_layer_matches_the_reference_recurrence():
    lin = CFG["linear_attn_config"]
    layer = nn.KimiDeltaAttention(64, lin["num_heads"], lin["head_dim"])
    p = {"m." + k: v for k, v in _values(layer, 1).items()}
    x = jax.random.normal(jax.random.key(2), (2, 70, 64))
    got = layer(paddle.to_tensor(x)).value
    want = jnp.stack([ref.kda(p, "m.", row, CFG, ref._dense) for row in x])
    assert _rel(got, want) < 1e-5


def test_mla_layer_matches_the_reference():
    layer = nn.MLAttention(64, 4, 16, 8, 16, 32, epsilon=1e-5)
    p = {"m." + k: v for k, v in _values(layer, 3).items()}
    x = jax.random.normal(jax.random.key(4), (2, 64, 64))
    got = layer(paddle.to_tensor(x)).value
    want = jnp.stack([ref.mla(p, "m.", row, CFG, ref.F32_MATMULS, 32)
                      for row in x])
    assert _rel(got, want) < 1e-5
    # the rotary variant is another layer (tests/test_latent_attention_
    # rope.py); a scaled rotation is still refused
    with pytest.raises(NotImplementedError):
        nn.MLAttention(64, 4, 16, 8, 16, 32,
                       rope={"rope_theta": 1e4, "rope_type": "yarn"})


def _moe(held, offset, seed=5):
    layer = nn.SparseMoELayer(64, 32, 16, 4, experts_held=held,
                              expert_offset=offset, scaling=2.446,
                              shared_width=32)
    whole = nn.SparseMoELayer(64, 32, 16, 4, scaling=2.446, shared_width=32)
    full = _values(whole, seed)
    for name, p in layer.named_parameters():
        v = full[name]
        p._value = v[offset:offset + held] if name.startswith("experts_") \
            else v
    p = {"f." + n: q.value for n, q in layer.named_parameters()}
    return layer, p, dict(CFG, expert_offset=offset)


@pytest.mark.parametrize("skew", ["even", "one expert takes most tokens"])
def test_sparse_layer_matches_a_dense_loop_over_its_experts(skew):
    layer, p, cfg = _moe(16, 0)
    x = jax.random.normal(jax.random.key(6), (3, 40, 64))
    if skew != "even":
        # every token close to one direction: the same experts win nearly
        # everywhere, and most experts take no token at all
        x = x * 0.05 + jax.random.normal(jax.random.key(7), (64,))
    got = layer(paddle.to_tensor(x)).value
    want = ref.moe(p, "f.", x.reshape(-1, 64), cfg, ref._dense)
    assert _rel(got.reshape(-1, 64), want) < 1e-5
    pairs, rows = np.asarray(layer.last_routing.numpy())
    assert pairs == 120 * 4 and rows >= pairs          # nothing dropped
    if skew != "even":
        _, picked = jax.lax.top_k(jax.nn.sigmoid(
            x.reshape(-1, 64) @ p["f.router.weight"]), 4)
        counts = np.bincount(np.asarray(picked).ravel(), minlength=16)
        assert counts.max() >= 100 and counts.min() == 0


@pytest.mark.parametrize("shares", [2, 4])
def test_the_shares_add_up_to_the_uncut_layer(shares):
    """Routed parts of all the shares, plus the shared expert counted
    once, equal the layer that holds all 16 experts."""
    x = paddle.to_tensor(jax.random.normal(jax.random.key(8), (2, 48, 64)))
    whole, _, _ = _moe(16, 0)
    held = 16 // shares
    total, shared_part = 0.0, None
    for s in range(shares):
        layer, p, cfg = _moe(held, s * held)
        out = layer(x).value
        shared_part = layer.shared(x).value
        total = total + (out - shared_part)
        # and each share is what the reference gives for that share
        want = ref.moe(p, "f.", x.value.reshape(-1, 64), cfg, ref._dense)
        assert _rel(out.reshape(-1, 64), want) < 1e-5
    assert _rel(total + shared_part, whole(x).value) < 1e-5


def test_every_rung_of_the_row_ladder_gives_the_same_layer():
    assert _row_ladder(8192 * 8, 8, 256) == (16384, 65536)
    assert _row_ladder(64 * 4, 16, 16) == (256,)       # all held: one rung
    assert _row_ladder(600, 1, 16) == (512, 768)
    # under a dense top rung a sorted rung stays at a third of the dense
    # rows or fewer: a quarter does (the Kimi cell's), a half does not
    assert _row_ladder(8192 * 8, 8, 256, 8192 * 8) == (16384, 65536)
    assert _row_ladder(600, 1, 16, 600) == (768,)
    assert _row_ladder(800, 1, 32, 800) == (256, 1024)
    layer, p, cfg = _moe(1, 5)      # expert 5 alone, of a router of 32
    router = jnp.concatenate([p["f.router.weight"], 0.2 * jax.random.normal(
        jax.random.key(8), (64, 16))], axis=1)
    x = jax.random.normal(jax.random.key(9), (800, 64))
    args = (router, jnp.zeros((32,)), p["f.experts_gate"],
            p["f.experts_up"], p["f.experts_down"])
    routed = {k: v for k, v in p.items() if "shared" not in k}
    routed["f.router.weight"] = router
    cfg = dict(cfg, num_shared_experts=0)
    out, (pairs, rows) = sparse_moe.raw_fn(x, *args, top_k=4,
                                           expert_offset=5, scaling=2.446)
    assert rows == 256 and 0 < pairs <= rows           # the sorted rows
    assert _rel(out, ref.moe(routed, "f.", x, cfg, ref._dense)) < 1e-5
    # a correction bias that makes the held expert every token's pick:
    # the count passes the lower rung, the layer runs every pair
    bias = jnp.zeros((32,)).at[5].set(10.0)
    out, (pairs, rows) = sparse_moe.raw_fn(
        x, args[0], bias, *args[2:], top_k=4, expert_offset=5,
        scaling=2.446)
    assert rows == 1024 and pairs == 800
    want = ref.moe(routed, "f.", x, cfg, ref._dense, router_bias=bias)
    assert _rel(out, want) < 1e-5


def test_the_kernels_ladders_follow_what_their_rungs_cost():
    """At the grouped kernels' costs (``_grouped_cost``: a launched row's
    products are a dense row's, its three gathers cost by the ROW, the
    way back is three passes of a one-hot product, and the sort walks
    every slot) the five MoE cells' shares under their dense rung: ONE
    grouped rung at eight times the even share for the wide experts
    (LFM2's is every pair), and none for the narrow ones: the Kanana
    share's rung would cost 139 thousand dense rows where the dense rung
    has 131,072, and the Mellum share's every pair is half its dense
    rows."""
    for tokens, d, f, top_k, held, experts, gated, rungs in (
            (8192, 2304, 1024, 8, 8, 256, True, (16384,)),        # Kimi
            (16384, 2688, 1856, 6, 8, 128, False, (49152,)),      # Nemotron
            (16384, 2048, 768, 6, 8, 128, True, ()),              # Kanana
            (16384, 2048, 1536, 4, 8, 64, True, (65536,)),        # LFM2
            (16384, 2304, 896, 8, 16, 64, True, ())):             # Mellum
        pairs = tokens * min(top_k, held)
        row, rung = _grouped_cost(tokens, top_k, held, d, f, gated)
        assert _row_ladder(pairs, held, experts, tokens * held, row,
                           rung_cost=rung)[:-1] == rungs
    # a launched row costs a dense row and a half at Nemotron's experts
    # and two at Kanana's; beside its rows a Nemotron rung costs what
    # 23 thousand dense rows do (8 tiles, 98,304 slots sorted, 512 visits
    # of the way back): 99 thousand in all, as the cell measured (102)
    row, rung = _grouped_cost(16384, 6, 8, 2688, 1856, False)
    assert 1.5 < row < 1.6 and 22000 < rung < 24000
    assert 98000 < row * 49152 + rung < 100000
    row, rung = _grouped_cost(16384, 6, 8, 2048, 768, True)
    assert 2.0 < row < 2.1 and 131072 < row * 49152 + rung < 142000
    # under a dense top a rung of every pair stays where it is cheaper
    assert _row_ladder(131072, 16, 64, 262144, row_cost=2.0,
                       skew=4) == (131072, 131072)
    assert _row_ladder(131072, 16, 64, 262144, row_cost=2.5,
                       skew=4) == (131072,)
    assert _row_ladder(131072, 16, 64, 262144, row_cost=1.5, skew=4,
                       rung_cost=70000) == (131072,)


def test_causal_lm_loss_and_gradients_match_the_reference():
    cfg = dict(CFG, experts_held=4, expert_offset=4)
    model = CausalLM.from_config(cfg)
    params = _values(model, 10, scale=0.05)
    for name in params:
        if "norm" in name:
            params[name] = 1.0 + params[name]
    model.load_param_pytree(params)
    rng = np.random.default_rng(0)
    ids = rng.integers(0, 256, (2, 64)).astype("int32")
    labels = np.full_like(ids, -100)
    labels[:, :-1] = ids[:, 1:]

    def program(p, recompute):
        model.recompute = recompute
        saved = {n: q._value for n, q in model.named_parameters()}
        model.load_param_pytree(p)
        try:
            return model.loss(paddle.to_tensor(ids),
                              paddle.to_tensor(labels)).value
        finally:
            model.load_param_pytree(saved)

    want, grads = jax.jit(lambda p: jax.value_and_grad(ref.loss)(
        p, cfg, ids, labels, ref.F32_MATMULS, 32))(params)
    floor = float(np.median([float(jnp.linalg.norm(g))
                             for g in grads.values()]))
    got, pgrads = jax.jit(jax.value_and_grad(
        lambda p: program(p, True)))(params)
    assert float(got) == pytest.approx(float(want), rel=2e-6)
    for name, g in grads.items():
        err = float(jnp.linalg.norm(pgrads[name] - g))
        assert err <= 1e-4 * max(float(jnp.linalg.norm(g)), floor), name
    # without recomputation: the same loss; and the logits' shape
    assert float(jax.jit(lambda p: program(p, False))(params)) == \
        pytest.approx(float(want), rel=2e-6)
    model.recompute = False
    assert tuple(model(paddle.to_tensor(ids)).shape) == (2, 64, 256)


def test_fused_xent_gate_follows_what_its_blocks_can_hold(interp):
    assert fx._pick_blocks(256, 2304, 512) == (256, 256)
    assert fx._eligible(8192, 2304, 20480)
    assert not fx._eligible(256, 8192, 512)        # no block pair fits
    rng = np.random.RandomState(0)
    h = jnp.asarray(rng.randn(256, 2304) * 0.05, jnp.float32)
    w = jnp.asarray(rng.randn(512, 2304) * 0.05, jnp.float32)
    lab = jnp.asarray(rng.randint(0, 512, 256), jnp.int32).at[-1].set(-100)
    got = fx.fused_linear_cross_entropy(h, w, jnp.zeros((512,)), lab)
    assert counters.snapshot()["fused_xent.pallas"] == 1
    logp = jax.nn.log_softmax(h @ w.T, axis=-1)
    want = -jnp.sum(jnp.where(
        lab != -100,
        jnp.take_along_axis(logp, jnp.maximum(lab, 0)[:, None], 1)[:, 0],
        0.0)) / 255
    assert float(got) == pytest.approx(float(want), rel=2e-5)


def test_stream_flash_takes_a_value_width_of_its_own(interp):
    ks = jax.random.split(jax.random.key(11), 4)
    q, k = (jax.random.normal(s, (1, 256, 2, 192)) for s in ks[:2])
    v = jax.random.normal(ks[2], (1, 256, 2, 128))
    w = jax.random.normal(ks[3], (1, 256, 2, 128))
    assert fa._pallas_ok(q, k, True, v=v)
    assert not fa._pallas_ok(q, k, True, v=jnp.zeros((1, 256, 2, 96)))

    def run(f):
        return jax.value_and_grad(
            lambda q, k, v: jnp.sum(f(q, k, v, True) * w),
            argnums=(0, 1, 2))(q, k, v)

    (lk, gk) = run(fa._local_attention)
    assert counters.snapshot()["flash_attention.pallas"] >= 1
    (lx, gx) = run(lambda q, k, v, c: fa._xla_attention(q, k, v, None, 0.0,
                                                        c, None))
    assert float(lk) == pytest.approx(float(lx), rel=1e-4)
    for a, b in zip(gk, gx):
        assert _rel(a, b) < 1e-4
    with counters.capture("s"), counters.differentiated():
        fa._local_attention(q, k, v, True)
    work = counters.step_work("s")["flash_attention_stream_fwd"]
    assert work["flops"] == 4.0 * 0.5 * 2 * 256 * 256 * (192 + 128) / 2
