"""Fused linear+cross-entropy kernel (ops/pallas/fused_xent.py, the
bert512 MFU item — VERDICT r4 #2): interpret-mode numerics vs the
materialised-logits reference, gradients through the custom_vjp, the
ignore_index/padding contract, dispatch truth, and the BERT loss A/B.
Real Mosaic lowering is exercised by tests/test_fused_xent_tpu.py in
the live session."""
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu.framework.bringup as bringup
from paddle_tpu.ops.pallas import counters
from paddle_tpu.ops.pallas import fused_xent as fx

N, H, V = 512, 128, 1024


@pytest.fixture
def interp(monkeypatch):
    from jax.experimental import pallas as pl

    monkeypatch.setattr(pl, "pallas_call",
                        functools.partial(pl.pallas_call, interpret=True))
    monkeypatch.setattr(bringup, "pallas_enabled", lambda: True)
    counters.reset()
    # float32 operands stay float32 into the kernels' products only above
    # the default matmul precision (fused_xent._mxu_dtype): the tests
    # that hold the kernels to a float32 reference ask for it, as
    # chip_smoke.py does; test_tile_body_* below covers the default
    with jax.default_matmul_precision("highest"):
        yield
    counters.reset()


def _data(n=N, h=H, v=V, seed=0, ignore_frac=0.3):
    rng = np.random.RandomState(seed)
    hmat = jnp.asarray(rng.randn(n, h) * 0.2, jnp.float32)
    w = jnp.asarray(rng.randn(v, h) * 0.2, jnp.float32)
    b = jnp.asarray(rng.randn(v) * 0.1, jnp.float32)
    lab = rng.randint(0, v, n)
    lab[rng.rand(n) < ignore_frac] = -100
    return hmat, w, b, jnp.asarray(lab, jnp.int32)


def _ref_loss(h, w, b, lab, ignore_index=-100):
    logits = h @ w.T + b
    valid = lab != ignore_index
    safe = jnp.where(valid, lab, 0)
    logp = jax.nn.log_softmax(logits, axis=-1)
    ll = jnp.take_along_axis(logp, safe[:, None], axis=1)[:, 0]
    cnt = jnp.maximum(jnp.sum(valid.astype(jnp.float32)), 1.0)
    return jnp.sum(jnp.where(valid, -ll, 0.0)) / cnt


def test_forward_matches_reference(interp):
    h, w, b, lab = _data()
    out = fx.fused_linear_cross_entropy(h, w, b, lab)
    assert counters.snapshot().get("fused_xent.pallas", 0) == 1
    ref = _ref_loss(h, w, b, lab)
    np.testing.assert_allclose(float(out), float(ref), rtol=2e-5)


def test_all_ignored_is_finite(interp):
    h, w, b, _ = _data()
    lab = jnp.full((N,), -100, jnp.int32)
    out = fx.fused_linear_cross_entropy(h, w, b, lab)
    assert float(out) == 0.0


@pytest.mark.slow
def test_grads_match_reference(interp):
    h, w, b, lab = _data(seed=1)

    g_f = jax.grad(
        lambda *a: fx.fused_linear_cross_entropy(*a, lab) * 3.0,
        argnums=(0, 1, 2))(h, w, b)
    assert counters.snapshot().get("fused_xent.pallas", 0) >= 1
    g_r = jax.grad(lambda *a: _ref_loss(*a, lab) * 3.0,
                   argnums=(0, 1, 2))(h, w, b)
    for a, r, tol in zip(g_f, g_r, (2e-5, 2e-5, 2e-5)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                   rtol=1e-4, atol=tol)


@pytest.mark.slow
def test_row_padding_path(interp):
    """Row counts off the block modulus are padded with ignored labels
    — same loss, same grads for the real rows."""
    n = 300   # not a multiple of 256
    h, w, b, lab = _data(n=n, seed=2)
    out = fx.fused_linear_cross_entropy(h, w, b, lab)
    assert counters.snapshot().get("fused_xent.pallas", 0) == 1
    ref = _ref_loss(h, w, b, lab)
    np.testing.assert_allclose(float(out), float(ref), rtol=2e-5)
    gh = jax.grad(lambda x: fx.fused_linear_cross_entropy(
        x, w, b, lab))(h)
    gr = jax.grad(lambda x: _ref_loss(x, w, b, lab))(h)
    np.testing.assert_allclose(np.asarray(gh), np.asarray(gr),
                               rtol=1e-4, atol=2e-5)


@pytest.mark.slow
def test_vocab_128_modulus_dispatches(interp):
    """BERT's real vocab (30592 = 128*239) only admits 128-wide blocks
    — the divisor-pick must keep such vocabs on the kernel (the r5
    review caught a %512 gate silently rejecting the target workload)."""
    h, w, b, lab = _data(v=640, seed=7)    # 640 = 128*5, not %512/%256
    out = fx.fused_linear_cross_entropy(h, w, b, lab)
    assert counters.snapshot().get("fused_xent.pallas", 0) == 1
    np.testing.assert_allclose(float(out), float(_ref_loss(h, w, b, lab)),
                               rtol=2e-5)
    gh, gw, gb = jax.grad(
        lambda *a: fx.fused_linear_cross_entropy(*a, lab),
        argnums=(0, 1, 2))(h, w, b)
    gr = jax.grad(lambda *a: _ref_loss(*a, lab), argnums=(0, 1, 2))(h, w,
                                                                    b)
    for a, r in zip((gh, gw, gb), gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                   rtol=1e-4, atol=2e-5)


@pytest.mark.slow
def test_bf16_grads_accumulate_in_f32(interp):
    """bf16 inputs must not accumulate partial grads in bf16 across
    grid steps (f32 accumulator refs, single cast at the end)."""
    h, w, b, lab = _data(seed=8)
    h16, w16 = h.astype(jnp.bfloat16), w.astype(jnp.bfloat16)
    gh, gw, _ = jax.grad(
        lambda *a: fx.fused_linear_cross_entropy(*a, lab),
        argnums=(0, 1, 2))(h16, w16, b)
    assert gh.dtype == jnp.bfloat16 and gw.dtype == jnp.bfloat16
    gr = jax.grad(
        lambda hh, ww: _ref_loss(hh.astype(jnp.float32),
                                 ww.astype(jnp.float32), b, lab),
        argnums=(0, 1))(h16, w16)
    np.testing.assert_allclose(np.asarray(gh, jnp.float32),
                               np.asarray(gr[0], jnp.float32),
                               rtol=2e-2, atol=2e-3)
    np.testing.assert_allclose(np.asarray(gw, jnp.float32),
                               np.asarray(gr[1], jnp.float32),
                               rtol=2e-2, atol=2e-3)


# -- the grid step's body: lane-wise statistics folded over a tile's lane
# groups, per-row vectors as columns from scratch, dW on the transposed
# tile. Two row blocks of 256 over 8 (block_v 128) or 2 (512) vocabulary
# steps, the three arithmetics a caller can reach -------------------------
TN, TBN = 512, 256


def _bf16_round(x):
    return x.astype(jnp.bfloat16).astype(jnp.float32)


def _tile_case(case):
    """(h, w, b, labels) for one corner of the tile body; labels are the
    kernels' own (-1 where a row rides along unlabelled)."""
    h, w, b, lab = _data(n=TN, seed=11, ignore_frac=0.3)
    lab = np.array(jnp.where(lab < 0, -1, lab))
    if case == "max_in_last_lane_of_last_block":
        b = b.at[V - 1].set(25.0)
    elif case == "row_block_all_ignored":
        lab[:TBN] = -1
    elif case == "labels_in_first_and_last_column":
        lab[0::2], lab[1::2] = 0, V - 1
    elif case.startswith("lane_column_far_below"):
        # one lane column (every 128th logit) towers over the others by
        # 60 (a small weight in the finalize) or 120 (exp underflows to 0)
        b = b.at[3::128].add(float(case.rsplit("_", 1)[1]))
    return h, w, b, jnp.asarray(lab, jnp.int32)


@pytest.mark.parametrize("case", [
    "random", "max_in_last_lane_of_last_block", "row_block_all_ignored",
    "labels_in_first_and_last_column", "lane_column_far_below_60",
    "lane_column_far_below_120"])
@pytest.mark.parametrize("block_v", [128, 512])
@pytest.mark.parametrize("arith", ["float32", "float32_one_pass",
                                   "bfloat16"])
def test_tile_body_matches_the_logits_path(interp, case, block_v, arith):
    """loss, lse, the label logit, dh, dW and db of the three kernels
    against materialised logits. ``float32``: float32 operands under
    `highest`; ``float32_one_pass``: float32 inputs at the default
    precision, which the kernels round to bfloat16 once before the call
    as the MXU would on every step; ``bfloat16``: bfloat16 inputs. The
    reference sees the operands as the product does."""
    h, w, b, lab = _tile_case(case)
    narrow = arith != "float32"
    if arith == "bfloat16":
        h, w = h.astype(jnp.bfloat16), w.astype(jnp.bfloat16)
    g = jnp.where(lab >= 0, 0.37, 0.0)
    with jax.default_matmul_precision("highest" if arith == "float32"
                                      else "default"):
        lse, ll = fx._fwd_call(h, w, b, lab, TBN, block_v)
        dh, dw, db = fx._bwd_call(h, w, b, lab, lse, g, TBN, block_v)
    assert dh.dtype == h.dtype and dw.dtype == w.dtype

    hr, wr = (_bf16_round(x) if narrow else x for x in (h, w))
    with jax.default_matmul_precision("highest"):
        logits = hr @ wr.T + b
        hit = jnp.arange(V)[None, :] == lab[:, None]
        p = (jnp.exp(logits - lse[:, None]) - hit) * g[:, None]
        want = (jax.scipy.special.logsumexp(logits, axis=-1),
                jnp.sum(jnp.where(hit, logits, 0.0), axis=1),
                p @ wr, p.T @ hr, jnp.sum(p, axis=0))
    got = (lse, ll, dh, dw, db)
    assert all(bool(jnp.all(jnp.isfinite(x.astype(jnp.float32))))
               for x in got)
    # the statistics are float32 whatever the operands; a gradient's
    # probabilities are rounded to the operands' type before its product
    tols = (2e-6, 2e-6) + ((1e-2,) * 2 + (2e-6,) if narrow else (2e-6,) * 3)
    for name, a, r, tol in zip(("lse", "ll", "dh", "dw", "db"), got, want,
                               tols):
        a, r = np.asarray(a, np.float32), np.asarray(r, np.float32)
        scale = max(float(np.max(np.abs(r))), 1e-30)
        assert float(np.max(np.abs(a - r))) <= tol * scale, (name, case)
    valid = lab >= 0
    loss = jnp.sum(jnp.where(valid, lse - ll, 0.0))
    np.testing.assert_allclose(
        float(loss), float(jnp.sum(jnp.where(valid, want[0] - want[1], 0))),
        rtol=2e-6)


# -- the row-capacity ladder: 2048 rows in blocks of 256 may run at 256,
# 512, 1024 or all 2048 rows --------------------------------------------
LN, LBN, LK = 2048, 256, 512
RUNGS = (256, 512, 1024, 2048)


@pytest.fixture
def ladder(interp, monkeypatch):
    monkeypatch.setattr(fx, "_BN_CANDIDATES", (LBN,))
    assert fx._ladder(LN, LBN) == RUNGS
    assert fx._ladder(LBN, LBN) == (LBN,)     # one block: today's path


def _labelled(count, where, seed):
    """(h, w, b, labels) with exactly ``count`` labelled rows of LN."""
    h, w, b, lab = _data(n=LN, v=512, seed=seed, ignore_frac=0.0)
    rng = np.random.RandomState(seed + 100)
    keep = (rng.permutation(LN)[:count] if where == "scattered"
            else np.arange(LN - count, LN))
    mask = np.zeros(LN, bool)
    mask[keep] = True
    return h, w, b, jnp.where(jnp.asarray(mask), lab, -100)


@pytest.mark.parametrize("where", ["scattered", "bunched_at_end"])
@pytest.mark.parametrize("count", [0, 1, LK - 1, LK, LK + 1, LN])
def test_ladder_matches_reference(ladder, count, where):
    """Whatever rung the label count lands on — empty, one row, either
    side of a capacity, the full rows — loss and gradients are the
    reference's, and an unlabelled row's dh is exactly zero."""
    h, w, b, lab = _labelled(count, where, seed=count % 7)
    loss, (gh, gw, gb) = jax.jit(jax.value_and_grad(
        lambda *a: fx.fused_linear_cross_entropy(*a, lab),
        argnums=(0, 1, 2)))(h, w, b)
    snap = counters.snapshot()
    assert snap["fused_xent.pallas"] == 1 and snap["fused_xent.ladder"] == 1
    ref, g_r = jax.value_and_grad(
        lambda *a: _ref_loss(*a, lab), argnums=(0, 1, 2))(h, w, b)
    np.testing.assert_allclose(float(loss), float(ref), rtol=2e-5)
    for a, r in zip((gh, gw, gb), g_r):
        np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                   rtol=1e-4, atol=2e-5)
    assert not np.asarray(gh)[np.asarray(lab) == -100].any()
    # the smallest capacity that holds the count is the one that ran
    rung = jax.jit(lambda: fx._fused_xent_sums_fwd(
        h, w, b, lab, -100, RUNGS)[1][-1])()
    assert RUNGS[int(rung)] == min(k for k in RUNGS if k >= count)


def test_full_labels_run_todays_kernels_under_todays_names(ladder):
    """A feed whose labels fill the rows (causal LM) lands on the top
    rung: the kernels on all rows, under ``fused_xent_fwd`` / ``_bwd``;
    every lower rung's kernels have names of their own."""
    h, w, b, lab = _labelled(LN, "scattered", seed=3)
    text = jax.jit(jax.grad(
        lambda *a: fx.fused_linear_cross_entropy(*a, lab),
        argnums=(0, 1, 2))).lower(h, w, b).as_text(debug_info=True)
    for role in ["fused_xent_fwd", "fused_xent_bwd"] + [
            f"fused_xent_rows{k}_{d}" for k in RUNGS[:-1]
            for d in ("fwd", "bwd")]:
        assert f"pallas/{role}/pallas_call" in text, role


def test_ineligible_vocab_falls_back(interp):
    h, w, b, lab = _data(v=100, seed=3)   # 100 % 512 != 0
    out = fx.fused_linear_cross_entropy(h, w, b, lab)
    snap = counters.snapshot()
    assert snap.get("fused_xent.pallas", 0) == 0
    assert snap.get("fused_xent.xla", 0) == 1
    np.testing.assert_allclose(float(out), float(_ref_loss(h, w, b, lab)),
                               rtol=2e-5)


@pytest.mark.slow
def test_nmt_loss_flag_ab(interp):
    """The Transformer NMT head (Linear (H, V)) routes through the
    fused kernel too — flag on/off must agree."""
    import paddle_tpu as paddle
    from paddle_tpu.framework.flags import set_flags
    from paddle_tpu.models.transformer import TransformerNMT

    paddle.seed(0)
    m = TransformerNMT(src_vocab_size=512, tgt_vocab_size=512,
                       d_model=128, nhead=4, num_encoder_layers=1,
                       num_decoder_layers=1, dim_feedforward=128,
                       dropout=0.0)
    rng = np.random.RandomState(0)
    src = paddle.to_tensor(rng.randint(1, 512, (2, 16)).astype(np.int64))
    tin = paddle.to_tensor(rng.randint(1, 512, (2, 16)).astype(np.int64))
    tout = paddle.to_tensor(rng.randint(0, 512, (2, 16)).astype(np.int64))

    counters.reset()
    fused = float(m.loss(src, tin, tout).numpy())
    assert counters.snapshot().get("fused_xent.pallas", 0) == 1
    set_flags({"fused_vocab_xent": False})
    try:
        unfused = float(m.loss(src, tin, tout).numpy())
    finally:
        set_flags({"fused_vocab_xent": True})
    np.testing.assert_allclose(fused, unfused, rtol=5e-5)


@pytest.mark.slow
def test_bert_loss_flag_ab(interp):
    """FLAGS_fused_vocab_xent on/off agree on the BERT pretraining loss
    — the exact A/B the live session times."""
    import paddle_tpu as paddle
    from paddle_tpu.framework.flags import set_flags
    from paddle_tpu.models.bert import BertConfig, BertForPretraining

    cfg = BertConfig.tiny()          # vocab 1024 (512-modulus ok)
    cfg.num_hidden_layers = 2
    cfg.hidden_dropout_prob = 0.0
    cfg.attention_probs_dropout_prob = 0.0
    paddle.seed(0)
    m = BertForPretraining(cfg)
    rng = np.random.RandomState(0)
    ids = paddle.to_tensor(
        rng.randint(0, cfg.vocab_size, (2, 64)).astype(np.int32))
    tt = paddle.to_tensor(np.zeros((2, 64), np.int32))
    mlm = rng.randint(0, cfg.vocab_size, (2, 64))
    mlm[rng.rand(2, 64) < 0.8] = -100     # MLM masks ~20% of positions
    mlm_t = paddle.to_tensor(mlm.astype(np.int32))
    nsp = paddle.to_tensor(rng.randint(0, 2, (2,)).astype(np.int32))

    counters.reset()
    fused = float(m.loss(ids, tt, mlm_t, nsp).numpy())
    assert counters.snapshot().get("fused_xent.pallas", 0) == 1
    set_flags({"fused_vocab_xent": False})
    try:
        unfused = float(m.loss(ids, tt, mlm_t, nsp).numpy())
    finally:
        set_flags({"fused_vocab_xent": True})
    np.testing.assert_allclose(fused, unfused, rtol=5e-5)


@pytest.mark.slow
def test_multi_device_trainstep_gates_fused_path(interp):
    """Under a >1-device TrainStep trace the fused kernel self-gates
    (pjit cannot partition the opaque pallas call); the XLA path keeps
    the training step correct — and a mesh-free step keeps the kernel."""
    import paddle_tpu as paddle
    from jax.sharding import PartitionSpec

    from paddle_tpu import optimizer
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.models.bert import BertConfig, BertForPretraining
    from paddle_tpu.parallel import create_mesh

    cfg = BertConfig.tiny()
    cfg.num_hidden_layers = 1
    cfg.hidden_dropout_prob = 0.0
    cfg.attention_probs_dropout_prob = 0.0
    rng = np.random.RandomState(0)
    ids = paddle.to_tensor(
        rng.randint(0, cfg.vocab_size, (8, 32)).astype(np.int32))
    tt = paddle.to_tensor(np.zeros((8, 32), np.int32))
    mlm = paddle.to_tensor(
        rng.randint(0, cfg.vocab_size, (8, 32)).astype(np.int32))
    nsp = paddle.to_tensor(rng.randint(0, 2, (8,)).astype(np.int32))

    def loss_fn(m, *b):
        return m.loss(*b)

    def build(mesh):
        paddle.seed(0)
        m = BertForPretraining(cfg)
        opt = optimizer.AdamW(learning_rate=1e-4,
                              parameters=m.parameters())
        if mesh is None:
            return TrainStep(m, loss_fn, opt)
        return TrainStep(m, loss_fn, opt, mesh=mesh,
                         data_spec=PartitionSpec("dp"))

    counters.reset()
    mesh = create_mesh({"dp": 8})
    loss_dp = float(build(mesh)(ids, tt, mlm, nsp).numpy())
    snap = counters.snapshot()
    assert snap.get("fused_xent.pallas", 0) == 0, snap
    assert snap.get("fused_xent.xla", 0) >= 1, snap

    counters.reset()
    loss_single = float(build(None)(ids, tt, mlm, nsp).numpy())
    assert counters.snapshot().get("fused_xent.pallas", 0) >= 1
    np.testing.assert_allclose(loss_dp, loss_single, rtol=1e-4)


@pytest.mark.slow
def test_multi_device_trainstep_shards_fused_path(interp, monkeypatch):
    """When the batch rows DO divide into kernel-eligible shards, the
    multi-device TrainStep keeps the fused kernel via shard_map + psum
    (fused_xent.pallas_sharded) and matches the single-device loss."""
    import paddle_tpu as paddle
    import paddle_tpu.parallel.ring as ring_mod
    from jax.sharding import PartitionSpec

    from paddle_tpu import optimizer
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.models.bert import BertConfig, BertForPretraining
    from paddle_tpu.parallel import create_mesh
    from paddle_tpu.parallel.mesh import _global_mesh

    monkeypatch.setattr(ring_mod, "_SHARD_MAP_CHECK_VMA", [False])
    cfg = BertConfig.tiny()
    cfg.num_hidden_layers = 1
    cfg.hidden_dropout_prob = 0.0
    cfg.attention_probs_dropout_prob = 0.0
    rng = np.random.RandomState(0)
    B, S = 8, 128                     # n=1024; dp2 -> 512 local rows
    ids = paddle.to_tensor(
        rng.randint(0, cfg.vocab_size, (B, S)).astype(np.int32))
    tt = paddle.to_tensor(np.zeros((B, S), np.int32))
    mlm = paddle.to_tensor(
        rng.randint(0, cfg.vocab_size, (B, S)).astype(np.int32))
    nsp = paddle.to_tensor(rng.randint(0, 2, (B,)).astype(np.int32))

    def loss_fn(m, *b):
        return m.loss(*b)

    def build(mesh):
        paddle.seed(0)
        m = BertForPretraining(cfg)
        opt = optimizer.AdamW(learning_rate=1e-4,
                              parameters=m.parameters())
        if mesh is None:
            return TrainStep(m, loss_fn, opt)
        return TrainStep(m, loss_fn, opt, mesh=mesh,
                         data_spec=PartitionSpec("dp"))

    prev = _global_mesh[0]
    try:
        counters.reset()
        mesh = create_mesh({"dp": 2}, devices=jax.devices()[:2])
        loss_dp = float(build(mesh)(ids, tt, mlm, nsp).numpy())
        snap = counters.snapshot()
        assert snap.get("fused_xent.pallas_sharded", 0) >= 1, snap
    finally:
        _global_mesh[0] = prev

    counters.reset()
    loss_single = float(build(None)(ids, tt, mlm, nsp).numpy())
    assert counters.snapshot().get("fused_xent.pallas", 0) >= 1
    np.testing.assert_allclose(loss_dp, loss_single, rtol=1e-4)


def test_sharded_wrapper_under_check_vma(monkeypatch):
    """The shard_map wrapper with check_vma ON (what a real chip runs —
    the interpret-mode tests above switch it off): W and bias enter
    replicated over the row axes, each shard's dW/db is a partial sum,
    and the custom_vjp must type-check AND add the partials up. The two
    pallas_call plumbing functions are stood in by jnp math so the
    wrapper itself is what runs."""
    from jax.sharding import Mesh

    from paddle_tpu.ops.pallas import fused_xent as fx

    def fwd_call(h, w, bias, labels, bn, bv):
        logits = h @ w.T + bias
        hit = jnp.arange(w.shape[0])[None, :] == labels[:, None]
        return (jax.scipy.special.logsumexp(logits, axis=-1),
                jnp.sum(jnp.where(hit, logits, 0.0), axis=1))

    def bwd_call(h, w, bias, labels, lse, g, bn, bv):
        hit = jnp.arange(w.shape[0])[None, :] == labels[:, None]
        p = (jnp.exp(h @ w.T + bias - lse[:, None]) - hit) * g[:, None]
        return p @ w, p.T @ h, jnp.sum(p, 0)

    monkeypatch.setattr(fx, "_fwd_call", fwd_call)
    monkeypatch.setattr(fx, "_bwd_call", bwd_call)
    n, hd, v = 1024, 128, 256
    h = jax.random.normal(jax.random.key(0), (n, hd)) * 0.3
    w = jax.random.normal(jax.random.key(1), (v, hd)) * 0.3
    b = jax.random.normal(jax.random.key(2), (v,)) * 0.1
    lab = jax.random.randint(jax.random.key(3), (n,), 0, v)
    lab = lab.at[::5].set(-100)

    def ref(h, w, b):
        logits = h @ w.T + b
        valid = lab != -100
        lse = jax.scipy.special.logsumexp(logits, -1)
        ll = jnp.take_along_axis(
            logits, jnp.where(valid, lab, 0)[:, None], 1)[:, 0]
        return jnp.sum(jnp.where(valid, lse - ll, 0)) / jnp.sum(valid)

    want = jax.value_and_grad(ref, argnums=(0, 1, 2))(h, w, b)
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("dp", "tp"))
    for axes in (("dp",), ("dp", "tp")):
        got = jax.jit(lambda h, w, b: jax.value_and_grad(
            lambda h, w, b: fx._sharded_fused(h, w, b, lab, mesh, axes,
                                              -100),
            argnums=(0, 1, 2))(h, w, b))(h, w, b)
        np.testing.assert_allclose(got[0], want[0], rtol=1e-5)
        for g, r in zip(got[1], want[1]):
            np.testing.assert_allclose(g, r, rtol=1e-4, atol=1e-6)
        # a loss a row (PR 41): each shard hands back its own rows' and
        # takes their cotangents; weighed by 1 / count it is the mean
        rows, grads = jax.jit(lambda h, w, b: jax.value_and_grad(
            lambda h, w, b: (lambda r: (jnp.sum(r) / jnp.sum(lab != -100),
                                        r))(fx._sharded_fused(
                                            h, w, b, lab, mesh, axes, -100,
                                            per_row=True)),
            argnums=(0, 1, 2), has_aux=True)(h, w, b))(h, w, b)
        np.testing.assert_allclose(rows[0], want[0], rtol=1e-5)
        assert rows[1].shape == (n,)
        assert float(jnp.abs(rows[1][::5]).max()) == 0.0
        for g, r in zip(grads, want[1]):
            np.testing.assert_allclose(g, r, rtol=1e-4, atol=1e-6)
