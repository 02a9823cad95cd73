"""Plain reference for a Mellum-2 style causal LM (grouped-query
attention with rotary positions, sliding-window and full layers, a
softmax-routed dropless expert layer): loss, gradients and AdamW steps in
straightforward ``jax.numpy`` float32 at "highest" matmul precision. No
kernels, no autocast; imports nothing of the program. Written from the
layer's equations (ISSUE 31), for layer ``n`` of kind ``layer_types[n]``
on one row ``x`` (T, hidden):

    a  = RMSNorm(x)
    q  = a Wq -> (T, H, D);  k = a Wk -> (T, Hkv, D);  v = a Wv -> (T, Hkv, D)
    q, k = RMSNorm_D(q), RMSNorm_D(k)          per head, own scales (qk_norm)
    q, k = rope_kind(q, pos), rope_kind(k, pos)        pos = 0 .. T-1
    s_ij = q_i . k_j / sqrt(D)          query head h reads kv head h // (H/Hkv)
    allowed(i, j):  j <= i                             full_attention
                    0 <= i - j < sliding_window        sliding_attention
    x  = x + concat_heads(softmax_j(s_ij over allowed) v_j) Wo
    b  = RMSNorm(x)
    p  = softmax(b Wr) over all experts;  S = top-k of p
    w_e = p_e / sum_{e' in S} p_e'                     (norm_topk_prob)
    x  = x + sum_{e in S, e held} w_e (silu(b G_e) * (b U_e)) D_e

- **rope**, the rotate_half convention: channel i pairs with i + D/2;
  ``out = x cos + rotate_half(x) sin`` with the angles ``pos * inv_freq``
  repeated over both halves. ``default``: ``inv_freq_i = theta ** (-2i /
  D)``. ``yarn`` (Hugging Face ``_compute_yarn_parameters``): ``interp =
  1 / (factor theta^(2i/D))``, ``extrap = 1 / theta^(2i/D)``, ``low =
  floor(c(beta_fast))``, ``high = ceil(c(beta_slow))`` with ``c(r) = D
  ln(P / (2 pi r)) / (2 ln theta)`` clamped to [0, D - 1], ``ramp_i =
  clip((i - low) / (high - low), 0, 1)``, ``inv_freq = interp ramp +
  extrap (1 - ramp)``; cos and sin times ``attention_factor``.
- **attention** by an explicit (rows, T) mask, a block of query rows at
  a time, the key heads repeated to the query heads.
- **experts** by a dense loop over the experts the share is GIVEN
  (``experts_held`` from ``expert_offset``); what absent experts would
  add is left out. The pick passes no gradient.
- loss: mean next-token cross-entropy over the labelled positions, a
  block of rows at a time.

Parameters are a dict under the program's parameter names
(``drivers/gqa_lm_step.param_shapes``); the benchmark makes them from the
seed and hands the same values to both sides. ``matmuls`` swaps the dense
and batched matrix products for the lower-precision control
(:func:`fp8_matmuls`); the router, the norms and the rotation stay
float32 there, as they do in the program under autocast.
"""
from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

# the float32 pair of products, and fp8_matmuls for the control, which
# looks it up here by name; the AdamW step and the norms are Kimi's
from benchmarks.reference.bert import (  # noqa: F401
    F32_MATMULS, _dense, fp8_matmuls, leaf_norms)
from benchmarks.reference.kimi_linear import (
    _adamw, _change_norms, _gated, _rms_norm)


def layer_kinds(cfg: dict) -> list:
    """[(attention kind, ffn kind)] per layer from ``layer_types`` and
    ``mlp_layer_types``."""
    return list(zip(cfg["layer_types"], cfg["mlp_layer_types"]))


# ---------------------------------------------------------------------------
# rotary positions
# ---------------------------------------------------------------------------
def rope_inv_freq(head_dim: int, rope: dict) -> tuple:
    """(inv_freq float64 (D/2,), scale of cos and sin) of one
    ``rope_parameters`` entry."""
    i = np.arange(head_dim // 2, dtype=np.float64)
    extrap = 1.0 / rope["rope_theta"] ** (2.0 * i / head_dim)
    if rope.get("rope_type", "default") == "default":
        return extrap, 1.0
    if rope["rope_type"] != "yarn":
        raise ValueError(f"rope_type {rope['rope_type']!r}")
    interp = extrap / rope["factor"]

    def c(turns):
        return head_dim * math.log(
            rope["original_max_position_embeddings"]
            / (2 * math.pi * turns)) / (2 * math.log(rope["rope_theta"]))

    low = max(math.floor(c(rope["beta_fast"])), 0)
    high = min(math.ceil(c(rope["beta_slow"])), head_dim - 1)
    ramp = np.clip((i - low) / (high - low), 0.0, 1.0)
    return interp * ramp + extrap * (1.0 - ramp), rope["attention_factor"]


def rope(x, inv_freq, scale):
    """x (T, heads, D) at positions 0 .. T-1."""
    t, half = x.shape[0], x.shape[-1] // 2
    angles = jnp.arange(t, dtype=jnp.float32)[:, None] \
        * jnp.asarray(inv_freq, jnp.float32)[None, :]
    angles = jnp.concatenate([angles, angles], axis=-1)[:, None, :]
    rotated = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * (jnp.cos(angles) * scale) + rotated * (jnp.sin(angles) * scale)


def allowed(kind: str, window: int, rows, t: int):
    """(len(rows), T) bool: may query ``rows[i]`` read key j."""
    gap = rows[:, None] - jnp.arange(t)[None, :]
    if kind == "full_attention":
        return gap >= 0
    if kind == "sliding_attention":
        return (gap >= 0) & (gap < window)
    raise ValueError(f"layer type {kind!r}")


# ---------------------------------------------------------------------------
# attention, a block of query rows at a time
# ---------------------------------------------------------------------------
def attention(p, pre, x, cfg, kind, matmuls, block_rows):
    dense, bmm = matmuls
    heads, kv_heads = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d, eps = cfg["head_dim"], cfg["rms_norm_eps"]
    t = x.shape[0]
    q = dense(x, p[pre + "q_proj.weight"]).reshape(t, heads, d)
    k = dense(x, p[pre + "k_proj.weight"]).reshape(t, kv_heads, d)
    v = dense(x, p[pre + "v_proj.weight"]).reshape(t, kv_heads, d)
    if cfg.get("qk_norm", True):
        q = _rms_norm(q, p[pre + "q_norm.weight"], eps)
        k = _rms_norm(k, p[pre + "k_norm.weight"], eps)
    inv_freq, scale = rope_inv_freq(d, cfg["rope_parameters"][kind])
    q, k = rope(q, inv_freq, scale), rope(k, inv_freq, scale)
    group = heads // kv_heads
    kh = jnp.repeat(k, group, axis=1).transpose(1, 2, 0)     # (H, D, T)
    vh = jnp.repeat(v, group, axis=1).transpose(1, 0, 2)     # (H, T, D)
    rows = min(block_rows, t)
    if t % rows:
        raise ValueError(f"{t} rows are no whole blocks of {rows}")

    @jax.checkpoint
    def block(args):
        qb, start = args                                     # (rows, H, D)
        s = bmm(qb.transpose(1, 0, 2), kh) / math.sqrt(d)
        ok = allowed(kind, cfg["sliding_window"],
                     start + jnp.arange(rows), t)
        s = jnp.where(ok[None], s, -jnp.inf)
        return bmm(jax.nn.softmax(s, axis=-1), vh)           # (H, rows, D)

    out = jax.lax.map(block, (q.reshape(t // rows, rows, heads, d),
                              jnp.arange(0, t, rows)))
    out = out.transpose(0, 2, 1, 3).reshape(t, heads * d)
    return dense(out, p[pre + "o_proj.weight"])


# ---------------------------------------------------------------------------
# the expert layer
# ---------------------------------------------------------------------------
def router_weights(x, router_w, top_k, renormalize=True):
    """(picked (T, k), weight (T, k)): softmax over all experts, the top
    k, renormalised over the picks."""
    p = jax.nn.softmax(_dense(x, router_w), axis=-1)
    weight, picked = jax.lax.top_k(p, top_k)
    if renormalize:
        weight = weight / jnp.sum(weight, axis=1, keepdims=True)
    return picked, weight


def moe(p, pre, x, cfg, dense):
    """The share's routed part: ``experts_held`` experts from
    ``expert_offset`` are in ``p``; the router scores all
    ``num_experts``."""
    offset = cfg.get("expert_offset", 0)
    picked, weight = router_weights(x, p[pre + "router.weight"],
                                    cfg["num_experts_per_tok"],
                                    cfg["norm_topk_prob"])
    out = jnp.zeros_like(x)
    for e in range(p[pre + "experts_gate"].shape[0]):
        w_e = jnp.sum(jnp.where(picked == offset + e, weight, 0.0), axis=1)
        out = out + w_e[:, None] * _gated(
            x, p[pre + "experts_gate"][e], p[pre + "experts_up"][e],
            p[pre + "experts_down"][e], dense)
    return out


# ---------------------------------------------------------------------------
# the model and its loss
# ---------------------------------------------------------------------------
def hidden_states(p, cfg, ids, matmuls=F32_MATMULS, block_rows=512):
    """Final-norm hidden states of one row of token ids (T,)."""
    dense = matmuls[0]
    eps = cfg["rms_norm_eps"]
    x = p["embed.weight"][ids]
    for n, (kind, ffn) in enumerate(layer_kinds(cfg)):
        pre = f"layers.{n}."
        if ffn != "sparse":
            raise ValueError(f"mlp_layer_types entry {ffn!r}")

        @jax.checkpoint
        def layer(x, p, pre=pre, kind=kind):
            h = _rms_norm(x, p[pre + "input_norm.weight"], eps)
            x = x + attention(p, pre + "mixer.", h, cfg, kind, matmuls,
                              block_rows)
            h = _rms_norm(x, p[pre + "post_norm.weight"], eps)
            return x + moe(p, pre + "ffn.", h, cfg, dense)

        x = layer(x, p)
    return _rms_norm(x, p["final_norm.weight"], eps)


def loss(p, cfg, ids, labels, matmuls=F32_MATMULS, block_rows=512):
    """Mean cross-entropy over the positions of ``labels`` (B, T) that
    are not -100, of the logits ``hidden @ head^T``."""
    dense = matmuls[0]
    n_labelled = jnp.sum(labels != -100)
    total = 0.0
    for row_ids, row_labels in zip(ids, labels):
        h = hidden_states(p, cfg, row_ids, matmuls, block_rows)
        t = h.shape[0]
        rows = min(block_rows, t)

        @jax.checkpoint
        def block(args):
            hb, lab = args
            logp = jax.nn.log_softmax(dense(hb, p["head"].T), axis=-1)
            ll = jnp.take_along_axis(
                logp, jnp.maximum(lab, 0)[:, None], axis=1)[:, 0]
            return -jnp.sum(jnp.where(lab != -100, ll, 0.0))

        total = total + jnp.sum(jax.lax.map(
            block, (h.reshape(t // rows, rows, -1),
                    row_labels.reshape(t // rows, rows))))
    return total / n_labelled


@functools.lru_cache(maxsize=None)
def _grad_of(cfg_json, matmuls, block_rows):
    """The jitted loss-and-gradient of one configuration (compiled once
    for it, however often :func:`train` is called)."""
    cfg = json.loads(cfg_json)
    return jax.jit(lambda p, ids, labels: jax.value_and_grad(loss)(
        p, cfg, ids, labels, matmuls, block_rows))


def train(make_params, cfg, batches, hyper, block_rows=512,
          matmuls=F32_MATMULS):
    """Follow ``len(batches)`` AdamW steps from ``make_params()``, as
    ``reference.kimi_linear.train`` does (the same in-place, leaf-by-leaf
    update, so that 600 M parameters with their gradient and two moments
    fit beside the backward's activations). Returns the loss of each
    step, the per-leaf norm of the first step's gradient and the per-leaf
    norm of the parameters' change after the last step."""
    peak, warmup = hyper["learning_rate"], hyper["warmup_steps"]
    rule = (hyper["beta1"], hyper["beta2"], hyper["epsilon"],
            hyper["weight_decay"])
    grad_of = _grad_of(json.dumps(cfg, sort_keys=True), matmuls,
                       int(block_rows))
    p = dict(make_params())
    m = {k: jnp.zeros_like(x) for k, x in p.items()}
    v = {k: jnp.zeros_like(x) for k, x in p.items()}
    losses, grad_norm = [], None
    for t, (ids, labels) in enumerate(batches, start=1):
        value, grads = grad_of(p, jnp.asarray(ids), jnp.asarray(labels))
        losses.append(float(value))
        if t == 1:
            grad_norm = {k: float(x) for k, x in
                         jax.jit(leaf_norms)(grads).items()}
        lr = jnp.float32(peak * min(1.0, t / warmup))
        for k in list(p):
            p[k], m[k], v[k] = _adamw(p[k], m[k], v[k], grads.pop(k),
                                      jnp.float32(t), lr, *rule)
    del m, v
    delta = _change_norms(p, make_params())
    return {"loss": losses, "grad_norm": grad_norm,
            "delta_norm": {k: float(x) for k, x in delta.items()}}
