"""Plain reference for an LFM2-MoE style causal LM (``model_type:
lfm2_moe``: gated short convolutions in most layers, grouped-query
attention with rotary positions in the others, leading dense gated FFNs,
then sigmoid-routed gated experts, a head tied to the embedding): loss,
gradients and AdamW steps in straightforward ``jax.numpy`` float32 at
"highest" matmul precision. No kernels, no autocast; imports nothing of
the program. Written from the family's modelling code
(``transformers/models/lfm2/modeling_lfm2.py``: ``Lfm2ShortConv``,
``Lfm2Attention``, ``Lfm2MLP``, ``Lfm2DecoderLayer``) and ISSUE 46. On
one row ``x`` (T, hidden), layers counted from 1, ``u = RMSNorm(x)``:

    block      h = x + Op(RMSNorm(x; input_norm));  y = h + FFN(RMSNorm(h; post_norm))
    conv       [B | C | X] = u W_in        (hidden x 3 hidden, chunks in THAT order)
               z = B * X;  c_t = sum_{j=0..L-1} w_j * z_{t-(L-1)+j}   (depthwise,
               causal, zeros before the row's start; w is (hidden, L))
               Op = (C * c) W_out
    attention  q = RMSNorm_D(u W_q as H heads), k = RMSNorm_D(u W_k as Hkv heads),
               v = u W_v;  rotary (rotate-half, theta, the whole D) on q and k;
               causal softmax(q k^T / sqrt(D)) v, a key head to H / Hkv query
               heads;  Op = concat W_out
    dense FFN  layers 1 .. num_dense_layers:  W2 (silu(W1 a) * W3 a)
    experts    the others: s = sigmoid(a W_r) over ALL experts; picks = top-k of
               (s + b), b the bias buffer (zero here, outside the gradient);
               g = s[picks] / (sum s[picks] + 1e-6) x
               routed_scaling_factor;  out = sum_{e picked, held} g_e W2_e
               (silu(W1_e a) * W3_e a); no shared expert
    head       final RMSNorm; logits = h E^T with E the embedding (tied)

Departures from the source, each on purpose:

- **the convolution** as a sum over L shifted copies, not a padded
  ``Conv1d`` cut back to the row's length. The same numbers.
- **attention** by an explicit (rows, T) mask, a block of query rows at
  a time, the key heads repeated to the query heads, so that two rows of
  8,192 fit; the source masks the whole square at once.
- **experts** by a dense loop over the experts the share is GIVEN
  (``experts_held`` from ``expert_offset``); what the absent experts
  would add is left out, as in the program. The pick passes no gradient.
- loss: mean next-token cross-entropy over the labelled positions, a
  block of rows at a time.
- Adam's moments live on the HOST between updates and visit the device a
  leaf at a time (``reference/nemotron_h.train``'s reason).

Parameters are a dict under the program's parameter names
(``drivers/conv_hybrid_lm_step.param_shapes``); there is no ``head``
leaf. ``matmuls`` is (dense product, batched product):
:data:`F32_MATMULS` here, :data:`fp8_matmuls` for the lower-precision
control; the router, the norms, the rotation, the softmax and the
convolution with its two gates stay float32 there, as they are float32
inside the program's pass.
"""
from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

# the float32 pair of products, and fp8_matmuls for the control, which
# looks it up here by name; the AdamW step and the norms are Kimi's, the
# rotation Mellum's
from benchmarks.reference.bert import (  # noqa: F401
    F32_MATMULS, _dense, fp8_matmuls, leaf_norms)
from benchmarks.reference.kimi_linear import (
    _adamw, _change_norms, _gated, _rms_norm)
from benchmarks.reference.mellum2 import rope, rope_inv_freq

#: added to the sum of the picked scores before the division (the family's
#: modelling code; no key of the file): the configuration's ``assumed``
RENORM_EPSILON = 1e-6


def layer_kinds(cfg: dict) -> list:
    """[(mixer, ffn)] per layer: the mixer from ``layer_types`` (``conv``
    or ``full_attention``), the feed-forward ``dense`` for the first
    ``num_dense_layers`` layers and ``moe`` after them."""
    return [(kind, "dense" if n < cfg["num_dense_layers"] else "moe")
            for n, kind in enumerate(cfg["layer_types"])]


# ---------------------------------------------------------------------------
# the two mixers
# ---------------------------------------------------------------------------
def short_conv(p, pre, x, dense):
    """The gated short convolution on one row x (T, hidden)."""
    d = x.shape[1]
    bcx = dense(x, p[pre + "in_proj.weight"])
    b, c, xx = bcx[:, :d], bcx[:, d:2 * d], bcx[:, 2 * d:]
    z = b * xx
    w = p[pre + "conv_weight"]                          # (hidden, L)
    taps = w.shape[1]
    conv = jnp.zeros_like(z)
    for j in range(taps):
        back = taps - 1 - j         # tap j reads the token ``back`` before
        conv = conv + w[:, j] * jnp.concatenate(
            [jnp.zeros_like(z[:back]), z[:z.shape[0] - back]], axis=0)
    return dense(c * conv, p[pre + "out_proj.weight"])


def attention(p, pre, x, cfg, matmuls, block_rows):
    dense, bmm = matmuls
    heads, kv_heads = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = cfg.get("head_dim", cfg["hidden_size"] // heads)
    t = x.shape[0]
    q = dense(x, p[pre + "q_proj.weight"]).reshape(t, heads, d)
    k = dense(x, p[pre + "k_proj.weight"]).reshape(t, kv_heads, d)
    v = dense(x, p[pre + "v_proj.weight"]).reshape(t, kv_heads, d)
    q = _rms_norm(q, p[pre + "q_norm.weight"], cfg["norm_eps"])
    k = _rms_norm(k, p[pre + "k_norm.weight"], cfg["norm_eps"])
    inv_freq, scale = rope_inv_freq(d, cfg["rope_parameters"])
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
        ok = (start + jnp.arange(rows))[:, None] >= jnp.arange(t)[None, :]
        s = jnp.where(ok[None], s, -jnp.inf)
        return bmm(jax.nn.softmax(s, axis=-1), vh)           # (H, rows, D)

    out = jax.lax.map(block, (q.reshape(t // rows, rows, heads, d),
                              jnp.arange(0, t, rows)))
    out = out.transpose(0, 2, 1, 3).reshape(t, heads * d)
    return dense(out, p[pre + "o_proj.weight"])


# ---------------------------------------------------------------------------
# the expert layer
# ---------------------------------------------------------------------------
def router_weights(x, router_w, cfg, bias=None):
    """(picked (T, k), weight (T, k)): sigmoid scores of ALL experts, the
    top k of score + bias, the scores renormalised over the picks where
    the file says ``norm_topk_prob`` (their sum plus
    :data:`RENORM_EPSILON`), scaled."""
    scores = jax.nn.sigmoid(_dense(x, router_w))
    _, picked = jax.lax.top_k(scores if bias is None else scores + bias,
                              cfg["num_experts_per_tok"])
    weight = jnp.take_along_axis(scores, picked, axis=1)
    if cfg["norm_topk_prob"]:
        weight = weight / (jnp.sum(weight, axis=1, keepdims=True)
                           + RENORM_EPSILON)
    return picked, weight * cfg["routed_scaling_factor"]


def moe(p, pre, x, cfg, dense, router_bias=None):
    """The share's part of the layer: the experts in ``p`` are experts
    ``expert_offset`` .. of the router's ``num_experts``, one by one."""
    offset = cfg.get("expert_offset", 0)
    picked, weight = router_weights(x, p[pre + "router.weight"], cfg,
                                    router_bias)
    out = jnp.zeros_like(x)
    for e in range(p[pre + "experts_up"].shape[0]):
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
    eps = cfg["norm_eps"]
    x = p["embed.weight"][ids]
    for n, (mixer, ffn) in enumerate(layer_kinds(cfg)):
        pre = f"layers.{n}."

        @jax.checkpoint
        def layer(x, p, pre=pre, mixer=mixer, ffn=ffn):
            h = _rms_norm(x, p[pre + "input_norm.weight"], eps)
            if mixer == "conv":
                x = x + short_conv(p, pre + "mixer.", h, dense)
            elif mixer == "full_attention":
                x = x + attention(p, pre + "mixer.", h, cfg, matmuls,
                                  block_rows)
            else:
                raise ValueError(f"layer_types entry {mixer!r}")
            h = _rms_norm(x, p[pre + "post_norm.weight"], eps)
            f = pre + "ffn."
            if ffn == "dense":
                return x + _gated(h, p[f + "gate_proj.weight"],
                                  p[f + "up_proj.weight"],
                                  p[f + "down_proj.weight"], dense)
            return x + moe(p, f, h, cfg, dense)

        x = layer(x, p)
    return _rms_norm(x, p["final_norm.weight"], eps)


def loss(p, cfg, ids, labels, matmuls=F32_MATMULS, block_rows=512):
    """Mean cross-entropy over the positions of ``labels`` (B, T) that
    are not -100, of the logits ``hidden @ embedding^T`` (the tied
    head)."""
    if not cfg.get("tie_word_embeddings"):
        raise ValueError("this family's head is the embedding: the file "
                         "says tie_word_embeddings")
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
            logp = jax.nn.log_softmax(dense(hb, p["embed.weight"].T),
                                      axis=-1)
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
    ``reference.deepseek_v3.train`` does: the update in place, leaf by
    leaf, both Adam moments on the host between updates. Returns the
    loss of each step, the per-leaf norm of the first step's gradient and
    the per-leaf norm of the parameters' change after the last step.

    ``hyper``: learning_rate (the peak), warmup_steps (step t runs at
    peak * min(1, t / warmup_steps)), beta1, beta2, epsilon, weight_decay
    (decoupled, ``p -= lr * wd * p`` on every leaf, as the program's
    ``optimizer.AdamW`` does it)."""
    peak, warmup = hyper["learning_rate"], hyper["warmup_steps"]
    rule = (hyper["beta1"], hyper["beta2"], hyper["epsilon"],
            hyper["weight_decay"])
    grad_of = _grad_of(json.dumps(cfg, sort_keys=True), matmuls,
                       int(block_rows))
    p = dict(make_params())
    m = {k: np.zeros(x.shape, np.float32) for k, x in p.items()}
    v = {k: np.zeros(x.shape, np.float32) for k, x in p.items()}
    losses, grad_norm = [], None
    for t, (ids, labels) in enumerate(batches, start=1):
        value, grads = grad_of(p, jnp.asarray(ids), jnp.asarray(labels))
        losses.append(float(value))
        if t == 1:
            grad_norm = {k: float(x) for k, x in
                         jax.jit(leaf_norms)(grads).items()}
        lr = jnp.float32(peak * min(1.0, t / warmup))
        for k in list(p):
            p[k], m_k, v_k = _adamw(p[k], jnp.asarray(m[k]),
                                    jnp.asarray(v[k]), grads.pop(k),
                                    jnp.float32(t), lr, *rule)
            m[k], v[k] = np.asarray(m_k), np.asarray(v_k)
    del m, v
    delta = _change_norms(p, make_params())
    return {"loss": losses, "grad_norm": grad_norm,
            "delta_norm": {k: float(x) for k, x in delta.items()}}
