"""Plain reference for a DeepSeek-V3 style causal LM (``model_type:
deepseek_v3``: multi-head latent attention with a decoupled rotary key in
every layer, leading dense gated FFNs, then sigmoid-routed gated experts
beside shared ones): loss, gradients and AdamW steps in straightforward
``jax.numpy`` float32 at "highest" matmul precision. No kernels, no
autocast; imports nothing of the program. Written from the family's
modelling code (ISSUE 39). On one row ``x`` (T, hidden), pre-norm
residual, RMSNorm everywhere:

    MLA   q = a W_q                       -> heads x [q_nope | q_pe]
          [c | k_pe] = a W_kv_a           -> kv_lora_rank + d_pe
          [k_nope_h | v_h] = RMSNorm(c) W_kv_b
          q_pe, k_pe = R_t(P q_pe), R_t(P k_pe)
              P  (x0, x1, x2, x3, ...) -> [x0, x2, ... | x1, x3, ...]
                 where the file says ``rope_interleave`` (the source's
                 ``view(.., d/2, 2).transpose(-1, -2).reshape(.., d)``)
              R_t  x cos + rotate_half(x) sin,  cos/sin of [theta_t |
                 theta_t],  theta_t,i = t * rope_theta^(-2i / d_pe)
          k_h = [k_nope_h | k_pe]         ONE k_pe row for every head
          o_h = softmax_{j<=i}(q_h . k_h / sqrt(d_nope + d_pe)) v_h
          y = [o_h] W_o
    FFN   layers 1 .. first_k_dense_replace: (silu(b G) * b U) D
    MoE   s = sigmoid(b W_r) over all experts;  S = top-k of s + bias
          w_e = routed_scaling_factor * s_e / (sum_{e' in S} s_e' + 1e-20)
          y = sum_{e in S, held} w_e (silu(b G_e) * b U_e) D_e
              + (silu(b G_s) * b U_s) D_s       shared, n_shared x wide

Departures from the source, each on purpose:

- **attention** by an explicit (rows, T) mask, a block of query rows at
  a time, so that two rows of 8,192 fit; the source masks the whole
  square at once. The same numbers.
- **experts** by a dense loop over the experts the share is GIVEN
  (``experts_held`` from ``expert_offset``); what the absent experts
  would add is left out, as in the program. The pick passes no gradient
  (``top_k`` of the source's ``noaux_tc``); ``n_group = topk_group = 1``,
  so the group limit is no limit and is not written.
- no low-rank query projection (``q_lora_rank`` null), no ``mscale``
  (``rope_scaling`` null), no multi-token head: the file has none.
- loss: mean next-token cross-entropy over the labelled positions, a
  block of rows at a time.
- Adam's moments live on the HOST between updates and visit the device
  a leaf at a time (``reference/nemotron_h.train``'s reason: parameters,
  gradient and the float32 backward of two 8,192-token rows together).

Parameters are a dict under the program's parameter names
(``drivers/latent_moe_lm_step.param_shapes``). ``matmuls`` is (dense
product, batched product): :data:`F32_MATMULS` here, :data:`fp8_matmuls`
for the lower-precision control; the router, the norms, the rotation and
the softmax stay float32 there, as they do in the program's autocast
(the program rotates in bfloat16: one more rounding the control lacks).
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
    """The feed-forward of each layer: ``dense`` for the first
    ``first_k_dense_replace`` layers, ``moe`` for every
    ``moe_layer_freq``-th after them. The mixer is MLA everywhere."""
    return ["dense" if n < cfg["first_k_dense_replace"]
            or n % cfg.get("moe_layer_freq", 1) else "moe"
            for n in range(cfg["num_hidden_layers"])]


# ---------------------------------------------------------------------------
# the rotation
# ---------------------------------------------------------------------------
def _deinterleave(x):
    """(x0, x1, x2, x3, ...) -> [x0, x2, ... | x1, x3, ...] on the last
    axis."""
    *lead, d = x.shape
    return jnp.swapaxes(x.reshape(*lead, d // 2, 2), -1, -2).reshape(
        *lead, d)


def _rotate_half(x):
    half = x.shape[-1] // 2
    return jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)


def rotate(x, theta: float, interleave: bool):
    """x (T, heads, d) at positions 0 .. T-1."""
    t, d = x.shape[0], x.shape[-1]
    inv_freq = 1.0 / theta ** (np.arange(0, d, 2, dtype=np.float64) / d)
    freqs = jnp.arange(t, dtype=jnp.float32)[:, None] \
        * jnp.asarray(inv_freq, jnp.float32)[None, :]
    emb = jnp.concatenate([freqs, freqs], axis=-1)[:, None, :]
    if interleave:
        x = _deinterleave(x)
    return x * jnp.cos(emb) + _rotate_half(x) * jnp.sin(emb)


# ---------------------------------------------------------------------------
# MLA, a block of query rows at a time
# ---------------------------------------------------------------------------
def mla(p, pre, x, cfg, matmuls, block_rows):
    """One row: x (T, hidden)."""
    dense, bmm = matmuls
    heads = cfg["num_attention_heads"]
    nope, pe = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    vd, rank = cfg["v_head_dim"], cfg["kv_lora_rank"]
    theta = float(cfg["rope_theta"])
    interleave = bool(cfg.get("rope_interleave", False))
    t = x.shape[0]
    q = dense(x, p[pre + "q_proj.weight"]).reshape(t, heads, nope + pe)
    down = dense(x, p[pre + "kv_down_proj.weight"])
    latent = _rms_norm(down[:, :rank], p[pre + "kv_norm.weight"],
                       cfg["rms_norm_eps"])
    up = dense(latent, p[pre + "kv_up_proj.weight"]).reshape(
        t, heads, nope + vd)
    q_pe = rotate(q[:, :, nope:], theta, interleave)
    k_pe = rotate(down[:, None, rank:], theta, interleave)   # (T, 1, pe)
    q = jnp.concatenate([q[:, :, :nope], q_pe], axis=-1)
    k = jnp.concatenate([up[:, :, :nope],
                         jnp.broadcast_to(k_pe, (t, heads, pe))], axis=-1)
    kh = k.transpose(1, 2, 0)                          # (H, D, T)
    vh = up[:, :, nope:].transpose(1, 0, 2)            # (H, T, V)
    rows = min(block_rows, t)
    if t % rows:
        raise ValueError(f"{t} rows are no whole blocks of {rows}")

    @jax.checkpoint
    def block(args):
        qb, start = args                               # (rows, H, D)
        s = bmm(qb.transpose(1, 0, 2), kh) / math.sqrt(nope + pe)
        at = start + jnp.arange(rows)[:, None]
        s = jnp.where(at >= jnp.arange(t)[None, :], s, -jnp.inf)
        return bmm(jax.nn.softmax(s, axis=-1), vh)     # (H, rows, V)

    out = jax.lax.map(block, (q.reshape(t // rows, rows, heads, nope + pe),
                              jnp.arange(0, t, rows)))
    out = out.transpose(0, 2, 1, 3).reshape(t, heads * vd)
    return dense(out, p[pre + "o_proj.weight"])


# ---------------------------------------------------------------------------
# the expert layer
# ---------------------------------------------------------------------------
def router_weights(x, router_w, cfg, bias=None):
    """(picked (T, k), weight (T, k)): sigmoid scores of ALL experts, the
    top k of score + bias, the scores renormalised over the picks where
    the file says ``norm_topk_prob``, scaled."""
    scores = jax.nn.sigmoid(_dense(x, router_w))
    _, picked = jax.lax.top_k(scores if bias is None else scores + bias,
                              cfg["num_experts_per_tok"])
    weight = jnp.take_along_axis(scores, picked, axis=1)
    if cfg["norm_topk_prob"]:
        weight = weight / (jnp.sum(weight, axis=1, keepdims=True) + 1e-20)
    return picked, weight * cfg["routed_scaling_factor"]


def routed(p, pre, x, cfg, dense, router_bias=None):
    """The share's routed part: the experts in ``p`` are experts
    ``expert_offset`` .. of the router's ``n_routed_experts``."""
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


def shared(p, pre, x, dense):
    """The shared experts as ONE gated FFN of their summed width."""
    return _gated(x, p[pre + "shared.gate_proj.weight"],
                  p[pre + "shared.up_proj.weight"],
                  p[pre + "shared.down_proj.weight"], dense)


def moe(p, pre, x, cfg, dense, router_bias=None):
    return routed(p, pre, x, cfg, dense, router_bias) \
        + shared(p, pre, x, dense)


# ---------------------------------------------------------------------------
# the model and its loss
# ---------------------------------------------------------------------------
def hidden_states(p, cfg, ids, matmuls=F32_MATMULS, block_rows=512):
    """Final-norm hidden states of one row of token ids (T,)."""
    dense = matmuls[0]
    eps = cfg["rms_norm_eps"]
    x = p["embed.weight"][ids]
    for n, ffn in enumerate(layer_kinds(cfg)):
        pre = f"layers.{n}."

        @jax.checkpoint
        def layer(x, p, pre=pre, ffn=ffn):
            h = _rms_norm(x, p[pre + "input_norm.weight"], eps)
            x = x + mla(p, pre + "mixer.", h, cfg, matmuls, block_rows)
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
    ``reference.nemotron_h.train`` does: the update in place, leaf by
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
