"""Plain reference for a Kimi-Linear style causal LM: loss, gradients and
AdamW steps in straightforward ``jax.numpy`` float32 at "highest" matmul
precision. No kernels, no autocast, no chunking; imports nothing of the
program.

It follows the published block (pre-norm residual, RMSNorm everywhere):

- **KDA** (Kimi Delta Attention) by its RECURRENCE, token by token under
  ``lax.scan``: ``S' = Diag(exp(g_t)) S``; ``S = S' + beta_t k_t (v_t -
  S'^T k_t)^T``; ``o_t = S^T q_t``, with q, k, v from a causal 4-tap
  depthwise convolution, SiLU and (q, k) an L2 norm, the per-channel log
  decay ``g_t = -exp(A_log) softplus(x W_f1 W_f2 + dt_bias)``, ``beta_t =
  sigmoid(x W_b)`` and the output ``RMSNorm_head(o_t) * sigmoid(x W_g1
  W_g2 + b_g)``. The scan is checkpointed in segments so that its
  backward keeps one state per segment, not one per token.
- **MLA**, NoPE, expanded: ``q = x W_q``; ``[c, k_pe] = x W_kv_down``;
  ``[k_nope, v] = RMSNorm(c) W_kv_up``; ``k = [k_nope ; k_pe]``; causal
  softmax attention scaled by ``1/sqrt(d_nope + d_pe)``; computed a block
  of query rows at a time.
- **FFN**: ``(SiLU(x W_gate) * x W_up) W_down``; the sparse layer scores
  all experts with a sigmoid, picks the top k of ``score + bias``,
  renormalises over the picks, scales, and adds the part of the experts
  it is GIVEN (``experts_held`` from ``expert_offset``) by a dense loop
  over them, plus the shared expert. What absent experts would add is
  left out.
- loss: mean next-token cross-entropy over the labelled positions, a
  block of rows at a time.

Parameters are a dict under the program's parameter names
(``drivers/causal_lm_step.param_shapes``); the benchmark makes them from
the seed and hands the same values to both sides. ``matmuls`` swaps the
dense and batched matrix products for the lower-precision control
(:func:`fp8_matmuls`); the recurrence's state, the router and the norms
stay float32 there, as they do in the program under autocast.
"""
from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp

# the float32 pair of products, and fp8_matmuls for the control, which
# looks it up here by name
from benchmarks.reference.bert import (  # noqa: F401
    F32_MATMULS, _dense, fp8_matmuls, leaf_norms)

_HIGHEST = jax.lax.Precision.HIGHEST
#: tokens between two saved states of the KDA scan
SEGMENT = 64
#: added to the squared norm under the L2 normalisation of q and k
L2_EPS = 1e-6


def layer_kinds(cfg: dict) -> list:
    """[(mixer, ffn)] per layer, from the config's own keys: ``kda`` for
    the layers ``linear_attn_config.kda_layers`` lists (from 1), ``mla``
    for the rest; ``dense`` for the first ``first_k_dense_replace``
    layers, ``moe`` after them."""
    kda = set(cfg["linear_attn_config"]["kda_layers"])
    return [("kda" if n in kda else "mla",
             "dense" if n <= cfg["first_k_dense_replace"] else "moe")
            for n in range(1, cfg["num_hidden_layers"] + 1)]


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


# ---------------------------------------------------------------------------
# KDA: the recurrence
# ---------------------------------------------------------------------------
def delta_rule_recurrence(q, k, v, g, beta):
    """q, k, g: (T, H, K); v: (T, H, V); beta: (T, H). The state starts
    at zero. Returns o (T, H, V)."""
    t, h, kd = q.shape
    vd = v.shape[-1]
    pad = (-t) % SEGMENT
    if pad:          # tokens that neither write nor decay, dropped below
        q, k, v, g = (jnp.pad(a, ((0, pad), (0, 0), (0, 0)))
                      for a in (q, k, v, g))
        beta = jnp.pad(beta, ((0, pad), (0, 0)))

    def token(state, x):
        q_t, k_t, v_t, g_t, b_t = x
        state = state * jnp.exp(g_t)[:, :, None]
        seen = jnp.einsum("hkv,hk->hv", state, k_t, precision=_HIGHEST)
        state = state + (b_t[:, None] * k_t)[:, :, None] \
            * (v_t - seen)[:, None, :]
        return state, jnp.einsum("hkv,hk->hv", state, q_t,
                                 precision=_HIGHEST)

    @jax.checkpoint
    def segment(state, xs):
        return jax.lax.scan(token, state, xs)

    xs = tuple(a.reshape((-1, SEGMENT) + a.shape[1:])
               for a in (q, k, v, g, beta))
    _, o = jax.lax.scan(segment, jnp.zeros((h, kd, vd), jnp.float32), xs)
    return o.reshape((-1, h, vd))[:t]


def _short_conv(x, taps):
    """Causal depthwise convolution over the rows of x (T, C); taps
    (W, C), the last one on the current token."""
    width, t = taps.shape[0], x.shape[0]
    padded = jnp.pad(x, ((width - 1, 0), (0, 0)))
    return sum(padded[j:j + t] * taps[j] for j in range(width))


def _l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + L2_EPS)


def kda(p, pre, x, cfg, dense):
    """One row: x (T, hidden)."""
    lin = cfg["linear_attn_config"]
    heads, d = lin["num_heads"], lin["head_dim"]
    t = x.shape[0]

    def mixed(name):
        y = _short_conv(dense(x, p[pre + name + "_proj.weight"]),
                        p[pre + name + "_conv"])
        return jax.nn.silu(y).reshape(t, heads, d)

    q = _l2norm(mixed("q")) * d ** -0.5
    k = _l2norm(mixed("k"))
    v = mixed("v")
    decay = dense(dense(x, p[pre + "f_a_proj.weight"]),
                  p[pre + "f_b_proj.weight"]) + p[pre + "dt_bias"]
    g = -jnp.exp(p[pre + "A_log"])[:, None] \
        * jax.nn.softplus(decay).reshape(t, heads, d)
    beta = jax.nn.sigmoid(dense(x, p[pre + "b_proj.weight"]))
    o = delta_rule_recurrence(q, k, v, g, beta)
    gate = dense(dense(x, p[pre + "g_a_proj.weight"]),
                 p[pre + "g_b_proj.weight"]) + p[pre + "g_b_proj.bias"]
    o = _rms_norm(o, p[pre + "o_norm"], cfg["rms_norm_eps"]) \
        * jax.nn.sigmoid(gate).reshape(t, heads, d)
    return dense(o.reshape(t, heads * d), p[pre + "o_proj.weight"])


# ---------------------------------------------------------------------------
# MLA (NoPE), a block of query rows at a time
# ---------------------------------------------------------------------------
def mla(p, pre, x, cfg, matmuls, block_rows):
    dense, bmm = matmuls
    heads = cfg["num_attention_heads"]
    nope, pe = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    vd, rank = cfg["v_head_dim"], cfg["kv_lora_rank"]
    t = x.shape[0]
    q = dense(x, p[pre + "q_proj.weight"]).reshape(t, heads, nope + pe)
    down = dense(x, p[pre + "kv_down_proj.weight"])
    latent = _rms_norm(down[:, :rank], p[pre + "kv_norm.weight"],
                       cfg["rms_norm_eps"])
    up = dense(latent, p[pre + "kv_up_proj.weight"]).reshape(
        t, heads, nope + vd)
    k_pe = jnp.broadcast_to(down[:, None, rank:], (t, heads, pe))
    k = jnp.concatenate([up[:, :, :nope], k_pe], axis=-1)
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
# feed-forward
# ---------------------------------------------------------------------------
def _gated(x, gate, up, down, dense):
    return dense(jax.nn.silu(dense(x, gate)) * dense(x, up), down)


def moe(p, pre, x, cfg, dense, router_bias=None):
    """The share's routed part plus the shared expert. ``experts_held``
    experts from ``expert_offset`` are in ``p``; the router scores all
    ``num_experts``."""
    k = cfg["num_experts_per_token"]
    offset = cfg.get("expert_offset", 0)
    scores = jax.nn.sigmoid(_dense(x, p[pre + "router.weight"]))
    biased = scores if router_bias is None else scores + router_bias
    _, picked = jax.lax.top_k(biased, k)
    weight = jnp.take_along_axis(scores, picked, axis=1)
    if cfg["moe_renormalize"]:
        weight = weight / jnp.sum(weight, axis=1, keepdims=True)
    weight = weight * cfg["routed_scaling_factor"]
    out = jnp.zeros_like(x)
    for e in range(p[pre + "experts_gate"].shape[0]):
        w_e = jnp.sum(jnp.where(picked == offset + e, weight, 0.0), axis=1)
        out = out + w_e[:, None] * _gated(
            x, p[pre + "experts_gate"][e], p[pre + "experts_up"][e],
            p[pre + "experts_down"][e], dense)
    if cfg.get("num_shared_experts", 0):
        out = out + _gated(x, p[pre + "shared.gate_proj.weight"],
                           p[pre + "shared.up_proj.weight"],
                           p[pre + "shared.down_proj.weight"], dense)
    return out


# ---------------------------------------------------------------------------
# the model and its loss
# ---------------------------------------------------------------------------
def hidden_states(p, cfg, ids, matmuls=F32_MATMULS, block_rows=512):
    """Final-norm hidden states of one row of token ids (T,)."""
    dense = matmuls[0]
    eps = cfg["rms_norm_eps"]
    x = p["embed.weight"][ids]
    for n, (mixer, ffn) in enumerate(layer_kinds(cfg)):
        pre = f"layers.{n}."

        @jax.checkpoint
        def layer(x, p, pre=pre, mixer=mixer, ffn=ffn):
            h = _rms_norm(x, p[pre + "input_norm.weight"], eps)
            if mixer == "kda":
                x = x + kda(p, pre + "mixer.", h, cfg, dense)
            else:
                x = x + mla(p, pre + "mixer.", h, cfg, matmuls, block_rows)
            h = _rms_norm(x, p[pre + "post_norm.weight"], eps)
            if ffn == "dense":
                f = pre + "ffn."
                return x + _gated(h, p[f + "gate_proj.weight"],
                                  p[f + "up_proj.weight"],
                                  p[f + "down_proj.weight"], dense)
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


@functools.partial(jax.jit, donate_argnums=(0, 1, 2),
                   static_argnums=(6, 7, 8, 9))
def _adamw(p, m, v, g, t, lr, b1, b2, eps, wd):
    m2 = b1 * m + (1 - b1) * g
    v2 = b2 * v + (1 - b2) * jnp.square(g)
    p2 = p - lr * (m2 / (1 - b1 ** t)) / (jnp.sqrt(v2 / (1 - b2 ** t)) + eps)
    return p2 - lr * wd * p, m2, v2


@jax.jit
def _change_norms(a, b):
    return leaf_norms({k: a[k] - b[k] for k in a})


def train(make_params, cfg, batches, hyper, block_rows=512,
          matmuls=F32_MATMULS):
    """Follow ``len(batches)`` AdamW steps from ``make_params()``.
    Returns the loss of each step, the per-leaf norm of the first step's
    gradient and the per-leaf norm of the parameters' change after the
    last step. ``make_params`` is called a second time at the end for the
    starting point: every step's update is done in place, leaf by leaf,
    so that 600 M parameters with their gradient and two moments fit
    beside the backward's activations.

    ``hyper``: learning_rate (the peak), warmup_steps (step t runs at
    peak * min(1, t / warmup_steps)), beta1, beta2, epsilon, weight_decay
    — the decoupled decay ``p -= lr * wd * p`` on every leaf, as the
    program's ``optimizer.AdamW`` does it."""
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
