"""Plain reference for a Qwen3-Next style causal LM (``model_type``
``qwen3_next``): loss, gradients and AdamW steps in straightforward
``jax.numpy`` float32 at "highest" matmul precision. No kernels, no
autocast, no chunking; imports nothing of the program.

The layer equations, written from the family's modelling code as
remembered (no network here: every point the source's ``config.json`` does
not state is under the configuration's ``assumed`` with its other reading).

**Norm** (``N``): ``N(x) = x / sqrt(mean(x^2) + eps) * (1 + w)``, ``w``
started at 0, float32: a block's two norms, the final norm and the
per-head norms of q and k in the attention layer. The Gated DeltaNet's
output norm is the ordinary one (scale ``w``, started at 1).

**Block**, every layer: ``x += Mix(N(x)); x += MoE(N(x))``
(``decoder_sparse_step`` 1, ``mlp_only_layers`` empty: every feed-forward
is the expert layer). Layer ``l`` (from 1) mixes by full attention where
``l % full_attention_interval == 0``, else by Gated DeltaNet.

**Gated DeltaNet** (``H_k = linear_num_key_heads``, ``H_v =
linear_num_value_heads``, ``d_k = linear_key_head_dim``, ``d_v =
linear_value_head_dim``, ``r = H_v / H_k``, ``linear_conv_kernel_dim``
taps):

    [q | k | v | z] = x W_qkvz                 [b | a] = x W_ba
    q, k, v = SiLU(ShortConv([q | k | v]))     causal, depthwise, no bias
    q_h = L2norm(q_h) / sqrt(d_k),  k_h = L2norm(k_h)
                                    per head, x * rsqrt(sum x^2 + 1e-6)
    value head j reads query/key head j // r
    beta_t,j = sigmoid(b_t,j)
    g_t,j = -exp(A_log_j) * softplus(a_t,j + dt_bias_j)
    S' = exp(g_t,j) S_{t-1};  S_t = S' + beta_t k_t (v_t - S'^T k_t)^T
    o_t = S_t^T q_t              (S: d_k x d_v, zero at a row's start)
    y = [RMSNorm_{d_v}(o_j) * w * SiLU(z_j)]_j W_o

by its RECURRENCE, token by token (``kimi_linear.delta_rule_recurrence``
with the scalar decay written into all ``d_k`` channels of its head).

**Gated attention** (``num_attention_heads`` on ``num_key_value_heads``
heads of ``head_dim``, no bias):

    [q | gate] = x W_q    (a head's q then its gate);  k, v = x W_k, x W_v
    q, k = N(q), N(k) per head;  the first ``partial_rotary_factor *
    head_dim`` entries of each head rotated (rotate_half within them,
    ``rope_theta``, no scaling), the others passed through
    o = causal softmax(q k^T / sqrt(head_dim)) v, grouped
    y = (o * sigmoid(gate)) W_o

a block of query rows at a time.

**Experts**: scores ``softmax(x W_r)`` over ALL ``num_experts`` in float32,
the top ``num_experts_per_tok``, renormalised over the picks
(``norm_topk_prob``), gated SiLU experts; the part of the experts it is
GIVEN (``experts_held`` from ``expert_offset``) by a dense loop over them;
plus ``sigmoid(x w_s) * Shared(x)``, ``Shared`` one gated SiLU FFN of
``shared_expert_intermediate_size``, ``w_s`` a (hidden, 1) matrix without
bias. What absent experts would add is left out.

Untied head; loss: mean next-token cross-entropy over the labelled
positions, a block of rows at a time.

Parameters are a dict under the program's parameter names
(``drivers/gated_delta_lm_step.param_shapes``). ``matmuls`` swaps the
dense and batched products for the lower-precision control
(:func:`fp8_matmuls`); the recurrence's state, the decays, the router and
the norms stay float32 there, as they do in the program under autocast.
"""
from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

# the float32 pair of products, and fp8_matmuls for the control, which
# looks it up here by name; the recurrence, the convolution, the AdamW
# step and the ordinary norm are Kimi's, the rotation Mellum's
from benchmarks.reference.bert import (  # noqa: F401
    F32_MATMULS, _dense, fp8_matmuls, leaf_norms)
from benchmarks.reference.kimi_linear import (
    _adamw, _change_norms, _gated, _l2norm, _rms_norm, _short_conv,
    delta_rule_recurrence)
from benchmarks.reference.mellum2 import rope, rope_inv_freq


def layer_kinds(cfg: dict) -> list:
    """[(mixer, ffn)] per layer: ``gqa`` on every
    ``full_attention_interval``-th layer (from 1) and ``gdn`` on the
    others; the feed-forward is the expert layer everywhere (a file that
    says otherwise is refused)."""
    if cfg.get("mlp_only_layers") or cfg.get("decoder_sparse_step", 1) != 1:
        raise ValueError("this reference has the expert layer in every "
                         "block: mlp_only_layers empty, "
                         "decoder_sparse_step 1")
    return [("gdn" if n % cfg["full_attention_interval"] else "gqa", "moe")
            for n in range(1, cfg["num_hidden_layers"] + 1)]


def _norm(x, w, eps):
    """The zero-centred norm: scale ``1 + w``."""
    return _rms_norm(x, 1.0 + w, eps)


# ---------------------------------------------------------------------------
# the two mixers
# ---------------------------------------------------------------------------
def gated_delta_net(p, pre, x, cfg, dense):
    """One row: x (T, hidden)."""
    hk, hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    t = x.shape[0]
    kw, vw = hk * dk, hv * dv
    qkvz = dense(x, p[pre + "in_proj_qkvz.weight"])
    ba = dense(x, p[pre + "in_proj_ba.weight"])
    mixed = jax.nn.silu(_short_conv(qkvz[:, :2 * kw + vw],
                                    p[pre + "qkv_conv"]))
    q = _l2norm(mixed[:, :kw].reshape(t, hk, dk)) * dk ** -0.5
    k = _l2norm(mixed[:, kw:2 * kw].reshape(t, hk, dk))
    v = mixed[:, 2 * kw:].reshape(t, hv, dv)
    z = qkvz[:, 2 * kw + vw:].reshape(t, hv, dv)
    # value head j reads query/key head j // r
    q, k = (jnp.repeat(a, hv // hk, axis=1) for a in (q, k))
    beta = jax.nn.sigmoid(ba[:, :hv])
    g = -jnp.exp(p[pre + "A_log"]) * jax.nn.softplus(
        ba[:, hv:] + p[pre + "dt_bias"])                       # (T, Hv)
    o = delta_rule_recurrence(
        q, k, v, jnp.broadcast_to(g[:, :, None], (t, hv, dk)), beta)
    o = _rms_norm(o, p[pre + "o_norm"], cfg["rms_norm_eps"]) \
        * jax.nn.silu(z)
    return dense(o.reshape(t, vw), p[pre + "o_proj.weight"])


def partial_rope(x, rotary_dim, theta):
    """x (T, heads, D): the first ``rotary_dim`` entries of each head
    rotated by the token's position (rotate_half within them), the rest
    as they are."""
    inv_freq, scale = rope_inv_freq(
        rotary_dim, {"rope_type": "default", "rope_theta": theta})
    return jnp.concatenate([rope(x[..., :rotary_dim], inv_freq, scale),
                            x[..., rotary_dim:]], axis=-1)


def gated_attention(p, pre, x, cfg, matmuls, block_rows):
    dense, bmm = matmuls
    heads, kv_heads = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d, eps = cfg["head_dim"], cfg["rms_norm_eps"]
    if cfg.get("rope_scaling") is not None:
        raise ValueError("rope_scaling: this reference rotates by "
                         "rope_theta alone")
    rotary = int(cfg["partial_rotary_factor"] * d)
    t = x.shape[0]
    qg = dense(x, p[pre + "q_proj.weight"]).reshape(t, heads, 2 * d)
    q, gate = qg[..., :d], qg[..., d:].reshape(t, heads * d)
    k = dense(x, p[pre + "k_proj.weight"]).reshape(t, kv_heads, d)
    v = dense(x, p[pre + "v_proj.weight"]).reshape(t, kv_heads, d)
    q = partial_rope(_norm(q, p[pre + "q_norm.weight"], eps), rotary,
                     cfg["rope_theta"])
    k = partial_rope(_norm(k, p[pre + "k_norm.weight"], eps), rotary,
                     cfg["rope_theta"])
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
    return dense(out * jax.nn.sigmoid(gate), p[pre + "o_proj.weight"])


# ---------------------------------------------------------------------------
# the expert layer
# ---------------------------------------------------------------------------
def router_weights(x, router_w, cfg):
    """(picked (T, k), weight (T, k)): softmax scores over ALL experts in
    float32, a plain top k, renormalised over the picks where the file
    says ``norm_topk_prob``."""
    scores = jax.nn.softmax(_dense(x, router_w), axis=-1)
    weight, picked = jax.lax.top_k(scores, cfg["num_experts_per_tok"])
    if cfg["norm_topk_prob"]:
        weight = weight / jnp.sum(weight, axis=1, keepdims=True)
    return picked, weight


def routed(p, pre, x, cfg, dense):
    """The share's routed part: the experts in ``p`` are experts
    ``expert_offset`` .. of the router's ``num_experts``, one by one."""
    offset = cfg.get("expert_offset", 0)
    picked, weight = router_weights(x, p[pre + "router.weight"], cfg)
    out = jnp.zeros_like(x)
    for e in range(p[pre + "experts_up"].shape[0]):
        w_e = jnp.sum(jnp.where(picked == offset + e, weight, 0.0), axis=1)
        out = out + w_e[:, None] * _gated(
            x, p[pre + "experts_gate"][e], p[pre + "experts_up"][e],
            p[pre + "experts_down"][e], dense)
    return out


def shared_expert(p, pre, x, dense):
    """What every share computes alike: the shared expert under its
    sigmoid gate."""
    return jax.nn.sigmoid(dense(x, p[pre + "shared_gate.weight"])) * _gated(
        x, p[pre + "shared.gate_proj.weight"],
        p[pre + "shared.up_proj.weight"],
        p[pre + "shared.down_proj.weight"], dense)


def moe(p, pre, x, cfg, dense):
    return routed(p, pre, x, cfg, dense) + shared_expert(p, pre, x, dense)


# ---------------------------------------------------------------------------
# the model and its loss
# ---------------------------------------------------------------------------
def hidden_states(p, cfg, ids, matmuls=F32_MATMULS, block_rows=512):
    """Final-norm hidden states of one row of token ids (T,)."""
    dense = matmuls[0]
    eps = cfg["rms_norm_eps"]
    x = p["embed.weight"][ids]
    for n, (mixer, _ffn) in enumerate(layer_kinds(cfg)):
        pre = f"layers.{n}."

        @jax.checkpoint
        def layer(x, p, pre=pre, mixer=mixer):
            h = _norm(x, p[pre + "input_norm.weight"], eps)
            if mixer == "gdn":
                x = x + gated_delta_net(p, pre + "mixer.", h, cfg, dense)
            else:
                x = x + gated_attention(p, pre + "mixer.", h, cfg, matmuls,
                                        block_rows)
            h = _norm(x, p[pre + "post_norm.weight"], eps)
            return x + moe(p, pre + "ffn.", h, cfg, dense)

        x = layer(x, p)
    return _norm(x, p["final_norm.weight"], eps)


def loss(p, cfg, ids, labels, matmuls=F32_MATMULS, block_rows=512):
    """Mean cross-entropy over the positions of ``labels`` (B, T) that
    are not -100, of the logits ``hidden @ head^T``."""
    if cfg.get("tie_word_embeddings"):
        raise ValueError("this family's head is a matrix of its own")
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
    ``reference.lfm2.train`` does: the update in place, leaf by leaf,
    both Adam moments on the host between updates (626 M parameters with
    their gradient do not leave room for them beside the backward).
    Returns the loss of each step, the per-leaf norm of the first step's
    gradient and the per-leaf norm of the parameters' change after the
    last step.

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
