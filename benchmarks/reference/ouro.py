"""Plain reference for a looped ("universal transformer") causal LM
(``model_type: ouro``: one stack of layers applied ``total_ut_steps``
times with the same weights, four norms a block, a head and an exit gate
read after every pass, trained on the expected loss under the exit
distribution): loss, gradients and AdamW steps in straightforward
``jax.numpy`` float32 at "highest" matmul precision. No kernels, no
autocast; imports nothing of the program.

**Written from memory** of Zhu et al., *Scaling Latent Reasoning via
Looped Language Models* (arXiv:2510.25741, section 3) and of the family's
modelling code; this sandbox has no network to check either against. What
the source's ``config.json`` does not say is listed in the configuration
file under ``assumed`` with its other reading. On one row of ids, T =
``total_ut_steps``, all norms RMSNorm with their own scale:

    h^0 = E[ids]
    for t = 1 .. T:                       the SAME layers in every pass
        x = h^{t-1}
        for l = 1 .. L:
            x = x + N2_l(Attn_l(N1_l(x)))         the sublayer's OUTPUT is
            x = x + N4_l(FFN_l(N3_l(x)))          normed before it is added
        h^t = N_f(x)                      the final norm is INSIDE the loop
    Attn  q, k, v = a W_q, a W_k, a W_v -> heads x head_dim, no bias, no
          per-head norm;  q, k rotated by position (rotate_half:
          channel i pairs with i + D/2, angle pos * rope_theta^(-2i/D)),
          positions 0 .. S-1, the same in every pass;
          o_h = softmax_{j<=i}(q_h . k_h / sqrt(D)) v_h;   y = [o_h] W_o
    FFN   (silu(b G) * b U) D
    head  z^t = h^t W_head^T,  l^t_i = -log softmax(z^t_i)[y_i]
    gate  a^t_i = h^t_i . w_g + b_g,  lambda^t_i = sigmoid(a^t_i)
          p^t_i = lambda^t_i prod_{j<t} (1 - lambda^j_i)      t < T
          p^T_i = prod_{j<T} (1 - lambda^j_i)     (a^T is used by nothing)
    loss  (1/N) sum_i [ sum_t p^t_i l^t_i + beta sum_t p^t_i log p^t_i ]
          over the N labelled positions, beta = ``exit_entropy_beta``

Departures from the source, each on purpose:

- **the loops are written out**: a ``for`` over passes round a ``for``
  over layers, the four norms spelled out, no scan.
- **attention** by an explicit (rows, S) mask, a block of query rows at
  a time, and **each pass's logits** a block of ``block_rows`` rows at a
  time, so that one row of 8,192 against 49,152 columns fits; the source
  forms the whole square and the whole logits. The same numbers.
- **the exit distribution by the literal products** ``lambda^t prod (1 -
  lambda^j)``, as the paper writes it; the program computes it from
  log-sigmoids. The two have to agree.
- every pass runs: no early exit in training (``early_exit_threshold`` is
  inference's).
- Adam's moments live on the HOST between updates and visit the device
  a leaf at a time (``reference/nemotron_h.train``'s reason).

Parameters are a dict under the program's parameter names
(``drivers/looped_lm_step.param_shapes``). ``matmuls`` is (dense product,
batched product): :data:`F32_MATMULS` here, :data:`fp8_matmuls` for the
lower-precision control; the norms, the rotation, the gate, the exit
distribution and the softmaxes stay float32 there, as they do in the
program's autocast.
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


# ---------------------------------------------------------------------------
# the rotation
# ---------------------------------------------------------------------------
def _rotate_half(x):
    half = x.shape[-1] // 2
    return jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)


def rotate(x, theta: float):
    """x (S, heads, D) at positions 0 .. S-1."""
    s, d = x.shape[0], x.shape[-1]
    inv_freq = 1.0 / theta ** (np.arange(0, d, 2, dtype=np.float64) / d)
    freqs = jnp.arange(s, dtype=jnp.float32)[:, None] \
        * jnp.asarray(inv_freq, jnp.float32)[None, :]
    emb = jnp.concatenate([freqs, freqs], axis=-1)[:, None, :]
    return x * jnp.cos(emb) + _rotate_half(x) * jnp.sin(emb)


# ---------------------------------------------------------------------------
# attention, a block of query rows at a time
# ---------------------------------------------------------------------------
def attention(p, pre, x, cfg, matmuls, block_rows):
    """One row: x (S, hidden). One key head a query head."""
    dense, bmm = matmuls
    heads, d = cfg["num_attention_heads"], cfg["head_dim"]
    if cfg["num_key_value_heads"] != heads:
        raise ValueError("this family has one key head a query head")
    s = x.shape[0]
    theta = float(cfg["rope_theta"])
    q = rotate(dense(x, p[pre + "q_proj.weight"]).reshape(s, heads, d), theta)
    k = rotate(dense(x, p[pre + "k_proj.weight"]).reshape(s, heads, d), theta)
    v = dense(x, p[pre + "v_proj.weight"]).reshape(s, heads, d)
    kh = k.transpose(1, 2, 0)                          # (H, D, S)
    vh = v.transpose(1, 0, 2)                          # (H, S, D)
    rows = min(block_rows, s)
    if s % rows:
        raise ValueError(f"{s} rows are no whole blocks of {rows}")

    @jax.checkpoint
    def block(args):
        qb, start = args                               # (rows, H, D)
        scores = bmm(qb.transpose(1, 0, 2), kh) / math.sqrt(d)
        at = start + jnp.arange(rows)[:, None]
        scores = jnp.where(at >= jnp.arange(s)[None, :], scores, -jnp.inf)
        return bmm(jax.nn.softmax(scores, axis=-1), vh)    # (H, rows, D)

    out = jax.lax.map(block, (q.reshape(s // rows, rows, heads, d),
                              jnp.arange(0, s, rows)))
    out = out.transpose(0, 2, 1, 3).reshape(s, heads * d)
    return dense(out, p[pre + "o_proj.weight"])


# ---------------------------------------------------------------------------
# the looped stack
# ---------------------------------------------------------------------------
def pass_states(p, cfg, ids, matmuls=F32_MATMULS, block_rows=512):
    """[h^1 .. h^T] of one row of token ids (S,): the normed output of
    each pass."""
    dense = matmuls[0]
    eps = cfg["rms_norm_eps"]
    h = p["embed.weight"][ids]
    out = []
    for _t in range(cfg["total_ut_steps"]):
        x = h
        for n in range(cfg["num_hidden_layers"]):
            pre = f"layers.{n}."

            @jax.checkpoint
            def layer(x, p, pre=pre):
                a = _rms_norm(x, p[pre + "input_norm.weight"], eps)
                y = attention(p, pre + "mixer.", a, cfg, matmuls, block_rows)
                x = x + _rms_norm(y, p[pre + "mixer_out_norm.weight"], eps)
                b = _rms_norm(x, p[pre + "post_norm.weight"], eps)
                f = pre + "ffn."
                y = _gated(b, p[f + "gate_proj.weight"],
                           p[f + "up_proj.weight"],
                           p[f + "down_proj.weight"], dense)
                return x + _rms_norm(y, p[pre + "ffn_out_norm.weight"], eps)

            x = layer(x, p)
        h = _rms_norm(x, p["final_norm.weight"], eps)
        out.append(h)
    return out


def pass_losses(p, h, labels, dense, block_rows):
    """(S,) cross-entropy of each position of one pass's states ``h``
    (S, hidden) against ``labels`` (S,), zero where there is no label;
    the logits a block of rows at a time."""
    s = h.shape[0]
    rows = min(block_rows, s)

    @jax.checkpoint
    def block(args):
        hb, lab = args
        logp = jax.nn.log_softmax(dense(hb, p["head"].T), axis=-1)
        ll = jnp.take_along_axis(
            logp, jnp.maximum(lab, 0)[:, None], axis=1)[:, 0]
        return jnp.where(lab != -100, -ll, 0.0)

    return jax.lax.map(block, (h.reshape(s // rows, rows, -1),
                               labels.reshape(s // rows, rows))).reshape(s)


def exit_distribution(gate_logits):
    """[p^1 .. p^T] from the T - 1 gate logits (each (S,)), by the
    literal products."""
    lam = [jax.nn.sigmoid(a) for a in gate_logits]
    stay = jnp.ones_like(lam[0])
    out = []
    for lam_t in lam:
        out.append(lam_t * stay)
        stay = stay * (1.0 - lam_t)
    return out + [stay]


def loss(p, cfg, ids, labels, matmuls=F32_MATMULS, block_rows=512):
    """The expected loss under the exit distribution with the entropy
    term, the mean over the positions of ``labels`` (B, S) that are not
    -100."""
    dense = matmuls[0]
    beta = float(cfg["exit_entropy_beta"])
    n_labelled = jnp.sum(labels != -100)
    total = 0.0
    for row_ids, row_labels in zip(ids, labels):
        states = pass_states(p, cfg, row_ids, matmuls, block_rows)
        each = [pass_losses(p, h, row_labels, dense, block_rows)
                for h in states]
        # the gate stays float32 "highest" in the control too
        gates = [_dense(h, p["exit_gate.weight"])[:, 0] + p["exit_gate.bias"]
                 for h in states[:-1]]
        probs = exit_distribution(gates)
        at = sum(p_t * (l_t + beta * jnp.log(p_t))
                 for p_t, l_t in zip(probs, each))
        total = total + jnp.sum(jnp.where(row_labels != -100, at, 0.0))
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
