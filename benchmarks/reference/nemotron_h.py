"""Plain reference for a Nemotron-H style causal LM (blocks of ONE
sublayer laid out by ``hybrid_override_pattern``: Mamba-2 state-space
layers, grouped-query attention without positions, sigmoid-routed plain
relu^2 experts beside a wider shared one): loss, gradients and AdamW
steps in straightforward ``jax.numpy`` float32 at "highest" matmul
precision. No kernels, no autocast, no chunking; imports nothing of the
program. Written from the layers' equations (ISSUE 33). Layer ``n`` is
``x = x + Sub_n(RMSNorm(x))`` on one row ``x`` (T, hidden), ``Sub_n`` by
the n-th character of the pattern:

    M   [z | xBC | dt] = a W_in
        xBC = silu(conv(xBC) + b_conv)      causal, depthwise, the last
                                            tap on the current token
        [u | B | C] = xBC                   u (T, H, P); B, C (T, G, N);
                                            head h reads group h // (H/G)
        delta = softplus(dt + dt_bias);  A = -exp(A_log)       no clamp
        S_t = exp(delta_t A) S_{t-1} + delta_t u_t (x) B_t     S_0 = 0
        y_t = S_t C_t + D u_t
        y = RMSNorm_group(y * silu(z)) * w  the gate FIRST, then the norm
                                            over each of G groups
        Sub = y W_out
    *   q = a Wq (T, H, D); k = a Wk, v = a Wv (T, Hkv, D); no bias, no
        per-head norm, NO rotation unless the file gives a ``rope`` entry
        o_h = softmax_{j<=i}(q_h . k_{h // (H/Hkv)} / sqrt(D)) v;  Sub = [o] Wo
    E   s = sigmoid(b Wr) over all experts;  S = top-k of s (+ bias)
        w_e = scaling * s_e / sum_{e' in S} s_e'
        Sub = sum_{e in S, held} w_e relu(b U_e)^2 D_e + relu(b U_sh)^2 D_sh
    -   Sub = relu(a U)^2 D

- **the state-space layer is the literal recurrence over t**, a
  ``lax.scan`` whose spans of ``SEGMENT`` tokens are rematerialised so
  that its backward fits; the program's chunked form is not used here.
- **attention** by an explicit (rows, T) mask, a block of query rows at
  a time, the key heads repeated to the query heads.
- **experts** by a dense loop over the experts the share is GIVEN
  (``experts_held`` from ``expert_offset``); what absent experts would
  add is left out. The pick passes no gradient.
- loss: mean next-token cross-entropy over the labelled positions, a
  block of rows at a time.

Parameters are a dict under the program's parameter names
(``drivers/hybrid_ssm_lm_step.param_shapes``). ``matmuls`` is (dense
product, batched product, the rounding of the recurrence's operands):
:data:`F32_MATMULS` here, :data:`fp8_matmuls` for the lower-precision
control, which rounds what the program's autocast rounds, one step
lower: the operands of every matrix product and, in the recurrence, u, B,
C, the write ``delta_t u_t`` and the state as C reads it. The carried
state, the decays, the router, the norms and the convolution stay float32
there, as they do in the program under autocast.
"""
from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

# the AdamW step and the norm are Kimi's, the rotation (the file's other
# reading of the attention) Mellum's
from benchmarks.reference import bert
from benchmarks.reference.bert import _dense, leaf_norms
from benchmarks.reference.kimi_linear import (
    _adamw, _change_norms, _rms_norm, _short_conv)
from benchmarks.reference.mellum2 import rope, rope_inv_freq

_HIGHEST = jax.lax.Precision.HIGHEST
#: tokens between two saved states of the recurrence
SEGMENT = 64
#: a pattern's character -> the sublayer
KINDS = {"M": "mamba2", "*": "attention", "E": "moe", "-": "dense"}


def layer_kinds(cfg: dict) -> list:
    """The sublayer of each layer, from ``hybrid_override_pattern``."""
    return [KINDS[c] for c in
            cfg["hybrid_override_pattern"][:cfg["num_hidden_layers"]]]


def _relu2(x):
    return jnp.square(jax.nn.relu(x))


def _rounded(q_fwd, q_bwd):
    """The identity, with the value through ``q_fwd`` on the way forward
    and the cotangent through ``q_bwd`` on the way back."""
    @jax.custom_vjp
    def through(x):
        return q_fwd(x)

    through.defvjp(lambda x: (q_fwd(x), None), lambda _, g: (q_bwd(g),))
    return through


F32_MATMULS = bert.F32_MATMULS + (lambda x: x,)
#: the control (the driver looks it up here by name): ``bert``'s fp8
#: products, and its round trips (e4m3 forward, e5m2 for cotangents, one
#: scale a tensor) for the recurrence's operands
fp8_matmuls = bert.fp8_matmuls + (_rounded(
    lambda x: bert._fake_quant(x, jnp.float8_e4m3fn),
    lambda x: bert._fake_quant(x, jnp.float8_e5m2)),)


# ---------------------------------------------------------------------------
# Mamba-2: the recurrence
# ---------------------------------------------------------------------------
def ssm_recurrence(u, delta, a, b, c, low=lambda x: x):
    """u: (T, H, P); delta: (T, H); a: (H,); b, c: (T, G, N). The state
    (H, P, N) starts at zero. Returns y (T, H, P) without the skip.
    ``low`` rounds the operands of the two products, the write ``delta_t
    u_t (x) B_t`` and the read ``S_t C_t``; the carried state never."""
    t, h, p = u.shape
    g, n = b.shape[1:]
    u, b, c = low(u), low(b), low(c)
    pad = (-t) % SEGMENT
    if pad:          # tokens that neither write nor decay, dropped below
        u, b, c = (jnp.pad(x, ((0, pad), (0, 0), (0, 0))) for x in (u, b, c))
        delta = jnp.pad(delta, ((0, pad), (0, 0)))

    def token(state, x):
        u_t, d_t, b_t, c_t = x
        b_h, c_h = (jnp.repeat(m, h // g, axis=0) for m in (b_t, c_t))
        state = jnp.exp(d_t * a)[:, None, None] * state \
            + low(d_t[:, None] * u_t)[:, :, None] * b_h[:, None, :]
        return state, jnp.einsum("hpn,hn->hp", low(state), c_h,
                                 precision=_HIGHEST)

    @jax.checkpoint
    def segment(state, xs):
        return jax.lax.scan(token, state, xs)

    xs = tuple(x.reshape((-1, SEGMENT) + x.shape[1:])
               for x in (u, delta, b, c))
    _, y = jax.lax.scan(segment, jnp.zeros((h, p, n), jnp.float32), xs)
    return y.reshape((-1, h, p))[:t]


def mamba2(p, pre, x, cfg, dense, low=lambda x: x):
    """One row: x (T, hidden)."""
    heads, hd = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    groups, n = cfg["n_groups"], cfg["ssm_state_size"]
    inner, gn = heads * hd, groups * n
    t = x.shape[0]
    proj = dense(x, p[pre + "in_proj.weight"])
    z, xbc, dt = (proj[:, :inner], proj[:, inner:2 * inner + 2 * gn],
                  proj[:, 2 * inner + 2 * gn:])
    xbc = jax.nn.silu(_short_conv(xbc, p[pre + "xbc_conv"])
                      + p[pre + "conv_bias"])
    u = xbc[:, :inner].reshape(t, heads, hd)
    b = xbc[:, inner:inner + gn].reshape(t, groups, n)
    c = xbc[:, inner + gn:].reshape(t, groups, n)
    delta = jax.nn.softplus(dt + p[pre + "dt_bias"])
    y = ssm_recurrence(u, delta, -jnp.exp(p[pre + "A_log"]), b, c, low)
    y = (y + p[pre + "D"][:, None] * u).reshape(t, inner)
    y = (y * jax.nn.silu(z)).reshape(t, groups, inner // groups)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True)
                          + cfg["layer_norm_epsilon"])
    return dense(y.reshape(t, inner) * p[pre + "norm_weight"],
                 p[pre + "out_proj.weight"])


# ---------------------------------------------------------------------------
# attention, a block of query rows at a time
# ---------------------------------------------------------------------------
def attention(p, pre, x, cfg, matmuls, block_rows):
    dense, bmm = matmuls[:2]
    heads, kv_heads = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = cfg["head_dim"]
    t = x.shape[0]
    q = dense(x, p[pre + "q_proj.weight"]).reshape(t, heads, d)
    k = dense(x, p[pre + "k_proj.weight"]).reshape(t, kv_heads, d)
    v = dense(x, p[pre + "v_proj.weight"]).reshape(t, kv_heads, d)
    if cfg.get("rope") is not None:     # the file's other reading
        inv_freq, scale = rope_inv_freq(d, cfg["rope"])
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
def router_weights(x, router_w, top_k, scaling, bias=None):
    """(picked (T, k), weight (T, k)): sigmoid scores of all experts, the
    top k of score + bias, the scores renormalised over the picks and
    scaled."""
    scores = jax.nn.sigmoid(_dense(x, router_w))
    _, picked = jax.lax.top_k(scores if bias is None else scores + bias,
                              top_k)
    weight = jnp.take_along_axis(scores, picked, axis=1)
    return picked, scaling * weight / jnp.sum(weight, axis=1, keepdims=True)


def _plain(x, up, down, dense):
    return dense(_relu2(dense(x, up)), down)


def routed(p, pre, x, cfg, dense, router_bias=None):
    """The share's routed part: ``experts_held`` experts from
    ``expert_offset`` are in ``p``; the router scores all
    ``n_routed_experts``."""
    offset = cfg.get("expert_offset", 0)
    picked, weight = router_weights(
        x, p[pre + "router.weight"], cfg["num_experts_per_tok"],
        cfg["routed_scaling_factor"], router_bias)
    out = jnp.zeros_like(x)
    for e in range(p[pre + "experts_up"].shape[0]):
        w_e = jnp.sum(jnp.where(picked == offset + e, weight, 0.0), axis=1)
        out = out + w_e[:, None] * _plain(
            x, p[pre + "experts_up"][e], p[pre + "experts_down"][e], dense)
    return out


def moe(p, pre, x, cfg, dense, router_bias=None):
    """Routed part + the shared expert, unscaled, on every token."""
    return routed(p, pre, x, cfg, dense, router_bias) + _plain(
        x, p[pre + "shared.up_proj.weight"],
        p[pre + "shared.down_proj.weight"], dense)


# ---------------------------------------------------------------------------
# the model and its loss
# ---------------------------------------------------------------------------
def sublayer(kind, p, pre, h, cfg, matmuls, block_rows):
    dense = matmuls[0]
    if kind == "mamba2":
        return mamba2(p, pre + "mixer.", h, cfg, dense, matmuls[2])
    if kind == "attention":
        return attention(p, pre + "mixer.", h, cfg, matmuls, block_rows)
    if kind == "moe":
        return moe(p, pre + "ffn.", h, cfg, dense)
    return _plain(h, p[pre + "ffn.up_proj.weight"],
                  p[pre + "ffn.down_proj.weight"], dense)


def hidden_states(p, cfg, ids, matmuls=F32_MATMULS, block_rows=512):
    """Final-norm hidden states of one row of token ids (T,)."""
    eps = cfg["layer_norm_epsilon"]
    x = p["embed.weight"][ids]
    for n, kind in enumerate(layer_kinds(cfg)):
        pre = f"layers.{n}."

        @jax.checkpoint
        def layer(x, p, pre=pre, kind=kind):
            h = _rms_norm(x, p[pre + "norm.weight"], eps)
            return x + sublayer(kind, p, pre, h, cfg, matmuls, block_rows)

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
    ``reference.mellum2.train`` does (the same in-place, leaf-by-leaf
    update), with one difference: both Adam moments live on the HOST
    between updates and visit the device a leaf at a time. 667 M
    parameters with their gradient and two moments are 10.7 GB, and the
    float32 backward of two 8,192-token rows wants 7.2 GB beside them
    (the first chip run of PR 33 could not load it). Returns the loss of
    each step, the per-leaf norm of the first step's gradient and the
    per-leaf norm of the parameters' change after the last step."""
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
