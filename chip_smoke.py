"""chip_smoke.py — the quickest proof that paddle_tpu still starts on the chip.

Drives the repo's two hot paths once, through the entry points a user
calls, at BERT-base width with seeded random weights, and checks what
comes out by the repo's own means:

  train    models.bert.BertForPretraining + optimizer.AdamW +
           amp.auto_cast("O1", "bfloat16") + jit.TrainStep
           (examples/train_bert.py's path) at 128x128 and 32x512
  serve    inference.decode.DecodeEngine warm()/start()/generate(): a
           greedy engine checked token for token against the dense f32
           oracle, and an int8-KV sampling engine
  kernels  every Pallas family compiled (never interpreted) against its
           XLA reference, at the two phases' shapes and at the edge of
           its gate
  mesh     the train model on create_mesh({"dp": 2, "tp": 2}) — runs
           when jax reports >= 4 devices

One process (a chip belongs to one process at a time); no network; no
fallback: any failed check, any exception, any timeout exits non-zero,
and a platform other than "tpu" exits before the first phase. The last
stdout line of a passing run is one JSON object:

  {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

``python chip_smoke.py [phase ...]`` runs a subset (the builder's way of
spending less chip time while debugging); its JSON line then also names
the phases that ran. Bring-up, not a speed measurement: the times it
prints are set-up and wall times, never a rate.
"""
from __future__ import annotations

import faulthandler
import gc
import json
import math
import os
import sys
import time
import traceback

import numpy as np

#: the contract is 1200 s, compilation included; die (non-zero, with every
#: thread's stack) before the caller's own limit hides where it hung
DEADLINE_S = 1150

PHASES = ("train", "serve", "kernels", "mesh")

# (batch, seq, steps): examples/train_bert.py's shape, and the shape where
# _pallas_ok's 256 floor lets the streaming flash kernel in. Steps: the
# labels are random, so the loss starts at the uninformed optimum
# (ln V + ln 2) and only falls once the fixed batch starts to be
# memorised — Adam's first few unwarmed steps push it UP first (measured
# on a v5e: 11.10 -> 11.25 after 6 steps).
TRAIN_SHAPES = ((128, 128, 24), (32, 512, 8))

SERVE_MODEL = dict(vocab_size=16384, n_layers=12, n_heads=12, head_dim=64,
                   ffn_dim=3072, max_context=2048)
# page_size 128: the engine default of 16 fails _paged_ok (S % 128) and
# would never reach the kernel
SERVE_ENGINE = dict(max_batch=8, page_size=128, max_pages_per_seq=16,
                    n_pages=128, max_queue=64)
SERVE_A_PROMPTS = (64, 100, 128, 200, 256, 300, 384, 500,
                   512, 640, 700, 768, 900, 1000, 1024, 77)
SERVE_B_PROMPTS = (64, 150, 256, 400, 512, 700, 1024, 90)
NEW_TOKENS = 32
# top_k 8 = sampling._KERNEL_TOPK_MAX: a larger k is outside _sample_ok
# and would never reach the sampling kernel
SERVE_B_SAMPLING = dict(kv_codec="int8", temperature=0.8, top_k=8,
                        sample_seed=7)
#: engine token accepted when the oracle's logit for it is within this of
#: the oracle's max (both run at "highest" matmul precision, so anything
#: but a numerical tie is far outside it)
LOGIT_TIE_TOL = 1e-3

_compiles = [0]
_t0 = time.monotonic()


def _count_compile(event, _secs, **_kw) -> None:
    """jax monitoring listener: one event per compile request (a jit
    cache miss in this process, whether or not the disk cache serves it)."""
    if event == "/jax/core/compile/backend_compile_duration":
        _compiles[0] += 1


def log(msg: str = "") -> None:
    print(msg, flush=True)


def _disk_counts() -> tuple:
    from paddle_tpu import profiler

    snap = profiler.counters_snapshot()
    return (snap.get("disk_cache_hits", 0), snap.get("disk_cache_misses", 0))


class _Window:
    """Compilations and disk-cache traffic inside a ``with`` block."""

    def __enter__(self):
        self._c, self._d, self._t = _compiles[0], _disk_counts(), \
            time.monotonic()
        return self

    def __exit__(self, *exc):
        d = _disk_counts()
        self.compiles = _compiles[0] - self._c
        self.disk_hits = d[0] - self._d[0]
        self.disk_misses = d[1] - self._d[1]
        self.seconds = time.monotonic() - self._t
        return False

    def __str__(self):
        return (f"compiles={self.compiles} disk_hits={self.disk_hits} "
                f"disk_misses={self.disk_misses} wall={self.seconds:.1f}s")


def _verdicts(prefix=None) -> dict:
    """Autotune verdicts taken so far, keyed by their cache key."""
    from paddle_tpu.ops.pallas import autotune

    out = {}
    for k, v in autotune.cached_choices().items():
        fam = k[0] if isinstance(k[0], str) else "flash"
        if prefix is None or fam == prefix:
            out[k] = v
    return out


def _engaged(fails, what, counts, family, verdict_family, xla_ok=True):
    """``family``.pallas >= 1 — or 0 only beside a printed autotune
    verdict that XLA won (the kernels phase is then what proves the
    family compiles)."""
    n = counts.get(f"{family}.pallas", 0) + \
        counts.get(f"{family}.pallas_sharded", 0)
    if n >= 1:
        return
    lost = {k: v for k, v in _verdicts(verdict_family).items()
            if v == "xla"}
    if xla_ok and lost:
        log(f"  {what}: {family}.pallas == 0 beside autotune verdicts "
            f"that XLA won: {lost}")
        return
    fails.append(f"{what}: {family}.pallas == 0 with no autotune verdict "
                 f"for XLA (counts {counts})")


# ---------------------------------------------------------------------------
# train
# ---------------------------------------------------------------------------
def _bert_batch(cfg, batch, seq, seed=0):
    import paddle_tpu as paddle

    rng = np.random.RandomState(seed)
    return (
        paddle.to_tensor(
            rng.randint(0, cfg.vocab_size, (batch, seq)).astype(np.int32)),
        paddle.to_tensor(np.zeros((batch, seq), np.int32)),
        paddle.to_tensor(
            rng.randint(0, cfg.vocab_size, (batch, seq)).astype(np.int32)),
        paddle.to_tensor(rng.randint(0, 2, (batch,)).astype(np.int32)))


def _bert_step(cfg, **step_kwargs):
    import paddle_tpu as paddle
    from paddle_tpu import amp, optimizer
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.models.bert import BertForPretraining

    paddle.seed(0)
    model = BertForPretraining(cfg)
    opt = optimizer.AdamW(learning_rate=1e-4,
                          parameters=model.parameters())

    def loss_fn(m, ids, tt, mlm, nsp):
        with amp.auto_cast(level="O1", dtype="bfloat16"):
            return m.loss(ids, tt, mlm, nsp)

    return TrainStep(model, loss_fn, opt, **step_kwargs), model


def train_phase(cfg, shapes=TRAIN_SHAPES, results=None) -> list:
    from paddle_tpu.ops.pallas import counters

    fails = []
    step, _ = _bert_step(cfg)
    for batch, seq, steps in shapes:
        what = f"train {batch}x{seq}"
        data = _bert_batch(cfg, batch, seq)
        before = counters.snapshot()
        with _Window() as first:
            loss0 = float(step(*data))
        with _Window() as rest:
            losses = [float(step(*data)) for _ in range(steps - 1)]
        counts = counters.delta(before)
        log(f"  {what}: first step (compile + run) {first}")
        log(f"  {what}: next {steps - 1} steps {rest}")
        log(f"  {what}: loss " + " ".join(
            f"{x:.3f}" for x in [loss0] + losses))
        log(f"  {what}: pallas counters {counts}")
        if results is not None:
            results[(batch, seq)] = loss0
        if not np.all(np.isfinite([loss0] + losses)):
            fails.append(f"{what}: non-finite loss {[loss0] + losses}")
        elif not losses[-1] < loss0:
            fails.append(f"{what}: loss did not fall "
                         f"({loss0:.4f} -> {losses[-1]:.4f})")
        if rest.compiles:
            fails.append(f"{what}: {rest.compiles} compilations after "
                         "the first step")
        _engaged(fails, what, counts, "flash_attention", "flash",
                 xla_ok=True)
        _engaged(fails, what, counts, "fused_xent", None, xla_ok=False)
    from paddle_tpu.ops.pallas import autotune

    log(f"  autotune stats {autotune.stats()}; verdicts {_verdicts()}")
    return fails


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------
def _run_engine(name, cfg, engine_kw, lengths, seed, new_tokens, fails):
    """warm() / start() / submit everything at once / stop(): returns
    (engine, prompts, outputs, pallas counter delta). A request that ends
    in an error (RequestFailed and friends), a decode_failed count and a
    compilation while serving a greedy engine are failures."""
    from paddle_tpu.inference.decode import DecodeEngine
    from paddle_tpu.ops.pallas import counters

    before = counters.snapshot()
    eng = DecodeEngine(cfg, seed=0, **engine_kw)
    with _Window() as warm:
        n_exec = eng.warm()
    log(f"  {name}: warm() built {n_exec} executables {warm}")
    rng = np.random.RandomState(seed)
    prompts = [rng.randint(0, cfg.vocab_size, (n,)).tolist()
               for n in lengths]
    eng.start()
    try:
        with _Window() as run:
            handles = [eng.submit(p, max_new_tokens=new_tokens)
                       for p in prompts]
            outs = []
            for i, h in enumerate(handles):
                try:
                    outs.append(h.result(timeout=600))
                except Exception as e:      # reported, and fails the phase
                    outs.append(None)
                    fails.append(f"{name} request {i} (prompt "
                                 f"{len(prompts[i])}): "
                                 f"{type(e).__name__}: {e}")
    finally:
        eng.stop()
    c = eng.counters
    counts = counters.delta(before)
    log(f"  {name}: {len(prompts)} concurrent requests, prompts "
        f"{min(lengths)}-{max(lengths)}, {new_tokens} new tokens each, "
        f"{run}")
    log(f"  {name}: decode_steps={c.get('decode_steps', 0)} "
        f"decode_prefills={c.get('decode_prefills', 0)} "
        f"decode_failed={c.get('decode_failed', 0)}; pallas counters "
        f"{counts}")
    if c.get("decode_failed", 0):
        fails.append(f"{name}: decode_failed={c['decode_failed']}")
    # a sampled engine draws its first token eagerly after prefill, which
    # compiles; a greedy one runs nothing but what warm() built
    if run.compiles and not engine_kw.get("temperature"):
        fails.append(f"{name}: {run.compiles} compilations while serving "
                     "after warm()")
    return eng, prompts, outs, counts


def _oracle_check(cfg, params, prompts, outs, new_tokens, tol) -> tuple:
    """Teacher-forced greedy parity against the dense f32 oracle
    (model.dense_forward): position i of a request is right when the
    engine's token is the argmax of dense_forward(prompt + its earlier
    tokens). By induction that is exactly ``engine output ==
    reference_generate(prompt)`` — one dense forward per request instead
    of one eager recompute (and a fresh compile per length) per token."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.inference.decode.model import dense_forward

    longest = max(len(p) for p in prompts) + new_tokens
    pad_to = -(-longest // 128) * 128

    @jax.jit
    def rows(tokens, start):
        logits = dense_forward(cfg, params, tokens)[0]
        return jax.lax.dynamic_slice_in_dim(logits, start, new_tokens, 0)

    exact = ties = 0
    bad = []
    for i, (p, out) in enumerate(zip(prompts, outs)):
        if out is None:
            continue
        if len(out) != new_tokens:
            bad.append(f"request {i}: {len(out)} tokens, want {new_tokens}")
            continue
        toks = np.zeros((1, pad_to), np.int32)
        toks[0, :len(p)] = p
        toks[0, len(p):len(p) + new_tokens] = out
        ref = np.asarray(rows(jnp.asarray(toks), len(p) - 1))
        for j, tok in enumerate(out):
            gap = float(ref[j].max() - ref[j, tok])
            if int(ref[j].argmax()) == tok:
                exact += 1
            elif gap <= tol:
                ties += 1
            else:
                bad.append(f"request {i} (prompt {len(p)}) token {j}: "
                           f"engine {tok}, oracle {int(ref[j].argmax())}, "
                           f"oracle logit gap {gap:.4g}")
    return exact, ties, bad


def serve_phase(model_cfg=None, engine_kw=None, prompts_a=SERVE_A_PROMPTS,
                prompts_b=SERVE_B_PROMPTS, new_tokens=NEW_TOKENS) -> list:
    import jax

    from paddle_tpu.inference.decode import DecodeModelConfig
    from paddle_tpu.ops.pallas import autotune

    cfg = DecodeModelConfig(**(model_cfg or SERVE_MODEL))
    engine_kw = dict(engine_kw or SERVE_ENGINE)
    fails = []

    # -- engine A: greedy (the async tick), token parity with the oracle.
    # Both sides run at "highest" matmul precision: at the TPU default an
    # f32 dot is one bf16 pass, and two correct programs then disagree
    # about near-tied logits.
    jax.config.update("jax_default_matmul_precision", "highest")
    try:
        eng, prompts, outs, counts = _run_engine(
            "engine A (greedy)", cfg, engine_kw, prompts_a, 1, new_tokens,
            fails)
        exact, ties, bad = _oracle_check(cfg, eng.params, prompts, outs,
                                         new_tokens, LOGIT_TIE_TOL)
        log(f"  engine A vs dense oracle: {exact} tokens equal, {ties} "
            f"numerical ties (gap <= {LOGIT_TIE_TOL}), {len(bad)} wrong")
        fails += bad[:8]
        _engaged(fails, "engine A", counts, "paged_attention", "paged")
        del eng
        gc.collect()
    finally:
        jax.config.update("jax_default_matmul_precision", None)

    # -- engine B: int8 KV pages + in-step sampling (the sync tick), at
    # the default matmul precision
    eng, _prompts, outs, counts = _run_engine(
        "engine B (int8 KV, sampled)", cfg,
        {**engine_kw, **SERVE_B_SAMPLING}, prompts_b, 2, new_tokens, fails)
    for i, out in enumerate(outs):
        if out is not None and (
                len(out) != new_tokens or
                not all(0 <= t < cfg.vocab_size for t in out)):
            fails.append(f"engine B request {i}: bad output {out}")
    # the int8 leg takes no autotune verdict (paged_attention.py): the
    # kernel is the static dispatch there
    _engaged(fails, "engine B", counts, "paged_attention", "paged",
             xla_ok=False)
    _engaged(fails, "engine B", counts, "fused_sample", "sample")
    log(f"  autotune stats {autotune.stats()}; verdicts "
        f"{ {**_verdicts('paged'), **_verdicts('sample')} }")
    del eng
    gc.collect()
    return fails


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------
def _close(name, got, want, tol, fails):
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    if got.shape != want.shape:
        fails.append(f"{name}: shape {got.shape} != {want.shape}")
        return
    if not np.all(np.isfinite(got)):
        fails.append(f"{name}: non-finite values")
        return
    err = float(np.max(np.abs(got - want)))
    scale = float(np.max(np.abs(want))) or 1.0
    if err > tol * scale:
        fails.append(f"{name}: max |err| {err:.3g} > {tol} x {scale:.3g}")


def _kernel_checks():
    """[(name, check)]: each check(fails) compiles one family at one
    shape and compares it with its XLA reference."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas import autotune  # noqa: F401 (its flags)
    from paddle_tpu.ops.pallas import flash_attention as fa
    from paddle_tpu.ops.pallas import fused_embedding as fe
    from paddle_tpu.ops.pallas import fused_xent as fx
    from paddle_tpu.ops.pallas import paged_attention as pa
    from paddle_tpu.ops.pallas import sampling as sp

    def rnd(seed, shape, dtype=jnp.float32, scale=1.0):
        return (jax.random.normal(jax.random.key(seed), shape, jnp.float32)
                * scale).astype(dtype)

    def tol_of(dtype):
        return 3e-2 if dtype == jnp.bfloat16 else 2e-4

    checks = []

    # -- flash attention: value and all three gradients ---------------------
    def flash_check(name, kernel, ref, shape, dtype, gate):
        def check(fails):
            q, k, v = (rnd(s, shape, dtype) for s in (1, 2, 3))
            w = rnd(4, shape, jnp.float32)
            if not gate(q, k):
                fails.append(f"{name}: shape {shape} is outside its gate")
                return

            def run(f):
                return jax.jit(jax.value_and_grad(
                    lambda q, k, v: jnp.sum(
                        f(q, k, v).astype(jnp.float32) * w),
                    argnums=(0, 1, 2)))(q, k, v)

            (_, got), (_, want) = run(kernel), run(ref)
            _close(f"{name} out", jax.jit(kernel)(q, k, v),
                   jax.jit(ref)(q, k, v), tol_of(dtype), fails)
            for g, r, nm in zip(got, want, "qkv"):
                _close(f"{name} d{nm}", g, r, tol_of(dtype), fails)
        return check

    def flash(name, *args):
        checks.append((name, flash_check(name, *args)))

    def xla(causal=False, mask=None):
        return lambda q, k, v: fa._xla_attention(q, k, v, mask, 0.0,
                                                 causal, None)

    seed = jnp.asarray([[17]], jnp.int32)
    # dropout_p so small that the keep threshold int(p * 2^32) is 0: every
    # bit pattern is kept, so the dropout kernels (PRNG seeded and drawn,
    # rescale applied) must reproduce the no-dropout reference
    p0 = 1e-10
    bf16, f32 = jnp.bfloat16, jnp.float32
    for shape, dtype, tag in (((32, 512, 12, 64), bf16, "bert512"),
                              ((1, 8192, 1, 256), bf16, "gate edge"),
                              ((1, 8192, 1, 256), f32, "gate edge f32")):
        ok = lambda q, k: fa._pallas_ok(q, k, False)
        flash(f"flash stream {tag} {shape}",
              lambda q, k, v: fa._flash_attention_pallas(q, k, v),
              xla(), shape, dtype, ok)
    flash("flash stream causal (4, 1024, 4, 128)",
          lambda q, k, v: fa._flash_attention_pallas(q, k, v, causal=True),
          xla(causal=True), (4, 1024, 4, 128), bf16,
          lambda q, k: fa._pallas_ok(q, k, True))
    flash("flash stream dropout-kernel bert512 (32, 512, 12, 64)",
          lambda q, k, v: fa._flash_attention_pallas_dropout(
              q, k, v, seed, p0),
          xla(), (32, 512, 12, 64), bf16,
          lambda q, k: fa._pallas_ok(q, k, False))

    def flash_causal(label, q_shape, k_shape, v_shape, window=None):
        """The streaming kernels, causal, bfloat16, at the sequence
        ceiling, on operands whose shapes differ: output and the three
        gradients against the XLA path."""
        def check(fails):
            q, k, v = (rnd(s, shape, bf16) for s, shape in
                       ((1, q_shape), (2, k_shape), (3, v_shape)))
            w = rnd(4, q_shape[:-1] + v_shape[-1:], f32)
            if not fa._pallas_ok(q, k, True, v=v):
                fails.append(f"flash stream {label}: {q_shape} is outside "
                             "its gate")
                return

            def run(f):
                return jax.jit(jax.value_and_grad(
                    lambda q, k, v: jnp.sum(f(q, k, v).astype(f32) * w),
                    argnums=(0, 1, 2)))(q, k, v)

            def kernel(q, k, v):
                return fa._flash_attention_pallas(q, k, v, causal=True,
                                                  window=window)

            def ref(q, k, v):
                return fa._xla_attention(q, k, v, None, 0.0, True, None,
                                         window=window)

            (_, got), (_, want) = run(kernel), run(ref)
            _close(f"flash stream {label} out", jax.jit(kernel)(q, k, v),
                   jax.jit(ref)(q, k, v), tol_of(bf16), fails)
            for g, r, nm in zip(got, want, "qkv"):
                _close(f"flash stream {label} d{nm}", g, r, tol_of(bf16),
                       fails)
        return check
    # MLA's widths: keys and queries 192 wide, values 128. Four heads: the
    # XLA reference keeps (heads, 8192, 8192) scores
    checks.append(("flash stream causal, MLA widths (1, 8192, 4, 192 / "
                   "128)", flash_causal("MLA", (1, 8192, 4, 192),
                                        (1, 8192, 4, 192),
                                        (1, 8192, 4, 128))))
    # eight query heads on two key heads of 128, with and without a
    # sliding window: K and V through the block index maps, dK and dV
    # summed over the group in the kernel, the band's blocks alone visited
    for window in (None, 1024):
        checks.append((
            f"flash stream grouped 8q/2kv (1, 8192, ., 128) window={window}",
            flash_causal(f"grouped window={window}", (1, 8192, 8, 128),
                         (1, 8192, 2, 128), (1, 8192, 2, 128), window)))

    # eight query heads on two key heads of 64 (PR 46): the stream
    # kernels on 64-lane blocks, half a vreg
    checks.append((
        "flash stream grouped 8q/2kv (1, 8192, ., 64)",
        flash_causal("grouped, heads of 64", (1, 8192, 8, 64),
                     (1, 8192, 2, 64), (1, 8192, 2, 64))))

    # sixteen query heads on two key heads of 256 (PR 49): the widest
    # head the dispatch admits. The XLA reference keeps (heads, 8192,
    # 8192) scores, so it runs a key head's group at a time
    def flash_heads_of_256(fails, label="grouped 16q/2kv, heads of 256"):
        q, k, v = (rnd(s, (1, 8192, h, 256), bf16)
                   for s, h in ((1, 16), (2, 2), (3, 2)))
        w = rnd(4, (1, 8192, 16, 256), f32)
        if not fa._pallas_ok(q, k, True, v=v):
            fails.append(f"flash stream {label}: outside its gate")
            return

        def run(f, q, k, v, w):
            out, grads = jax.jit(jax.value_and_grad(
                lambda q, k, v: (lambda o: (jnp.sum(o.astype(f32) * w), o))(
                    f(q, k, v)), argnums=(0, 1, 2), has_aux=True))(q, k, v)
            return (out[1],) + grads

        got = run(lambda q, k, v: fa._flash_attention_pallas(
            q, k, v, causal=True), q, k, v, w)
        halves = [run(lambda q, k, v: fa._xla_attention(
            q, k, v, None, 0.0, True, None), q[:, :, 8 * n:8 * n + 8],
            k[:, :, n:n + 1], v[:, :, n:n + 1], w[:, :, 8 * n:8 * n + 8])
            for n in range(2)]
        for g, nm, parts in zip(got, ("out", "dq", "dk", "dv"),
                                zip(*halves)):
            _close(f"flash stream {label} {nm}", g,
                   jnp.concatenate(parts, axis=2), tol_of(bf16), fails)
    checks.append(("flash stream grouped 16q/2kv (1, 8192, ., 256)",
                   flash_heads_of_256))

    def flash_masked(fails, shape=(8, 512, 12, 64)):
        b, l = shape[:2]    # ragged key-padding: row i keeps l - 37 i keys
        keep = jnp.arange(l)[None, :] < l - 37 * jnp.arange(b)[:, None]
        bias = fa._kv_mask_bias(keep, b, l)
        flash_check(
            "flash stream masked",
            lambda q, k, v: fa._flash_attention_pallas_masked(q, k, v, bias),
            xla(mask=keep[:, None, None, :]), shape, bf16,
            lambda q, k: fa._pallas_ok(q, k, False))(fails)
    checks.append(("flash stream masked (8, 512, 12, 64)", flash_masked))

    # packed (B, L, H*D) blocks: two 64-wide heads a block (the seq512
    # cell's own shape), one 128- or 256-wide head a block; an odd head
    # count at 64 keeps the transposing wrapper
    for shape, dtype, tag in (((128, 128, 12, 64), bf16, "bert128"),
                              ((64, 512, 12, 64), bf16, "bert512"),
                              ((8, 512, 4, 128), bf16, "one head a block"),
                              ((8, 512, 3, 64), bf16, "odd heads, merged"),
                              ((2, 512, 2, 256), f32, "gate edge")):
        ok = lambda q, k: fa._short_ok(q, k, False)
        flash(f"flash short {tag} {shape}",
              lambda q, k, v: fa._flash_attention_pallas_short(q, k, v),
              xla(), shape, dtype, ok)
        flash(f"flash short dropout-kernel {tag} {shape}",
              lambda q, k, v: fa._flash_attention_pallas_short(
                  q, k, v, seed=seed, dropout_p=p0),
              xla(), shape, dtype, ok)

    def flash_short_dropout_live(fails, p=0.25):
        """Dropout on in a block of two heads (float32, so that a mask is
        the only thing that can tell runs apart). The second head is a
        copy of the first: the same q, k, v must come out different,
        because each head seeds a mask of its own, and as they come out
        of the merged layout, where a head is a row and its index the
        program's. The backward must draw the forward's masks again: the
        output is linear in v, so <out, w> = <v, dv> for the mask the
        forward drew and for no other, and the slopes along a direction
        in q and k are those of the forward run twice on one seed."""
        b, l, h, d = 4, 256, 2, 64
        q, k, v, w, u = (jnp.repeat(rnd(s, (b, l, 1, d)), h, axis=2)
                         for s in (1, 2, 3, 4, 5))

        def short(q, k, v, s=seed):
            return fa._flash_attention_pallas_short(q, k, v, seed=s,
                                                    dropout_p=p)
        out = jax.jit(short)(q, k, v)
        if not bool(jnp.all(out == jax.jit(short)(q, k, v))) or \
                bool(jnp.all(out == short(q, k, v, seed + 1))):
            fails.append("flash short dropout: not a function of its seed")
        if bool(jnp.all(out[:, :, 0] == out[:, :, 1])):
            fails.append("flash short dropout: the two heads of a block "
                         "drew one mask")
        plain = fa._flash_attention_pallas_short(q, k, v)
        if not bool(jnp.all(plain[:, :, 0] == plain[:, :, 1])):
            fails.append("flash short: equal heads of a block differ "
                         "with dropout off")
        # one head a row: (b*h, l, 1, d) takes the merged layout, and
        # row b*h + a is head a of batch b
        rows = [jnp.swapaxes(x, 1, 2).reshape(b * h, l, 1, d)
                for x in (q, k, v)]
        merged = jnp.swapaxes(short(*rows).reshape(b, h, l, d), 1, 2)
        _close("flash short dropout: packed heads against merged rows",
               out, merged, 2e-4, fails)

        loss = lambda q, k, v: jnp.sum(short(q, k, v) * w)  # noqa: E731
        val, (dq, dk, dv) = jax.jit(jax.value_and_grad(
            loss, argnums=(0, 1, 2)))(q, k, v)
        _close("flash short dropout <out, w> = <v, dv>", val,
               jnp.sum(v * dv), 2e-4, fails)
        eps = 1e-2
        for nm, g, at in (("q", dq, lambda t: (q + t * u, k, v)),
                          ("k", dk, lambda t: (q, k + t * u, v))):
            slope = (loss(*at(eps)) - loss(*at(-eps))) / (2 * eps)
            _close(f"flash short dropout slope along d{nm}",
                   jnp.sum(g * u), slope, 2e-2, fails)
    checks.append(("flash short dropout p=0.25, two heads a block "
                   "(4, 256, 2, 64)", flash_short_dropout_live))

    def flash_dropout_live(fails):
        """p = 0.1: finite, reproducible for a seed, different across
        seeds, and unbiased — the mean over 32 seeds approaches the
        undropped output (one draw is off by ~1/3 of it, 32 by ~0.06)."""
        shape = (2, 512, 4, 64)
        q, k, v = (rnd(s, shape, bf16) for s in (1, 2, 3))
        f = jax.jit(lambda s: fa._flash_attention_pallas_dropout(
            q, k, v, s, 0.1).astype(f32))
        draws = [f(seed + i) for i in range(32)]
        base = jax.jit(xla())(q, k, v).astype(f32)
        if not bool(jnp.all(jnp.isfinite(jnp.stack(draws)))):
            fails.append("flash dropout p=0.1: non-finite")
        if not bool(jnp.all(draws[0] == f(seed))) or \
                bool(jnp.all(draws[0] == draws[1])):
            fails.append("flash dropout p=0.1: not a function of its seed")
        rel = float(jnp.abs(jnp.mean(jnp.stack(draws), 0) - base).mean()
                    / jnp.abs(base).mean())
        if rel > 0.12:
            fails.append(f"flash dropout p=0.1: mean of 32 draws off by "
                         f"{rel:.3f} of the undropped output")
    checks.append(("flash stream dropout p=0.1 (2, 512, 4, 64)",
                   flash_dropout_live))

    # -- fused vocab cross-entropy: loss and dh, dW, db ----------------------
    def xent(n, hd, v, dtype, tag, unlabelled=lambda i: i % 7 == 0):
        name = f"fused xent {tag} n={n} hd={hd} v={v}"

        def check(fails):
            if not fx._eligible(n, hd, v):
                fails.append(f"{name}: outside its gate")
                return
            h = rnd(1, (n, hd), dtype, 0.2)
            w = rnd(2, (v, hd), dtype, 0.2)
            b = rnd(3, (v,), f32, 0.1)
            lab = jax.random.randint(jax.random.key(4), (n,), 0, v)
            lab = jnp.where(unlabelled(jnp.arange(n)), -100, lab)

            def ref(h, w, b):
                logits = jnp.dot(h, w.T, preferred_element_type=f32) + b
                valid = lab != -100
                lse = jax.scipy.special.logsumexp(logits, axis=-1)
                ll = jnp.take_along_axis(
                    logits, jnp.where(valid, lab, 0)[:, None], 1)[:, 0]
                return jnp.sum(jnp.where(valid, lse - ll, 0.0)) / \
                    jnp.sum(valid)

            def run(f):
                return jax.jit(jax.value_and_grad(f, argnums=(0, 1, 2)))(
                    h, w, b)

            (lg, gg), (lr, gr) = run(
                lambda h, w, b: fx._fused_xent_core(h, w, b, lab, -100)), \
                run(ref)
            _close(f"{name} loss", lg, lr, tol_of(dtype), fails)
            for g, r, nm in zip(gg, gr, ("h", "W", "b")):
                _close(f"{name} d{nm}", g, r, tol_of(dtype), fails)
        checks.append((name, check))

    xent(16384, 768, 30592, bf16, "bert mlm head")
    # 80 labels in 512, as the benchmark feeds them: the 4,096-row rung
    xent(16384, 768, 30592, bf16, "bert mlm head, 80 of 512 labelled",
         unlabelled=lambda i: i % 512 >= 80)
    xent(1024, 2048, 32768, f32, "gate edge")
    xent(2048, 1024, 32768, f32, "gate edge (largest _fits count)")
    xent(256, 2048, 128, bf16, "gate edge small")
    # a causal LM's head: every row but the last labelled, the top rung
    xent(8192, 2304, 20480, f32, "causal-LM head, hidden 2304, top rung",
         unlabelled=lambda i: i == 8191)

    def xent_cells_call(fails, n=32768, hd=768, v=30592, every=512, held=80):
        """The BERT cells' own call, which runs at the step's precision:
        float32 h and table at the DEFAULT matmul precision, where the
        kernels round both to bfloat16 once before the call as the MXU
        would on every grid step, through the ladder to the 8,192-row
        rung. The logits path sees the operands as the product does and
        only the labelled rows: the others add nothing to the loss or to
        any gradient, and the logits of all 32,768 would be 4 GB."""
        name = f"fused xent n={n} hd={hd} v={v}"
        h, w = rnd(1, (n, hd)), rnd(2, (v, hd), scale=0.02)
        b = rnd(3, (v,), scale=0.01)
        lab = jax.random.randint(jax.random.key(4), (n,), 0, v)
        labelled = np.arange(n) % every < held
        lab = jnp.where(labelled, lab, -100)
        rows = np.flatnonzero(labelled)

        def rounded(x):
            return x.astype(bf16).astype(f32)

        def ref(hl, w, b):
            logits = rounded(hl) @ rounded(w).T + b
            lse = jax.scipy.special.logsumexp(logits, axis=-1)
            ll = jnp.take_along_axis(logits, lab[rows][:, None], 1)[:, 0]
            return jnp.mean(lse - ll), lse

        (lr, lse_r), (dhl, dw_r, db_r) = jax.jit(jax.value_and_grad(
            ref, argnums=(0, 1, 2), has_aux=True))(h[rows], w, b)
        dh_r = jnp.zeros_like(h).at[rows].set(dhl)
        with jax.default_matmul_precision("default"):
            ladder = fx._ladder(n, fx._blocks(h, w)[0])
            if min(k for k in ladder if k >= rows.size) != 8192:
                fails.append(f"{name}: {rows.size} labelled rows do not "
                             f"land on the 8,192-row rung of {ladder}")
            lg, gg = jax.jit(jax.value_and_grad(
                lambda h, w, b: fx._fused_xent_core(h, w, b, lab, -100),
                argnums=(0, 1, 2)))(h, w, b)
            lse, _ = jax.jit(lambda hl, w, b: fx._fwd_call(
                hl, w, b, lab[rows], *fx._blocks(hl, w)))(h[rows], w, b)
        _close(f"{name} loss", lg, lr, tol_of(f32), fails)
        # the probabilities are rounded to bfloat16 before dh's and dW's
        # products; db sums them in float32
        for g, r, nm, dt in zip(gg, (dh_r, dw_r, db_r), "hWb",
                                (bf16, bf16, f32)):
            _close(f"{name} d{nm}", g, r, tol_of(dt), fails)
        # summation order only: on a v5e 7e-7 of the largest lse (PR 30)
        _close(f"{name} lse", lse, lse_r, 4e-6, fails)
    checks.append(("fused xent, the BERT cells' call: float32 at the default "
                   "precision, 80 of 512 labelled", xent_cells_call))

    def xent_rows_call(fails, n=32768, hd=2048, v=49152, block=2048):
        """A looped model's ONE head call (``reduction="none"`` on its
        four passes' rows stacked, every row but a pass's last labelled:
        the top rung), as its step runs it: float32 rows and table at the
        DEFAULT matmul precision. A loss a row out, a weight a row in as
        the cotangent. The logits path sees the operands as the product
        does, a block of rows at a time: all of them would be 6.4 GB."""
        from paddle_tpu.ops.pallas import counters

        name = f"fused xent per row n={n} hd={hd} v={v}"
        h, w = rnd(1, (n, hd)), rnd(2, (v, hd), scale=0.02)
        b = jnp.zeros((v,), f32)
        lab = jax.random.randint(jax.random.key(4), (n,), 0, v)
        lab = jnp.where(jnp.arange(n) % 8192 == 8191, -100, lab)
        weight = jax.random.uniform(jax.random.key(5), (n,), f32, 0.1, 1.0)

        def rounded(x):
            return x.astype(bf16).astype(f32)

        def ref(h, w, b):
            @jax.checkpoint
            def rows_of(args):
                hb, lb = args
                logits = rounded(hb) @ rounded(w).T + b
                lse = jax.scipy.special.logsumexp(logits, axis=-1)
                ll = jnp.take_along_axis(
                    logits, jnp.maximum(lb, 0)[:, None], 1)[:, 0]
                return jnp.where(lb != -100, lse - ll, 0.0)

            rows = jax.lax.map(rows_of, (h.reshape(-1, block, hd),
                                         lab.reshape(-1, block))).reshape(n)
            return jnp.sum(rows * weight), rows

        def fused(h, w, b):
            rows = fx.fused_linear_cross_entropy(h, w, b, lab,
                                                 reduction="none")
            return jnp.sum(rows * weight), rows

        def run(f):
            return jax.jit(jax.value_and_grad(f, argnums=(0, 1, 2),
                                              has_aux=True))(h, w, b)

        before = counters.snapshot()
        with jax.default_matmul_precision("default"):
            (_, rows_g), gg = run(fused)
        took = counters.delta(before)
        if not (took.get("fused_xent.per_row") and
                took.get("fused_xent.pallas")):
            fails.append(f"{name}: not dispatched to the kernels ({took})")
        (_, rows_r), gr = run(ref)
        _close(f"{name} rows", rows_g, rows_r, tol_of(f32), fails)
        for g, r, nm, dt in zip(gg, gr, "hWb", (bf16, bf16, f32)):
            _close(f"{name} d{nm}", g, r, tol_of(dt), fails)
    checks.append(("fused xent per row, a looped head's call: 4 x 8192 rows "
                   "at hidden 2048 on 49152 columns", xent_rows_call))

    # -- KDA chunk kernels against the same chunk formulas under XLA: on
    # random keys at the family's starting decays, and on keys at a mean
    # cosine of 0.8 with beta 0.99 and weak decay, where an inverse formed
    # from powers of the whole chunk's matrix is lost to rounding (PR 38) ----
    def kda_chunks(cosine, shape=(1, 8192, 32, 128)):
        name = f"kda chunk, key cosine {cosine}"

        def check(fails):
            from paddle_tpu.ops.pallas import kda

            def unit(x):
                return x / jnp.linalg.norm(x, axis=-1, keepdims=True)

            q, k = unit(rnd(1, shape)) * shape[-1] ** -0.5, unit(rnd(2, shape))
            v, w = rnd(3, shape), rnd(4, shape)
            if cosine:
                common = unit(rnd(7, (1, 1) + shape[2:]))
                k = unit(cosine ** 0.5 * common + (1 - cosine) ** 0.5 * k)
                g = -jax.random.uniform(jax.random.key(5), shape, f32,
                                        1e-3, 0.05)
                beta = jnp.full(shape[:3], 0.99, f32)
            else:
                # log decays of the family's start: -A softplus(.), A in
                # [1, 16]
                g = -jax.random.uniform(jax.random.key(5), shape, f32,
                                        1e-3, 1.6)
                beta = jax.nn.sigmoid(rnd(6, shape[:3]))
            if not kda._kernel_takes(shape[3], shape[3], kda.CHUNK):
                fails.append(f"{name}: {shape} is outside its gate")
                return

            def flat(x):
                return x.reshape(*shape[:2], -1)

            def run(kernel):
                return jax.jit(jax.value_and_grad(
                    lambda *a: jnp.sum(kda._chunk_kda(*a, kda.CHUNK, kernel)
                                       * flat(w)), argnums=(0, 1, 2, 3, 4)))(
                    flat(q), flat(k), flat(v), flat(g), beta)

            (lk, got), (lx, want) = run(True), run(False)
            _close(f"{name} sum(o w)", lk, lx, tol_of(f32), fails)
            for a, r, nm in zip(got, want, ("q", "k", "v", "g", "beta")):
                _close(f"{name} d{nm}", a, r, tol_of(f32), fails)
        checks.append((f"kda chunk fwd + bwd {shape}, key cosine {cosine}",
                       check))

    kda_chunks(0)
    kda_chunks(0.8)

    # -- the state-space scan's kernels against the same chunk formula under
    # XLA, which jax differentiates: the hand-derived backward's oracle -------
    def ssd_chunks(dtype, b=2, t=8192, heads=64, p=64, groups=8, n=128):
        name = f"ssd chunk fwd + bwd ({b}, {t}, {heads} x {p}; {groups} " \
               f"x {n}) {jnp.dtype(dtype).name}"

        def check(fails):
            from paddle_tpu.ops.pallas import ssd

            u = rnd(1, (b, t, heads * p), dtype)
            bm = rnd(2, (b, t, groups * n), dtype, n ** -0.5)
            cm = rnd(3, (b, t, groups * n), dtype)
            w = rnd(4, (b, t, heads * p), f32)
            # the family's start: steps log-uniform in [1e-3, 1e-1] around
            # their bias, rates A in [1, 16]
            delta = jnp.exp(jax.random.uniform(
                jax.random.key(5), (b, t, heads), f32, math.log(1e-3),
                math.log(1e-1)))
            a = -jax.random.uniform(jax.random.key(6), (heads,), f32, 1.0,
                                    16.0)
            if ssd._ineligible(heads * p, groups * n, groups, ssd.CHUNK):
                fails.append(f"{name}: outside its gate")
                return

            def run(form):
                def loss(u, delta, a, bm, cm):
                    rows = ssd._rows(delta, a, groups, ssd.CHUNK)
                    return jnp.sum(form(u, bm, cm, rows, ssd.CHUNK)
                                   .astype(f32) * w)
                return jax.jit(jax.value_and_grad(
                    loss, argnums=(0, 1, 2, 3, 4)))(u, delta, a, bm, cm)

            (lk, got), (lx, want) = (run(ssd._pallas_scan),
                                     run(ssd._xla_scan))
            _close(f"{name} sum(y w)", lk, lx, tol_of(dtype), fails)
            for g, r, nm in zip(got, want, ("u", "delta", "A", "B", "C")):
                _close(f"{name} d{nm}", g, r, tol_of(dtype), fails)
        checks.append((name, check))

    ssd_chunks(bf16)
    ssd_chunks(f32, b=1, t=2048)

    # -- the Mamba-2 mixer's fused element-wise stages against the float32
    # formulas they replace, at the Nemotron cell's shapes -------------------
    def mamba2_stage(stage, b=2, t=8192, heads=64, p=64, groups=8, n=128,
                     dtype=bf16):
        inner, gn = heads * p, groups * n
        total = 2 * inner + 2 * gn + heads
        name = f"mamba2 {stage} stage fwd + bwd ({b}, {t}, {total}) " \
               f"{jnp.dtype(dtype).name}"

        def check(fails):
            from paddle_tpu.ops.pallas import counters
            from paddle_tpu.ops.pallas import mamba2_stages as stages

            proj = rnd(1, (b, t, total), dtype)
            if stage == "conv":
                widths = (inner, gn, gn)
                args = (proj, rnd(2, (4, sum(widths)), f32, 0.5),
                        rnd(3, (sum(widths),), f32, 0.3))
                ws = [rnd(4 + i, (b, t, w), f32)
                      for i, w in enumerate(widths)]
                names = ("xBC", "taps", "bias")

                def run(form):
                    def loss(*a):
                        outs = form(*a, inner, widths)
                        return sum(jnp.sum(o.astype(f32) * w)
                                   for o, w in zip(outs, ws)), outs
                    return loss
                forms = (stages.conv_silu, stages.conv_silu_xla)
            else:
                args = (rnd(2, (b, t, inner), dtype),
                        rnd(3, (b, t, inner), dtype), proj,
                        1.0 + rnd(4, (heads,), f32, 0.5),
                        1.0 + rnd(5, (inner,), f32, 0.2))
                w = rnd(6, (b, t, inner), f32)
                names = ("y", "u", "z", "D", "weight")

                def run(form):
                    def loss(*a):
                        out = form(*a, groups, 1e-5)
                        return jnp.sum(out.astype(f32) * w), (out,)
                    return loss
                forms = (stages.gate_norm, stages.gate_norm_xla)
            before = counters.snapshot()
            (_, outs), got = jax.jit(jax.value_and_grad(
                run(forms[0]), argnums=tuple(range(len(args))),
                has_aux=True))(*args)
            if counters.delta(before) != {"mamba2_stage.fused": 1}:
                fails.append(f"{name}: outside its gate")
                return
            (_, refs), want = jax.jit(jax.value_and_grad(
                run(forms[1]), argnums=tuple(range(len(args))),
                has_aux=True))(*args)
            for i, (o, r) in enumerate(zip(outs, refs)):
                _close(f"{name} out {i}", o, r, tol_of(dtype), fails)
            for g, r, nm in zip(got, want, names):
                _close(f"{name} d{nm}", g, r, tol_of(dtype), fails)
        checks.append((name, check))

    mamba2_stage("conv")
    mamba2_stage("gate_norm")

    # -- the KDA mixer's fused element-wise stages against the float32
    # formulas they replace, at the Kimi cell's shapes -----------------------
    def kda_stage(stage, b=1, t=8192, heads=32, d=128, dtype=bf16):
        shape = (b, t, heads * d)
        name = f"kda {stage} stage fwd + bwd {shape} " \
               f"{jnp.dtype(dtype).name}"

        def check(fails):
            from paddle_tpu.ops.pallas import counters
            from paddle_tpu.ops.pallas import kda_stages as stages

            if stage == "conv":
                args = tuple(rnd(i, shape, dtype) for i in (1, 2, 3)) \
                    + tuple(rnd(i, (4, heads * d), f32, 0.5)
                            for i in (4, 5, 6))
                ws = [rnd(i, shape, f32) for i in (7, 8, 9)]
                names = ("q", "k", "v", "q_taps", "k_taps", "v_taps")
                forms = (lambda *a: stages.conv_norm(*a, d),
                         lambda *a: stages.conv_norm_xla(*a, d))
            else:
                args = (rnd(1, shape, f32), rnd(2, shape, dtype),
                        1.0 + rnd(3, (d,), f32, 0.2))
                ws = [rnd(4, shape, f32)]
                names = ("o", "gate", "weight")
                forms = (lambda *a: (stages.norm_gate(*a, 1e-5),),
                         lambda *a: (stages.norm_gate_xla(*a, 1e-5),))

            def run(form):
                def loss(*a):
                    outs = form(*a)
                    return sum(jnp.sum(o * w) for o, w in zip(outs, ws)), outs
                return jax.jit(jax.value_and_grad(
                    loss, argnums=tuple(range(len(args))),
                    has_aux=True))(*args)

            before = counters.snapshot()
            (_, outs), got = run(forms[0])
            if counters.delta(before) != {"kda_stage.fused": 1}:
                fails.append(f"{name}: outside its gate")
                return
            (_, refs), want = run(forms[1])
            for i, (o, r) in enumerate(zip(outs, refs)):
                if o.dtype != f32:
                    fails.append(f"{name} out {i}: {o.dtype}, not float32")
                _close(f"{name} out {i}", o, r, tol_of(f32), fails)
            for g, r, nm in zip(got, want, names):
                _close(f"{name} d{nm}", g, r, tol_of(g.dtype), fails)
        checks.append((name, check))

    kda_stage("conv")
    kda_stage("gate_norm")

    # -- a KDA mixer that ``recompute`` runs again against the mixer as it
    # is, at the Kimi cell's shapes: the segment keeps the chunk kernel's o
    # and states (PR 48), so its traced gradient holds ONE chunk forward
    # and its numbers are the unrecomputed block's ---------------------------
    def kda_block_recomputed(b=1, t=8192, hidden=2304, heads=32, d=128):
        name = f"kda block recomputed ({b}, {t}, {hidden}), " \
               f"{heads} heads of {d}"

        def check(fails):
            import paddle_tpu as paddle
            from paddle_tpu import nn
            from paddle_tpu.framework import tape
            from paddle_tpu.framework.tensor import Tensor
            from paddle_tpu.ops.pallas import counters
            from paddle_tpu.optimizer.meta import recompute

            paddle.seed(0)
            mixer = nn.KimiDeltaAttention(hidden, heads, d)
            params = list(mixer.parameters())
            values = [p.value for p in params]
            x, w = rnd(1, (b, t, hidden)), rnd(2, (b, t, hidden))

            def grads(again):
                def loss(xv, pv):
                    # the parameters hold tracers, the tape off: how
                    # TrainStep traces a model
                    try:
                        for p, v in zip(params, pv):
                            p._value = v
                        with tape.no_grad():
                            h = Tensor(xv)
                            y = recompute(mixer, h) if again else mixer(h)
                    finally:
                        for p, v in zip(params, values):
                            p._value = v
                    return jnp.sum((xv + y.value) * w)
                return jax.value_and_grad(loss, argnums=(0, 1))

            before = counters.snapshot()
            text = str(jax.make_jaxpr(grads(True))(x, values))
            seen = counters.delta(before)
            if seen.get("kda_chunk.kept_across_recompute") != 1 \
                    or seen.get("kda_chunk.pallas") != 1:
                fails.append(f"{name}: outside its gate ({seen})")
                return
            if text.count("name=kda_chunk_fwd") != 1 \
                    or text.count("name=kda_chunk_bwd") != 1:
                fails.append(
                    f"{name}: {text.count('name=kda_chunk_fwd')} forward, "
                    f"{text.count('name=kda_chunk_bwd')} backward launches")
            (lk, got), (lx, want) = (jax.jit(grads(a))(x, values)
                                     for a in (True, False))
            _close(f"{name} loss", lk, lx, tol_of(f32), fails)
            leaves = list(zip(
                jax.tree_util.tree_leaves(got),
                jax.tree_util.tree_leaves(want),
                ["x"] + [n for n, _ in mixer.named_parameters()]))
            for a, r, nm in leaves:
                _close(f"{name} d{nm}", a, r, tol_of(f32), fails)
            same = sum(bool(jnp.all(a == r)) for a, r, _ in leaves)
            log(f"       {name}: {same} of {len(leaves)} gradients equal "
                "bit for bit")
        checks.append((name, check))

    kda_block_recomputed()

    # -- a Gated DeltaNet mixer at the Qwen3-Next cell's shapes (PR 49): the
    # convolution stage on 16 key heads, the chunk kernels' scalar-decay
    # form under 32 value heads, the gated norm with SiLU, against the
    # same layer on the XLA forms; decays from the family's start (up to
    # 21 a token) ------------------------------------------------------------
    def gdn_mixer(b=1, t=8192, hidden=2048, hk=16, hv=32, d=128):
        name = f"gdn mixer ({b}, {t}, {hidden}), {hk}k/{hv}v heads of {d}"

        def check(fails):
            import paddle_tpu as paddle
            import paddle_tpu.framework.bringup as bringup
            from paddle_tpu import nn
            from paddle_tpu.framework import tape
            from paddle_tpu.framework.tensor import Tensor
            from paddle_tpu.ops.pallas import counters

            paddle.seed(0)
            mixer = nn.GatedDeltaNet(hidden, hk, hv, d, d)
            params = list(mixer.parameters())
            values = [p.value for p in params]
            x, w = rnd(1, (b, t, hidden)), rnd(2, (b, t, hidden))

            def loss(xv, pv):
                try:
                    for p, v in zip(params, pv):
                        p._value = v
                    with tape.no_grad():
                        y = mixer(Tensor(xv))
                finally:
                    for p, v in zip(params, values):
                        p._value = v
                return jnp.sum(y.value * w)

            def run():
                return jax.jit(jax.value_and_grad(loss, argnums=(0, 1)))(
                    x, values)

            before = counters.snapshot()
            lk, got = run()
            seen = counters.delta(before)
            if seen != {"kda_stage.fused": 2, "gdn.scalar_decay": 1,
                        "kda_chunk.pallas": 1,
                        f"kda_chunk.heads{_heads(hv, d)}": 1}:
                fails.append(f"{name}: outside its gate ({seen})")
                return
            gate = bringup.pallas_enabled
            bringup.pallas_enabled = lambda: False
            try:
                lx, want = run()
            finally:
                bringup.pallas_enabled = gate
            _close(f"{name} loss", lk, lx, tol_of(f32), fails)
            for a, r, nm in zip(
                    jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want),
                    ["x"] + [n for n, _ in mixer.named_parameters()]):
                _close(f"{name} d{nm}", a, r, tol_of(f32), fails)
        checks.append((name, check))

    def _heads(h, d):
        from paddle_tpu.ops.pallas import kda

        return kda._heads_a_step(h, d, d, kda.CHUNK)

    gdn_mixer()

    # -- the gated short convolution's fused stage against its float32
    # formula, at the LFM2 cell's shapes -------------------------------------
    def gated_conv_stage(b=2, t=8192, d=2048, taps=3, dtype=bf16):
        name = f"gated_conv stage fwd + bwd ({b}, {t}, {3 * d}) " \
               f"{jnp.dtype(dtype).name}"

        def check(fails):
            from paddle_tpu.ops.pallas import counters, gated_conv

            args = (rnd(1, (b, t, 3 * d), dtype), rnd(2, (taps, d), f32, 0.5))
            w = rnd(3, (b, t, d), f32)

            def run(form):
                def loss(*a):
                    out = form(*a)
                    return jnp.sum(out.astype(f32) * w), out
                return jax.jit(jax.value_and_grad(
                    loss, argnums=(0, 1), has_aux=True))(*args)

            before = counters.snapshot()
            (_, out), got = run(gated_conv.gated_conv)
            if counters.delta(before) != {"gated_conv.fused": 1}:
                fails.append(f"{name}: outside its gate")
                return
            (_, ref), want = run(gated_conv.gated_conv_xla)
            if out.dtype != dtype:
                fails.append(f"{name} out: {out.dtype}")
            _close(f"{name} out", out, ref, tol_of(dtype), fails)
            for g, r, nm in zip(got, want, ("proj", "taps")):
                _close(f"{name} d{nm}", g, r, tol_of(dtype), fails)
        checks.append((name, check))

    gated_conv_stage()

    # -- the dropless expert layer, each rung against a dense loop -----------
    def experts(held, dtype, rungs, t=8192, d=2304, f=1024,
                num_experts=256, top_k=8, scaling=2.446,
                score_func="sigmoid", plain=False, highest=False):
        """A cell's layer shapes (by default the Kimi cell's), one compiled
        layer through each of its rungs. ``rungs`` maps a number of held experts that the
        correction bias makes every token's picks to the rows that must
        then run: the count of pairs decides the rung, the grouped
        kernels (``ops.pallas.grouped_ffn``) on a lower one, on the top
        one where more than twice as many experts are held as a token
        picks, every token through every expert (t x held rows) where
        not. ``plain`` experts are ``relu(x U)^2 D`` and get no gate
        matrix. ``highest``: bfloat16 operands under the phase's own
        precision too (``ragged_dot`` refused them there; the kernels
        take them)."""
        from paddle_tpu.nn.moe import SCORE_FUNCS, sparse_moe

        name = f"sparse experts {held} of {num_experts} held, " \
               f"{score_func}, {'plain' if plain else 'gated'}, {t} x {d} x {f}, " \
               f"rungs {sorted(rungs.values())}, {jnp.dtype(dtype).name}" \
               + (" at highest" if highest else "")

        def layer(x, router, wg, wu, wd, bias, w):
            out, routing = sparse_moe.raw_fn(
                x, router, bias, None if plain else wg, wu, wd,
                top_k=top_k, scaling=scaling, score_func=score_func)
            return jnp.sum(out * w), (out, routing)

        def loop(x, router, wg, wu, wd, bias, w):
            scores = SCORE_FUNCS[score_func](jnp.matmul(
                x.astype(f32), router, precision=jax.lax.Precision.HIGHEST))
            _, picked = jax.lax.top_k(scores + bias, top_k)
            weight = jnp.take_along_axis(scores, picked, axis=1)
            weight = scaling * weight / jnp.sum(weight, axis=1,
                                                keepdims=True)
            out = jnp.zeros((t, d), f32)
            for e in range(held):
                up = jnp.matmul(x, wu[e])
                y = jnp.matmul(
                    jnp.square(jax.nn.relu(up)) if plain else
                    jax.nn.silu(jnp.matmul(x, wg[e])) * up, wd[e])
                mine = jnp.sum(jnp.where(picked == e, weight, 0.0), 1)
                out = out + y.astype(f32) * mine[:, None]
            return jnp.sum(out * w), (out, None)

        def check(fails):
            x = rnd(1, (t, d), dtype)
            router = rnd(2, (d, num_experts), f32, d ** -0.5)
            wg, wu = (rnd(s, (held, d, f), dtype, d ** -0.5) for s in (3, 4))
            wd = rnd(5, (held, f, d), dtype, f ** -0.5)
            w = rnd(6, (t, d), f32)
            layer_, loop_ = (jax.jit(jax.value_and_grad(
                fn, argnums=(0, 1, 2, 3, 4), has_aux=True))
                for fn in (layer, loop))
            for forced, rows in rungs.items():
                bias = jnp.zeros((num_experts,), f32).at[:forced].set(10.0)
                args = (x, router, wg, wu, wd, bias, w)
                # bfloat16 operands at the step's own precision, as a
                # step runs them
                with jax.default_matmul_precision(
                        "highest" if dtype == f32 or highest else "default"):
                    ((_, (got, routing)), dgot) = layer_(*args)
                    ((_, (want, _)), dwant) = loop_(*args)
                pairs, ran = (int(v) for v in np.asarray(routing))
                if ran != rows or not 0 < pairs <= rows:
                    fails.append(f"{name}: {pairs} pairs ran on {ran} rows, "
                                 f"not on {rows}")
                _close(f"{name} {rows} out", got, want, tol_of(dtype), fails)
                for a, r, nm in zip(dgot, dwant,
                                    ("x", "router", "gate", "up", "down")):
                    if nm == "gate" and plain:
                        continue        # plain experts have none
                    _close(f"{name} {rows} d{nm}", a, r, tol_of(dtype),
                           fails)
        checks.append((name, check))

    # the Kimi cell's share in the step's type (``ragged_dot`` still: its
    # own ladder serves it, and the kernels take the dense rung's work
    # only); a share that holds twice the experts a token picks (the
    # Mellum cell's ratio), whose top rung is still every token through
    # every expert; and one that holds three times as many, whose rungs
    # are the grouped kernels, the top one on every pair. The rungs are
    # ``nn.moe._row_ladder``'s: eight times the even share
    experts(8, bf16, {0: 16384, 8: 65536})
    experts(16, f32, {0: 32768, 8: 131072})
    experts(24, f32, {0: 49152, 8: 65536})
    # the Mellum cell's share at its own shapes: one rung, the dense one
    # (one gated FFN of width 16 x 896), whatever the routing
    experts(16, bf16, {0: 262144}, t=16384, f=896, num_experts=64,
            scaling=1.0, score_func="softmax")
    # the Nemotron cell's share at its own shapes: plain relu^2 experts
    # of a width that is no whole number of lanes; the grouped rung of
    # 49,152 rows and the dense one above it, at the step's precision and
    # once under this phase's (``ragged_dot`` refused bfloat16 there)
    experts(8, bf16, {0: 49152, 6: 131072}, t=16384, d=2688, f=1856,
            num_experts=128, top_k=6, scaling=2.5, plain=True)
    experts(8, bf16, {0: 49152, 6: 131072}, t=16384, d=2688, f=1856,
            num_experts=128, top_k=6, scaling=2.5, plain=True, highest=True)
    # the Qwen3-Next cell's share at its own shapes (PR 49): 32 held of
    # top 10 in 512 is more than twice the picks, so there is no dense
    # rung: both sorted rungs, 40,960 and 81,920 rows, on the grouped
    # kernels, 32 groups of width 512
    experts(32, bf16, {0: 40960, 10: 81920}, d=2048, f=512,
            num_experts=512, top_k=10, scaling=1.0, score_func="softmax")

    # -- fused embedding bag --------------------------------------------------
    def bag(vocab, d, b, s, dtype, combiner):
        name = f"fused embedding {combiner} table=({vocab}, {d}) " \
               f"ids=({b}, {s})"

        def check(fails):
            table = rnd(1, (vocab, d), dtype)
            ids = jax.random.randint(jax.random.key(2), (b, s), -1, vocab)
            if not fe._eligible(table, ids):
                fails.append(f"{name}: outside its gate")
                return
            _close(name, jax.jit(lambda t, i: fe._bag_pallas(
                t, i, combiner))(table, ids),
                jax.jit(lambda t, i: fe._xla_bag(t, i, combiner))(
                    table, ids), tol_of(dtype), fails)
        checks.append((name, check))

    bag(4096, 128, 64, 16, f32, "sum")
    bag(100000, 1024, 256, 64, f32, "mean")
    bag(8192, 256, 8, 8, f32, "sqrtn")

    # -- paged attention (f32 and int8 pools) ---------------------------------
    def paged(b, h, d, s, t, quant, tag):
        name = f"paged attention{' int8' if quant else ''} {tag} " \
               f"B={b} H={h} D={d} S={s} T={t}"

        def check(fails):
            from paddle_tpu.ps.codec import jnp_encode_kv_rows

            pages = b * t + 1
            q = rnd(1, (b, h, d))
            kp, vp = rnd(2, (pages, s, h, d)), rnd(3, (pages, s, h, d))
            if not pa._paged_ok(q, kp):
                fails.append(f"{name}: outside its gate")
                return
            rs = np.random.RandomState(0)
            lens = rs.randint(1, t * s + 1, (b,)).astype(np.int32)
            lens[0], lens[-1] = t * s, 1
            table = rs.permutation(np.arange(1, pages)).reshape(b, t)
            table = np.where(np.arange(t)[None, :] * s < lens[:, None],
                             table, -1).astype(np.int32)
            table, lens = jnp.asarray(table), jnp.asarray(lens)
            if quant:
                qk, sk = jnp_encode_kv_rows(kp.reshape(-1, h, d))
                qv, sv = jnp_encode_kv_rows(vp.reshape(-1, h, d))
                args = (q, qk.reshape(kp.shape), qv.reshape(vp.shape),
                        sk.reshape(pages, s), sv.reshape(pages, s),
                        table, lens)
                got = pa._paged_attention_pallas_quant(*args)
                want = jax.jit(pa._xla_paged_attention_quant)(*args)
            else:
                got = pa._paged_attention_pallas(q, kp, vp, table, lens)
                want = jax.jit(pa._xla_paged_attention)(q, kp, vp, table,
                                                        lens)
            _close(name, got, want, 2e-4, fails)
        checks.append((name, check))

    for quant in (False, True):
        paged(8, 12, 64, 128, 16, quant, "engine")
        paged(2, 12, 64, 512, 3, quant, "gate edge (page)")
        paged(2, 12, 256, 128, 3, quant, "gate edge (head dim)")
        paged(2, 48, 64, 128, 3, quant, "gate edge (heads)")

    # -- fused sampling --------------------------------------------------------
    def sample(b, v, top_k):
        name = f"fused sampling B={b} V={v} top_k={top_k}"

        def check(fails):
            logits = rnd(1, (b, v))
            noise = -jnp.log(-jnp.log(jax.random.uniform(
                jax.random.key(2), (b, v), f32, 1e-6, 1 - 1e-6)))
            if not sp._sample_ok(logits, top_k, 1.0):
                fails.append(f"{name}: outside its gate")
                return
            got = jax.jit(lambda l, n: sp._fused_sample_pallas(
                l, n, 0.8, top_k))(logits, noise)
            want = jax.jit(lambda l, n: sp._xla_sample(
                l, n, 0.8, top_k, 1.0))(logits, noise)
            if not np.array_equal(np.asarray(got), np.asarray(want)):
                fails.append(f"{name}: tokens {np.asarray(got)} != "
                             f"{np.asarray(want)}")
        checks.append((name, check))

    for b, v, top_k in ((8, 16384, 8), (1, 16384, 8), (8, 16384, 0),
                        (67, 16384, 1), (8, 128, 4)):
        sample(b, v, top_k)
    return checks


def kernels_phase() -> list:
    import jax

    fails = []
    # f32 references at the TPU default are one bf16 pass; compare at
    # "highest" so a tolerance means the kernel, not the reference
    jax.config.update("jax_default_matmul_precision", "highest")
    try:
        for name, check in _kernel_checks():
            mine = []
            t = time.monotonic()
            try:
                check(mine)
            except Exception as e:
                traceback.print_exc()
                mine.append(f"{name}: {type(e).__name__}: "
                            f"{str(e).strip()[:2000]}")
            log(f"  {'ok  ' if not mine else 'FAIL'} {name} "
                f"({time.monotonic() - t:.1f}s)")
            for m in mine:
                log(f"       {m}")
            fails += mine
    finally:
        jax.config.update("jax_default_matmul_precision", None)
    return fails


# ---------------------------------------------------------------------------
# mesh
# ---------------------------------------------------------------------------
def mesh_phase(cfg, batch=128, seq=128, one_chip_loss=None) -> list:
    import jax

    from paddle_tpu.ops.pallas import counters
    from paddle_tpu.parallel import create_mesh
    from paddle_tpu.parallel.sharding import TRANSFORMER_TP_RULES

    fails = []
    data = _bert_batch(cfg, batch, seq)
    if one_chip_loss is None:
        step1, _m = _bert_step(cfg)
        one_chip_loss = float(step1(*data))
        del step1, _m
        gc.collect()
    mesh = create_mesh({"dp": 2, "tp": 2}, devices=jax.devices()[:4])
    before = counters.snapshot()
    step, model = _bert_step(cfg, mesh=mesh,
                             param_rules=TRANSFORMER_TP_RULES)
    with _Window() as first:
        loss0 = float(step(*data))
    with _Window() as second:
        loss1 = float(step(*data))
    with _Window() as third:
        loss2 = float(step(*data))
    log(f"  mesh dp2 x tp2 {batch}x{seq}: first step {first}")
    log(f"  mesh: second step {second}")
    log(f"  mesh: third step {third}")
    log(f"  mesh: loss {loss0:.4f} -> {loss1:.4f} -> {loss2:.4f}; one chip "
        f"first step {one_chip_loss:.4f}; pallas counters "
        f"{counters.delta(before)}")
    if not np.all(np.isfinite([loss0, loss1, loss2])):
        fails.append(f"mesh: non-finite loss {loss0}, {loss1}, {loss2}")
    # same seed, same batch: the sharded program's first loss differs
    # from the one-chip one by reduction order only (measured on four
    # v5e chips: 11.0971 vs 11.0970)
    if abs(loss0 - one_chip_loss) > 0.005 * abs(one_chip_loss):
        fails.append(f"mesh: first-step loss {loss0:.4f} not within 0.5% "
                     f"of the one-chip {one_chip_loss:.4f}")
    # TrainStep hands params, slots and the step counter back in the
    # placement they came in with, so step 2 already reuses step 1's
    # executable (before PR 21 it compiled a second time, 67 s on 4 chips)
    if second.compiles or third.compiles:
        fails.append(f"mesh: {second.compiles} + {third.compiles} "
                     "compilations on steps 2 and 3")
    w = dict(model.named_parameters())[
        "bert.encoder.layers.0.linear1.weight"].value
    shard_devs = {s.device.id for s in w.addressable_shards}
    shard_shape = w.addressable_shards[0].data.shape
    log(f"  mesh: linear1.weight {w.shape} sharded as {w.sharding.spec}: "
        f"shard {shard_shape} on devices {sorted(shard_devs)}")
    if len(shard_devs) != 4 or shard_shape == w.shape:
        fails.append(f"mesh: linear1.weight is not sharded over four "
                     f"devices (shard {shard_shape} on {shard_devs})")
    for d in jax.devices()[:4]:
        used = (d.memory_stats() or {}).get("bytes_in_use", 0)
        log(f"  mesh: device {d.id} bytes_in_use={used}")
        if not used > 0:
            fails.append(f"mesh: device {d.id} holds nothing")
    return fails


# ---------------------------------------------------------------------------
# main
# ---------------------------------------------------------------------------
def _barrier_really_waits() -> list:
    """paddle_tpu.utils.timing (the autotuner's clock) closes its window
    with block_until_ready and re-dispatches identical arguments. Check
    both assumptions on this device: the barrier must cover the work a
    host fetch covers, and a repeated identical dispatch must take as
    long as a fresh one."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def work(x):
        for _ in range(8):
            x = jnp.tanh(x @ x) * 0.5
        return x

    x = jax.random.normal(jax.random.key(0), (4096, 4096), jnp.bfloat16)
    jax.block_until_ready(work(x))

    def timed(arg, fetch):
        t = time.perf_counter()
        out = work(arg)
        if fetch:
            float(out[0, 0])
        else:
            jax.block_until_ready(out)
        return (time.perf_counter() - t) * 1e3

    fresh = [x * (1 + 2.0 ** -6 * (i + 1)) for i in range(5)]
    jax.block_until_ready(fresh)
    barrier = min(timed(x, False) for _ in range(5))
    fetch = min(timed(x, True) for _ in range(5))
    varied = min(timed(a, False) for a in fresh)
    log(f"timing check: 8 x (4096^3 bf16 matmul + tanh): barrier "
        f"{barrier:.2f} ms, host fetch {fetch:.2f} ms, fresh inputs "
        f"{varied:.2f} ms")
    fails = []
    if barrier < 0.7 * fetch:
        fails.append("block_until_ready returned before the work a host "
                     "fetch waits for")
    if barrier < 0.7 * varied:
        fails.append("identical repeat dispatches ran faster than fresh "
                     "ones (elided?)")
    return fails


def main(argv) -> int:
    phases = tuple(argv) or PHASES
    unknown = [p for p in phases if p not in PHASES]
    if unknown:
        log(f"unknown phase(s) {unknown}; choose from {PHASES}")
        return 2
    faulthandler.dump_traceback_later(DEADLINE_S, exit=True)

    import jax
    import jaxlib

    import paddle_tpu as paddle
    from paddle_tpu.models.bert import BertConfig
    from paddle_tpu.observability.device_peaks import peaks_for
    from paddle_tpu.static import compile_cache

    dev = jax.devices()[0]      # the one backend initialisation
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    try:
        from importlib.metadata import version

        libtpu = version("libtpu")
    except Exception:
        libtpu = "not installed"
    compile_cache.ensure_enabled()
    log(f"platform={device['platform']} device_kind={device['kind']!r} "
        f"device_count={device['count']} jax={jax.__version__} "
        f"jaxlib={jaxlib.__version__} libtpu={libtpu}")
    log(f"compile_cache_dir={compile_cache.cache_dir()} "
        f"(JAX_COMPILATION_CACHE_DIR "
        f"{'set' if os.environ.get('JAX_COMPILATION_CACHE_DIR') else 'unset'}"
        f"; jax reads {jax.config.jax_compilation_cache_dir})")
    if device["platform"] != "tpu":
        log(f"FAIL: jax found platform {device['platform']!r}, not 'tpu' — "
            "chip_smoke.py runs on the chip only")
        return 2
    for name in ("PADDLE_PEAK_FLOPS", "PADDLE_PEAK_HBM_GBPS"):
        if os.environ.get(name):
            log(f"FAIL: {name} is set — peaks must resolve from "
                "device_kind, not from an override")
            return 2
    peak = peaks_for(device["kind"])
    if peak is None:
        log(f"FAIL: device_kind {device['kind']!r} matches no row of "
            "observability.device_peaks.DEVICE_PEAKS")
        return 2
    log(f"device_peaks: {device['kind']!r} -> row {peak.kind!r}: "
        f"{peak.flops:.3g} FLOP/s bf16, {peak.hbm_bytes_per_s:.3g} B/s HBM")

    jax.monitoring.register_event_duration_secs_listener(_count_compile)
    # every route to XLA prints why (counters keep counts, not reasons;
    # importing the module is what defines the flag)
    from paddle_tpu.ops.pallas import counters  # noqa: F401

    paddle.set_flags({"log_pallas_fallback": True})

    failed = {}
    timing = _barrier_really_waits()
    if timing:
        failed["timing"] = timing
        log("FAIL timing: " + "; ".join(timing))

    cfg = BertConfig.base()
    results = {}
    for phase in phases:
        if phase == "mesh" and device["count"] < 4:
            log(f"mesh: not run ({device['count']} device)")
            continue
        log(f"== {phase} ==")
        with _Window() as win:
            try:
                if phase == "train":
                    fails = train_phase(cfg, results=results)
                elif phase == "serve":
                    fails = serve_phase()
                elif phase == "kernels":
                    fails = kernels_phase()
                else:
                    fails = mesh_phase(
                        cfg, one_chip_loss=results.get((128, 128)))
            except Exception as e:
                traceback.print_exc()
                fails = [f"{type(e).__name__}: {str(e).strip()[:2000]}"]
        gc.collect()
        for f in fails:
            log(f"  FAIL {phase}: {f}")
        log(f"{'FAIL' if fails else 'PASS'} {phase} ({win}; "
            f"{time.monotonic() - _t0:.0f}s since start)")
        if fails:
            failed[phase] = fails

    faulthandler.cancel_dump_traceback_later()
    from paddle_tpu.ops.pallas import autotune

    h, m = _disk_counts()
    log(f"total: {time.monotonic() - _t0:.0f}s wall, {_compiles[0]} "
        f"compilations, disk cache hits={h} misses={m}, autotune "
        f"{autotune.stats()}")
    if failed:
        log(f"FAILED: {sorted(failed)}")
        return 1
    result = {"ok": True, "device": device}
    if phases != PHASES:
        result["phases"] = list(phases)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
