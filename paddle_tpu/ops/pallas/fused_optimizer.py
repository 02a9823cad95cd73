"""Fused Pallas optimizer update kernels (sgd / momentum / adam / lamb).

The static optimizer ops (static/kernels.py) and the ZeRO per-bucket
chunk update (static/stepplan.py) lower each parameter update to 5-8
separate XLA elementwise ops; every one of them re-reads the param /
grad / moment buffers from HBM. Optimizer updates are pure bandwidth —
at ZeRO bucket sizes the update region is the post-backward hot loop
(ISSUE 19) — so the win is a single grid pass over (rows, 128) blocks
that reads grad + param + moments ONCE and writes param + moments ONCE,
with the step scalars (lr, beta-pows, the fp16 FoundInfinite skip flag)
prefetched into SMEM.

Established kernel pattern (fused_embedding / paged_attention):

- XLA fallback whose math is VERBATIM the static kernels' (bitwise: the
  ``PADDLE_FUSED_OPT=0`` escape and every ineligible shape produce
  exactly the pre-fusion update)
- ``fused_opt.pallas`` / ``fused_opt.xla`` dispatch counters with
  reasons (ops/pallas/counters.py)
- eligibility gate: f32, >= one (8, 128) tile, pallas importable and
  enabled for the backend (``PADDLE_FUSED_OPT_INTERPRET=1`` forces the
  kernel in interpret mode — CI / CPU-probe leg)
- autotune verdict per (op, n) persisted in the PR 10 disk cache
  (autotune.best_fused_opt_impl)

Three entry points:

- :func:`fused_op_update` — the static KERNELS delegate (plain step,
  the replicated ``_comm_step_fn`` optimizer region, and op_test)
- :func:`fused_chunk_update` — the ZeRO per-bucket (chunk,) update;
  for lamb it runs the TWO-PHASE trust-ratio plan: per-chunk partial
  per-param sq-norms -> tiny ``psum`` over the dp axis -> the fused
  elementwise update consumes the global norms. This is what makes
  lamb chunk-shardable and removes PR 18's counted ZeRO refusal.
- :func:`fused_try_rule` — the dygraph ``optimizer.step()`` hook;
  returns None unless the Pallas kernel actually engages, so the
  reference rule (and the CPU path) stays bitwise by construction.

The dygraph rules place epsilon differently from the static ops (eps
added to sqrt(vhat) of the NORMALIZED moment); the kernels carry a
``dygraph`` variant so each caller gets its own reference math.
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .counters import bump, kernel_call

__all__ = ["FUSED_OPS", "fused_op_update", "fused_chunk_update",
           "fused_try_rule", "fused_opt_escaped"]

# rules with a fused kernel; lamb's trust ratio is two-phase (the
# elementwise m/v/r pass is the kernel, the norms stay XLA reductions)
FUSED_OPS = ("sgd", "momentum", "adam", "lamb")

_LANE = 128
_TILE = 8 * _LANE          # one f32 (8, 128) tile = 1024 elements


def fused_opt_escaped() -> bool:
    """True when ``PADDLE_FUSED_OPT=0`` pins the bitwise XLA escape."""
    return os.environ.get("PADDLE_FUSED_OPT", "").strip() in (
        "0", "off", "false")


def _interpret_forced() -> bool:
    return os.environ.get("PADDLE_FUSED_OPT_INTERPRET", "").strip() in (
        "1", "on", "true")


# ---------------------------------------------------------------------------
# XLA reference updates — VERBATIM static/kernels.py math (the escape
# leg must stay bitwise with the pre-fusion static ops) plus the
# dygraph-variant forms from optimizer/optimizer.py
# ---------------------------------------------------------------------------


def _gate_update(ins, outs):
    """FoundInfinite skip-step gate: on a non-finite step every output
    keeps its previous value (GradScaler semantics, compiled)."""
    found = ins.get("FoundInfinite")
    if not found:
        return outs
    skip = found[0].reshape(())
    olds = {"ParamOut": "Param", "VelocityOut": "Velocity",
            "Moment1Out": "Moment1", "Moment2Out": "Moment2",
            "Beta1PowOut": "Beta1Pow", "Beta2PowOut": "Beta2Pow"}
    return {slot: [jnp.where(skip, ins[olds[slot]][0], new)
                   for new in vals]
            for slot, vals in outs.items()}


def _xla_sgd(ins, attrs):
    p, g, lr = ins["Param"][0], ins["Grad"][0], ins["LearningRate"][0]
    return _gate_update(ins, {"ParamOut": [p - lr * g]})


def _xla_momentum(ins, attrs):
    p, g, v = ins["Param"][0], ins["Grad"][0], ins["Velocity"][0]
    lr = ins["LearningRate"][0]
    mu = attrs.get("mu", 0.9)
    use_nesterov = attrs.get("use_nesterov", False)
    v_new = mu * v + g
    if use_nesterov:
        p_new = p - (g + mu * v_new) * lr
    else:
        p_new = p - lr * v_new
    return _gate_update(ins, {"ParamOut": [p_new],
                              "VelocityOut": [v_new]})


def _xla_adam(ins, attrs):
    p, g = ins["Param"][0], ins["Grad"][0]
    m, v = ins["Moment1"][0], ins["Moment2"][0]
    b1p, b2p = ins["Beta1Pow"][0], ins["Beta2Pow"][0]
    lr = ins["LearningRate"][0]
    b1 = attrs.get("beta1", 0.9)
    b2 = attrs.get("beta2", 0.999)
    eps = attrs.get("epsilon", 1e-8)
    m_new = b1 * m + (1 - b1) * g
    v_new = b2 * v + (1 - b2) * g * g
    lr_t = lr * jnp.sqrt(1 - b2p * b2) / (1 - b1p * b1)
    p_new = p - lr_t * m_new / (jnp.sqrt(v_new) + eps)
    return _gate_update(ins, {
        "ParamOut": [p_new], "Moment1Out": [m_new],
        "Moment2Out": [v_new], "Beta1PowOut": [b1p * b1],
        "Beta2PowOut": [b2p * b2]})


def _xla_lamb(ins, attrs):
    p, g = ins["Param"][0], ins["Grad"][0]
    m, v = ins["Moment1"][0], ins["Moment2"][0]
    b1p, b2p = ins["Beta1Pow"][0], ins["Beta2Pow"][0]
    lr = ins["LearningRate"][0]
    b1 = attrs.get("beta1", 0.9)
    b2 = attrs.get("beta2", 0.999)
    eps = attrs.get("epsilon", 1e-6)
    wd = attrs.get("weight_decay", 0.01)
    m_new = b1 * m + (1 - b1) * g
    v_new = b2 * v + (1 - b2) * g * g
    m_hat = m_new / (1 - b1p * b1)
    v_hat = v_new / (1 - b2p * b2)
    r = m_hat / (jnp.sqrt(v_hat) + eps) + wd * p
    p_norm = jnp.linalg.norm(p)
    r_norm = jnp.linalg.norm(r)
    trust = jnp.where((p_norm > 0) & (r_norm > 0), p_norm / r_norm, 1.0)
    return _gate_update(ins, {
        "ParamOut": [p - lr * trust * r], "Moment1Out": [m_new],
        "Moment2Out": [v_new], "Beta1PowOut": [b1p * b1],
        "Beta2PowOut": [b2p * b2]})


_XLA = {"sgd": _xla_sgd, "momentum": _xla_momentum, "adam": _xla_adam,
        "lamb": _xla_lamb}


# ---------------------------------------------------------------------------
# Pallas kernel bodies: one grid pass over (block_rows, 128) VMEM
# blocks; scalars arrive as (1, 1) SMEM refs; the FoundInfinite gate
# folds into the SAME pass (no second read of the old state)
# ---------------------------------------------------------------------------


def _sgd_kernel(lr_ref, skip_ref, p_ref, g_ref, p_out):
    lr = lr_ref[0, 0]
    skip = skip_ref[0, 0] != 0
    p = p_ref[...]
    p_out[...] = jnp.where(skip, p, p - lr * g_ref[...])


def _momentum_kernel(lr_ref, skip_ref, p_ref, g_ref, v_ref, p_out,
                     v_out, *, mu, nesterov):
    lr = lr_ref[0, 0]
    skip = skip_ref[0, 0] != 0
    p, g, v = p_ref[...], g_ref[...], v_ref[...]
    v_new = mu * v + g
    if nesterov:
        p_new = p - (g + mu * v_new) * lr
    else:
        p_new = p - lr * v_new
    p_out[...] = jnp.where(skip, p, p_new)
    v_out[...] = jnp.where(skip, v, v_new)


def _adam_kernel(lr_ref, c1_ref, c2_ref, skip_ref, p_ref, g_ref, m_ref,
                 v_ref, p_out, m_out, v_out, *, b1, b2, eps, dygraph):
    """c1/c2: the ADVANCED beta-pows (static: b1p*b1, b2p*b2) or the
    dygraph bias-correction denominators (1 - b**t)."""
    lr = lr_ref[0, 0]
    c1 = c1_ref[0, 0]
    c2 = c2_ref[0, 0]
    skip = skip_ref[0, 0] != 0
    p, g, m, v = p_ref[...], g_ref[...], m_ref[...], v_ref[...]
    m_new = b1 * m + (1 - b1) * g
    v_new = b2 * v + (1 - b2) * g * g
    if dygraph:
        p_new = p - lr * (m_new / c1) / (jnp.sqrt(v_new / c2) + eps)
    else:
        lr_t = lr * jnp.sqrt(1 - c2) / (1 - c1)
        p_new = p - lr_t * m_new / (jnp.sqrt(v_new) + eps)
    p_out[...] = jnp.where(skip, p, p_new)
    m_out[...] = jnp.where(skip, m, m_new)
    v_out[...] = jnp.where(skip, v, v_new)


def _lamb_phase1_kernel(c1_ref, c2_ref, p_ref, g_ref, m_ref, v_ref,
                        m_out, v_out, r_out, *, b1, b2, eps, wd,
                        dygraph):
    """Lamb elementwise phase: m/v advance + the trust-ratio numerator
    ``r`` in one read of p/g/m/v. The norms (phase 2) are XLA
    reductions — per-param globally, per-segment + psum on a ZeRO
    chunk — and the final ``p - lr*trust*r`` is elementwise XLA."""
    c1 = c1_ref[0, 0]
    c2 = c2_ref[0, 0]
    p, g, m, v = p_ref[...], g_ref[...], m_ref[...], v_ref[...]
    m_new = b1 * m + (1 - b1) * g
    v_new = b2 * v + (1 - b2) * g * g
    if dygraph:
        m_hat = m_new / c1
        v_hat = v_new / c2
        r = m_hat / (jnp.sqrt(v_hat) + eps) + wd * p
    else:
        m_hat = m_new / (1 - c1)
        v_hat = v_new / (1 - c2)
        r = m_hat / (jnp.sqrt(v_hat) + eps) + wd * p
    m_out[...] = m_new
    v_out[...] = v_new
    r_out[...] = r


# ---------------------------------------------------------------------------
# dispatch plumbing
# ---------------------------------------------------------------------------


def _scal(x):
    """Any scalar-ish value -> (1, 1) f32 for the SMEM block."""
    return jnp.asarray(x, jnp.float32).reshape(-1)[:1].reshape(1, 1)


def _block_rows(rows: int) -> int:
    for br in (512, 256, 64, 8):
        if rows % br == 0:
            return br
    return 8


def _pad_flat(x, n_pad):
    flat = x.reshape(-1).astype(jnp.float32)
    if flat.shape[0] == n_pad:
        return flat
    return jnp.concatenate(
        [flat, jnp.zeros((n_pad - flat.shape[0],), jnp.float32)])


#: f32 streams a fused update reads and writes once, by rule kind
_STREAMS = {"sgd": 3, "momentum": 5, "adam": 7, "lamb": 7}


def _engaged(role: str, kind: str, n: int) -> None:
    """Count an engaged kernel and declare its work to the ledger: no
    matmul; param, grad and moments read, param and moments written."""
    bump("fused_opt", "pallas",
         work={role: (0.0, 4.0 * _STREAMS[kind] * n)})


def _run_grid(role, kernel, scalars, tensors, n_outs, n, interpret):
    """Common pallas_call under the kernel's ``role`` name: scalars as
    SMEM (1,1) refs, tensors padded to whole (8, 128) tiles and blocked
    (block_rows, 128) over a 1-D grid. Returns the outputs sliced back
    to ``n`` flat elements."""
    n_pad = -(-n // _TILE) * _TILE
    rows = n_pad // _LANE
    br = _block_rows(rows)
    blk = pl.BlockSpec((br, _LANE), lambda i: (i, 0))
    outs = kernel_call(
        role,
        kernel,
        grid=(rows // br,),
        in_specs=([pl.BlockSpec(memory_space=pltpu.SMEM)
                   for _ in scalars] + [blk for _ in tensors]),
        out_specs=[blk] * n_outs,
        out_shape=[jax.ShapeDtypeStruct((rows, _LANE), jnp.float32)
                   for _ in range(n_outs)],
        interpret=interpret,
    )(*scalars, *[_pad_flat(t, n_pad).reshape(rows, _LANE)
                  for t in tensors])
    return [o.reshape(-1)[:n] for o in outs]


def _dispatch(op_type: str, n: int, dtype) -> tuple:
    """('pallas'|'xla', reason, interpret) — the one gate every entry
    point funnels through. ``PADDLE_FUSED_OPT=0`` is the bitwise
    escape; the autotune verdict (TPU only) can demote to XLA."""
    if op_type not in FUSED_OPS:
        return "xla", f"no fused kernel for {op_type!r}", False
    if fused_opt_escaped():
        return "xla", "disabled (PADDLE_FUSED_OPT=0)", False
    interpret = _interpret_forced()
    if not interpret:
        from ...framework.bringup import pallas_enabled
        from ...parallel.mesh import auto_partitioned_trace

        if not pallas_enabled():
            return "xla", "pallas disabled for this backend", False
        if auto_partitioned_trace():
            return ("xla", "multi-device GSPMD trace: Mosaic kernels "
                           "cannot be automatically partitioned", False)
    if jnp.dtype(dtype) != jnp.float32:
        return "xla", f"dtype {jnp.dtype(dtype).name} is not f32", False
    if n < _TILE:
        return ("xla", f"n={n} below one (8, 128) tile "
                       f"({_TILE} elems)", False)
    from .autotune import fused_opt_choice

    if fused_opt_choice(op_type, n, str(jnp.dtype(dtype))) == "xla":
        return "xla", "autotune verdict: xla", False
    return "pallas", "", interpret


def _pick(ins, role):
    x = ins[role][0]
    return x


def _found_scal(ins):
    found = ins.get("FoundInfinite")
    if not found:
        return _scal(0.0)
    return _scal(found[0].reshape(()).astype(jnp.float32))


def _pallas_update(op_type, ins, attrs, interpret, dygraph=False,
                   c1=None, c2=None):
    """The fused kernel leg. c1/c2 override the beta-pow scalars for
    the dygraph variant (bias-correction by step count)."""
    role = "fused_" + op_type
    p = _pick(ins, "Param")
    shape, dtype = p.shape, p.dtype
    n = p.size
    lr = _scal(ins["LearningRate"][0])
    skip = _found_scal(ins)
    if op_type == "sgd":
        (p_new,) = _run_grid(
            role, _sgd_kernel, [lr, skip], [p, _pick(ins, "Grad")], 1, n,
            interpret)
        return {"ParamOut": [p_new.reshape(shape).astype(dtype)]}
    if op_type == "momentum":
        kern = functools.partial(
            _momentum_kernel, mu=attrs.get("mu", 0.9),
            nesterov=bool(attrs.get("use_nesterov", False)))
        p_new, v_new = _run_grid(
            role, kern, [lr, skip],
            [p, _pick(ins, "Grad"), _pick(ins, "Velocity")], 2, n,
            interpret)
        return {"ParamOut": [p_new.reshape(shape).astype(dtype)],
                "VelocityOut": [v_new.reshape(shape).astype(dtype)]}
    b1 = attrs.get("beta1", 0.9)
    b2 = attrs.get("beta2", 0.999)
    # the beta-pow accumulators keep their own shape ((1,) in the static
    # programs), as the XLA reference returns them
    b1p, b2p = ins["Beta1Pow"][0], ins["Beta2Pow"][0]
    if c1 is None:
        c1, c2 = b1p * b1, b2p * b2
    if op_type == "adam":
        kern = functools.partial(
            _adam_kernel, b1=b1, b2=b2,
            eps=attrs.get("epsilon", 1e-8), dygraph=dygraph)
        p_new, m_new, v_new = _run_grid(
            role, kern, [lr, _scal(c1), _scal(c2), skip],
            [p, _pick(ins, "Grad"), _pick(ins, "Moment1"),
             _pick(ins, "Moment2")], 3, n, interpret)
        return _gate_scalars(ins, {
            "ParamOut": [p_new.reshape(shape).astype(dtype)],
            "Moment1Out": [m_new.reshape(shape).astype(dtype)],
            "Moment2Out": [v_new.reshape(shape).astype(dtype)],
            "Beta1PowOut": [b1p * b1], "Beta2PowOut": [b2p * b2]})
    # lamb: fused elementwise phase + XLA norms + elementwise finish
    kern = functools.partial(
        _lamb_phase1_kernel, b1=b1, b2=b2,
        eps=attrs.get("epsilon", 1e-6),
        wd=attrs.get("weight_decay", 0.01), dygraph=dygraph)
    m_new, v_new, r = _run_grid(
        role, kern, [_scal(c1), _scal(c2)],
        [p, _pick(ins, "Grad"), _pick(ins, "Moment1"),
         _pick(ins, "Moment2")], 3, n, interpret)
    pf = p.reshape(-1).astype(jnp.float32)
    p_norm = jnp.linalg.norm(pf)
    r_norm = jnp.linalg.norm(r)
    trust = jnp.where((p_norm > 0) & (r_norm > 0), p_norm / r_norm, 1.0)
    lr_s = ins["LearningRate"][0].reshape(())
    p_new = pf - lr_s * trust * r
    outs = _gate_update(
        {**ins, "Param": [pf],
         "Moment1": [ins["Moment1"][0].reshape(-1)],
         "Moment2": [ins["Moment2"][0].reshape(-1)]},
        {"ParamOut": [p_new], "Moment1Out": [m_new],
         "Moment2Out": [v_new], "Beta1PowOut": [b1p * b1],
         "Beta2PowOut": [b2p * b2]})
    return _shape_back(outs, shape, dtype)


def _shape_back(outs, shape, dtype):
    for slot in ("ParamOut", "Moment1Out", "Moment2Out"):
        if slot in outs:
            outs[slot] = [outs[slot][0].reshape(shape).astype(dtype)]
    return outs


def _gate_scalars(ins, outs):
    """The tensor slots were gated INSIDE the kernel; gate only the
    replicated scalar accumulators here."""
    found = ins.get("FoundInfinite")
    if not found:
        return outs
    skip = found[0].reshape(())
    for slot, old in (("Beta1PowOut", "Beta1Pow"),
                      ("Beta2PowOut", "Beta2Pow")):
        if slot in outs:
            outs[slot] = [jnp.where(skip, ins[old][0], outs[slot][0])]
    return outs


def fused_op_update(op_type, ins, attrs):
    """The static KERNELS delegate: same (ins, attrs) -> outs slot
    convention as static/kernels.py. Ineligible / escaped dispatches
    run the verbatim XLA reference (bitwise with the pre-fusion ops);
    an engaged kernel is counted ``fused_opt.pallas``."""
    p = ins["Param"][0]
    path, reason, interpret = _dispatch(op_type, p.size, p.dtype)
    if path == "pallas":
        out = _pallas_update(op_type, ins, attrs, interpret)
        _engaged("fused_" + op_type, op_type, p.size)
        return out
    bump("fused_opt", "xla", f"{op_type}: {reason}")
    return _XLA[op_type](ins, attrs)


# ---------------------------------------------------------------------------
# ZeRO chunk update (stepplan.apply_bucket): lamb's two-phase trust plan
# ---------------------------------------------------------------------------


def _chunk_segments(param_elems, position, c):
    """Per-element segment ids of a (c,) chunk inside the bucket's
    padded concat buffer: element j of param i maps to segment i, the
    padding tail to the sentinel segment len(param_elems)."""
    ends = np.cumsum(np.asarray(param_elems, np.int64))
    pos = position + jnp.arange(c, dtype=jnp.int32)
    return jnp.searchsorted(jnp.asarray(ends, jnp.int32), pos,
                            side="right")


def fused_chunk_update(op_type, ins, attrs, *, axis=None,
                       param_elems=None, position=None):
    """One ZeRO bucket's per-device (chunk,) update.

    sgd/momentum/adam are elementwise-closed on the chunk — they ARE
    :func:`fused_op_update`. lamb needs the per-param trust ratio, a
    GLOBAL norm over buffers this device only holds 1/g of — the
    two-phase plan:

    1. segment the chunk by ``param_elems`` (static per-param element
       counts; ``position`` is this device's traced flat offset) and
       reduce per-segment partial sq-norms of the param chunk and the
       lamb ``r`` numerator (whose m/v/r elementwise pass is the fused
       kernel when eligible)
    2. one tiny ``lax.psum`` of the two (n_params+1,) partials over
       ``axis`` -> global per-param norms -> per-element trust gathered
       back through the segment ids -> elementwise finish.

    Parity vs the unsharded lamb op is TOLERANCE, not bitwise: the
    sq-norm sum reassociates across devices (documented; the ZeRO
    parity gate is the same amp-style loss tolerance the int8 ring
    uses)."""
    if op_type != "lamb":
        return fused_op_update(op_type, ins, attrs)

    p = ins["Param"][0].reshape(-1)
    g = ins["Grad"][0].reshape(-1)
    m = ins["Moment1"][0].reshape(-1)
    v = ins["Moment2"][0].reshape(-1)
    b1p = ins["Beta1Pow"][0].reshape(())
    b2p = ins["Beta2Pow"][0].reshape(())
    lr = ins["LearningRate"][0].reshape(())
    b1 = attrs.get("beta1", 0.9)
    b2 = attrs.get("beta2", 0.999)
    eps = attrs.get("epsilon", 1e-6)
    wd = attrs.get("weight_decay", 0.01)
    c = p.shape[0]

    path, reason, interpret = _dispatch("lamb", c, p.dtype)
    if path == "pallas":
        kern = functools.partial(
            _lamb_phase1_kernel, b1=b1, b2=b2, eps=eps, wd=wd,
            dygraph=False)
        m_new, v_new, r = _run_grid(
            "fused_lamb", kern, [_scal(b1p * b1), _scal(b2p * b2)],
            [p, g, m, v], 3, c, interpret)
        _engaged("fused_lamb", "lamb", c)
    else:
        bump("fused_opt", "xla", f"lamb chunk: {reason}")
        m_new = b1 * m + (1 - b1) * g
        v_new = b2 * v + (1 - b2) * g * g
        m_hat = m_new / (1 - b1p * b1)
        v_hat = v_new / (1 - b2p * b2)
        r = m_hat / (jnp.sqrt(v_hat) + eps) + wd * p

    n_seg = len(param_elems) + 1
    seg = _chunk_segments(param_elems, position, c)
    sq_p = jax.ops.segment_sum(p * p, seg, num_segments=n_seg)
    sq_r = jax.ops.segment_sum(r * r, seg, num_segments=n_seg)
    if axis is not None:
        sq_p = jax.lax.psum(sq_p, axis)
        sq_r = jax.lax.psum(sq_r, axis)
    p_norm = jnp.sqrt(sq_p)
    r_norm = jnp.sqrt(sq_r)
    trust = jnp.where((p_norm > 0) & (r_norm > 0),
                      p_norm / jnp.where(r_norm > 0, r_norm, 1.0), 1.0)
    p_new = p - lr * trust[seg] * r
    return _gate_update(
        {**ins, "Param": [p], "Moment1": [m], "Moment2": [v]},
        {"ParamOut": [p_new], "Moment1Out": [m_new],
         "Moment2Out": [v_new], "Beta1PowOut": [b1p * b1],
         "Beta2PowOut": [b2p * b2]})


# ---------------------------------------------------------------------------
# dygraph hook (optimizer/optimizer.py): engage-or-None
# ---------------------------------------------------------------------------

# optimizer class name -> (rule kind, slot names in kernel order)
_DY_RULES = {
    "SGD": ("sgd", ()),
    "Momentum": ("momentum", ("velocity",)),
    "Adam": ("adam", ("moment1", "moment2")),
    "AdamW": ("adam", ("moment1", "moment2")),
    "Lamb": ("lamb", ("moment1", "moment2")),
}


def fused_try_rule(opt, g, p, slots, lr, step):
    """Fused replacement for ``opt.rule(g, p, slots, lr, step)``:
    returns ``(p2, new_slots)`` when the Pallas kernel engages, None
    otherwise — the caller then runs the reference rule, so every
    non-engaging path (CPU included) is bitwise the old behavior. The
    dygraph bias-correction variant (eps on the normalized moments) is
    what the kernels compute here."""
    ent = _DY_RULES.get(type(opt).__name__)
    if ent is None:
        return None
    kind, slot_names = ent
    path, _reason, interpret = _dispatch(kind, p.size, p.dtype)
    if path != "pallas":
        return None

    # the kernel shows under the optimizer's own name (``fused_adamw``)
    role = "fused_" + type(opt).__name__.lower()
    shape, dtype = p.shape, p.dtype
    n = p.size
    _engaged(role, kind, n)
    if kind == "sgd":
        (p_new,) = _run_grid(role, _sgd_kernel, [_scal(lr), _scal(0.0)],
                             [p, g], 1, n, interpret)
        return p_new.reshape(shape).astype(dtype), slots
    if kind == "momentum":
        kern = functools.partial(_momentum_kernel,
                                 mu=opt._momentum,
                                 nesterov=bool(opt._nesterov))
        p_new, v_new = _run_grid(
            role, kern, [_scal(lr), _scal(0.0)],
            [p, g, slots["velocity"]], 2, n, interpret)
        return (p_new.reshape(shape).astype(dtype),
                {"velocity": v_new.reshape(shape).astype(dtype)})
    b1, b2 = opt._beta1, opt._beta2
    tf = step.astype(jnp.float32)
    c1 = (1 - b1 ** tf).astype(jnp.float32)
    c2 = (1 - b2 ** tf).astype(jnp.float32)
    if kind == "adam":
        kern = functools.partial(_adam_kernel, b1=b1, b2=b2,
                                 eps=opt._eps, dygraph=True)
        p_new, m_new, v_new = _run_grid(
            role, kern, [_scal(lr), _scal(c1), _scal(c2), _scal(0.0)],
            [p, g, slots["moment1"], slots["moment2"]], 3, n,
            interpret)
        return (p_new.reshape(shape).astype(dtype),
                {"moment1": m_new.reshape(shape).astype(dtype),
                 "moment2": v_new.reshape(shape).astype(dtype)})
    # lamb
    kern = functools.partial(_lamb_phase1_kernel, b1=b1, b2=b2,
                             eps=opt._eps, wd=opt._lamb_wd,
                             dygraph=True)
    m_new, v_new, r = _run_grid(
        role, kern, [_scal(c1), _scal(c2)],
        [p, g, slots["moment1"], slots["moment2"]], 3, n,
        interpret)
    pf = p.reshape(-1).astype(jnp.float32)
    w_norm = jnp.sqrt(jnp.sum(jnp.square(pf)))
    r_norm = jnp.sqrt(jnp.sum(jnp.square(r)))
    trust = jnp.where((w_norm > 0) & (r_norm > 0),
                      w_norm / r_norm, 1.0)
    p_new = pf - jnp.asarray(lr, jnp.float32) * trust * r
    return (p_new.reshape(shape).astype(dtype),
            {"moment1": m_new.reshape(shape).astype(dtype),
             "moment2": v_new.reshape(shape).astype(dtype)})
