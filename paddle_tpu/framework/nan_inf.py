"""``FLAGS_check_nan_inf``: where a value first stops being finite.

Two readers of one flag (reference ``details/nan_inf_utils_detail.cc``):

- **eager**: every op's outputs are scanned as they are made and the
  first non-finite one raises ``FloatingPointError`` naming the op
  (:func:`check_outputs`, called by ``framework.op.primitive``).
- **inside a compiled** ``jit.TrainStep``: nothing can raise from the
  device, so a step built while the flag is set computes a RECORD beside
  its loss, one row ``[non-finite elements, largest finite |x|, smallest
  finite |x|]`` in float32 for

  - *forward*, each inexact output of every sublayer call, keyed by the
    layer's parameter-name path with its container index
    (``layers.3.mixer``; a second output ``layers.3:1``), and the loss;
    a looped model (``models.causal_lm``, ``total_ut_steps`` > 1) calls
    one layer once a pass, and the rows made in pass t are keyed
    ``layers.3@ut<t>`` (``layers.3.mixer@ut2``, ``final_norm@ut4``);
  - *backward*, each parameter's gradient leaf as ``value_and_grad``
    returns it, keyed by the parameter's name, deepest layer first;
  - *named probes*, :func:`probe` ``(name, x)`` at a point of interest
    inside a layer, keyed ``<layer path>/<name>``, and with ``grad=True``
    the cotangent that reaches that point as ``<layer path>/<name>.grad``.

  ``TrainStep.numerics()`` fetches the last step's record. The flag is
  read when the step is built; a step built with it off traces exactly
  what it traced before this module existed (``record`` stays None, and
  :func:`probe`, :func:`checkpoint`, :func:`switch` are then the identity,
  ``jax.checkpoint`` and ``lax.switch``).

A value made inside ``jax.checkpoint`` or a ``lax.switch`` branch leaves
it only as an output: the program's sites go through :func:`checkpoint`
and :func:`switch`, which hand the rows made inside out beside the result
(a recomputed forward makes its rows again and nothing reads them). Not
carried: ``lax.scan`` / ``while_loop`` bodies (``nn.RNN``), a
``custom_vjp``'s rules, ``shard_map`` and the static executor: a sublayer
call or probe there leaks a tracer while the flag is set.
"""
from __future__ import annotations

import contextlib
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np

from . import dtype as dtype_mod
from . import flags
from .tensor import Tensor

_F32 = jnp.float32
#: what :func:`ut_step` appends to a key
_UT = re.compile(r"@ut\d+")
#: the record of the step being traced; None outside a trace and in a step
#: built with the flag off. ``Layer.__call__`` and :func:`probe` read this.
record = None
#: rows the gradient probes of one step may fill
GRAD_SLOTS = 64


def enabled() -> bool:
    return bool(flags.get_flag("check_nan_inf"))


def check_outputs(op_name, out):
    """The eager scan: raise on the first non-finite output of an op.
    A traced value has no truth to ask for; the compiled step's record
    reads those."""
    for leaf in jax.tree_util.tree_leaves(out):
        if isinstance(leaf, jax.core.Tracer) \
                or not dtype_mod.is_inexact(leaf.dtype):
            continue
        if bool(jnp.any(~jnp.isfinite(leaf))):
            raise FloatingPointError(
                f"Operator {op_name} output contains NaN/Inf")


def row(x, smallest=False):
    """``[count of non-finite elements, largest finite |x|, smallest
    finite |x|]`` of ``x`` as a (3,) float32 array; the maximum is 0 and
    the minimum inf where nothing is finite. The minimum is computed
    only when asked for (inf else)."""
    x = jax.lax.stop_gradient(x)
    finite = jnp.isfinite(x)
    mag = jnp.abs(x).astype(_F32)
    return jnp.stack([
        jnp.sum(~finite, dtype=jnp.int32).astype(_F32),
        jnp.max(jnp.where(finite, mag, 0.0), initial=0.0),
        jnp.min(jnp.where(finite, mag, jnp.inf), initial=jnp.inf)
        if smallest else jnp.asarray(jnp.inf, _F32)])


class _Frame:
    """Rows made in one tracing scope, in program order."""

    def __init__(self):
        self.entries = []       # (key, gradient slot or None, smallest)
        self.rows = []          # (m, 3) pieces: the forward entries' rows

    def stacked(self):
        return jnp.concatenate(self.rows) if self.rows \
            else jnp.zeros((0, 3), _F32)


class Record:
    """The record of one traced step: see the module docstring."""

    def __init__(self, model):
        self.names = {id(layer): name
                      for name, layer in model.named_sublayers()}
        self.path = []          # full names of the layer calls under way
        self.suffix = ""        # "@ut<t>" inside a looped model's pass t
        self.frames = [_Frame()]
        self.sink = None        # (GRAD_SLOTS, 3) zeros, differentiated
        self.grad_slots = 0

    # -- making rows --------------------------------------------------------
    def add_row(self, key, row, smallest=False):
        frame = self.frames[-1]
        frame.entries.append((key, None, smallest))
        frame.rows.append(row[None])

    def layer_call(self, layer, inputs, kwargs):
        """``layer.forward`` with a row for each inexact output."""
        name = self.names.get(id(layer))
        if name is None:        # a layer the model's tree does not hold
            name = ".".join(self.path[-1:] + [layer.__dict__["_scope"]])
        self.path.append(name)
        try:
            out = layer.forward(*inputs, **kwargs)
        finally:
            self.path.pop()
        leaves = [_array(x) for x in jax.tree_util.tree_leaves(
            out, is_leaf=lambda x: isinstance(x, Tensor))]
        key = name + self.suffix
        for i, x in enumerate(a for a in leaves if a is not None):
            self.add_row(f"{key}:{i}" if i else key, row(x))
        return out

    def probe_key(self, name):
        return "/".join([p + self.suffix for p in self.path[-1:]] + [name])

    def probe(self, name, x, grad, smallest):
        key = self.probe_key(name)
        self.add_row(key, row(x, smallest), smallest)
        if not grad:
            return x
        if self.grad_slots == GRAD_SLOTS:
            raise RuntimeError(
                f"more than {GRAD_SLOTS} gradient probes in one step "
                "(framework.nan_inf.GRAD_SLOTS)")
        slot, self.grad_slots = self.grad_slots, self.grad_slots + 1
        self.frames[-1].entries.append((key + ".grad", slot, False))
        return _tap(x, self.sink[slot])

    # -- through a transform's own scope ------------------------------------
    @contextlib.contextmanager
    def frame(self):
        self.frames.append(_Frame())
        try:
            yield self.frames[-1]
        finally:
            self.frames.pop()

    def take(self, frame, rows):
        """``frame``'s entries, whose rows came out of its scope as
        ``rows``, into the scope around it."""
        self.frames[-1].entries += frame.entries
        self.frames[-1].rows.append(rows)

    # -- the step's one array -----------------------------------------------
    def finish(self, loss, forward_rows, grad_rows, grads):
        """(keys, table): ``forward_rows`` as the loss function handed
        them out, the loss, then the backward rows in the order the
        backward makes them: ``grad_rows`` is the sink's cotangent,
        ``grads`` the parameters' gradients by name."""
        seen, entries = {}, []
        for key, slot, smallest in self.frames[0].entries:
            n = seen[key] = seen.get(key, 0) + 1    # a layer called again
            entries.append((key if n == 1 else f"{key}#{n}", slot, smallest))
        forward = [e for e in entries if e[1] is None]
        at = {key: i for i, (key, _, _) in enumerate(entries)}
        # a looped model's layer under its plain name too, where its
        # FIRST pass returned: the backward reaches that use last, and
        # only there is a parameter's gradient the sum over its uses
        for i, (key, _, _) in enumerate(entries):
            at.setdefault(_UT.sub("", key), i)

        def owner_at(param):
            # where the layer that holds it (or, for one that is handed
            # on and never called, the nearest layer round it) returned;
            # the root's leaves after everything
            owner = param.rpartition(".")[0]
            while owner and owner not in at:
                owner = owner.rpartition(".")[0]
            return at[owner] if owner else len(entries)

        backward = [(-at[key], 1, key, grad_rows[slot])
                    for key, slot, _ in entries if slot is not None]
        backward += [(-owner_at(name), 0, name, row(g))
                     for name, g in grads.items()
                     if dtype_mod.is_inexact(g.dtype)]
        backward.sort(key=lambda b: b[:2])      # stable: a layer's leaves
        keys = [(key, "forward", smallest) for key, _, smallest in forward]
        keys.append(("loss", "forward", False))
        keys += [(key, "backward", False) for _, _, key, _ in backward]
        table = jnp.concatenate(
            [forward_rows, row(loss)[None]]
            + [r[None] for _, _, _, r in backward])
        return tuple(keys), table


def _array(x):
    """The inexact array of an output leaf, else None."""
    x = x._value if isinstance(x, Tensor) else x
    return x if isinstance(x, jax.Array) and dtype_mod.is_inexact(x.dtype) \
        else None


@jax.custom_vjp
def _tap(x, slot):
    """``x``; its cotangent's row leaves as the cotangent of ``slot``, a
    (3,) slice of the record's differentiated sink."""
    return x


_tap.defvjp(lambda x, slot: (x, None), lambda _, ct: (ct, row(ct)))


@contextlib.contextmanager
def ut_step(t):
    """Inside, the rows of the step being recorded are keyed
    ``<key>@ut<t>``: pass ``t`` of a looped model, which calls each of
    its layers once a pass. Nothing outside a step built with the flag
    set."""
    if record is None:
        yield
        return
    saved, record.suffix = record.suffix, f"@ut{t}"
    try:
        yield
    finally:
        record.suffix = saved


@contextlib.contextmanager
def recording(model):
    """Make ``model``'s sublayer calls and the probes record, for the
    trace of one step."""
    global record
    saved, record = record, Record(model)
    try:
        yield record
    finally:
        record = saved


def probe(name, x, grad=False, smallest=False):
    """Record ``x`` (an array, or a Tensor) under ``<enclosing layer's
    path>/<name>``; with ``grad`` also the cotangent that reaches it, as
    ``.../<name>.grad``; with ``smallest`` also its smallest finite
    ``|x|`` (a denominator). Returns ``x``. The identity, adding nothing
    to the traced program, outside a step built with the flag set."""
    if record is None:
        return x
    if isinstance(x, Tensor):
        return Tensor(record.probe(name, x._value, grad, smallest),
                      stop_gradient=x.stop_gradient)
    return record.probe(name, x, grad, smallest)


def probe_row(name, row):
    """Record a row that :func:`row` made where no probe can stand (a
    ``custom_vjp``'s forward rule hands it out as an output)."""
    if record is not None:
        record.add_row(record.probe_key(name), row)


def _carrying(rec, fn, made):
    """``fn`` in a frame of its own, appended to ``made``: (its result,
    the rows made inside)."""
    def run(*args):
        with rec.frame() as frame:
            out = fn(*args)
        made.append(frame)
        return out, frame.stacked()
    return run


def checkpoint(fn, **kwargs):
    """``jax.checkpoint(fn, **kwargs)``, handing out the rows made
    inside."""
    rec = record
    if rec is None:
        return jax.checkpoint(fn, **kwargs)

    @functools.wraps(fn)
    def call(*args):
        made = []
        out, rows = jax.checkpoint(_carrying(rec, fn, made), **kwargs)(*args)
        rec.take(made[0], rows)
        return out

    return call


def switch(index, branches, *operands):
    """``lax.switch``, handing out the rows made inside the branch that
    runs: every branch has to make the same forward rows, and no
    gradient probe."""
    rec = record
    if rec is None:
        return jax.lax.switch(index, branches, *operands)
    made = []
    out, rows = jax.lax.switch(
        index, [_carrying(rec, b, made) for b in branches], *operands)
    if any(f.entries != made[0].entries for f in made) \
            or any(slot is not None for _, slot, _ in made[0].entries):
        raise ValueError(
            "the branches of a switch make different records: "
            f"{[[e[0] for e in f.entries] for f in made]}")
    rec.take(made[0], rows)
    return out


# ---------------------------------------------------------------------------
# reading a fetched record
# ---------------------------------------------------------------------------
class Numerics(dict):
    """``{key: {"pass", "nonfinite", "absmax"[, "absmin"]}}`` in
    execution order. ``first_nonfinite`` is the first forward key with a
    non-finite element, else the deepest backward one (a non-finite
    cotangent born in block k reaches every earlier block, so the
    deepest layer whose own gradients are non-finite brackets the
    origin); None, as ``first_pass``, where everything is finite."""

    first_nonfinite = None
    first_pass = None


def report(keys, table, check=False) -> Numerics:
    """The fetched ``table`` (n, 3) under its static ``keys`` as a
    :class:`Numerics`; with ``check``, raise ``FloatingPointError`` naming
    ``first_nonfinite`` and its pass."""
    table = np.asarray(table)
    out = Numerics()
    for (key, which, smallest), (n, big, small) in zip(keys, table):
        out[key] = {"pass": which, "nonfinite": int(n), "absmax": float(big)}
        if smallest:
            out[key]["absmin"] = float(small)
    bad = [k for k, v in out.items() if v["nonfinite"]]
    if bad:
        # forward keys come first, and the backward ones deepest first
        out.first_nonfinite = bad[0]
        out.first_pass = out[bad[0]]["pass"]
        if check:
            raise FloatingPointError(
                f"train step: {out.first_nonfinite!r} is the first "
                f"non-finite value, in the {out.first_pass} pass "
                f"({out[bad[0]]['nonfinite']} elements; largest finite "
                f"|x| {out[bad[0]]['absmax']:.6g})")
    return out
