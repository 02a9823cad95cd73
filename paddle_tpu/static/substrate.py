"""Shared AOT compiled-step substrate.

The ONE build path for compiled device steps, extracted from
``Executor._build`` (ROADMAP-flagged: executor.py had absorbed the
whole build/dispatch stack, and the serving predictor and the LLM
decode engine were each about to grow a near-duplicate of it). Every
compiled-step consumer — the training ``Executor``, the serving
``AnalysisPredictor`` (through ``Executor.run``), and the decode
engine's prefill/decode executables — funnels through
:func:`aot_compile`:

- jit with optional DONATION (state buffers reused in place by XLA)
  and explicit in/out shardings (GSPMD boundary maps, PR 10)
- the lower()/compile() AOT split, so trace time and XLA-compile time
  stay separately measurable (``trace_ms`` / ``compile_ms`` counters)
- the persistent disk compile cache (compile_cache.py) armed before the first compile, so a relaunched
  process pays a disk read instead of a cold build
"""
from __future__ import annotations

import time
from typing import Any, Callable, Optional, Sequence, Tuple

__all__ = ["CompiledStep", "aot_compile"]


class CompiledStep:
    """One AOT-compiled executable plus its build timings.

    ``compiled`` is the raw jax ``Compiled`` object (kept accessible:
    the executor's memory/cost planes read ``compiled.memory_analysis()``
    off it); calling the ``CompiledStep`` dispatches it.

    ``jitted`` is the ``jax.jit`` wrapper the executable was lowered
    from. For per-call-latency-critical loops (the decode engine's
    tick) it is the better dispatch handle: the jit wrapper's C++
    fast path skips the Python argument processing every
    ``Compiled.__call__`` pays, and its own first call recompiles
    through the XLA compilation cache the AOT build just populated —
    same executable, cheaper dispatch."""

    __slots__ = ("compiled", "jitted", "trace_ms", "compile_ms")

    def __init__(self, compiled, trace_ms: float, compile_ms: float,
                 jitted=None):
        self.compiled = compiled
        self.jitted = jitted
        self.trace_ms = trace_ms
        self.compile_ms = compile_ms

    def __call__(self, *args):
        return self.compiled(*args)

    def memory_analysis(self):
        try:
            return self.compiled.memory_analysis()
        except Exception:
            return None


def aot_compile(step_fn: Callable, example_args: Tuple[Any, ...], *,
                donate_argnums: Optional[Sequence[int]] = None,
                in_shardings=None, out_shardings=None,
                bump: Optional[Callable[[str, float], None]] = None
                ) -> CompiledStep:
    """AOT-compile ``step_fn`` against ``example_args``.

    ``donate_argnums``: argument indices whose buffers XLA may reuse in
    place (device-resident state — params, KV pages, rng). Donation is
    a liveness contract, not just an optimization: a donated input is
    dead the moment the step dispatches, so any buffer a caller must
    read back later — e.g. the decode engine's token chain, where the
    previous tick's output feeds the next tick's input while a lagged
    harvest still wants to fetch it — must stay OUT of the donate set.
    ``in_/out_shardings``: jit boundary shardings (omit to let jax
    infer from the committed arguments). ``bump(name, value)``: counter
    sink for the ``trace_ms`` / ``compile_ms`` build timings (the
    executor passes its ``_bump``; pass None to skip accounting)."""
    import jax

    from .compile_cache import ensure_enabled

    ensure_enabled()  # disk compile cache, idempotent
    jit_kwargs = {}
    if donate_argnums:
        jit_kwargs["donate_argnums"] = tuple(donate_argnums)
    if in_shardings is not None:
        jit_kwargs["in_shardings"] = in_shardings
    if out_shardings is not None:
        jit_kwargs["out_shardings"] = out_shardings
    jitted = jax.jit(step_fn, **jit_kwargs)
    t0 = time.perf_counter()
    lowered = jitted.lower(*example_args)
    t1 = time.perf_counter()
    compiled = lowered.compile()
    t2 = time.perf_counter()
    trace_ms = round((t1 - t0) * 1e3, 3)
    compile_ms = round((t2 - t1) * 1e3, 3)
    if bump is not None:
        bump("trace_ms", trace_ms)
        bump("compile_ms", compile_ms)
    return CompiledStep(compiled, trace_ms, compile_ms, jitted=jitted)
