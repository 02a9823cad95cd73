"""Driver ``looped_lm_step``: a decoder-only pretraining cell whose stack
of layers is applied ``total_ut_steps`` times a step with the SAME
weights (``model_type: ouro``): full rotary attention with one key head a
query head, gated FFNs, four norms a block, and after every pass one
untied head and one exit gate, trained on the expected loss under the
exit distribution, through the program's
``models.causal_lm.CausalLM.from_config`` + ``optimizer.AdamW`` +
``amp.auto_cast`` + ``jit.TrainStep``: the entry points of the other four
decoder drivers. ``causal_lm_step.Loop`` (the compiled step with its
state, the window's call and feed), its seeded initialiser,
``train_step.compare`` and ``lm_traffic`` are used as they are; the
parameter shapes, the reference call and the operation counts are the
family's own, so this file carries its own: ``model_config``,
``param_shapes``, ``reference/ouro.py`` and ``work_ouro.py``. The window
loop below repeats ``latent_moe_lm_step.run`` with those swapped
(PERF.md section 7.4 asks the next benchmark PR to merge the five).

As there: the plain reference runs first, while the device holds nothing
else; ONE object is built in set-up, driven from the seed through its
first steps by the window's own call and feed, compared with the
reference over those steps (``train_step.compare``: ``loss_gap``,
``grad_norm_gap``, ``delta_norm_gap``) and handed to the window, in which
nothing compiles and every fetched loss is finite.
"""
from __future__ import annotations

import statistics
import time

import numpy as np

from benchmarks import harness, lm_traffic, work_ouro
from benchmarks.drivers import causal_lm_step
from benchmarks.drivers.train_step import compare
from benchmarks.reference import ouro as reference

_FILE_ONLY = ("published", "program", "assumed", "departs", "reduced")


def model_config(cfg: dict) -> dict:
    """The configuration as the model (and the reference) is built from
    it: every key of the file but its notes, ``total_ut_steps``,
    ``sandwich_norm``, ``qk_norm`` and ``exit_entropy_beta`` among
    them."""
    out = {k: v for k, v in cfg.items() if k not in _FILE_ONLY}
    out["initializer_range"] = cfg["program"]["initializer_range"]
    return out


def param_shapes(mcfg: dict) -> dict:
    """name -> shape under the program's parameter names."""
    h, v = mcfg["hidden_size"], mcfg["vocab_size"]
    heads, kv_heads = (mcfg["num_attention_heads"],
                       mcfg["num_key_value_heads"])
    d, inner = mcfg["head_dim"], mcfg["intermediate_size"]
    out = {"embed.weight": (v, h), "head": (v, h), "final_norm.weight": (h,),
           "exit_gate.weight": (h, 1), "exit_gate.bias": (1,)}
    for n in range(mcfg["num_hidden_layers"]):
        pre = f"layers.{n}."
        m, f = pre + "mixer.", pre + "ffn."
        out.update({
            pre + "input_norm.weight": (h,),
            pre + "mixer_out_norm.weight": (h,),
            pre + "post_norm.weight": (h,),
            pre + "ffn_out_norm.weight": (h,),
            m + "q_proj.weight": (h, heads * d),
            m + "k_proj.weight": (h, kv_heads * d),
            m + "v_proj.weight": (h, kv_heads * d),
            m + "o_proj.weight": (heads * d, h),
            f + "gate_proj.weight": (h, inner),
            f + "up_proj.weight": (h, inner),
            f + "down_proj.weight": (inner, h)})
    return out


def make_params(mcfg: dict, seed: int) -> dict:
    """The configuration's ``assumed`` initialisation from the seed,
    float32, by ``causal_lm_step``'s initialiser: matrices, embeddings
    and the gate's weight normal(0, initializer_range); norm scales one;
    the gate's bias zero."""
    import jax

    make = causal_lm_step._maker(tuple(sorted(param_shapes(mcfg).items())),
                                 float(mcfg["initializer_range"]))
    return make(jax.random.fold_in(
        jax.random.key(seed & 0xFFFFFFFF), seed >> 32))


class Loop(causal_lm_step.Loop):
    """``causal_lm_step.Loop`` on this family's ``model_config``: it is
    handed the model's configuration with the file's ``program`` block,
    which its own ``model_config`` passes through."""

    def __init__(self, cfg: dict, cell: dict, params: dict, seed: int):
        super().__init__(dict(model_config(cfg), program=cfg["program"]),
                         cell, params, seed)


def first_steps(loop: Loop, mcfg: dict, batches: list, seed: int,
                n_steps: int) -> dict:
    """Drive the object through its first steps and read what the
    comparison needs."""
    losses, grad_norm = [], None
    for t in range(n_steps):
        losses.append(float(loop.feed_and_step(batches[t % len(batches)])))
        if t == 0:
            grad_norm = loop.first_gradient_norms()
    # the step donated the seeded weights; make them again for the change
    delta = loop.change_norms(make_params(mcfg, seed))
    return {"loss": losses, "grad_norm": grad_norm, "delta_norm": delta}


def loop_and_batches(ctx) -> tuple:
    """(the cell's Loop from the seed, its host batches): for tools that
    drive the step themselves (``tools/profile_step.py``)."""
    mcfg = model_config(ctx.config)
    batches = lm_traffic.lm_batches(ctx.cell["traffic"], mcfg["vocab_size"],
                                    ctx.seed)
    return Loop(ctx.config, ctx.cell, make_params(mcfg, ctx.seed),
                ctx.seed), batches


def _reference(mcfg, cell, batches, seed, **kw):
    return reference.train(
        lambda: make_params(mcfg, seed), mcfg, batches, cell["optimizer"],
        block_rows=int(cell["correct"]["block_rows"]), **kw)


def run(ctx) -> dict:
    import jax

    try:            # before the reference's minutes, not after them
        from paddle_tpu.nn.functional import (  # noqa: F401
            expected_exit_loss)
    except ImportError as e:
        raise harness.Refused(
            f"the program's models.causal_lm does not read total_ut_steps "
            f"(no looped stack, no expected exit loss: {e}): it cannot run "
            "a configuration of this driver") from e
    cfg, cell, log = ctx.config, ctx.cell, ctx.log
    mcfg = model_config(cfg)
    feed = cell["traffic"]
    n_check = int(cell["correct"]["steps"])
    batches = lm_traffic.lm_batches(feed, mcfg["vocab_size"], ctx.seed)
    batch, seq = int(feed["batch"]), int(feed["seq"])
    tokens_per_step = batch * seq

    # -- the reference first, while the device holds nothing else
    t_ref = time.monotonic()
    ref = _reference(mcfg, cell, batches[:n_check], ctx.seed)
    ref_s = time.monotonic() - t_ref

    # -- the one object, its first steps, the comparison
    loop = Loop(cfg, cell, make_params(mcfg, ctx.seed), ctx.seed)
    prog = first_steps(loop, mcfg, batches, ctx.seed, n_check)
    checks = compare(prog, ref, cell["correct"]["limits"])
    log(f"reference: {n_check} steps in {ref_s:.1f}s (not in setup_s); "
        f"loss program {prog['loss']} reference {ref['loss']}")

    from paddle_tpu.ops.pallas import autotune, counters

    log(f"pallas counters {counters.snapshot()}; autotune "
        f"{autotune.stats()} verdicts {autotune.cached_choices()}")

    # -- the window
    every = int(feed["loss_fetch_every"])
    compiles0 = ctx.compiles.count
    fetched, dispatch_ms, marks = [], [], []
    traced_s, traced_steps = 0.0, 0
    setup_s = time.monotonic() - ctx.t_start - ref_s
    t0 = time.monotonic()
    steps, loss = 0, None
    while True:
        trace_now = ctx.trace and steps == every
        if trace_now:
            ctx.tracer.start()
            t_tr = time.monotonic()
        for _ in range(every):
            t = time.perf_counter()
            loss = loop.feed_and_step(batches[loop.steps % len(batches)])
            dispatch_ms.append((time.perf_counter() - t) * 1e3)
        steps += every
        with harness.span("bench.loss_fetch"):
            fetched.append(float(loss))    # a logger's fetch; a barrier
        # when each fetch returned: nothing in this step follows the
        # data, so a slow stretch is the machine's
        marks.append(round(time.monotonic() - t0, 2))
        if trace_now:
            ctx.tracer.stop()
            traced_s = time.monotonic() - t_tr
            traced_steps = every
        if time.monotonic() - t0 >= ctx.seconds:
            break
    jax.block_until_ready(loss)
    elapsed = time.monotonic() - t0
    compiles = ctx.compiles.count - compiles0

    rate = steps * tokens_per_step / elapsed
    rate_untraced = (steps - traced_steps) * tokens_per_step \
        / (elapsed - traced_s)
    log(f"window: {steps} steps of {tokens_per_step} tokens in "
        f"{elapsed:.3f}s; loss every {every} steps {fetched}; "
        f"dispatch p50 {statistics.median(dispatch_ms):.3f} ms; "
        f"compilations in the window {compiles}; each fetch's seconds "
        f"into the window {marks}")
    bad = sum(1 for x in fetched if not np.isfinite(x))
    checks += [
        harness.check("window_compilations", compiles, 0),
        harness.check("window_nonfinite_losses", bad, 0),
    ]
    observations = {
        "dispatch_ms": dispatch_ms,
        "train_tokens_per_s": rate_untraced,
        "flops_per_token": work_ouro.train_flops_per_token(
            mcfg, seq, seq - 1),
    }
    return {
        "attempted": steps, "failed": bad * every, "checks": checks,
        "setup_s": setup_s,
        "metrics": {"train_tokens_per_s": rate},
        "observations": observations,
    }


def control(ctx) -> dict:
    """The reference in the program's place, one precision step below
    the configuration's bfloat16 (fp8 operands of every dense and batched
    product, see ``reference.fp8_matmuls``), through the same comparison.
    Needs no window and none of the program."""
    cfg, cell = ctx.config, ctx.cell
    mcfg = model_config(cfg)
    n_check = int(cell["correct"]["steps"])
    batches = lm_traffic.lm_batches(cell["traffic"], mcfg["vocab_size"],
                                    ctx.seed)[:n_check]
    ref = _reference(mcfg, cell, batches, ctx.seed)
    checks = []
    for name in cell["correct"]["control_precisions"]:
        low = _reference(mcfg, cell, batches, ctx.seed,
                         matmuls=getattr(reference, name + "_matmuls"))
        checks += [dict(c, name=name + " " + c["name"])
                   for c in compare(low, ref, cell["correct"]["limits"])]
    return {"checks": checks}
