"""Driver ``gated_delta_lm_step``: a decoder-only pretraining cell whose
blocks are Gated DeltaNet layers (a scalar-decay delta rule, fewer key
heads than value heads) with an output-gated attention layer every
``full_attention_interval``-th, zero-centred norms, softmax-routed dropless
experts beside a gated shared expert in every layer, and a head of its own,
through the program's ``models.causal_lm.CausalLM.from_config`` +
``optimizer.AdamW`` + ``amp.auto_cast`` + ``jit.TrainStep`` — the entry
points of ``causal_lm_step.py``'s Kimi cell. That driver's ``Loop`` (the
compiled step with its state, the window's call and feed), its
``model_config`` (every top-level key of the file reaches the model) and
its seeded initialiser are used as they are, and ``train_step.compare``;
``conv_hybrid_lm_step.KernelRowTracer`` is imported for the ``kernel:``
rows under the reduction's tenth. This file carries this family's
``param_shapes``, the leaves whose start is not the initialiser's (zero
norm weights, the decays), ``reference/qwen3_next.py`` and
``work_qwen3_next.py``. The window loop below repeats
``conv_hybrid_lm_step.run`` with those swapped (the seventh copy of the
decoder driver: PERF.md section 7.4 asks the next benchmark PR to merge
them).

As there: what the cell needs of the program is asked for BEFORE the
reference's minutes (a program without the mixer refuses within
seconds, exit code 2); the plain reference runs first, while the device
holds nothing else; ONE object is built in set-up, driven from the seed
through its first steps by the window's own call and feed, compared with
the reference over those steps (``train_step.compare``: ``loss_gap``,
``grad_norm_gap``, ``delta_norm_gap``) and handed to the window, in which
nothing compiles and every fetched loss is finite.
"""
from __future__ import annotations

import statistics
import time

import numpy as np

from benchmarks import harness, lm_traffic, work_qwen3_next
from benchmarks.drivers import causal_lm_step
from benchmarks.drivers.causal_lm_step import Loop, model_config
from benchmarks.drivers.conv_hybrid_lm_step import KernelRowTracer
from benchmarks.drivers.train_step import compare
from benchmarks.reference import qwen3_next as reference


def param_shapes(mcfg: dict) -> dict:
    """name -> shape under the program's parameter names."""
    h, v = mcfg["hidden_size"], mcfg["vocab_size"]
    heads, kv_heads, d = (mcfg["num_attention_heads"],
                          mcfg["num_key_value_heads"], mcfg["head_dim"])
    hk, hv = mcfg["linear_num_key_heads"], mcfg["linear_num_value_heads"]
    dk, dv = mcfg["linear_key_head_dim"], mcfg["linear_value_head_dim"]
    keys, values = hk * dk, hv * dv
    held, inner = mcfg["experts_held"], mcfg["moe_intermediate_size"]
    shared = mcfg["shared_expert_intermediate_size"]
    out = {"embed.weight": (v, h), "head": (v, h), "final_norm.weight": (h,)}
    for n, (mixer, _ffn) in enumerate(reference.layer_kinds(mcfg)):
        pre = f"layers.{n}."
        out[pre + "input_norm.weight"] = (h,)
        out[pre + "post_norm.weight"] = (h,)
        m, f = pre + "mixer.", pre + "ffn."
        if mixer == "gdn":
            out.update({
                m + "in_proj_qkvz.weight": (h, 2 * keys + 2 * values),
                m + "in_proj_ba.weight": (h, 2 * hv),
                m + "qkv_conv": (mcfg["linear_conv_kernel_dim"],
                                 2 * keys + values),
                m + "A_log": (hv,), m + "dt_bias": (hv,),
                m + "o_norm": (dv,),
                m + "o_proj.weight": (values, h)})
        else:
            out.update({m + "q_proj.weight": (h, 2 * heads * d),
                        m + "k_proj.weight": (h, kv_heads * d),
                        m + "v_proj.weight": (h, kv_heads * d),
                        m + "q_norm.weight": (d,),
                        m + "k_norm.weight": (d,),
                        m + "o_proj.weight": (heads * d, h)})
        out.update({f + "router.weight": (h, mcfg["num_experts"]),
                    f + "experts_gate": (held, h, inner),
                    f + "experts_up": (held, h, inner),
                    f + "experts_down": (held, inner, h),
                    f + "shared.gate_proj.weight": (h, shared),
                    f + "shared.up_proj.weight": (h, shared),
                    f + "shared.down_proj.weight": (shared, h),
                    f + "shared_gate.weight": (h, 1)})
    return out


def make_params(mcfg: dict, seed: int) -> dict:
    """The configuration's ``assumed`` initialisation from the seed,
    float32, by ``causal_lm_step``'s initialiser (matrices, embeddings and
    expert stacks normal(0, initializer_range); convolution taps
    uniform(+-1/sqrt(taps)); the DeltaNet's output norm one), and then
    this family's own start where it differs: the zero-centred norms'
    weights zero, ``A_log = log A`` with A uniform in (0, 16] and
    ``dt_bias`` one, a value head each."""
    import jax
    import jax.numpy as jnp

    make = causal_lm_step._maker(tuple(sorted(param_shapes(mcfg).items())),
                                 float(mcfg["initializer_range"]))
    key = jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)
    out = dict(make(key))
    for idx, name in enumerate(sorted(out)):
        if name.endswith("norm.weight"):
            out[name] = jnp.zeros_like(out[name])
        elif name.endswith("dt_bias"):
            out[name] = jnp.ones_like(out[name])
        elif name.endswith("A_log"):
            out[name] = jnp.log(16.0 * (1.0 - jax.random.uniform(
                jax.random.fold_in(key, idx), out[name].shape, jnp.float32)))
    return out


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
        from paddle_tpu.nn import GatedDeltaNet  # noqa: F401
    except ImportError as e:
        raise harness.Refused(
            f"the program has no nn.GatedDeltaNet ({e}): it cannot run a "
            "configuration of this driver") from e
    if ctx.trace:
        ctx.tracer = KernelRowTracer(ctx.name)
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
        # when each fetch returned, and the largest rung an expert layer
        # ran at in that step (this share's ladder has two sorted rungs
        # and no dense one: a slow stretch is the data's or the machine's)
        marks.append((round(time.monotonic() - t0, 2),
                      int(np.asarray(loop.routing.numpy())[:, 1].max())))
        if trace_now:
            ctx.tracer.stop()
            traced_s = time.monotonic() - t_tr
            traced_steps = every
        if time.monotonic() - t0 >= ctx.seconds:
            break
    jax.block_until_ready(loss)
    elapsed = time.monotonic() - t0
    compiles = ctx.compiles.count - compiles0
    rows_used = loop.rows_used_pct()

    rate = steps * tokens_per_step / elapsed
    rate_untraced = (steps - traced_steps) * tokens_per_step \
        / (elapsed - traced_s)
    log(f"window: {steps} steps of {tokens_per_step} tokens in "
        f"{elapsed:.3f}s; loss every {every} steps {fetched}; "
        f"dispatch p50 {statistics.median(dispatch_ms):.3f} ms; "
        f"compilations in the window {compiles}; last step's routing "
        f"(pairs on held experts, rung rows) per layer "
        f"{np.asarray(loop.routing.numpy()).tolist()}; each fetch's "
        f"(seconds into the window, largest rung) {marks}")
    bad = sum(1 for x in fetched if not np.isfinite(x))
    checks += [
        harness.check("window_compilations", compiles, 0),
        harness.check("window_nonfinite_losses", bad, 0),
    ]
    observations = {
        "dispatch_ms": dispatch_ms,
        "train_tokens_per_s": rate_untraced,
        "flops_per_token": work_qwen3_next.train_flops_per_token(
            mcfg, seq, seq - 1),
    }
    if rows_used is not None:
        observations["moe_rows_used_pct"] = rows_used
    if ctx.trace:
        observations["kernel_rows_under_top"] = list(ctx.tracer.under_top)
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
