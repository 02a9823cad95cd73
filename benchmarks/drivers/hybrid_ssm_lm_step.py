"""Driver ``hybrid_ssm_lm_step``: a decoder-only pretraining cell whose
blocks hold ONE sublayer each, laid out by ``hybrid_override_pattern``
(Mamba-2 state-space layers, grouped-query attention without positions,
sigmoid-routed plain relu^2 experts beside a shared one), through the
program's ``models.causal_lm.CausalLM.from_config`` + ``optimizer.AdamW``
+ ``amp.auto_cast`` + ``jit.TrainStep`` — the entry points of
``causal_lm_step.py``'s Kimi cell and ``gqa_lm_step.py``'s Mellum cell.
``causal_lm_step.Loop`` (the compiled step with its state, the window's
call and feed), its seeded initialiser and ``train_step.compare`` are
used as they are; the accepted ``model_config`` reads
``published.num_experts`` and this family counts its experts under
``n_routed_experts``, and the parameter shapes, the reference call and
the operation counts are the other families', so this file carries its
own: ``model_config``, ``param_shapes``, ``reference/nemotron_h.py`` and
``work_nemotron_h.py``. The window loop below repeats
``gqa_lm_step.run`` with those swapped (PERF.md section 7.4 asks the
next benchmark PR to merge the three).

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

from benchmarks import harness, lm_traffic, work_nemotron_h
from benchmarks.drivers import causal_lm_step
from benchmarks.drivers.train_step import compare
from benchmarks.reference import nemotron_h as reference

_FILE_ONLY = ("published", "program", "assumed", "departs", "reduced")


def model_config(cfg: dict) -> dict:
    """The configuration as the model (and the reference) is built from
    it. In a file cut to a chip's share ``n_routed_experts`` is the
    number of experts HELD and ``published.n_routed_experts`` the
    router's width."""
    out = {k: v for k, v in cfg.items() if k not in _FILE_ONLY}
    out["experts_held"] = cfg["n_routed_experts"]
    out["n_routed_experts"] = cfg.get("published", {}).get(
        "n_routed_experts", cfg["n_routed_experts"])
    out["expert_offset"] = cfg["program"].get("expert_offset", 0)
    out["initializer_range"] = cfg["program"]["initializer_range"]
    return out


def param_shapes(mcfg: dict) -> dict:
    """name -> shape under the program's parameter names."""
    h, v = mcfg["hidden_size"], mcfg["vocab_size"]
    heads, kv_heads = (mcfg["num_attention_heads"],
                       mcfg["num_key_value_heads"])
    d = mcfg["head_dim"]
    m_heads = mcfg["mamba_num_heads"]
    inner = m_heads * mcfg["mamba_head_dim"]
    conv = inner + 2 * mcfg["n_groups"] * mcfg["ssm_state_size"]
    held, width = mcfg["experts_held"], mcfg["moe_intermediate_size"]
    shared = mcfg["n_shared_experts"] \
        * mcfg["moe_shared_expert_intermediate_size"]
    out = {"embed.weight": (v, h), "head": (v, h), "final_norm.weight": (h,)}
    for n, kind in enumerate(reference.layer_kinds(mcfg)):
        pre = f"layers.{n}."
        out[pre + "norm.weight"] = (h,)
        m, f = pre + "mixer.", pre + "ffn."
        if kind == "mamba2":
            out.update({
                m + "in_proj.weight": (h, inner + conv + m_heads),
                m + "xbc_conv": (mcfg["conv_kernel"], conv),
                m + "conv_bias": (conv,),
                m + "A_log": (m_heads,), m + "dt_bias": (m_heads,),
                m + "D": (m_heads,), m + "norm_weight": (inner,),
                m + "out_proj.weight": (inner, h)})
        elif kind == "attention":
            out.update({
                m + "q_proj.weight": (h, heads * d),
                m + "k_proj.weight": (h, kv_heads * d),
                m + "v_proj.weight": (h, kv_heads * d),
                m + "o_proj.weight": (heads * d, h)})
        elif kind == "moe":
            out.update({
                f + "router.weight": (h, mcfg["n_routed_experts"]),
                f + "experts_up": (held, h, width),
                f + "experts_down": (held, width, h),
                f + "shared.up_proj.weight": (h, shared),
                f + "shared.down_proj.weight": (shared, h)})
        else:
            out.update({
                f + "up_proj.weight": (h, mcfg["intermediate_size"]),
                f + "down_proj.weight": (mcfg["intermediate_size"], h)})
    return out


def make_params(mcfg: dict, seed: int) -> dict:
    """The configuration's ``assumed`` initialisation from the seed,
    float32, by ``causal_lm_step``'s initialiser: matrices, embeddings
    and expert stacks normal(0, initializer_range); convolution taps
    uniform(+-1/sqrt(taps)); ``A_log = log(A)``, A uniform in [1, 16];
    ``dt_bias`` the inverse softplus of a step log-uniform in [1e-3,
    1e-1] (``time_step_min`` / ``_max``; the floor 1e-4 lies under it);
    ``D`` and the norm scales one; the convolution's bias zero."""
    import jax
    import jax.numpy as jnp

    if (mcfg["time_step_min"], mcfg["time_step_max"]) != (1e-3, 1e-1) \
            or mcfg["time_step_floor"] > mcfg["time_step_min"]:
        raise harness.Refused("causal_lm_step's initialiser draws dt in "
                              "[1e-3, 1e-1]; this file says otherwise")
    make = causal_lm_step._maker(tuple(sorted(param_shapes(mcfg).items())),
                                 float(mcfg["initializer_range"]))
    params = make(jax.random.fold_in(
        jax.random.key(seed & 0xFFFFFFFF), seed >> 32))
    for name in params:
        if name.endswith("conv_bias"):
            params[name] = jnp.zeros_like(params[name])
    return params


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
        from paddle_tpu.nn import Mamba2Mixer  # noqa: F401
    except ImportError as e:
        raise harness.Refused(
            f"the program has no nn.Mamba2Mixer ({e}): it cannot run a "
            "configuration of this driver") from e
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
        # ran at in that step: a slow stretch is then the data's (a rung
        # above the usual one) or the machine's
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
        "flops_per_token": work_nemotron_h.train_flops_per_token(
            mcfg, seq, seq - 1),
    }
    if rows_used is not None:
        observations["moe_rows_used_pct"] = rows_used
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
