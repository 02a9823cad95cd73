"""Driver ``latent_moe_lm_step``: a decoder-only pretraining cell whose
every layer mixes by multi-head latent attention WITH its decoupled
rotary key (``model_type: deepseek_v3``), a leading dense gated FFN and
then sigmoid-routed gated experts beside shared ones, through the
program's ``models.causal_lm.CausalLM.from_config`` + ``optimizer.AdamW``
+ ``amp.auto_cast`` + ``jit.TrainStep``: the entry points of the other
three decoder drivers. ``causal_lm_step.Loop`` (the compiled step with
its state, the window's call and feed), its seeded initialiser and
``train_step.compare`` are used as they are; the accepted
``model_config`` reads ``published.num_experts`` and this family counts
its experts under ``n_routed_experts``, and the parameter shapes, the
reference call and the operation counts are the family's own, so this
file carries its own: ``model_config``, ``param_shapes``,
``reference/deepseek_v3.py`` and ``work_deepseek_v3.py``. The window
loop below repeats ``hybrid_ssm_lm_step.run`` with those swapped
(PERF.md section 7.4 asks the next benchmark PR to merge the four).

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

from benchmarks import harness, lm_traffic, work_deepseek_v3
from benchmarks.drivers import causal_lm_step
from benchmarks.drivers.train_step import compare
from benchmarks.reference import deepseek_v3 as reference

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
    a = mcfg["num_attention_heads"]
    nope, pe = mcfg["qk_nope_head_dim"], mcfg["qk_rope_head_dim"]
    rank, vd = mcfg["kv_lora_rank"], mcfg["v_head_dim"]
    held, width = mcfg["experts_held"], mcfg["moe_intermediate_size"]

    def gated(pre, inner):
        return {pre + "gate_proj.weight": (h, inner),
                pre + "up_proj.weight": (h, inner),
                pre + "down_proj.weight": (inner, h)}

    out = {"embed.weight": (v, h), "head": (v, h), "final_norm.weight": (h,)}
    for n, ffn in enumerate(reference.layer_kinds(mcfg)):
        pre = f"layers.{n}."
        m, f = pre + "mixer.", pre + "ffn."
        out.update({
            pre + "input_norm.weight": (h,), pre + "post_norm.weight": (h,),
            m + "q_proj.weight": (h, a * (nope + pe)),
            m + "kv_down_proj.weight": (h, rank + pe),
            m + "kv_norm.weight": (rank,),
            m + "kv_up_proj.weight": (rank, a * (nope + vd)),
            m + "o_proj.weight": (a * vd, h)})
        if ffn == "dense":
            out.update(gated(f, mcfg["intermediate_size"]))
        else:
            out.update({f + "router.weight": (h, mcfg["n_routed_experts"]),
                        f + "experts_gate": (held, h, width),
                        f + "experts_up": (held, h, width),
                        f + "experts_down": (held, width, h)})
            out.update(gated(f + "shared.",
                             mcfg["n_shared_experts"] * width))
    return out


def make_params(mcfg: dict, seed: int) -> dict:
    """The configuration's ``assumed`` initialisation from the seed,
    float32, by ``causal_lm_step``'s initialiser: matrices, embeddings
    and expert stacks normal(0, initializer_range); norm scales one."""
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
        from paddle_tpu.nn.latent_attention import mla_rope  # noqa: F401
    except ImportError as e:
        raise harness.Refused(
            f"the program's nn.MLAttention has no rotary variant ({e}): it "
            "cannot run a configuration of this driver") from e
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
        "flops_per_token": work_deepseek_v3.train_flops_per_token(
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
