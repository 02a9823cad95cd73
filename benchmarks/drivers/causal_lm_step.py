"""Driver ``causal_lm_step``: a decoder-only pretraining cell through the
program's ``models.causal_lm.CausalLM.from_config`` + ``optimizer.AdamW``
+ ``amp.auto_cast`` + ``jit.TrainStep`` — the path of ``train_step.py``'s
BERT cells, for a model assembled from its config's keys. It is generic
over those keys (per-layer mixer and feed-forward kinds, a chip's share
of the experts and of the vocabulary): the next decoder configuration is
a data file that names this driver.

As in ``train_step.py``: the plain reference runs first, while the device
holds nothing else; ONE object — the compiled step with its state — is
built in set-up, driven from the seed through its first steps by the
window's own call and feed (:func:`Loop.feed_and_step`), compared with
the reference over those steps (``train_step.compare``: ``loss_gap``,
``grad_norm_gap``, ``delta_norm_gap``) and handed to the window, in which
nothing compiles and every fetched loss is finite. Batches come from
``benchmarks/lm_traffic.py``, the reference is
``reference/kimi_linear.py``, the operations from
``work_kimi_linear.py``.
"""
from __future__ import annotations

import functools
import math
import statistics
import time

import numpy as np

from benchmarks import harness, lm_traffic, work_kimi_linear
from benchmarks.drivers import train_step
from benchmarks.drivers.train_step import compare
from benchmarks.reference import kimi_linear as reference


def model_config(cfg: dict) -> dict:
    """The configuration as the model (and the reference) is built from
    it. In a file cut to a chip's share ``num_experts`` is the number of
    experts HELD and ``published.num_experts`` the router's width."""
    out = {k: v for k, v in cfg.items() if k not in (
        "published", "program", "assumed", "departs", "reduced")}
    routed = cfg.get("published", {}).get("num_experts")
    if routed is not None:
        out["experts_held"], out["num_experts"] = cfg["num_experts"], routed
    out["expert_offset"] = cfg["program"].get("expert_offset", 0)
    out["initializer_range"] = cfg["program"]["initializer_range"]
    return out


# ---------------------------------------------------------------------------
# weights from the seed, on the device, in one jitted call
# ---------------------------------------------------------------------------
def param_shapes(mcfg: dict) -> dict:
    """name -> shape under the program's parameter names."""
    h, v = mcfg["hidden_size"], mcfg["vocab_size"]
    out = {"embed.weight": (v, h), "head": (v, h), "final_norm.weight": (h,)}
    lin = mcfg["linear_attn_config"]
    width, low = lin["num_heads"] * lin["head_dim"], lin["head_dim"]
    taps = lin["short_conv_kernel_size"]
    a = mcfg["num_attention_heads"]
    nope, pe = mcfg["qk_nope_head_dim"], mcfg["qk_rope_head_dim"]
    rank, vd = mcfg["kv_lora_rank"], mcfg["v_head_dim"]

    def gated(pre, inner):
        return {pre + "gate_proj.weight": (h, inner),
                pre + "up_proj.weight": (h, inner),
                pre + "down_proj.weight": (inner, h)}

    for n, (mixer, ffn) in enumerate(reference.layer_kinds(mcfg)):
        pre = f"layers.{n}."
        out[pre + "input_norm.weight"] = (h,)
        out[pre + "post_norm.weight"] = (h,)
        m = pre + "mixer."
        if mixer == "kda":
            for name in "qkv":
                out[m + name + "_proj.weight"] = (h, width)
                out[m + name + "_conv"] = (taps, width)
            out.update({
                m + "f_a_proj.weight": (h, low),
                m + "f_b_proj.weight": (low, width),
                m + "A_log": (lin["num_heads"],), m + "dt_bias": (width,),
                m + "b_proj.weight": (h, lin["num_heads"]),
                m + "g_a_proj.weight": (h, low),
                m + "g_b_proj.weight": (low, width),
                m + "g_b_proj.bias": (width,),
                m + "o_norm": (lin["head_dim"],),
                m + "o_proj.weight": (width, h)})
        else:
            out.update({
                m + "q_proj.weight": (h, a * (nope + pe)),
                m + "kv_down_proj.weight": (h, rank + pe),
                m + "kv_norm.weight": (rank,),
                m + "kv_up_proj.weight": (rank, a * (nope + vd)),
                m + "o_proj.weight": (a * vd, h)})
        f = pre + "ffn."
        if ffn == "dense":
            out.update(gated(f, mcfg["intermediate_size"]))
        else:
            held, inner = mcfg["experts_held"], mcfg["moe_intermediate_size"]
            out.update({f + "router.weight": (h, mcfg["num_experts"]),
                        f + "experts_gate": (held, h, inner),
                        f + "experts_up": (held, h, inner),
                        f + "experts_down": (held, inner, h)})
            if mcfg.get("num_shared_experts", 0):
                out.update(gated(f + "shared.",
                                 mcfg["num_shared_experts"] * inner))
    return out


@functools.lru_cache(maxsize=None)
def _maker(shapes: tuple, std: float):
    """The jitted initialiser of one set of shapes (compiled once; a run
    calls it four times)."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def make(key):
        out = {}
        for idx, (name, shape) in enumerate(shapes):
            k = jax.random.fold_in(key, idx)
            if name.endswith("_conv"):
                bound = 1.0 / math.sqrt(shape[0])
                out[name] = jax.random.uniform(k, shape, jnp.float32,
                                               -bound, bound)
            elif name.endswith("A_log"):
                out[name] = jnp.log(jax.random.uniform(
                    k, shape, jnp.float32, 1.0, 16.0))
            elif name.endswith("dt_bias"):
                dt = jnp.exp(jax.random.uniform(
                    k, shape, jnp.float32, math.log(1e-3), math.log(1e-1)))
                out[name] = dt + jnp.log(-jnp.expm1(-dt))
            elif len(shape) >= 2:
                out[name] = std * jax.random.normal(k, shape, jnp.float32)
            elif name.endswith(".bias"):
                out[name] = jnp.zeros(shape, jnp.float32)
            else:                               # a norm's scale
                out[name] = jnp.ones(shape, jnp.float32)
        return out

    return make


def make_params(mcfg: dict, seed: int) -> dict:
    """The configuration's ``assumed`` initialisation from the seed,
    float32: matrices, embeddings and expert stacks normal(0,
    initializer_range); norm scales one; biases zero; convolution taps
    uniform(+-1/sqrt(taps)); KDA's ``A_log = log(A)``, A uniform in
    [1, 16], and ``dt_bias`` the inverse softplus of dt, log-uniform in
    [1e-3, 1e-1] (the family's start)."""
    import jax

    make = _maker(tuple(sorted(param_shapes(mcfg).items())),
                  float(mcfg["initializer_range"]))
    return make(jax.random.fold_in(
        jax.random.key(seed & 0xFFFFFFFF), seed >> 32))


# ---------------------------------------------------------------------------
# the program under test
# ---------------------------------------------------------------------------
class Loop(train_step.Loop):
    """The compiled step with its state, and the window's call and feed;
    ``first_gradient_norms`` and ``change_norms`` are the BERT loop's."""

    def __init__(self, cfg: dict, cell: dict, params: dict, seed: int):
        import paddle_tpu as paddle
        from paddle_tpu import amp, optimizer
        from paddle_tpu.jit import TrainStep
        from paddle_tpu.models.causal_lm import CausalLM

        prog = cfg["program"]
        paddle.seed(seed & 0x7FFFFFFF)
        self.model = CausalLM.from_config(
            model_config(cfg), recompute=prog["recompute"] == "block")
        named = dict(self.model.named_parameters())
        if {k: tuple(p.shape) for k, p in named.items()} != \
                {k: tuple(v.shape) for k, v in params.items()}:
            raise RuntimeError("the program's CausalLM parameters no longer "
                               "match drivers/causal_lm_step.param_shapes")
        for k, p in named.items():
            p._value = params[k]
        hyper = cell["optimizer"]
        # linear warm-up to the peak rate; stepped before each train
        # step, so step t runs at peak * t / warmup_steps
        self.schedule = optimizer.lr.LinearWarmup(
            learning_rate=hyper["learning_rate"],
            warmup_steps=hyper["warmup_steps"], start_lr=0.0,
            end_lr=hyper["learning_rate"])
        opt = optimizer.AdamW(
            learning_rate=self.schedule, beta1=hyper["beta1"],
            beta2=hyper["beta2"], epsilon=hyper["epsilon"],
            weight_decay=hyper["weight_decay"],
            parameters=self.model.parameters())
        level, dtype = prog["amp_level"], prog["amp_dtype"]

        def loss_fn(m, ids, labels):
            with amp.auto_cast(level=level, dtype=dtype):
                return m.loss(ids, labels, return_routing=True)

        self.step = TrainStep(self.model, loss_fn, opt,
                              seed=seed & 0x7FFFFFFF)
        self._to_tensor = paddle.to_tensor
        self.beta1 = hyper["beta1"]
        self.steps = 0
        self.routing = None

    def feed_and_step(self, batch):
        """The window's own call: this step's host-to-device copy, then
        the step. Returns the loss, still on the device; the step's
        routing counters stay on the device in ``self.routing``."""
        with harness.span("bench.h2d"):
            tensors = [self._to_tensor(a) for a in batch]
        with harness.span("bench.step"):
            self.schedule.step()
            loss, self.routing = self.step(*tensors)
        self.steps += 1
        return loss

    def rows_used_pct(self):
        """Of the last step: (token, expert) pairs routed to the experts
        held, over the rows of the rungs their grouped products ran at,
        summed over the expert layers; None for a model with none."""
        pairs, rows = np.asarray(self.routing.numpy()).sum(axis=0)
        return 100.0 * float(pairs) / float(rows) if rows else None


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
        import paddle_tpu.models.causal_lm  # noqa: F401
    except ImportError as e:
        raise harness.Refused(
            f"the program has no models.causal_lm ({e}): it cannot run a "
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
        "flops_per_token": work_kimi_linear.train_flops_per_token(
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
