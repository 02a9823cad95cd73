"""Driver ``train_step``: a BERT pretraining cell through the program's
``models.bert.BertForPretraining`` + ``optimizer.AdamW`` +
``amp.auto_cast`` + ``jit.TrainStep`` (the path of
``examples/train_bert.py``).

One object — the compiled step with its state — is built in set-up,
driven from the seed through its first steps by the window's own call
and feed (:func:`Loop.feed_and_step`), compared with the plain reference
over those steps, and handed to the window.
"""
from __future__ import annotations

import statistics
import time

import numpy as np

from benchmarks import flops, harness, traffic
from benchmarks.reference import bert as reference


# ---------------------------------------------------------------------------
# weights from the seed, on the device, in one jitted call
# ---------------------------------------------------------------------------
def param_shapes(cfg: dict) -> dict:
    """name -> shape under the program's parameter names."""
    h, i = cfg["hidden_size"], cfg["intermediate_size"]
    v = cfg["program"]["padded_vocab_size"]
    out = {
        "mlm_bias": (v,),
        "bert.embeddings.word_embeddings.weight": (v, h),
        "bert.embeddings.position_embeddings.weight":
            (cfg["max_position_embeddings"], h),
        "bert.embeddings.token_type_embeddings.weight":
            (cfg["type_vocab_size"], h),
        "bert.embeddings.layer_norm.weight": (h,),
        "bert.embeddings.layer_norm.bias": (h,),
    }
    for n in range(cfg["num_hidden_layers"]):
        pre = f"bert.encoder.layers.{n}."
        for lin, shape in (("self_attn.q_proj", (h, h)),
                           ("self_attn.k_proj", (h, h)),
                           ("self_attn.v_proj", (h, h)),
                           ("self_attn.out_proj", (h, h)),
                           ("linear1", (h, i)), ("linear2", (i, h))):
            out[pre + lin + ".weight"] = shape
            out[pre + lin + ".bias"] = shape[1:]
        for norm in ("norm1", "norm2"):
            out[pre + norm + ".weight"] = (h,)
            out[pre + norm + ".bias"] = (h,)
    for lin, shape in (("bert.pooler", (h, h)), ("mlm_transform", (h, h)),
                       ("nsp", (h, 2))):
        out[lin + ".weight"] = shape
        out[lin + ".bias"] = shape[1:]
    out["mlm_norm.weight"] = (h,)
    out["mlm_norm.bias"] = (h,)
    return out


def make_params(cfg: dict, seed: int) -> dict:
    """BERT's own initialisation from the seed: matrices and embeddings
    normal(0, initializer_range), norm scales one, biases zero; float32."""
    import jax
    import jax.numpy as jnp

    shapes = param_shapes(cfg)
    std = cfg["initializer_range"]

    @jax.jit
    def make(key):
        out = {}
        for idx, (name, shape) in enumerate(sorted(shapes.items())):
            if len(shape) == 2:
                out[name] = std * jax.random.normal(
                    jax.random.fold_in(key, idx), shape, jnp.float32)
            elif name.endswith(".weight"):      # a norm's scale
                out[name] = jnp.ones(shape, jnp.float32)
            else:
                out[name] = jnp.zeros(shape, jnp.float32)
        return out

    return make(jax.random.fold_in(
        jax.random.key(seed & 0xFFFFFFFF), seed >> 32))


def reference_config(cfg: dict) -> dict:
    return {**{k: cfg[k] for k in (
        "hidden_size", "num_attention_heads", "num_hidden_layers",
        "layer_norm_eps")},
        "encoder_layer_norm_eps": cfg["program"]["encoder_layer_norm_eps"]}


# ---------------------------------------------------------------------------
# the comparison that decides ``correct``
# ---------------------------------------------------------------------------
#: a leaf whose reference gradient norm is under this share of the median
#: leaf's has no gradient but rounding noise
NOISE_LEAF = 1e-4


def leaf_gaps(prog: dict, ref: dict) -> dict:
    """leaf -> the program's norm minus the reference's, against the
    reference's norm of that leaf or of the median leaf, whichever is
    larger (some gradients are all but zero)."""
    floor = statistics.median(ref.values())
    return {k: (prog[k] - ref[k]) / max(ref[k], floor) for k in ref}


def worst_leaf(gaps: dict) -> tuple:
    """(gap, leaf) of the leaf whose norm is farthest off."""
    return max((abs(g), k) for k, g in gaps.items())


def compare(prog: dict, ref: dict, limits: dict) -> list:
    """``prog`` / ``ref``: {"loss": [...], "grad_norm": {leaf: x},
    "delta_norm": {leaf: x}} over the same first steps."""
    loss_gap = max(abs(a - b) / abs(b)
                   for a, b in zip(prog["loss"], ref["loss"]))
    grad = leaf_gaps(prog["grad_norm"], ref["grad_norm"])
    # Adam divides a leaf's step by the size of its gradient, so a leaf
    # whose gradient is rounding noise (a key projection's bias: softmax
    # ignores a shift of every score) moves by the sign of that noise at
    # full rate. Such leaves stay in the gradient comparison, under the
    # median floor, and are left out of the comparison of the change.
    floor = NOISE_LEAF * statistics.median(ref["grad_norm"].values())
    moved = [k for k, g in ref["grad_norm"].items() if g >= floor]
    delta = leaf_gaps({k: prog["delta_norm"][k] for k in moved},
                      {k: ref["delta_norm"][k] for k in moved})
    grad_gap, grad_leaf = worst_leaf(grad)
    delta_gap, delta_leaf = worst_leaf(delta)
    return [
        harness.check("loss_gap", loss_gap, limits["loss_gap"]),
        dict(harness.check("grad_norm_gap", grad_gap,
                           limits["grad_norm_gap"]), leaf=grad_leaf),
        dict(harness.check("delta_norm_gap", delta_gap,
                           limits["delta_norm_gap"]), leaf=delta_leaf),
    ]


# ---------------------------------------------------------------------------
# the program under test
# ---------------------------------------------------------------------------
class Loop:
    """The compiled step with its state, and the window's call and feed."""

    def __init__(self, cfg: dict, cell: dict, params: dict, seed: int):
        import paddle_tpu as paddle
        from paddle_tpu import amp, optimizer
        from paddle_tpu.jit import TrainStep
        from paddle_tpu.models.bert import BertConfig, BertForPretraining

        prog = cfg["program"]
        paddle.seed(seed & 0x7FFFFFFF)
        self.model = BertForPretraining(BertConfig(
            vocab_size=prog["padded_vocab_size"],
            hidden_size=cfg["hidden_size"],
            num_hidden_layers=cfg["num_hidden_layers"],
            num_attention_heads=cfg["num_attention_heads"],
            intermediate_size=cfg["intermediate_size"],
            hidden_act=cfg["hidden_act"],
            hidden_dropout_prob=cfg["hidden_dropout_prob"],
            attention_probs_dropout_prob=cfg[
                "attention_probs_dropout_prob"],
            max_position_embeddings=cfg["max_position_embeddings"],
            type_vocab_size=cfg["type_vocab_size"],
            layer_norm_eps=cfg["layer_norm_eps"]))
        if prog["dropout"] == "off":
            self.model.eval()
        named = dict(self.model.named_parameters())
        if {k: tuple(p.shape) for k, p in named.items()} != \
                {k: tuple(v.shape) for k, v in params.items()}:
            raise RuntimeError("the program's BERT parameters no longer "
                               "match drivers/train_step.param_shapes")
        for k, p in named.items():
            p._value = params[k]
        hyper = cell["optimizer"]
        # BERT's schedule: linear warm-up to the peak rate; stepped before
        # each train step, so step t runs at peak * t / warmup_steps
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

        def loss_fn(m, ids, tt, mlm, nsp):
            with amp.auto_cast(level=level, dtype=dtype):
                return m.loss(ids, tt, mlm, nsp)

        self.step = TrainStep(self.model, loss_fn, opt,
                              seed=seed & 0x7FFFFFFF)
        self._to_tensor = paddle.to_tensor
        self.beta1 = hyper["beta1"]
        self.steps = 0

    def feed_and_step(self, batch):
        """The window's own call: this step's host-to-device copy, then
        the step. Returns the loss, still on the device."""
        with harness.span("bench.h2d"):
            tensors = [self._to_tensor(a) for a in batch]
        with harness.span("bench.step"):
            self.schedule.step()
            loss = self.step(*tensors)
        self.steps += 1
        return loss

    def first_gradient_norms(self) -> dict:
        """Per-leaf norm of the gradient as the optimizer got it, worked
        out from Adam's first moment after ONE step: m1 = (1 - b1) g."""
        import jax

        slots = self.step.opt_state["slots"]
        norms = jax.jit(reference.leaf_norms)(
            {k: s["moment1"] for k, s in slots.items()})
        return {k: float(v) / (1.0 - self.beta1) for k, v in norms.items()}

    def change_norms(self, params0: dict) -> dict:
        import jax

        now = {k: p.value for k, p in self.model.named_parameters()}
        norms = jax.jit(lambda a, b: reference.leaf_norms(
            {k: a[k] - b[k] for k in a}))(now, params0)
        return {k: float(v) for k, v in norms.items()}


def first_steps(loop: Loop, cfg: dict, batches: list, seed: int,
                n_steps: int) -> dict:
    """Drive the object through its first steps and read what the
    comparison needs."""
    losses, grad_norm = [], None
    for t in range(n_steps):
        losses.append(float(loop.feed_and_step(batches[t % len(batches)])))
        if t == 0:
            grad_norm = loop.first_gradient_norms()
    # the step donated the seeded weights; make them again for the change
    delta = loop.change_norms(make_params(cfg, seed))
    return {"loss": losses, "grad_norm": grad_norm, "delta_norm": delta}


def run(ctx) -> dict:
    import jax

    cfg, cell, log = ctx.config, ctx.cell, ctx.log
    feed = cell["traffic"]
    n_check = int(cell["correct"]["steps"])
    batches = traffic.train_batches(feed, cfg["vocab_size"], ctx.seed)
    tokens_per_step = int(feed["batch"]) * int(feed["seq"])

    # -- the reference first, while the device holds nothing else
    t_ref = time.monotonic()
    params0 = make_params(cfg, ctx.seed)
    ref = reference.train(params0, reference_config(cfg),
                          batches[:n_check], cell["optimizer"],
                          block_rows=int(cell["correct"]["block_rows"]))
    ref_s = time.monotonic() - t_ref

    # -- the one object, its first steps, the comparison
    loop = Loop(cfg, cell, params0, ctx.seed)
    del params0
    prog = first_steps(loop, cfg, batches, ctx.seed, n_check)
    checks = compare(prog, ref, cell["correct"]["limits"])
    log(f"reference: {n_check} steps in {ref_s:.1f}s (not in setup_s); "
        f"loss program {prog['loss']} reference {ref['loss']}")

    from paddle_tpu.ops.pallas import autotune, counters

    log(f"pallas counters {counters.snapshot()}; autotune "
        f"{autotune.stats()} verdicts {autotune.cached_choices()}")

    # -- the window
    every = int(feed["loss_fetch_every"])
    compiles0 = ctx.compiles.count
    fetched, dispatch_ms = [], []
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
        f"compilations in the window {compiles}")
    bad = sum(1 for x in fetched if not np.isfinite(x))
    checks += [
        harness.check("window_compilations", compiles, 0),
        harness.check("window_nonfinite_losses", bad, 0),
    ]
    return {
        "attempted": steps, "failed": bad * every, "checks": checks,
        "setup_s": setup_s,
        "metrics": {"train_tokens_per_s": rate},
        "observations": {
            "dispatch_ms": dispatch_ms,
            "train_tokens_per_s": rate_untraced,
            "flops_per_token": flops.bert_train_flops_per_token(
                cfg, int(feed["seq"]), int(feed["labelled"])),
        },
    }


def control(ctx) -> dict:
    """The reference in the program's place, one precision step below
    the configuration's bfloat16 (fp8 operands, see
    ``reference.fp8_matmuls``), through the same comparison. Needs no
    window and none of the program."""
    cfg, cell = ctx.config, ctx.cell
    n_check = int(cell["correct"]["steps"])
    batches = traffic.train_batches(cell["traffic"], cfg["vocab_size"],
                                    ctx.seed)[:n_check]
    params0 = make_params(cfg, ctx.seed)
    rows = int(cell["correct"]["block_rows"])
    ref = reference.train(params0, reference_config(cfg), batches,
                          cell["optimizer"], block_rows=rows)
    checks = []
    for name in cell["correct"]["control_precisions"]:
        low = reference.train(
            params0, reference_config(cfg), batches, cell["optimizer"],
            block_rows=rows, matmuls=getattr(reference, name + "_matmuls"))
        checks += [dict(c, name=name + " " + c["name"])
                   for c in compare(low, ref, cell["correct"]["limits"])]
    return {"checks": checks}
