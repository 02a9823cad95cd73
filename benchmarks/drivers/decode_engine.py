"""Driver ``decode_engine``: a serving cell through the program's
``inference.decode.DecodeEngine`` — ``warm()``, ``start()``, open-loop
``submit()`` from one generator thread, ``stop()``.

The engine that set-up builds and warms is the engine the window drives;
``correct`` compares what that window served with the plain reference
once the window has closed and the engine's state is freed.
"""
from __future__ import annotations

import gc
import threading
import time
from typing import List

import numpy as np

from benchmarks import harness, traffic
from benchmarks.reference import decoder as reference

#: stream numbers of the seed (traffic.py uses 1-6)
_SAMPLE_STREAM = 7
#: output tokens of a warm-up request: long enough to still be decoding
#: when the next one joins
_WARM_TOKENS = 64


# ---------------------------------------------------------------------------
# weights from the seed, on the device, in one jitted call
# ---------------------------------------------------------------------------
def make_params(cfg: dict, seed: int) -> dict:
    """The engine's parameter dict under the keys, shapes and scales of
    the program's ``init_decode_params`` (normal * 1/sqrt(fan_in);
    embeddings 0.5 and 0.1; norm scales one), float32."""
    import jax
    import jax.numpy as jnp

    e, f = cfg["hidden_size"], cfg["ffn_dim"]
    v, ctx = cfg["vocab_size"], cfg["max_position_embeddings"]
    spec = {"tok_emb": ((v, e), 0.5), "pos_emb": ((ctx, e), 0.1),
            "lnf": ((e,), None), "head": ((e, v), e ** -0.5)}
    for i in range(cfg["num_hidden_layers"]):
        for n in ("wq", "wk", "wv", "wo"):
            spec[f"l{i}.{n}"] = ((e, e), e ** -0.5)
        spec[f"l{i}.w1"] = ((e, f), e ** -0.5)
        spec[f"l{i}.w2"] = ((f, e), f ** -0.5)
        spec[f"l{i}.ln1"] = ((e,), None)
        spec[f"l{i}.ln2"] = ((e,), None)

    @jax.jit
    def make(key):
        out = {}
        for idx, (name, (shape, scale)) in enumerate(sorted(spec.items())):
            if scale is None:
                out[name] = jnp.ones(shape, jnp.float32)
            else:
                out[name] = scale * jax.random.normal(
                    jax.random.fold_in(key, idx), shape, jnp.float32)
        return out

    return make(jax.random.fold_in(
        jax.random.key(seed & 0xFFFFFFFF), seed >> 32))


# ---------------------------------------------------------------------------
# the program under test
# ---------------------------------------------------------------------------
class _Recorder:
    """Stands in for one of the engine's latency histograms: keeps every
    observation raw (the histogram's buckets are 10-25-50 ms wide) and
    passes it on."""

    def __init__(self, inner):
        self.inner = inner
        self.values: List[float] = []

    def observe(self, value) -> None:
        self.values.append(float(value))
        self.inner.observe(value)

    def __getattr__(self, name):
        return getattr(self.inner, name)


def build_engine(cfg: dict, cell: dict, params: dict):
    """The engine, warmed: every executable compiled or read from the
    compile cache."""
    import jax

    from paddle_tpu.inference.decode import DecodeEngine, DecodeModelConfig

    jax.config.update("jax_default_matmul_precision",
                      cfg["program"]["matmul_precision"])
    heads = cfg["num_attention_heads"]
    mcfg = DecodeModelConfig(
        vocab_size=cfg["vocab_size"], n_layers=cfg["num_hidden_layers"],
        n_heads=heads, head_dim=cfg["hidden_size"] // heads,
        ffn_dim=cfg["ffn_dim"], max_context=cfg["max_position_embeddings"])
    eng = DecodeEngine(mcfg, params=params, dtype=cfg["program"]["dtype"],
                       **cell["engine"])
    eng._h_step = _Recorder(eng._h_step)
    eng._h_prefill = _Recorder(eng._h_prefill)
    eng.warm()
    return eng


def warm_traffic(eng, cfg: dict, cell: dict) -> None:
    """One request through each prefill bucket (1, 2, 4, ... pages), each
    submitted once the one before is decoding, so that a prefill joins a
    running chain: every executable and every host-side splice the window
    will use runs once in set-up."""
    size = int(cell["engine"]["page_size"])
    width = int(cell["engine"]["max_pages_per_seq"])
    rng = np.random.default_rng(0)
    handles, pages = [], 1
    while True:
        pages = min(pages, width)
        n = size // 2 if pages == 1 else (pages // 2) * size + 5
        steps = eng.counters.get("decode_steps", 0)
        handles.append(eng.submit(
            rng.integers(0, cfg["vocab_size"], n).tolist(),
            max_new_tokens=_WARM_TOKENS))
        deadline = time.monotonic() + float(cell["drain_s"])
        while eng.counters.get("decode_steps", 0) < steps + 3:
            if time.monotonic() > deadline:
                raise RuntimeError("warm-up request made no progress")
            time.sleep(0.001)
        if pages == width:
            break
        pages *= 2
    for h in handles:
        h.result(timeout=float(cell["drain_s"]))


def serve_window(eng, requests: List[traffic.Request], seconds: float,
                 drain_s: float, tracer=None, trace_at: float = 0.0,
                 trace_s: float = 0.0) -> dict:
    """Offer ``requests`` open-loop from one generator thread for
    ``seconds``, then wait for every request that was due. Returns the
    per-request records and the window's bounds on the engine's clock
    (``time.monotonic``)."""
    recs: list = []
    t0 = time.monotonic() + 0.05

    def generate():
        for r in requests:
            due = t0 + r.due_s
            wait = due - time.monotonic()
            if wait > 0:
                with harness.span("bench.wait_arrival"):
                    time.sleep(wait)
            rec = {"req": r, "due": due, "handle": None, "error": None}
            with harness.span("bench.submit"):
                rec["submitted"] = time.monotonic()
                try:
                    rec["handle"] = eng.submit(
                        r.prompt, max_new_tokens=r.max_new_tokens)
                except Exception as e:     # refused: counted, not raised
                    rec["error"] = e
            recs.append(rec)

    gen = threading.Thread(target=generate, name="bench-generator")
    gen.start()
    if tracer is not None:
        time.sleep(max(0.0, t0 + trace_at - time.monotonic()))
        tracer.start()
        time.sleep(trace_s)
        tracer.stop()
    time.sleep(max(0.0, t0 + seconds - time.monotonic()))
    t1 = t0 + seconds
    in_flight_end = sum(1 for r in list(recs)
                        if r["handle"] is not None
                        and not r["handle"].done())
    gen.join()
    deadline = t1 + drain_s
    for rec in recs:
        h = rec["handle"]
        if h is None:
            continue
        try:
            rec["tokens"] = h.result(
                timeout=max(0.0, deadline - time.monotonic()))
            rec["times"] = list(h.meta.get("token_times", ()))
        except Exception as e:              # timed out, failed typed
            rec["error"] = e
    return {"records": recs, "t0": t0, "t1": t1,
            "in_flight_end": in_flight_end}


def _ok(rec: dict) -> bool:
    return rec["error"] is None and rec.get("tokens") is not None \
        and len(rec["tokens"]) == rec["req"].max_new_tokens \
        and len(rec.get("times", ())) == len(rec["tokens"])


def reduce_window(win: dict, seconds: float) -> dict:
    """The window's numbers from its request records. A failed or
    refused request counts the window's length as its first-token time;
    gaps are taken over every request due in the window, drain included;
    the rate counts only tokens stamped inside the window."""
    recs = win["records"]
    ttft, gaps, late = [], [], []
    in_window = 0
    for rec in recs:
        late.append((rec["submitted"] - rec["due"]) * 1e3)
        if not _ok(rec):
            ttft.append(seconds * 1e3)
            continue
        times = np.asarray(rec["times"])
        ttft.append((times[0] - rec["due"]) * 1e3)
        gaps.extend((np.diff(times) * 1e3).tolist())
        in_window += int((times <= win["t1"]).sum())
    return {"ttft_ms": ttft, "gap_ms": gaps, "late_ms": late,
            "tokens_in_window": in_window,
            "attempted": len(recs),
            "failed": sum(1 for r in recs if not _ok(r)),
            "in_flight_end": win["in_flight_end"]}


# ---------------------------------------------------------------------------
# the comparison that decides ``correct``
# ---------------------------------------------------------------------------
def _round_up(n: int, to: int) -> int:
    return -(-n // to) * to


def sample_requests(recs: list, n: int, seed: int) -> list:
    """``n`` finished requests drawn from the seed, the longest context
    always among them."""
    done = [r for r in recs if _ok(r)]
    if not done:
        return []
    longest = max(done, key=lambda r: len(r["req"].prompt)
                  + len(r["tokens"]))
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng([seed & (2 ** 63 - 1), _SAMPLE_STREAM])
    pick = rng.permutation(len(rest))[:max(0, n - 1)]
    return [longest] + [rest[i] for i in sorted(pick)]


def served_token_gaps(cfg: dict, params: dict, sample: list,
                      low_precision: str = None) -> dict:
    """Run the reference once over each sampled prompt with its served
    tokens. For every served token: how far its reference logit lies
    below the reference's best at that position. With ``low_precision``
    also the control's reading: the same gap for the token the reference
    puts first when computed at that precision."""
    widest, widest_low, n_tokens, n_diff, n_diff_low = 0.0, 0.0, 0, 0, 0
    worst = None
    for rec in sample:
        prompt, served = rec["req"].prompt, rec["tokens"]
        # few distinct shapes, so few reference programs to compile
        start = len(prompt) - 1
        rows = _round_up(len(served), 64)
        length = min(cfg["max_position_embeddings"],
                     _round_up(max(len(prompt) + len(served),
                                   start + rows), 256))
        rows = min(rows, length - start)
        tokens = np.zeros((length,), np.int32)
        tokens[:len(prompt)] = prompt
        tokens[len(prompt):len(prompt) + len(served)] = served
        ref = np.asarray(reference.logits_rows(
            cfg, params, tokens, start, rows, "highest"))[:len(served)]
        best = ref.max(axis=-1)
        got = ref[np.arange(len(served)), np.asarray(served)]
        gap = best - got
        n_tokens += len(served)
        n_diff += int((gap > 0).sum())
        if float(gap.max()) >= widest:
            widest = float(gap.max())
            worst = (rec["req"].idx, len(prompt), int(gap.argmax()))
        if low_precision is not None:
            low = np.asarray(reference.logits_rows(
                cfg, params, tokens, start, rows,
                low_precision))[:len(served)]
            first = low.argmax(axis=-1)
            gap_low = best - ref[np.arange(len(served)), first]
            n_diff_low += int((gap_low > 0).sum())
            widest_low = max(widest_low, float(gap_low.max()))
    return {"widest_gap": widest, "tokens": n_tokens, "not_best": n_diff,
            "worst": worst, "control_widest_gap": widest_low,
            "control_not_best": n_diff_low}


# ---------------------------------------------------------------------------
# a run
# ---------------------------------------------------------------------------
def _set_up(ctx):
    cfg, cell = ctx.config, ctx.cell
    params = make_params(cfg, ctx.seed)
    eng = build_engine(cfg, cell, params)
    eng.start()
    warm_traffic(eng, cfg, cell)
    return params, eng


def run(ctx) -> dict:
    cfg, cell, log = ctx.config, ctx.cell, ctx.log
    mix = cell["traffic"]
    requests = traffic.open_loop_requests(
        mix, cfg["vocab_size"], ctx.seconds, ctx.seed)
    edges = [int(cell["engine"]["page_size"]) * p
             for p in (1, 2, 4, 8, 16)]
    log(f"traffic: {len(requests)} requests at {mix['rate_rps']} rps; "
        f"prompt tokens {traffic.length_histogram(requests, edges)}; "
        f"output tokens in all "
        f"{sum(r.max_new_tokens for r in requests)}")

    params, eng = _set_up(ctx)
    from paddle_tpu.ops.pallas import autotune, counters

    log(f"pallas counters {counters.snapshot()}; autotune "
        f"{autotune.stats()} verdicts {autotune.cached_choices()}")

    eng._h_step.values.clear()
    eng._h_prefill.values.clear()
    before = dict(eng.counters)
    compiles0 = ctx.compiles.count
    setup_s = time.monotonic() - ctx.t_start
    win = serve_window(
        eng, requests, ctx.seconds, drain_s=float(cell["drain_s"]),
        tracer=ctx.tracer, trace_at=0.4 * ctx.seconds,
        trace_s=min(float(cell["trace_seconds"]), 0.4 * ctx.seconds))
    compiles = ctx.compiles.count - compiles0
    after = dict(eng.counters)
    eng.stop()
    tick_ms = list(eng._h_step.values)
    prefill_ms = list(eng._h_prefill.values)
    max_batch = int(cell["engine"]["max_batch"])
    # free the program's state before the reference runs
    del eng
    gc.collect()

    delta = {k: after.get(k, 0) - before.get(k, 0)
             for k in ("decode_steps", "decode_tokens", "decode_prefills",
                       "decode_failed", "decode_shed", "decode_requests")}
    red = reduce_window(win, ctx.seconds)
    t_ref = time.monotonic()
    sample = sample_requests(win["records"], int(cell["correct"]["sample"]),
                             ctx.seed)
    found = served_token_gaps(cfg, params, sample)
    ref_s = time.monotonic() - t_ref
    limit = cell["correct"]["limits"]["served_token_gap"]
    wrong = found["widest_gap"] > limit

    def line(name, values):
        t = harness.tail(values)
        return (f"{name}: n={t['n']} p50={t['p50']} highest supported "
                f"p{t['highest_supported']}={t['at_highest']}")

    log(line("ttft_ms", red["ttft_ms"]))
    log(line("token_gap_ms", red["gap_ms"]))
    log(line("generator_late_ms", red["late_ms"]))
    log(f"window: attempted {red['attempted']} failed {red['failed']} "
        f"in flight at its end {red['in_flight_end']}; engine counters "
        f"{delta}; compilations in the window {compiles}")
    log(f"reference: {len(sample)} requests, {found['tokens']} served "
        f"tokens in {ref_s:.1f}s; {found['not_best']} not the "
        f"reference's best; widest gap {found['widest_gap']} at "
        f"(request, prompt, token) {found['worst']}")
    checks = [
        harness.check("served_token_gap", found["widest_gap"], limit),
        harness.check("sampled_requests", len(sample),
                      int(cell["correct"]["sample"]),
                      ok=len(sample) >= min(int(cell["correct"]["sample"]),
                                            red["attempted"])
                      and len(sample) > 0),
        harness.check("decode_failed", delta["decode_failed"], 0),
        harness.check("window_compilations", compiles, 0),
    ]
    ticks = max(1, delta["decode_steps"])
    return {
        "attempted": red["attempted"],
        "failed": red["failed"] + (1 if wrong else 0),
        "checks": checks, "setup_s": setup_s,
        "metrics": {
            "serve_tokens_per_s": red["tokens_in_window"] / ctx.seconds,
            "ttft_p95_ms": harness.percentile(red["ttft_ms"], 95),
            "token_gap_p95_ms": harness.percentile(red["gap_ms"], 95)
            if red["gap_ms"] else ctx.seconds * 1e3,
        },
        "observations": {
            "tick_ms": tick_ms, "prefill_ms": prefill_ms,
            "late_ms": red["late_ms"],
            "batch_fill_pct": 100.0 * (delta["decode_tokens"]
                                       - delta["decode_prefills"])
            / (ticks * max_batch),
            "in_flight_end": red["in_flight_end"],
        },
    }


def control(ctx) -> dict:
    """A short window at the cell's own load, then the reference over
    its sample twice: at "highest", and at the precision one step below
    (``correct.control_precision``), whose first token's gap is the
    control's reading."""
    cfg, cell = ctx.config, ctx.cell
    requests = traffic.open_loop_requests(
        cell["traffic"], cfg["vocab_size"], ctx.seconds, ctx.seed)
    params, eng = _set_up(ctx)
    win = serve_window(eng, requests, ctx.seconds,
                       drain_s=float(cell["drain_s"]))
    eng.stop()
    del eng
    gc.collect()
    sample = sample_requests(win["records"], int(cell["correct"]["sample"]),
                             ctx.seed)
    limit = cell["correct"]["limits"]["served_token_gap"]
    out = {}
    for precision in cell["correct"]["control_precisions"]:
        found = served_token_gaps(cfg, params, sample, precision)
        out[precision] = found
        out.setdefault("checks", []).append(harness.check(
            f"control_{precision}_gap", found["control_widest_gap"],
            limit))
    return out
