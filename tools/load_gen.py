"""Deterministic closed-loop load generator for the serving engine.

Closed loop: each worker submits a request, BLOCKS for its completion,
then submits the next — so offered load self-regulates to the engine's
service rate and the measurement is a throughput/latency probe, not a
queue-explosion test (open-loop overload is what the admission-control
tests cover). Request CONTENT is deterministic: request ``i`` always
carries the same rows (seeded by ``i``) and the same size from the
``sizes`` cycle, whatever thread runs it — so a test or a chaos
drill replays identically.

Library use::

    from tools.load_gen import LoadGen
    summary = LoadGen(engine, total_requests=60, workers=4,
                      sizes=(1, 2, 3)).run()

CLI (against a saved inference blob)::

    python tools/load_gen.py --model-dir /path/to/blob \
        --requests 64 --workers 4 --sizes 1,2,3 [--deadline-s 5]

prints one JSON summary: requests/s, p50/p99 latency, and the
shed/deadline/degraded/failed outcome counts.

Decode workload mode (``DecodeLoadGen`` / ``--decode``): drives the
LLM decode engine with a DETERMINISTIC mixed-length workload —
request ``i`` cycles its prompt length and ``max_new_tokens`` through
the configured ``prompt_lens``/``output_lens`` tuples and draws its
token content from ``RandomState(i)`` — and reports the
autoregressive latency decomposition next to the closed-loop fields:
per-token client latency, TTFT (submit → first token) vs inter-token
percentiles, ``decode_tokens_per_sec``, the prefill-vs-decode token
split (``prefill_tokens[_per_sec]``), and the speculative-decoding
economics (``spec_proposed`` / ``spec_accepted`` /
``spec_accept_rate`` — zeros when ``--spec-k`` is 0). ``--kv-codec
int8`` drives the same workload over int8 KV pages.

Fleet mode (``FleetLoadGen`` / ``--fleet N``): N decode engines behind
one in-process ``FleetRouter``, sprayed with a zipf-distributed
session workload (a few hot sessions dominate — the shape that makes
session affinity and prefix caching earn their keep). Reports
``fleet_tokens_per_sec``, ``fleet_p99_ttft_ms``, the PER-ENGINE token
share (each engine's ``decode_tokens`` delta), the session spread, and
the router's dispatch/failover/affinity/shed counters.
"""
from __future__ import annotations

import itertools
import json
import os
import sys
import threading
import time
import zlib
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)

from paddle_tpu.observability import tracing


def _slowest_traces(rows: List[Tuple[float, Optional[str]]],
                    n: int = 5) -> List[dict]:
    """Top-N slowest requests as ``{"trace_id", "ms"}`` rows — the
    bridge from a bad client p99 to ``trace_view --trace <id>``."""
    ranked = sorted((r for r in rows if r[1]), key=lambda r: -r[0])
    return [{"trace_id": t, "ms": round(ms, 3)}
            for ms, t in ranked[:n]]


def default_feed_maker(predictor) -> Callable[[int, int], Dict[str, np.ndarray]]:
    """Feed factory from the predictor's declared feed specs: request
    ``i`` of ``size`` rows gets RandomState(i)-seeded values — floats
    standard-normal, ints in [0, 8)."""

    specs = predictor._feed_specs

    def make(size: int, i: int) -> Dict[str, np.ndarray]:
        rng = np.random.RandomState(i)
        feed = {}
        for name, (tail, dtype) in specs.items():
            shape = (size,) + tail
            if np.issubdtype(dtype, np.floating):
                feed[name] = rng.randn(*shape).astype(dtype)
            else:
                feed[name] = rng.randint(0, 8, shape).astype(dtype)
        return feed

    return make


class LoadGen:
    """Drive ``engine`` with ``total_requests`` requests from ``workers``
    closed-loop threads; sizes cycle deterministically per request index.
    ``run()`` returns the summary dict (and stores it as ``.summary``)."""

    def __init__(self, engine, total_requests: int = 64, workers: int = 4,
                 sizes: Sequence[int] = (1, 2, 3),
                 deadline_s: Optional[float] = None,
                 make_feed: Optional[Callable] = None,
                 timeout_s: float = 120.0):
        self.engine = engine
        self.total_requests = int(total_requests)
        self.workers = max(1, int(workers))
        self.sizes = tuple(int(s) for s in sizes)
        self.deadline_s = deadline_s
        self.make_feed = make_feed or default_feed_maker(engine.predictor)
        self.timeout_s = float(timeout_s)
        self.summary: Optional[dict] = None

    def run(self) -> dict:
        from paddle_tpu.inference.serving import (DeadlineExceeded,
                                                  EngineStopped,
                                                  Overloaded,
                                                  RequestFailed)

        counter = itertools.count()
        outcomes = {"ok": 0, "shed": 0, "deadline_expired": 0,
                    "failed": 0, "stopped": 0, "other_error": 0}
        lock = threading.Lock()

        def record(kind: str):
            with lock:
                outcomes[kind] += 1

        client_lat_ms = []
        traced: List[Tuple[float, Optional[str]]] = []

        def worker():
            while True:
                i = next(counter)
                if i >= self.total_requests:
                    return
                feed = self.make_feed(self.sizes[i % len(self.sizes)], i)
                t0 = time.perf_counter()
                try:
                    # client-side root span: the engine's serve.request
                    # span parents under it, so the trace id reported
                    # next to a bad client p99 names the WHOLE tree
                    with tracing.span("loadgen.request", parent=False,
                                      request_index=i) as sp:
                        self.engine.infer(feed,
                                          deadline_s=self.deadline_s,
                                          timeout=self.timeout_s)
                    dt_ms = (time.perf_counter() - t0) * 1e3
                    with lock:
                        client_lat_ms.append(dt_ms)
                        traced.append((dt_ms,
                                       format(sp.trace_id, "016x")))
                    record("ok")
                except Overloaded:
                    record("shed")
                except DeadlineExceeded:
                    record("deadline_expired")
                except RequestFailed:
                    record("failed")
                except EngineStopped:
                    record("stopped")
                    return
                except Exception:
                    record("other_error")

        threads = [threading.Thread(target=worker, daemon=True,
                                    name=f"loadgen-{w}")
                   for w in range(self.workers)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=self.timeout_s)
        dt = time.perf_counter() - t0
        completed = sum(outcomes.values())
        lat = self.engine.latency_stats()
        # engine-side truth: percentiles DERIVED FROM THE HISTOGRAM
        # BUCKETS the engine records per request — the latency record a
        # /metrics scraper sees, independent of this client's clocks
        eng = self.engine.engine_latency_stats()
        clat = np.asarray(client_lat_ms, np.float64)
        self.summary = {
            "requests": self.total_requests,
            "completed": completed,
            "wall_s": round(dt, 4),
            # throughput counts SERVED requests only: sheds/expiries are
            # rejected at CPU speed in a closed loop, so counting them
            # would report near the offered rate while the engine
            # actually serves a fraction of it
            "requests_per_sec":
                round(outcomes.get("ok", 0) / dt, 2) if dt else 0.0,
            "completed_per_sec":
                round(completed / dt, 2) if dt else 0.0,
            "workers": self.workers,
            "sizes": list(self.sizes),
            "p50_ms": lat["p50_ms"],
            "p99_ms": lat["p99_ms"],
            "mean_ms": lat["mean_ms"],
            # client-observed: wall time around infer() in THIS process
            # (submit -> result delivery, including handle wakeup)
            "client_p50_ms": (round(float(np.percentile(clat, 50)), 3)
                              if clat.size else 0.0),
            "client_p99_ms": (round(float(np.percentile(clat, 99)), 3)
                              if clat.size else 0.0),
            # engine-reported: bucket-derived, scrape-reproducible
            "engine_p50_ms": eng["e2e_p50_ms"],
            "engine_p99_ms": eng["e2e_p99_ms"],
            "queue_wait_p50_ms": eng["queue_wait_p50_ms"],
            "queue_wait_p99_ms": eng["queue_wait_p99_ms"],
            # the tail, NAMED: a bad client_p99 is one
            # `trace_view --trace <id>` away from its span tree
            "slowest_traces": _slowest_traces(traced),
            **outcomes,
        }
        return self.summary


class DecodeLoadGen:
    """Closed-loop decode workload: ``workers`` threads each submit a
    generation request, block for ALL its tokens, then submit the
    next. Mixed lengths are deterministic per request index: request
    ``i`` draws ``prompt_len`` from ``prompt_lens``, ``max_new_tokens``
    from ``output_lens`` (cycled), and its token ids from
    ``RandomState(i)`` — a test or drill replays identically.

    ``run()`` returns (and stores as ``.summary``) the decode metrics:
    ``decode_tokens_per_sec`` (generated tokens / wall), client-side
    TTFT and inter-token-latency percentiles (from the engine's
    per-token clock stamps), engine-side bucket-derived e2e/step
    percentiles, and the typed outcome counts.

    ``arrival_rate`` (requests/second) switches the gen OPEN-LOOP:
    request ``i`` is submitted no earlier than ``i / arrival_rate``
    seconds after the run starts — a deterministic arrival schedule,
    so queueing (and with a host KV tier, session parking) is driven
    by the OFFERED rate instead of adapting to service time the way
    closed-loop workers do. ``workers`` then caps in-flight requests:
    if all workers are blocked the schedule slips, which is exactly
    the saturation evidence an open-loop run exists to surface."""

    def __init__(self, engine, total_requests: int = 16, workers: int = 4,
                 prompt_lens: Sequence[int] = (4, 12, 24, 8),
                 output_lens: Sequence[int] = (4, 8, 16),
                 deadline_s: Optional[float] = None,
                 timeout_s: float = 300.0, keep_outputs: bool = False,
                 arrival_rate: Optional[float] = None):
        self.engine = engine
        self.total_requests = int(total_requests)
        self.workers = max(1, int(workers))
        self.prompt_lens = tuple(int(p) for p in prompt_lens)
        self.output_lens = tuple(int(o) for o in output_lens)
        self.deadline_s = deadline_s
        self.timeout_s = float(timeout_s)
        self.keep_outputs = bool(keep_outputs)
        self.arrival_rate = float(arrival_rate) if arrival_rate else None
        self.outputs: dict = {}   # request index -> generated tokens
        self.summary: Optional[dict] = None

    def _make_prompt(self, i: int) -> list:
        rng = np.random.RandomState(i)
        n = self.prompt_lens[i % len(self.prompt_lens)]
        vocab = self.engine.config.vocab_size
        return [int(t) for t in rng.randint(0, vocab, size=n)]

    def run(self) -> dict:
        from paddle_tpu.inference.serving import (DeadlineExceeded,
                                                  EngineStopped,
                                                  Overloaded,
                                                  RequestFailed)

        counter = itertools.count()
        outcomes = {"ok": 0, "shed": 0, "deadline_expired": 0,
                    "failed": 0, "stopped": 0, "other_error": 0}
        lock = threading.Lock()
        ttft_ms: list = []
        itl_ms: list = []
        tokens_out = [0]
        tokens_in = [0]
        traced: List[Tuple[float, Optional[str]]] = []

        def record(kind: str):
            with lock:
                outcomes[kind] += 1

        t_start = [0.0]

        def worker():
            while True:
                i = next(counter)
                if i >= self.total_requests:
                    return
                if self.arrival_rate:
                    # open loop: hold request i until its scheduled
                    # arrival — the schedule is a pure function of the
                    # index, so two runs offer identical load
                    delay = (t_start[0] + i / self.arrival_rate
                             - time.perf_counter())
                    if delay > 0:
                        time.sleep(delay)
                prompt = self._make_prompt(i)
                out_n = self.output_lens[i % len(self.output_lens)]
                t0 = time.perf_counter()
                try:
                    # client root span: the engine's decode.request
                    # parents under it — the trace id reported in
                    # slowest_traces names the full tree
                    with tracing.span("loadgen.decode", parent=False,
                                      request_index=i) as sp:
                        h = self.engine.submit(
                            prompt, max_new_tokens=out_n,
                            deadline_s=self.deadline_s)
                        toks = h.result(self.timeout_s)
                    st = h.stats()
                    dt_ms = (time.perf_counter() - t0) * 1e3
                    with lock:
                        if self.keep_outputs:
                            self.outputs[i] = list(toks)
                        tokens_out[0] += len(toks)
                        tokens_in[0] += len(prompt)
                        if "ttft_ms" in st:
                            ttft_ms.append(st["ttft_ms"])
                        times = st.get("token_times") or []
                        itl_ms.extend(
                            (b - a) * 1e3
                            for a, b in zip(times, times[1:]))
                        traced.append((dt_ms,
                                       format(sp.trace_id, "016x")))
                    record("ok")
                except Overloaded:
                    record("shed")
                except DeadlineExceeded:
                    record("deadline_expired")
                except RequestFailed:
                    record("failed")
                except EngineStopped:
                    record("stopped")
                    return
                except Exception:
                    record("other_error")

        threads = [threading.Thread(target=worker, daemon=True,
                                    name=f"decode-loadgen-{w}")
                   for w in range(self.workers)]
        t0 = time.perf_counter()
        t_start[0] = t0
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=self.timeout_s)
        dt = time.perf_counter() - t0

        def pct(arr, q):
            a = np.asarray(arr, np.float64)
            return round(float(np.percentile(a, q)), 3) if a.size else 0.0

        eng = self.engine.engine_latency_stats()
        try:
            ectr = self.engine.counters
        except Exception:
            ectr = {}
        self.summary = {
            "requests": self.total_requests,
            "completed": sum(outcomes.values()),
            "wall_s": round(dt, 4),
            "decode_tokens": tokens_out[0],
            # generated tokens per wall second across the whole
            # closed-loop run — the headline the padded-bucket
            # baseline is compared against
            "decode_tokens_per_sec":
                round(tokens_out[0] / dt, 2) if dt else 0.0,
            # prefill vs decode split: prompt tokens ingested (batched
            # prefill) vs tokens generated (one ragged step each) —
            # the two phases have opposite economics, so a workload
            # row that only reports decode throughput hides half the
            # token bill
            "prefill_tokens": tokens_in[0],
            "prefill_tokens_per_sec":
                round(tokens_in[0] / dt, 2) if dt else 0.0,
            # speculative-decoding economics (0s when spec is off):
            # drafted vs accepted counts and the engine's accept-rate
            # gauge — accepted/proposed, the fraction of draft work
            # that became real tokens
            "spec_proposed": int(ectr.get("spec_proposed", 0)),
            "spec_accepted": int(ectr.get("spec_accepted", 0)),
            "spec_accept_rate": float(ectr.get("spec_accept_rate", 0.0)),
            "workers": self.workers,
            # open- vs closed-loop provenance: at a fixed offered rate
            # the latency percentiles mean something different than
            # under back-pressure-adapted submission
            "mode": "open" if self.arrival_rate else "closed",
            "arrival_rate": self.arrival_rate or 0.0,
            "prompt_lens": list(self.prompt_lens),
            "output_lens": list(self.output_lens),
            # TTFT vs inter-token: the autoregressive latency split
            # (client view, from the engine's per-token clock stamps)
            "ttft_p50_ms": pct(ttft_ms, 50),
            "ttft_p99_ms": pct(ttft_ms, 99),
            "itl_p50_ms": pct(itl_ms, 50),
            "itl_p99_ms": pct(itl_ms, 99),
            # engine-reported: bucket-derived, scrape-reproducible
            "engine_p50_ms": eng["e2e_p50_ms"],
            "engine_p99_ms": eng["e2e_p99_ms"],
            "step_p50_ms": eng["step_p50_ms"],
            "step_p99_ms": eng["step_p99_ms"],
            # the tail, NAMED: the worst requests' trace ids next to
            # the client p99 (`trace_view --trace <id>`)
            "slowest_traces": _slowest_traces(traced),
            **outcomes,
        }
        return self.summary


class FleetLoadGen:
    """Closed-loop fleet workload: spray a :class:`FleetRouter` from
    ``workers`` threads with requests whose SESSION ids follow a zipf
    distribution — a few hot sessions dominate, the realistic shape for
    session-affine routing (uniform sessions would make affinity free
    and prefix caching useless). Deterministic like the other gens:
    request ``i`` draws its session from ``RandomState(77000 + i)``,
    its prompt is the session's shared prefix (so affinity converts to
    prefix-cache hits) plus an ``i``-seeded tail, and lengths cycle
    through ``prompt_lens``/``output_lens``.

    ``run()`` reports the fleet view next to the closed-loop fields:
    ``fleet_tokens_per_sec``, ``fleet_p99_ttft_ms``, PER-ENGINE token
    share (from each engine's ``decode_tokens`` delta — the balance
    evidence), the session spread, and the router's own counters
    (dispatches/failovers/affinity hits/sheds)."""

    def __init__(self, router, total_requests: int = 24, workers: int = 4,
                 prompt_lens: Sequence[int] = (4, 12, 24, 8),
                 output_lens: Sequence[int] = (4, 8, 16),
                 n_sessions: Optional[int] = None, zipf_a: float = 1.5,
                 deadline_s: Optional[float] = None,
                 timeout_s: float = 300.0, keep_outputs: bool = False):
        self.router = router
        self.total_requests = int(total_requests)
        self.workers = max(1, int(workers))
        self.prompt_lens = tuple(int(p) for p in prompt_lens)
        self.output_lens = tuple(int(o) for o in output_lens)
        self.n_sessions = int(n_sessions or max(4, total_requests // 3))
        self.zipf_a = float(zipf_a)
        self.deadline_s = deadline_s
        self.timeout_s = float(timeout_s)
        self.keep_outputs = bool(keep_outputs)
        self.outputs: dict = {}   # request index -> generated tokens
        self.summary: Optional[dict] = None

    def _session_for(self, i: int) -> str:
        rng = np.random.RandomState(77_000 + i)
        rank = int(rng.zipf(self.zipf_a))
        return f"sess-{(rank - 1) % self.n_sessions:03d}"

    def _make_prompt(self, i: int, session: str) -> list:
        cfg = getattr(self.router, "config", None)
        vocab = cfg.vocab_size if cfg is not None else 128
        n = self.prompt_lens[i % len(self.prompt_lens)]
        # shared per-session prefix: affinity keeps the session on one
        # replica, whose prefix cache then serves these tokens for free
        # (crc32, NOT hash(): str hash is salted per process and this
        # workload must replay identically)
        srng = np.random.RandomState(
            zlib.crc32(session.encode()) & 0x7FFFFFFF)
        prefix = [int(t) for t in srng.randint(0, vocab, size=4)]
        rng = np.random.RandomState(i)
        tail = [int(t) for t in rng.randint(0, vocab, size=max(1, n - 4))]
        return prefix + tail

    def run(self) -> dict:
        from paddle_tpu.inference.serving import (DeadlineExceeded,
                                                  EngineStopped,
                                                  Overloaded,
                                                  RequestFailed)

        counter = itertools.count()
        outcomes = {"ok": 0, "shed": 0, "deadline_expired": 0,
                    "failed": 0, "stopped": 0, "other_error": 0}
        lock = threading.Lock()
        ttft_ms: list = []
        tokens_out = [0]
        session_hits: Dict[str, int] = {}

        def engine_tokens() -> Dict[str, int]:
            out = {}
            for r in getattr(self.router, "replicas", []):
                eng = getattr(r, "engine", None)
                if eng is None:
                    continue
                try:
                    out[r.name] = int(eng.counters.get("decode_tokens", 0))
                except Exception:
                    out[r.name] = 0
            return out

        base_tokens = engine_tokens()

        def record(kind: str):
            with lock:
                outcomes[kind] += 1

        def worker():
            while True:
                i = next(counter)
                if i >= self.total_requests:
                    return
                session = self._session_for(i)
                prompt = self._make_prompt(i, session)
                out_n = self.output_lens[i % len(self.output_lens)]
                try:
                    h = self.router.submit(
                        prompt, max_new_tokens=out_n,
                        deadline_s=self.deadline_s, session=session)
                    toks = h.result(self.timeout_s)
                    st = h.stats()
                    with lock:
                        if self.keep_outputs:
                            self.outputs[i] = list(toks)
                        tokens_out[0] += len(toks)
                        session_hits[session] = \
                            session_hits.get(session, 0) + 1
                        if "ttft_ms" in st:
                            ttft_ms.append(st["ttft_ms"])
                    record("ok")
                except Overloaded:
                    record("shed")
                except DeadlineExceeded:
                    record("deadline_expired")
                except RequestFailed:
                    record("failed")
                except EngineStopped:
                    record("stopped")
                    return
                except Exception:
                    record("other_error")

        threads = [threading.Thread(target=worker, daemon=True,
                                    name=f"fleet-loadgen-{w}")
                   for w in range(self.workers)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=self.timeout_s)
        dt = time.perf_counter() - t0

        def pct(arr, q):
            a = np.asarray(arr, np.float64)
            return round(float(np.percentile(a, q)), 3) if a.size else 0.0

        per_engine = {
            name: tok - base_tokens.get(name, 0)
            for name, tok in engine_tokens().items()}
        total_eng = sum(per_engine.values())
        rctr = self.router.counters
        self.summary = {
            "requests": self.total_requests,
            "completed": sum(outcomes.values()),
            "wall_s": round(dt, 4),
            "fleet_tokens": tokens_out[0],
            "fleet_tokens_per_sec":
                round(tokens_out[0] / dt, 2) if dt else 0.0,
            "fleet_ttft_p50_ms": pct(ttft_ms, 50),
            "fleet_p99_ttft_ms": pct(ttft_ms, 99),
            # balance evidence: each engine's decode_tokens delta over
            # the run, and its share of the fleet total
            "per_engine_tokens": per_engine,
            "per_engine_token_share": {
                name: (round(tok / total_eng, 4) if total_eng else 0.0)
                for name, tok in per_engine.items()},
            "sessions": self.n_sessions,
            "session_spread": dict(sorted(
                session_hits.items(), key=lambda kv: -kv[1])[:8]),
            "zipf_a": self.zipf_a,
            "workers": self.workers,
            "prompt_lens": list(self.prompt_lens),
            "output_lens": list(self.output_lens),
            "router_requests": int(rctr.get("router_requests", 0)),
            "router_dispatches": int(rctr.get("router_dispatches", 0)),
            "router_failovers": int(rctr.get("router_failovers", 0)),
            "router_affinity_hits":
                int(rctr.get("router_affinity_hits", 0)),
            "router_sheds": int(rctr.get("router_sheds", 0)),
            **outcomes,
        }
        return self.summary


def _fleet_main(args):
    """--fleet N CLI leg: N self-contained decode engines behind one
    in-process ``FleetRouter``, sprayed with the zipf-session
    workload."""
    from paddle_tpu.inference.decode import DecodeEngine, DecodeModelConfig
    from paddle_tpu.serving import FleetRouter

    cfg = DecodeModelConfig(vocab_size=args.vocab, n_layers=args.layers,
                            n_heads=args.heads, head_dim=args.head_dim,
                            ffn_dim=args.ffn,
                            max_context=args.pages_per_seq
                            * args.page_size)
    engines = []
    for _ in range(max(1, args.fleet)):
        e = DecodeEngine(
            cfg, seed=0, max_batch=args.max_batch, n_pages=args.pages,
            page_size=args.page_size,
            max_pages_per_seq=args.pages_per_seq,
            kv_codec=args.kv_codec)
        e.warm()
        e.start()
        engines.append(e)
    router = FleetRouter(engines, config=cfg,
                         chunk_tokens=args.chunk_tokens)
    try:
        gen = FleetLoadGen(
            router, total_requests=args.requests, workers=args.workers,
            prompt_lens=[int(p) for p in args.prompt_lens.split(",")],
            output_lens=[int(o) for o in args.output_lens.split(",")],
            n_sessions=args.sessions or None, zipf_a=args.zipf_a,
            deadline_s=args.deadline_s)
        summary = gen.run()
        print(json.dumps(summary))
    finally:
        router.drain(timeout=30)


def _decode_main(args):
    """--decode CLI leg: a self-contained tiny decode engine (no blob
    needed — the mode demos/benches the decode data path itself)."""
    from paddle_tpu.inference.decode import DecodeEngine, DecodeModelConfig

    cfg = DecodeModelConfig(vocab_size=args.vocab, n_layers=args.layers,
                            n_heads=args.heads, head_dim=args.head_dim,
                            ffn_dim=args.ffn,
                            max_context=args.pages_per_seq
                            * args.page_size)
    proposer = None
    if args.spec_k:
        from paddle_tpu.inference.decode import NgramProposer
        proposer = NgramProposer()
    engine = DecodeEngine(
        cfg, seed=0, max_batch=args.max_batch, n_pages=args.pages,
        page_size=args.page_size, max_pages_per_seq=args.pages_per_seq,
        kv_codec=args.kv_codec, spec_k=args.spec_k, proposer=proposer,
        host_kv_bytes=args.host_kv_bytes)
    engine.warm()
    engine.start()
    try:
        gen = DecodeLoadGen(
            engine, total_requests=args.requests, workers=args.workers,
            prompt_lens=[int(p) for p in args.prompt_lens.split(",")],
            output_lens=[int(o) for o in args.output_lens.split(",")],
            deadline_s=args.deadline_s, arrival_rate=args.arrival_rate)
        summary = gen.run()
        summary["engine_counters"] = {
            k: v for k, v in sorted(engine.counters.items())
            if k.startswith(("decode_", "kv_", "spec_"))}
        print(json.dumps(summary))
    finally:
        engine.drain(timeout=30)


def main():
    import argparse

    ap = argparse.ArgumentParser("tools/load_gen.py")
    ap.add_argument("--model-dir",
                    help="static.save_inference_model directory "
                         "(serving mode)")
    ap.add_argument("--decode", action="store_true",
                    help="decode workload mode: drive a self-contained "
                         "LLM decode engine with deterministic mixed "
                         "prompt/output lengths")
    ap.add_argument("--fleet", type=int, default=0, metavar="N",
                    help="fleet mode: N decode engines behind one "
                         "FleetRouter, sprayed with a zipf-session "
                         "workload; reports per-engine token share and "
                         "fleet p99 TTFT")
    ap.add_argument("--sessions", type=int, default=0,
                    help="fleet mode: session pool size (0 = derive "
                         "from --requests)")
    ap.add_argument("--zipf-a", type=float, default=1.5,
                    help="fleet mode: zipf exponent for the session "
                         "distribution (higher = hotter head)")
    ap.add_argument("--chunk-tokens", type=int, default=8,
                    help="fleet mode: router dispatch chunk size "
                         "(failover granularity)")
    ap.add_argument("--prompt-lens", default="4,12,24,8",
                    help="decode mode: comma-separated prompt lengths "
                         "(cycled per request)")
    ap.add_argument("--output-lens", default="4,8,16",
                    help="decode mode: comma-separated max_new_tokens "
                         "(cycled per request)")
    ap.add_argument("--spec-k", type=int, default=0,
                    help="decode mode: speculative draft length per "
                         "slot (0 = off; uses the n-gram prompt-lookup "
                         "proposer)")
    ap.add_argument("--kv-codec", default="off", choices=("off", "int8"),
                    help="decode mode: KV page codec (int8 halves pool "
                         "bytes; per-token-row scales)")
    ap.add_argument("--arrival-rate", type=float, default=None,
                    help="decode mode: OPEN-LOOP arrivals at this "
                         "requests/second (request i submits at "
                         "i/rate — deterministic schedule; default is "
                         "closed-loop workers)")
    ap.add_argument("--host-kv-bytes", type=int, default=0,
                    help="decode mode: host-RAM KV offload tier budget "
                         "in bytes (0 = off; under pool pressure the "
                         "engine parks the coldest session to host RAM "
                         "instead of preempt-requeuing)")
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--pages", type=int, default=64)
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--pages-per-seq", type=int, default=8)
    ap.add_argument("--vocab", type=int, default=128)
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--heads", type=int, default=4)
    ap.add_argument("--head-dim", type=int, default=16)
    ap.add_argument("--ffn", type=int, default=128)
    ap.add_argument("--requests", type=int, default=64)
    ap.add_argument("--workers", type=int, default=4)
    ap.add_argument("--sizes", default="1,2,3",
                    help="comma-separated request row counts (cycled)")
    ap.add_argument("--buckets", default="1,2,4,8",
                    help="comma-separated padded batch buckets")
    ap.add_argument("--deadline-s", type=float, default=None)
    args = ap.parse_args()

    if args.fleet:
        _fleet_main(args)
        return
    if args.decode:
        _decode_main(args)
        return
    if not args.model_dir:
        ap.error("--model-dir is required (or pass --decode)")

    from paddle_tpu.inference.serving import (AnalysisPredictor,
                                              ServingEngine)

    predictor = AnalysisPredictor(
        args.model_dir,
        batch_buckets=[int(b) for b in args.buckets.split(",")])
    predictor.warm()
    engine = ServingEngine(predictor).start()
    try:
        gen = LoadGen(engine, total_requests=args.requests,
                      workers=args.workers,
                      sizes=[int(s) for s in args.sizes.split(",")],
                      deadline_s=args.deadline_s)
        summary = gen.run()
        summary["engine_counters"] = {
            k: v for k, v in sorted(engine.counters.items())
            if k.startswith("serve_")}
        print(json.dumps(summary))
    finally:
        engine.drain(timeout=10)


if __name__ == "__main__":
    main()
