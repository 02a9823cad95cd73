"""The benchmark's harness: finds a cell, its configuration, its driver
and the per-layer metric readers BY NAME in the data directories, runs
the cell once and prints the contract's result line last.

Nothing here names a cell, a configuration or a metric: a later PR adds
``workloads/<cell>.json``, ``configs/<config>.json``,
``layer_metrics/<metric>.json`` (+ ``.py``) or ``drivers/<driver>.py`` and
an entry in ``BENCHMARK.json``, and edits no file that is there.
"""
from __future__ import annotations

import importlib.util
import json
import math
import os
import shutil
import sys
import time
import types
from typing import Callable, List, Optional, Sequence

ROOT = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(ROOT)
#: everything a run writes (traces) goes here, inside the checkout
OUT_DIR = os.path.join(REPO, ".bench_out")

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class Refused(Exception):
    """The run cannot be a measurement (no chip, unknown device, ...)."""


# ---------------------------------------------------------------------------
# data files
# ---------------------------------------------------------------------------
def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_py(path: str) -> types.ModuleType:
    """Import one file by path (a metric's name may hold dots)."""
    name = "bench_" + "".join(c if c.isalnum() else "_" for c in
                              os.path.relpath(path, ROOT))
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(name: str, root: str = ROOT) -> tuple:
    """(cell, configuration) of the cell ``name``."""
    path = os.path.join(root, "workloads", name + ".json")
    if not os.path.isfile(path):
        raise Refused(f"no cell file {path}")
    cell = load_json(path)
    config = load_json(os.path.join(root, "configs",
                                    cell["config"] + ".json"))
    return cell, config


def load_driver(config: dict, root: str = ROOT) -> types.ModuleType:
    return load_py(os.path.join(root, "drivers", config["driver"] + ".py"))


def _reader(meta: dict, folder: str, stem: str) -> Callable:
    """A metric's reader: ``<metric>.py``'s ``read(run)`` when the file
    is there, else what the ``.json`` names under ``from`` — a key of the
    trace reduction, or an observation of the driver, reduced by a
    percentile ("p50", "p95") when it is a list."""
    path = os.path.join(folder, stem + ".py")
    if os.path.isfile(path):
        return load_py(path).read
    src = meta["from"]
    if "trace" in src:
        return lambda run: (run.get("trace") or {}).get(src["trace"])
    key, how = src["observation"], src.get("reduce")

    def read(run):
        value = run["observations"].get(key)
        if how is None or value is None:
            return value
        return percentile(value, float(how[1:])) if value else None

    return read


def layer_metrics(root: str = ROOT) -> List[dict]:
    """Every per-layer metric in ``layer_metrics/``: one ``.json`` each,
    with a reader that returns None when it finds nothing to read."""
    folder = os.path.join(root, "layer_metrics")
    out = []
    for fname in sorted(os.listdir(folder)):
        if not fname.endswith(".json"):
            continue
        stem = fname[:-len(".json")]
        meta = load_json(os.path.join(folder, fname))
        meta.setdefault("name", stem)
        meta["read"] = _reader(meta, folder, stem)
        out.append(meta)
    return out


def peaks_for(kind: str, root: str = ROOT) -> dict:
    table = load_json(os.path.join(root, "peaks.json"))
    if kind not in table:
        raise Refused(f"device_kind {kind!r} is not in peaks.json "
                      f"({sorted(table)}): add its row with a source")
    return table[kind]


# ---------------------------------------------------------------------------
# arithmetic
# ---------------------------------------------------------------------------
def percentile(values: Sequence[float], q: float) -> float:
    """Nearest-rank percentile: the smallest value with at least q% of
    the samples at or below it."""
    if not values:
        raise ValueError("percentile of no samples")
    ordered = sorted(values)
    rank = max(1, math.ceil(q / 100.0 * len(ordered)))
    return float(ordered[rank - 1])


_LADDER = (50.0, 75.0, 90.0, 95.0, 99.0, 99.9)


def supported_percentile(n: int, ladder=_LADDER) -> Optional[float]:
    """The highest percentile of the ladder with at least ten samples
    beyond it among ``n``; None when even the lowest has fewer."""
    best = None
    for q in ladder:
        if n - math.ceil(q / 100.0 * n) >= 10:
            best = q
    return best


def tail(values: Sequence[float]) -> dict:
    """Median, the highest supported percentile, and the sample count."""
    n = len(values)
    q = supported_percentile(n)
    return {"n": n, "p50": percentile(values, 50) if n else None,
            "highest_supported": q,
            "at_highest": percentile(values, q) if q else None}


# ---------------------------------------------------------------------------
# what a run observes about the process
# ---------------------------------------------------------------------------
class CompileCounter:
    """Counts compile requests (a jit cache miss in this process, whether
    or not the disk cache serves it) through jax's monitoring events."""

    def __init__(self):
        import jax

        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, _secs, **_kw):
        if event == _COMPILE_EVENT:
            self.count += 1


def device_info() -> dict:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes(chips: int) -> int:
    """Peak bytes in use on the fullest of the chips used (0 where the
    backend reports none, as the CPU does)."""
    import jax

    peak = 0
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


class Tracer:
    """One profiler trace of a slice of the window, reduced on ``stop``.
    The slice is wrapped in the ``bench.trace_window`` span so that the
    reducer knows its extent on the trace's own clock."""

    def __init__(self, cell_name: str):
        self.dir = os.path.join(OUT_DIR, "trace", cell_name)
        self.result = None
        self.kernels: List[str] = []
        self._span = None

    def start(self) -> None:
        import jax

        from . import trace_reduce

        shutil.rmtree(self.dir, ignore_errors=True)
        os.makedirs(self.dir, exist_ok=True)
        jax.profiler.start_trace(self.dir)
        self._span = jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN)
        self._span.__enter__()

    def stop(self) -> None:
        import jax

        from . import trace_reduce

        self._span.__exit__(None, None, None)
        jax.profiler.stop_trace()
        path = trace_reduce.find_xplane(self.dir)
        if path is not None:
            trace = trace_reduce.load_xplane(path)
            self.result = trace_reduce.reduce(trace)
            # the names under which Pallas kernels show in the trace
            self.kernels = sorted({
                trace_reduce.op_family(n)
                for events in trace["devices"].values()
                for n, _, _ in events if n.startswith("kernel:")})
        shutil.rmtree(self.dir, ignore_errors=True)


def span(name: str):
    """A host span in the profiler's own trace (free when none runs)."""
    import jax

    return jax.profiler.TraceAnnotation(name)


# ---------------------------------------------------------------------------
# one run of one cell
# ---------------------------------------------------------------------------
def check(name: str, value, limit, ok: Optional[bool] = None) -> dict:
    """One compared number beside its limit (``value <= limit`` unless
    ``ok`` says otherwise)."""
    if ok is None:
        ok = value is not None and not math.isnan(value) and value <= limit
    return {"name": name, "value": value, "limit": limit, "ok": bool(ok)}


def gate(chips: int, root: str = ROOT) -> dict:
    """Refuse anything but enough chips of a known kind."""
    info = device_info()
    if info["platform"] != "tpu":
        raise Refused(f"jax found platform {info['platform']!r}, not "
                      "'tpu': the benchmark measures on the chip only")
    if info["count"] < chips:
        raise Refused(f"the cell needs {chips} chips, jax found "
                      f"{info['count']}")
    peaks_for(info["kind"], root)
    return info


def context(name: str, seed: int, seconds: float, root: str = ROOT,
            check_device: bool = True,
            log: Callable[[str], None] = print) -> tuple:
    """(ctx, driver, device info) of one cell: its files loaded, the
    device checked, the compile cache armed the program's own way
    (JAX_COMPILATION_CACHE_DIR if set, else <checkout>/.jax_cache). What
    ``run.py`` and the tools under ``tools/`` hand to a driver."""
    cell, config = load_cell(name, root)
    chips = int(cell["chips"])
    info = gate(chips, root) if check_device else device_info()
    from paddle_tpu.static import compile_cache

    compile_cache.ensure_enabled()
    log(f"compile_cache_dir={compile_cache.cache_dir()}")
    ctx = types.SimpleNamespace(
        cell=cell, config=config, name=name, seed=int(seed),
        seconds=float(seconds), chips=chips, log=log, trace=False,
        tracer=None, t_start=time.monotonic(), compiles=CompileCounter())
    return ctx, load_driver(config, root), info


def run_cell(name: str, seed: int, seconds: float, trace: bool,
             root: str = ROOT, t_start: Optional[float] = None,
             peaks: Optional[dict] = None, check_device: bool = True,
             log: Callable[[str], None] = print) -> dict:
    """Run the cell once and return the result object (the caller prints
    it). ``check_device=False`` with a ``peaks`` row is the CPU
    rehearsal the tests drive; it is never a measurement."""
    t_start = time.monotonic() if t_start is None else t_start
    ctx, driver, info = context(name, seed, seconds, root, check_device, log)
    cell, config, chips = ctx.cell, ctx.config, ctx.chips
    peaks = peaks if peaks is not None else peaks_for(info["kind"], root)
    log(f"cell={name} config={cell['config']} driver={config['driver']} "
        f"seed={seed} seconds={seconds} trace={int(trace)} device={info}")
    ctx.t_start, ctx.trace = t_start, bool(trace)
    ctx.tracer = Tracer(name) if trace else None
    out = driver.run(ctx)

    for c in out["checks"]:
        log(f"check {c['name']}: value {c['value']} limit {c['limit']} "
            f"{'ok' if c['ok'] else 'NOT OK'}"
            + (f" (worst leaf {c['leaf']})" if "leaf" in c else ""))
    correct = all(c["ok"] for c in out["checks"])
    device = dict(info, count=chips,
                  memory_peak_bytes=memory_peak_bytes(chips))
    run = dict(out, cell=cell, config=config, peaks=peaks, chips=chips,
               device=device, seconds=float(seconds))
    if device["memory_peak_bytes"]:
        run["observations"]["hbm_peak_pct"] = \
            100.0 * device["memory_peak_bytes"] / peaks["hbm_bytes"]
    result = {"correct": correct, "attempted": int(out["attempted"]),
              "failed": int(out["failed"]), "device": device}
    if trace:
        red = ctx.tracer.result
        run["trace"] = red
        log(f"trace: kernels {ctx.tracer.kernels}")
        if red is not None:
            device["busy_s"] = red["busy_s"]
            device["window_s"] = red["window_s"]
            result["breakdown"] = {"device_ops": red["device_ops"],
                                   "idle_gaps": red["idle_gaps"]}
        elif check_device:
            raise Refused("the traced slice holds no device operation")
        metrics = {}
        for meta in layer_metrics(root):
            # a layer metric belongs to every cell that reports the
            # end-to-end metric it moves, those of later PRs too
            if meta["moves"] not in cell["end_to_end"]:
                continue
            value = meta["read"](run)
            if value is not None:
                metrics[meta["name"]] = {"value": float(value),
                                         "unit": meta["unit"]}
    else:
        units = cell["end_to_end"]
        metrics = {k: {"value": float(v), "unit": units[k]}
                   for k, v in out["metrics"].items()}
        metrics["setup_s"] = {"value": float(out["setup_s"]),
                              "unit": units["setup_s"]}
    result["metrics"] = metrics
    return result


def main(argv: Sequence[str], t_start: float) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace), t_start=t_start)
    except Refused as e:
        print(f"REFUSED: {e}", file=sys.stderr, flush=True)
        return 2
    sys.stdout.flush()
    print(json.dumps(result), flush=True)
    return 0
