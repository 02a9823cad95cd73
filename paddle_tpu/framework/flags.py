"""Global flag registry.

TPU-native equivalent of the reference gflags layer
(/root/reference/paddle/fluid/platform/flags.cc plus the
pybind/global_value_getter_setter.cc export): a typed in-process registry,
seeded from FLAGS_* environment variables, settable via set_flags()
(parity with fluid.set_flags / fluid.get_flags).
"""
from __future__ import annotations

import os
import threading
from typing import Any, Dict

_lock = threading.Lock()
_registry: Dict[str, Any] = {}
_docs: Dict[str, str] = {}


def define_flag(name: str, default, doc: str = ""):
    with _lock:
        if name in _registry:
            return
        env = os.environ.get(f"FLAGS_{name}")
        value = default
        if env is not None:
            if isinstance(default, bool):
                value = env.lower() in ("1", "true", "yes", "on")
            elif isinstance(default, int):
                value = int(env)
            elif isinstance(default, float):
                value = float(env)
            else:
                value = env
        _registry[name] = value
        _docs[name] = doc


def get_flags(names):
    if isinstance(names, str):
        names = [names]
    return {n: _registry[n] for n in names}


def get_flag(name: str):
    return _registry[name]


def set_flags(flags: Dict[str, Any]):
    with _lock:
        for name, value in flags.items():
            if name not in _registry:
                raise KeyError(f"Flag {name!r} is not defined")
            _registry[name] = value


def all_flags():
    return dict(_registry)


# Core flags (subset of the reference's platform/flags.cc that is meaningful on TPU).
define_flag("check_nan_inf", False,
            "Scan op outputs for NaN/Inf (reference flags.cc:44): eager "
            "ops raise FloatingPointError naming the op; a jit.TrainStep "
            "BUILT while it is set computes a finiteness and magnitude "
            "record by layer, gradient leaf and named probe beside its "
            "loss (TrainStep.numerics(), framework/nan_inf.py). Not "
            "carried through lax.scan bodies, shard_map or the static "
            "executor")
define_flag("prng_impl", "auto",
            "PRNG key impl: auto|rbg|threefry2x32. auto = rbg on TPU "
            "(hardware RngBitGenerator; measured +27% BERT train step vs "
            "threefry from cheaper dropout masks), threefry elsewhere")
define_flag("benchmark", False, "Sync + time each op in eager mode")
define_flag("eager_delete_tensor_gb", 0.0, "Kept for API parity; XLA manages buffers")
define_flag("paddle_num_threads", 1, "Host threads for data pipeline")
define_flag("use_pinned_memory", True, "Kept for API parity; jax manages transfers")
define_flag("fraction_of_gpu_memory_to_use", 0.92, "API parity; XLA preallocation governs")
define_flag("init_allocated_mem", False, "API parity")
define_flag("cudnn_deterministic", False, "Maps to XLA deterministic ops")
define_flag("max_inplace_grad_add", 0, "API parity")
define_flag("tracer_profile_fname", "", "Eager tracer profile output path")
define_flag("sp_fallback_warn", True,
            "Warn when sequence-parallel (ring/Ulysses) attention falls "
            "back to the replicated local path — a silent perf cliff")
define_flag("flash_short_seq", False,
            "Route 128<=seq<=256 mask-free attention to the "
            "single-block Pallas kernel (direct softmax, one fused bwd "
            "launch) instead of the XLA dispatch floor. Off until an "
            "on-chip A/B proves it wins")
define_flag("sp_mask_fallback", False,
            "Allow query-dependent attention masks the ring cannot "
            "decompose to fall back to replicated XLA attention instead "
            "of raising (causal + key-padding masks never need this: "
            "they ride the ring natively)")
