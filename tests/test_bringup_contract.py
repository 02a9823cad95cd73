"""Bring-up contract: regression tests for chip_smoke.py and the backend.

The contract: jax initialises in-process, once, and nothing lands on the
CPU because the platform asked for was missing — a first device touch on
an unusable platform raises, a ``TPUPlace`` without a TPU raises, a Pallas
kernel that was chosen and then fails raises, and ``chip_smoke.py``
refuses any platform but ``tpu``. The disk compile cache lives where
``JAX_COMPILATION_CACHE_DIR`` says, else at one fixed path in the
checkout.
"""
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _env(**extra):
    env = dict(os.environ)
    env["PYTHONPATH"] = f"{REPO}{os.pathsep}{env.get('PYTHONPATH', '')}"
    env.update(extra)
    return env


def _py(src, env, timeout=180):
    return subprocess.run([sys.executable, "-c", src], env=env,
                          capture_output=True, text=True, timeout=timeout)


def test_chip_smoke_refuses_cpu():
    """chip_smoke.py under JAX_PLATFORMS=cpu exits non-zero before any
    phase, naming the platform it found, and prints no result."""
    out = subprocess.run([sys.executable, SMOKE], env=_env(),
                         capture_output=True, text=True, timeout=180)
    assert out.returncode != 0
    assert "platform 'cpu'" in out.stdout, out.stdout
    assert "== " not in out.stdout, "a phase started on the CPU"
    assert not out.stdout.strip().splitlines()[-1].startswith("{")


def test_first_touch_on_unusable_platform_raises():
    """`import paddle_tpu; to_tensor(...)` with a platform jax cannot
    initialise raises — it does not come back as a CPU tensor."""
    src = ("import numpy as np, paddle_tpu as paddle\n"
           "t = paddle.to_tensor(np.ones((2, 2), np.float32))\n"
           "print('PLATFORM', t.value.devices().pop().platform)\n")
    out = _py(src, _env(JAX_PLATFORMS="nosuchchip"))
    assert out.returncode != 0
    assert "PLATFORM" not in out.stdout
    assert "nosuchchip" in out.stderr, out.stderr[-2000:]


def test_place_without_its_device_raises():
    import paddle_tpu as paddle

    with pytest.raises(RuntimeError, match="no such device"):
        paddle.TPUPlace(0).jax_device()
    with pytest.raises(RuntimeError, match="no such device"):
        paddle.CPUPlace(10 ** 6).jax_device()     # no modulo wrap
    assert paddle.CPUPlace(0).jax_device().platform == "cpu"
    assert paddle.to_tensor([1.0]).place == paddle.CPUPlace(0)
    assert not paddle.is_compiled_with_tpu()


def test_bringup_starts_no_process_and_writes_no_file():
    import inspect

    import paddle_tpu.framework.bringup as bringup

    src = inspect.getsource(bringup)
    for word in ("subprocess", "open(", "tempfile", "expanduser"):
        assert word not in src, word


_CACHE_SRC = (
    "import jax, paddle_tpu\n"
    "from paddle_tpu.static import compile_cache as cc\n"
    "from paddle_tpu.ops.pallas import autotune\n"
    "before = jax.config.jax_compilation_cache_dir\n"
    "seen = []\n"
    "real = jax.config.update\n"
    "jax.config.update = lambda k, v: (seen.append(k), real(k, v))\n"
    "cc.ensure_enabled()\n"
    "print('DIR', cc.cache_dir())\n"
    "print('JAX', before, jax.config.jax_compilation_cache_dir)\n"
    "print('AUTOTUNE', autotune._cache_dir())\n"
    "print('UPDATED', 'jax_compilation_cache_dir' in seen)\n")


def _cache_report(env):
    out = _py(_CACHE_SRC, env)
    assert out.returncode == 0, out.stderr[-2000:]
    return dict(ln.split(" ", 1) for ln in out.stdout.splitlines())


def test_compile_cache_dir_resolution(tmp_path):
    """JAX_COMPILATION_CACHE_DIR set: used, and jax's cache-directory
    config is never touched. Unset: one fixed path in the checkout, the
    same in every process. The autotune verdicts sit beside either."""
    given = str(tmp_path / "given")
    rep = _cache_report(_env(JAX_COMPILATION_CACHE_DIR=given))
    assert rep["DIR"] == given
    assert rep["JAX"] == f"{given} {given}"
    assert rep["AUTOTUNE"] == os.path.join(given, "autotune")
    assert rep["UPDATED"] == "False"

    env = _env()
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    fixed = os.path.join(REPO, ".jax_cache")
    reps = [_cache_report(env), _cache_report(env)]
    assert reps[0] == reps[1]
    assert reps[0]["DIR"] == fixed
    assert reps[0]["JAX"] == f"None {fixed}"
    assert reps[0]["AUTOTUNE"] == os.path.join(fixed, "autotune")
    assert reps[0]["UPDATED"] == "True"


@pytest.mark.parametrize("family", ["flash", "fused_xent", "fused_embedding",
                                    "kda_chunk"])
def test_chosen_pallas_kernel_failure_propagates(monkeypatch, family):
    """A kernel that passed its gate and then fails raises; it is not
    counted as ``<family>.xla`` and served from the XLA reference.
    (paged attention and sampling: tests/test_paged_attention.py,
    tests/test_fused_sampling.py.)"""
    import jax.numpy as jnp

    import paddle_tpu.framework.bringup as bringup
    from paddle_tpu.ops.pallas import (counters, flash_attention,
                                       fused_embedding, fused_xent, kda)

    def boom(*a, **k):
        raise RuntimeError("mosaic said no")

    monkeypatch.setattr(bringup, "pallas_enabled", lambda: True)
    counters.reset()
    if family == "flash":
        monkeypatch.setattr(flash_attention, "_flash_attention_pallas", boom)
        q = jnp.zeros((1, 256, 1, 64), jnp.float32)
        call = lambda: flash_attention.flash_attention_or_fallback(q, q, q)
    elif family == "fused_xent":
        monkeypatch.setattr(fused_xent, "_fused_xent_core", boom)
        call = lambda: fused_xent.fused_linear_cross_entropy(
            jnp.zeros((256, 128)), jnp.zeros((256, 128)), jnp.zeros((256,)),
            jnp.zeros((256,), jnp.int32))
    elif family == "fused_embedding":
        monkeypatch.setattr(fused_embedding, "_bag_pallas", boom)
        call = lambda: fused_embedding.fused_embedding_seq_pool(
            jnp.zeros((64, 128)), jnp.zeros((8, 8), jnp.int32))
    else:
        monkeypatch.setattr(kda, "_pallas_fwd", boom)
        x = jnp.zeros((1, 64, 1, 128), jnp.float32)
        call = lambda: kda.chunk_kda(x, x, x, x, jnp.zeros((1, 64, 1)))
    with pytest.raises(RuntimeError, match="mosaic said no"):
        call()
    assert not [k for k in counters.snapshot() if k.endswith(".xla")]


_KEY_SRC = """
import hashlib, sys
import jax, jax.numpy as jnp
import paddle_tpu
from jax._src import cache_key
from paddle_tpu.ops.pallas import sampling as sp
from paddle_tpu.static import compile_cache

compile_cache.ensure_enabled()
l = jnp.zeros((8, 1024), jnp.float32)

def lowered(f, *a):      # Mosaic lowering needs no device
    return jax.jit(f).trace(*a).lower(lowering_platforms=("tpu",))

if sys.argv[1] == "tuned":
    # what an autotune round leaves behind: the same kernel traced
    # earlier from another call site, filling jax's inner-jit caches
    lowered(lambda ll: sp._fused_sample_pallas(ll, l, 1.0, 8), l).as_text()
step = lowered(lambda a, n: sp._fused_sample_pallas(a * 2, n, 0.8, 8), l, l)
ir = cache_key._canonicalize_ir(step.compiler_ir("stablehlo"),
                                cache_key.IgnoreCallbacks.NO)
print("KEY", hashlib.sha256(ir).hexdigest())
"""


def test_kernel_cache_key_ignores_trace_history():
    """The disk-cache key of a step holding a Pallas kernel must not
    depend on what was traced before it: a run whose autotuner timed
    the kernel first and a run that read the verdict from disk have to
    find each other's executables (on a v5e they did not, PR 21)."""
    keys = []
    for mode in ("fresh", "tuned"):
        out = subprocess.run([sys.executable, "-c", _KEY_SRC, mode],
                             env=_env(), capture_output=True, text=True,
                             timeout=180)
        assert out.returncode == 0, out.stderr[-2000:]
        keys.append([ln for ln in out.stdout.splitlines()
                     if ln.startswith("KEY")][0])
    assert keys[0] == keys[1]
