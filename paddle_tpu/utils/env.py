"""Environment report (reference tools/summary_env.py: collects
paddle/python/OS/CUDA versions for bug reports — here the TPU-stack
equivalents: jax/jaxlib/libtpu, device inventory, host info)."""
from __future__ import annotations

import platform
import sys


def summary_env(print_out: bool = False):
    """Collect a {section: value} environment report; optionally print the
    reference-style block."""
    info = {}
    try:
        from .. import __version__ as ptu_version
    except ImportError:
        ptu_version = "unknown"
    info["paddle_tpu"] = ptu_version
    info["python"] = sys.version.split()[0]
    info["platform"] = platform.platform()
    try:
        import jax

        info["jax"] = jax.__version__
        try:
            import jaxlib

            info["jaxlib"] = jaxlib.__version__
        except ImportError:
            pass
        try:
            devs = jax.devices()
            info["backend"] = jax.default_backend()
            info["devices"] = ", ".join(
                f"{d.platform}:{d.id}({getattr(d, 'device_kind', '?')})"
                for d in devs)
            info["device_count"] = str(len(devs))
        except RuntimeError as e:  # no backend reachable
            info["devices"] = f"unavailable ({e})"
    except ImportError:
        info["jax"] = "not installed"
    for mod in ("numpy", "flax", "optax"):
        try:
            info[mod] = __import__(mod).__version__
        except ImportError:
            pass
    if print_out:
        width = max(len(k) for k in info)
        print("*" * 10 + " paddle_tpu environment " + "*" * 10)
        for k, v in info.items():
            print(f"{k.ljust(width)} : {v}")
        print("*" * 44)
    return info


if __name__ == "__main__":
    summary_env(print_out=True)
