"""Installation verifier (reference fluid/install_check.py:47 run_check):
run a tiny train step single-device and, when more devices exist, a
sharded step over a data-parallel mesh, then report."""
from __future__ import annotations

import numpy as np

__all__ = ["run_check"]


def run_check():
    import jax

    from . import nn, optimizer, to_tensor
    from .jit import TrainStep

    print("Running verify paddle_tpu program ... ")
    devices = jax.devices()
    print(f"Found {len(devices)} device(s): "
          f"{[str(d) for d in devices[:4]]}"
          f"{' ...' if len(devices) > 4 else ''}")

    def tiny_step(mesh=None):
        from . import seed

        seed(0)
        model = nn.Linear(2, 1)
        opt = optimizer.SGD(learning_rate=0.01,
                            parameters=model.parameters())
        step = TrainStep(model, lambda m, x: (m(x) ** 2).mean(), opt,
                         mesh=mesh)
        rows = max(2, len(devices))
        x = to_tensor(np.tile(np.array([[1.0, 2.0], [3.0, 4.0]],
                                       np.float32), (rows // 2 + 1, 1))[:rows])
        first = float(step(x))
        for _ in range(3):
            last = float(step(x))
        if not last < first:
            raise AssertionError(
                f"loss did not decrease ({first} -> {last})")

    tiny_step()
    print("Your paddle_tpu works well on SINGLE device.")
    if len(devices) > 1:
        from .parallel.mesh import create_mesh

        tiny_step(mesh=create_mesh({"dp": len(devices)}))
        print(f"Your paddle_tpu works well on {len(devices)} devices "
              "(data parallel).")
    print("paddle_tpu is installed successfully! "
          "Let's start deep learning with paddle_tpu now.")
