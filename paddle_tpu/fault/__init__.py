"""paddle_tpu.fault — failure as a first-class, testable code path.

Two building blocks the rest of the framework composes:

- :mod:`retry` — ``Retrier``/``retry``: exponential backoff with jitter,
  attempt budget, wall-clock deadline, retryable-exception filter.
- :mod:`injector` — ``FaultInjector``/``fault.point(name)``: named fault
  points that tests or ``PADDLE_FAULT_SPEC`` arm to fail
  deterministically N times, so every recovery path (torn checkpoint
  commit, transient fetch failure, trainer relaunch) is exercisable in
  CI without real kills.

All activity lands in process-global profiler counters
(``retry_attempts``, ``retry_giveups``, ``faults_injected``, ...)
surfaced through ``Executor.counters``.
"""
from . import injector  # noqa: F401
from .injector import (  # noqa: F401
    FaultInjector, InjectedFault, arm, armed, default_injector, disarm,
    disarm_all, load_env_spec, point,
)
from .retry import Backoff, Retrier, retry  # noqa: F401
