"""Retry with exponential backoff + jitter.

The repo-wide policy object for transient failures: network fetches
(incubate weights, fleet KV barriers), filesystem flakes (NFS-mounted
checkpoint roots), and the launch supervisor's relaunch pacing all share
this one implementation so budget/backoff semantics — and their
counters — stay uniform.

Defaults come from env knobs so an operator can harden a job without
code changes::

    PADDLE_RETRY_MAX_ATTEMPTS   total attempts incl. the first (default 3)
    PADDLE_RETRY_BASE_DELAY_S   first backoff delay (default 0.1)
    PADDLE_RETRY_MAX_DELAY_S    backoff cap (default 30.0)

Counters (paddle_tpu.profiler, surfaced via ``exe.counters``):
``retry_attempts`` — re-attempts after a retryable failure;
``retry_giveups`` — exhaustions (budget/deadline spent, last error
re-raised).
"""
from __future__ import annotations

import functools
import os
import random
import time
from typing import Callable, Optional, Tuple, Type, Union

__all__ = ["Backoff", "Retrier", "retry", "env_backoff",
           "env_max_attempts"]


def _env_float(name: str, default: float) -> float:
    try:
        return float(os.environ.get(name, default))
    except (TypeError, ValueError):
        return default


def _env_int(name: str, default: int) -> int:
    try:
        return int(os.environ.get(name, default))
    except (TypeError, ValueError):
        return default


class Backoff:
    """Exponential backoff schedule with proportional jitter.

    ``delay(attempt)`` for attempt 0,1,2,... is
    ``min(cap, base * factor**attempt)`` with the last ``jitter``
    fraction of it randomized (jitter=0 → deterministic, for tests;
    jitter=1 → full jitter a la the AWS architecture blog).
    """

    def __init__(self, base: Optional[float] = None, factor: float = 2.0,
                 cap: Optional[float] = None, jitter: float = 0.5,
                 rng: Optional[random.Random] = None):
        self.base = (base if base is not None
                     else _env_float("PADDLE_RETRY_BASE_DELAY_S", 0.1))
        self.factor = float(factor)
        self.cap = (cap if cap is not None
                    else _env_float("PADDLE_RETRY_MAX_DELAY_S", 30.0))
        self.jitter = min(1.0, max(0.0, float(jitter)))
        self._rng = rng or random.Random()

    def delay(self, attempt: int) -> float:
        raw = min(self.cap, self.base * (self.factor ** max(0, attempt)))
        if self.jitter <= 0.0:
            return raw
        fixed = raw * (1.0 - self.jitter)
        return fixed + self._rng.random() * (raw - fixed)


def env_backoff(base: float, cap: float, **kwargs) -> Backoff:
    """A Backoff with site-specific defaults that the PADDLE_RETRY_*
    env knobs override — call sites that hard-code a schedule would
    otherwise make the documented operator knobs dead letters."""
    return Backoff(base=_env_float("PADDLE_RETRY_BASE_DELAY_S", base),
                   cap=_env_float("PADDLE_RETRY_MAX_DELAY_S", cap),
                   **kwargs)


def env_max_attempts(default: int) -> int:
    """Site default for attempt budget, overridable by
    PADDLE_RETRY_MAX_ATTEMPTS."""
    return _env_int("PADDLE_RETRY_MAX_ATTEMPTS", default)


_RetryOn = Union[Type[BaseException], Tuple[Type[BaseException], ...],
                 Callable[[BaseException], bool]]


class Retrier:
    """Callable retry policy: deadline, attempt budget, exception filter.

    Usable three ways::

        Retrier(max_attempts=5).call(fetch, url)     # imperative
        @Retrier(retry_on=(OSError,))                # decorator
        def fetch(url): ...
        retry(max_attempts=5)(fetch)                 # via the helper

    ``retry_on`` is an exception type/tuple or a predicate; ``giveup_on``
    types pass through immediately even when they match ``retry_on``
    (e.g. retry OSError but never FileNotFoundError). On exhaustion the
    LAST error is re-raised — no wrapper type to unwrap at call sites.
    """

    def __init__(self, max_attempts: Optional[int] = None,
                 deadline: Optional[float] = None,
                 backoff: Optional[Backoff] = None,
                 retry_on: _RetryOn = (OSError, ConnectionError,
                                       TimeoutError),
                 giveup_on: Tuple[Type[BaseException], ...] = (),
                 sleep: Callable[[float], None] = time.sleep,
                 name: Optional[str] = None):
        self.max_attempts = (max_attempts if max_attempts is not None
                             else _env_int("PADDLE_RETRY_MAX_ATTEMPTS", 3))
        if self.max_attempts < 1:
            raise ValueError("max_attempts must be >= 1")
        self.deadline = deadline
        self.backoff = backoff or Backoff()
        self.retry_on = retry_on
        self.giveup_on = tuple(giveup_on)
        self._sleep = sleep
        self.name = name

    def _retryable(self, exc: BaseException) -> bool:
        if self.giveup_on and isinstance(exc, self.giveup_on):
            return False
        if callable(self.retry_on) and not isinstance(self.retry_on, type):
            return bool(self.retry_on(exc))
        return isinstance(exc, self.retry_on)

    def call(self, fn: Callable, *args, **kwargs):
        from .. import profiler

        t0 = time.monotonic()
        attempt = 0
        while True:
            try:
                return fn(*args, **kwargs)
            except BaseException as e:  # noqa: B036 (filtered below)
                if not self._retryable(e):
                    raise
                attempt += 1
                out_of_budget = attempt >= self.max_attempts
                delay = self.backoff.delay(attempt - 1)
                past_deadline = (
                    self.deadline is not None
                    and time.monotonic() - t0 + delay > self.deadline)
                if out_of_budget or past_deadline:
                    profiler.bump_counter("retry_giveups")
                    try:
                        from ..observability.flight_recorder import \
                            flight_recorder

                        fr = flight_recorder()
                        fr.record("retry_giveup", name=self.name,
                                  attempts=attempt,
                                  error=type(e).__name__,
                                  message=str(e)[:500])
                        fr.dump(reason=f"retry_giveup:{self.name}")
                    except Exception:
                        pass   # postmortem writer must not mask the error
                    raise
                profiler.bump_counter("retry_attempts")
                self._sleep(delay)

    def __call__(self, fn: Callable) -> Callable:
        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            return self.call(fn, *args, **kwargs)

        wrapper.retrier = self
        return wrapper

    wrap = __call__


def retry(fn: Optional[Callable] = None, **kwargs) -> Callable:
    """Decorator form: ``@retry``, ``@retry(max_attempts=5, ...)``, or
    direct ``retry(fn, max_attempts=5)`` -> wrapped callable.

    Keyword arguments are Retrier's.
    """
    if fn is None:
        return Retrier(**kwargs)
    if not callable(fn):
        raise TypeError(f"retry: first argument must be callable, "
                        f"got {fn!r}")
    return Retrier(**kwargs)(fn)
