"""Retry helpers + failure taxonomy for the training loop.

Re-designs the reference's `retry.py:27` (generic exponential-backoff
decorator) and the error classification of `base_runner._RunLoop`
(`base_runner.py:399-528`): transient infrastructure errors (Unavailable /
Aborted / deadline / connection loss — the things a preempted TPU or a lost
host connection produce) are retryable, typically by restoring the last
checkpoint;
compilation and shape/type errors are programmer errors and fatal.
"""

from __future__ import annotations

import functools
import random
import time
from typing import Callable

# Substrings identifying retryable infrastructure failures (jax/PJRT wraps
# grpc status names into exception text).
TRANSIENT_PATTERNS = (
    "UNAVAILABLE",
    "Unavailable",
    "DEADLINE_EXCEEDED",
    "DeadlineExceeded",
    "ABORTED",
    "Socket closed",
    "Connection reset",
    "connection attempts failed",
    "failed to connect",
    "heartbeat failure",
)

# Substrings identifying definitely-NOT-retryable failures even when they
# co-occur with transient-looking text (ref _RunLoop: compile errors fatal).
FATAL_PATTERNS = (
    "Compilation failure",
    "RESOURCE_EXHAUSTED",
    "Out of memory",
    "INVALID_ARGUMENT",
)


def IsTransient(exc: BaseException) -> bool:
  """True when `exc` looks like a retryable infrastructure failure."""
  text = f"{type(exc).__name__}: {exc}"
  if any(pat in text for pat in FATAL_PATTERNS):
    return False
  return any(pat in text for pat in TRANSIENT_PATTERNS)


def Retry(initial_delay_sec: float = 1.0,
          max_delay_sec: float = 60.0,
          max_retries: int = 5,
          retry_if: Callable[[BaseException], bool] = IsTransient):
  """Exponential-backoff retry decorator (ref `retry.py:27`).

  Retries calls whose exception satisfies `retry_if`, sleeping
  initial_delay * 2^attempt (jittered, capped at max_delay) between tries.
  Non-matching exceptions and attempts past max_retries re-raise.
  """

  def Decorator(fn):
    @functools.wraps(fn)
    def Wrapped(*args, **kwargs):
      delay = initial_delay_sec
      for attempt in range(max_retries + 1):
        try:
          return fn(*args, **kwargs)
        except BaseException as e:  # noqa: BLE001
          if attempt >= max_retries or not retry_if(e):
            raise
          sleep = min(delay, max_delay_sec) * (0.5 + random.random())
          print(f"[retry] {type(e).__name__} (attempt {attempt + 1}/"
                f"{max_retries}), retrying in {sleep:.1f}s: {e}", flush=True)
          time.sleep(sleep)
          delay *= 2
      raise AssertionError("unreachable")

    return Wrapped

  return Decorator
